"""Pipeline parallelism (GPipe-style) over a mesh axis — the >512-chip
scaling path sketched in DESIGN.md §9.

``pipeline_forward`` runs a scanned layer stack split into S stages along a
mesh axis: each stage holds n_layers/S of the (stacked) weights; microbatch
activations flow stage-to-stage with ``jax.lax.ppermute`` inside a
``shard_map``.  The classic GPipe schedule processes M microbatches in
M + S − 1 ticks (bubble fraction (S−1)/(M+S−1)).

This is the inter-pod configuration for very deep models: mesh
(stage, data, model) with DCN crossing only between consecutive stages
(point-to-point, not all-reduce) — the cheapest possible inter-pod traffic
pattern.  Shipped as a first-class prototype with tests; the per-arch
launch configs keep pod-DP as the default (DESIGN.md §9 rationale).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

PyTree = Any


def pipeline_forward(layer_fn: Callable, stacked_params: PyTree,
                     x_micro: jax.Array, mesh, axis: str = "stage"
                     ) -> jax.Array:
    """Run x through L layers split across the ``axis`` mesh dim.

    layer_fn(lp, x) -> x'  — one layer.
    stacked_params — leaves with leading dim L (L % n_stages == 0).
    x_micro — (M, mb, …) microbatched activations, M ≥ n_stages.
    Returns (M, mb, …) outputs after all L layers.
    """
    S = mesh.shape[axis]
    M = x_micro.shape[0]
    assert M >= S, f"need ≥ {S} microbatches to fill the pipeline"
    L = jax.tree.leaves(stacked_params)[0].shape[0]
    assert L % S == 0

    def stage_fn(lp_stage, xs):
        # lp_stage: this stage's (L/S, …) weights; xs: (M, mb, …)
        sid = jax.lax.axis_index(axis)
        n_ticks = M + S - 1
        fwd_perm = [(i, i + 1) for i in range(S - 1)]

        def run_stage(x):
            def body(x, lp):
                return layer_fn(lp, x), None
            x, _ = jax.lax.scan(body, x, lp_stage)
            return x

        def tick(carry, t):
            outs, inflight = carry
            # stage 0 injects microbatch t (others use the permuted input)
            mb_idx = jnp.clip(t, 0, M - 1)
            x_in = jnp.where(sid == 0, xs[mb_idx], inflight)
            y = run_stage(x_in)
            # last stage emits microbatch (t − S + 1)
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            emit = jnp.logical_and(sid == S - 1, t >= S - 1)
            outs = jax.tree.map(
                lambda o, v: o.at[out_idx].set(
                    jnp.where(emit, v, o[out_idx])), outs, y)
            nxt = jax.lax.ppermute(y, axis, fwd_perm)
            return (outs, nxt), None

        outs0 = jnp.zeros_like(xs)
        inflight0 = jnp.zeros_like(xs[0])
        (outs, _), _ = jax.lax.scan(tick, (outs0, inflight0),
                                    jnp.arange(n_ticks))
        # replicate the last stage's outputs to every stage (masked psum —
        # ppermute needs a bijection, so it can't broadcast)
        outs = jax.lax.psum(
            jnp.where(sid == S - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    # stage s holds layers [s·L/S, (s+1)·L/S)
    in_specs = (jax.tree.map(lambda _: P(axis), stacked_params), P())
    fn = jax.shard_map(stage_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=P(), check_vma=False)
    return fn(stacked_params, x_micro)
