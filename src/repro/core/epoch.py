"""Epoch-based adaptive-sampling engine (the paper's Algorithm 2, TPU-native).

One function, :func:`run_worker`, implements the per-worker program for all
five strategies of :class:`~repro.core.frames.FrameStrategy`.  It is written
against the :class:`~repro.core.frames.Collectives` abstraction, so the same
code executes

* sequentially (``sequential_collectives()``, W=1 — the correctness oracle),
* with **virtual workers** under ``vmap(..., axis_name=...)`` (CPU tests and
  the paper-figure benchmarks), and
* with **real devices** under ``shard_map`` on a mesh axis (production).

Strategy semantics (see DESIGN.md §2 for the shared-memory → TPU mapping):

LOCK          reduce + check after *every* sampling round; the decision is a
              data dependency of the next round (original-KADABRA analog).
BARRIER       reduce + check after K rounds; collective still on the critical
              path between epochs ("OpenMP baseline", paper §2.4).
LOCAL_FRAME   the paper's §3.2: the collective consumes the *previous* epoch's
              delta frame, so inside one loop body the reduction of epoch e−1
              and the sampling of epoch e have no data dependency — XLA's
              latency-hiding scheduler can overlap them (async all-reduce on
              TPU).  The stop decision therefore lags one epoch: exactly the
              paper's "termination latency" (App. C.3).
SHARED_FRAME  like LOCAL_FRAME but the reduction is a *reduce-scatter*: each
              worker keeps only its 1/W shard of the consistent state (Θ(n/W)
              memory — the paper's Θ(1)-per-thread trade-off with F = W) and
              evaluates the stopping condition on its shard; the 1-bit
              verdicts are AND-combined with a tiny all-reduce.  Hardware
              accumulation in the reduce-scatter replaces fetch-add.
INDEXED_FRAME deterministic (paper §D.2): frame *m* (= epoch·W + worker) is a
              pure function of ``fold_in(seed, m)`` with a fixed number of
              samples; the checker consumes frames **in index order** and
              stops at the first prefix satisfying the condition ⇒ the result
              is bit-identical for every worker count W.

Consistency (Prop. 1): every state the condition is evaluated on equals
``⊕`` over an *integral* set of per-worker sample prefixes — the proof
obligation ("all stores visible before accumulation") holds by SSA data
dependence: a frame snapshot is a value, not a memory location.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .frames import (Collectives, FrameStrategy, StateFrame, accumulate,
                     combine, sequential_collectives, zeros_like_frame)

PyTree = Any
# sample_fn(key, carry) -> (delta: StateFrame, carry')   — one sampling round
SampleFn = Callable[[jax.Array, PyTree], Tuple[StateFrame, PyTree]]
# check_fn(total: StateFrame) -> (stop: bool scalar, aux pytree)
CheckFn = Callable[[StateFrame], Tuple[jax.Array, PyTree]]


@dataclasses.dataclass(frozen=True)
class EpochConfig:
    strategy: FrameStrategy = FrameStrategy.LOCAL_FRAME
    rounds_per_epoch: int = 8     # K sampling rounds between checks (paper's N)
    max_epochs: int = 1_000
    # App. C.3 heuristic: coordinator cadence N₀ = N / W^ξ. Applied via
    # :func:`rounds_for_world` when building per-run configs.
    xi: float = 0.0
    # Execution substrate (core/substrate.py): "sequential" | "vmap" |
    # "shard_map" (or the Substrate enum); None → sequential at W=1, vmap
    # otherwise.  Consumed by substrate.run_on_substrate, not run_worker.
    substrate: "str | None" = None


def rounds_for_world(n_samples_between_checks: int, round_batch: int,
                     world: int, xi: float) -> int:
    """Paper App. C.3: check after N₀ = N / W^ξ samples (per worker)."""
    n0 = n_samples_between_checks / max(1.0, float(world) ** xi)
    return max(1, int(round(n0 / max(1, round_batch))))


class EpochState(NamedTuple):
    key: jax.Array
    carry: PyTree
    total: StateFrame       # consistent reduced state (shard for SHARED)
    pending: StateFrame     # this worker's delta of the epoch just finished
    stop: jax.Array         # bool scalar
    aux: PyTree
    epoch: jax.Array        # int32
    stop_epoch: jax.Array   # epoch at which stop was first seen (for latency stats)


def _sample_epoch(sample_fn: SampleFn, template: PyTree, rounds: int,
                  key: jax.Array, carry: PyTree) -> Tuple[StateFrame, PyTree]:
    """K sampling rounds accumulated into a fresh delta frame."""

    def body(st, k):
        frame, carry = st
        delta, carry = sample_fn(k, carry)
        return (combine(frame, delta), carry), None

    keys = jax.random.split(key, rounds)
    (frame, carry), _ = jax.lax.scan(body, (zeros_like_frame(template), carry), keys)
    return frame, carry


class EpochProgram(NamedTuple):
    """The epoch engine decomposed into single-epoch pieces.

    ``init(key, worker_id)`` builds the primed epoch-0 state; ``body(state,
    worker_id)`` advances exactly one epoch; ``cond(state)`` is the
    keep-running predicate.  ``run_worker`` is literally
    ``while_loop(cond, body, init(...))`` — the serving layer
    (:mod:`repro.serve`) drives the same ``body`` one epoch at a time from
    the host, which is what makes sessions checkpointable and schedulable at
    epoch granularity with *bit-identical* results: the state between epochs
    is a plain pytree (frame snapshots are values, not memory), so
    save → restore → step ≡ step.
    """

    init: Callable[[jax.Array, jax.Array], "EpochState"]
    body: Callable[["EpochState", jax.Array], "EpochState"]
    cond: Callable[["EpochState"], jax.Array]
    cfg: EpochConfig
    fold: Optional[int]


def make_program(
    sample_fn: SampleFn,
    check_fn: CheckFn,
    template: PyTree,
    cfg: EpochConfig,
    colls: Collectives,
    aux_template: Optional[PyTree] = None,
    seed_scalar: Optional[jax.Array] = None,
    fold: Optional[int] = None,
) -> EpochProgram:
    """Build the per-worker epoch program for one strategy.

    ``template`` — pytree with the shape/dtype of ``frame.data`` (for SHARED
    strategies this is the *full* frame; the engine keeps the sharded total).
    ``aux_template`` — shape of check aux (obtained via ``jax.eval_shape`` if
    omitted).  ``seed_scalar`` — required for INDEXED_FRAME (the ``init``/
    ``body`` callables take the worker id as their second argument).

    ``fold = k`` runs **k logical workers per physical worker** (elastic
    re-sharding, :mod:`repro.serve.elastic`): ``state.key`` carries k stacked
    PRNG keys and ``state.carry`` k stacked carries, each epoch samples every
    logical stream and combines the k deltas before the collective.  Because
    ``∘`` is associative/commutative over integer frames, the global epoch
    delta — and hence (τ, estimate) — is bit-identical to the unfolded run
    with W_logical = W_physical · k workers.  Supported for every strategy
    except INDEXED_FRAME (whose frame indices are already W-independent).
    """
    strat = cfg.strategy
    W = colls.world
    if fold is not None and strat == FrameStrategy.INDEXED_FRAME:
        raise ValueError("fold is not supported for INDEXED_FRAME (its "
                         "result is already worker-count independent)")

    F = colls.frame_shards or W
    if aux_template is None:
        zf = zeros_like_frame(template)
        if strat == FrameStrategy.SHARED_FRAME and colls.scatter_frames is not None:
            zf = _shard_zeros(zf, F)
        _, aux_template = jax.eval_shape(check_fn, zf)
    zero_aux = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), aux_template)

    if strat == FrameStrategy.SHARED_FRAME:
        total0 = _shard_zeros(zeros_like_frame(template), F)
    else:
        total0 = zeros_like_frame(template)

    def split_keys(key):
        """Per-epoch key evolution — vmapped over the fold's logical streams
        so each stream's split sequence is identical to its unfolded run."""
        if fold is None:
            return _split(key)
        return jax.vmap(_split)(key)

    def sample_epoch(k_epoch, carry, rounds):
        if fold is None:
            return _sample_epoch(sample_fn, template, rounds, k_epoch, carry)
        frames, carry = jax.vmap(
            lambda k, c: _sample_epoch(sample_fn, template, rounds, k, c)
        )(k_epoch, carry)
        return accumulate(frames), carry

    # the exchange of frames between workers and the stop check are named
    # scopes, so a trace can tell them from the sampling around them
    @jax.named_scope("stop_check")
    def check_full(total: StateFrame):
        stop, aux = check_fn(total)
        if W > 1:
            # all workers compute the same verdict on replicated data; the
            # psum(min) keeps the verdict well-defined even if reductions are
            # reordered differently per worker (cheap 1-element collective).
            stop = colls.reduce_scalar(stop.astype(jnp.int32)) >= W
        return stop, aux

    @jax.named_scope("stop_check")
    def check_sharded(total_shard: StateFrame):
        stop_local, aux = check_fn(total_shard)
        stop = colls.reduce_scalar(stop_local.astype(jnp.int32)) >= W
        return stop, aux

    # ----- LOCK / BARRIER: reduce + check on the critical path -----------
    if strat in (FrameStrategy.LOCK, FrameStrategy.BARRIER):
        rounds = 1 if strat == FrameStrategy.LOCK else cfg.rounds_per_epoch

        def body(st: EpochState, worker_id) -> EpochState:
            k_epoch, key = split_keys(st.key)
            delta, carry = sample_epoch(k_epoch, st.carry, rounds)
            with jax.named_scope("frame_exchange"):
                reduced = colls.reduce_frames(delta)      # blocking barrier
            total = combine(st.total, reduced)
            stop, aux = check_full(total)
            e = st.epoch + 1
            return EpochState(key, carry, total, delta, stop, aux, e,
                              jnp.where(stop & ~st.stop, e, st.stop_epoch))

    # ----- LOCAL_FRAME: lagged all-reduce, overlappable ------------------
    elif strat == FrameStrategy.LOCAL_FRAME:

        def body(st: EpochState, worker_id) -> EpochState:
            # (a) fold in the PREVIOUS epoch's deltas — no data dependency on
            # (b), so the all-reduce can overlap the sampling compute.
            with jax.named_scope("frame_exchange"):
                reduced = colls.reduce_frames(st.pending)
            total = combine(st.total, reduced)
            stop, aux = check_full(total)
            # (b) sample the current epoch.
            k_epoch, key = split_keys(st.key)
            delta, carry = sample_epoch(k_epoch, st.carry, cfg.rounds_per_epoch)
            e = st.epoch + 1
            return EpochState(key, carry, total, delta, stop, aux, e,
                              jnp.where(stop & ~st.stop, e, st.stop_epoch))

    # ----- SHARED_FRAME: lagged reduce-scatter + 1-bit verdict -----------
    elif strat == FrameStrategy.SHARED_FRAME:
        assert colls.scatter_frames is not None, "SHARED_FRAME needs scatter_frames"

        def body(st: EpochState, worker_id) -> EpochState:
            with jax.named_scope("frame_exchange"):
                reduced_shard = colls.scatter_frames(st.pending)
            total = combine(st.total, reduced_shard)
            stop, aux = check_sharded(total)
            k_epoch, key = split_keys(st.key)
            delta, carry = sample_epoch(k_epoch, st.carry, cfg.rounds_per_epoch)
            e = st.epoch + 1
            return EpochState(key, carry, total, delta, stop, aux, e,
                              jnp.where(stop & ~st.stop, e, st.stop_epoch))

    # ----- INDEXED_FRAME: deterministic prefix checking ------------------
    elif strat == FrameStrategy.INDEXED_FRAME:
        assert seed_scalar is not None, "INDEXED_FRAME needs seed_scalar"
        assert colls.all_frames is not None

        def sample_indexed(epoch: jax.Array, worker_id, carry: PyTree):
            m = epoch * W + worker_id          # global frame index
            k = jax.random.fold_in(jax.random.key(0), seed_scalar)
            k = jax.random.fold_in(k, m)
            return _sample_epoch(sample_fn, template, cfg.rounds_per_epoch, k, carry)

        def body(st: EpochState, worker_id) -> EpochState:
            with jax.named_scope("frame_exchange"):   # (W, ...) per-frame deltas
                gathered = colls.all_frames(st.pending)

            def prefix_step(acc, j):
                total, stop, aux, stop_epoch = acc
                fj = jax.tree.map(lambda x: x[j], gathered)
                total_j = combine(total, fj)
                with jax.named_scope("stop_check"):
                    s_j, aux_j = check_fn(total_j)
                # freeze at the FIRST stopping prefix (determinism).
                first = s_j & ~stop
                total = jax.tree.map(lambda new, old: jnp.where(stop, old, new),
                                     total_j, total)
                aux = jax.tree.map(lambda new, old: jnp.where(first, new, old),
                                   aux_j, aux)
                stop_epoch = jnp.where(first, st.epoch + 1, stop_epoch)
                return (total, stop | s_j, aux, stop_epoch), None

            (total, stop, aux, stop_epoch), _ = jax.lax.scan(
                prefix_step, (st.total, st.stop, st.aux, st.stop_epoch),
                jnp.arange(W))
            if W > 1:  # verdicts agree (same data), keep them in lockstep
                with jax.named_scope("stop_check"):
                    stop = colls.reduce_scalar(stop.astype(jnp.int32)) >= W
            delta, carry = sample_indexed(st.epoch, worker_id, st.carry)
            return EpochState(st.key, carry, total, delta, stop, aux,
                              st.epoch + 1, stop_epoch)

    else:  # pragma: no cover
        raise ValueError(f"unknown strategy {strat}")

    def cond(st: EpochState):
        return jnp.logical_and(~st.stop, st.epoch < cfg.max_epochs)

    # Epoch 0 produces the first pending frame (there is no SF for epoch 0 —
    # Alg. 2 note on line 9).
    def init(key: jax.Array, worker_id, carry: PyTree = None) -> EpochState:
        state0 = EpochState(
            key=key, carry=carry, total=total0,
            pending=zeros_like_frame(template),
            stop=jnp.zeros((), bool), aux=zero_aux,
            epoch=jnp.zeros((), jnp.int32), stop_epoch=jnp.zeros((), jnp.int32))
        if strat == FrameStrategy.INDEXED_FRAME:
            # NB: body samples frame for st.epoch (already advanced), so
            # indexed frame indices stay contiguous: 0·W+wid, 1·W+wid, ...
            delta, carry0 = sample_indexed(jnp.zeros((), jnp.int32),
                                           worker_id, state0.carry)
            return state0._replace(pending=delta, carry=carry0,
                                   epoch=jnp.ones((), jnp.int32))
        if strat in (FrameStrategy.LOCAL_FRAME, FrameStrategy.SHARED_FRAME):
            k0, key2 = split_keys(state0.key)
            delta0, carry0 = sample_epoch(k0, state0.carry,
                                          cfg.rounds_per_epoch)
            return state0._replace(key=key2, carry=carry0, pending=delta0,
                                   epoch=jnp.ones((), jnp.int32))
        return state0

    return EpochProgram(init=init, body=body, cond=cond, cfg=cfg, fold=fold)


def run_worker(
    sample_fn: SampleFn,
    check_fn: CheckFn,
    template: PyTree,
    init_carry: PyTree,
    key: jax.Array,
    cfg: EpochConfig,
    colls: Optional[Collectives] = None,
    aux_template: Optional[PyTree] = None,
    seed_scalar: Optional[jax.Array] = None,
    worker_id: Optional[jax.Array] = None,
) -> EpochState:
    """Run the adaptive-sampling loop for one (SPMD) worker to completion.

    Convenience wrapper: ``while_loop`` over :func:`make_program`'s pieces.
    ``seed_scalar``/``worker_id`` — required for INDEXED_FRAME.
    """
    colls = colls or sequential_collectives()
    if cfg.strategy == FrameStrategy.INDEXED_FRAME:
        assert worker_id is not None, "INDEXED_FRAME needs worker_id"
    wid = worker_id if worker_id is not None else jnp.zeros((), jnp.int32)
    prog = make_program(sample_fn, check_fn, template, cfg, colls,
                        aux_template=aux_template, seed_scalar=seed_scalar)
    state0 = prog.init(key, wid, init_carry)
    return jax.lax.while_loop(prog.cond, lambda st: prog.body(st, wid), state0)


def _split(key):
    k1, k2 = jax.random.split(key)
    return k1, k2


def _shard_zeros(frame: StateFrame, world: int) -> StateFrame:
    """Zero frame shaped like this worker's 1/W reduce-scatter shard."""
    def shard(x):
        if x.ndim == 0:
            return x
        assert x.shape[0] % world == 0, (
            f"SHARED_FRAME needs leading dim divisible by W={world}; pad the "
            f"frame (got {x.shape}) — see frames.shard_frame_pad")
        return jnp.zeros((x.shape[0] // world,) + x.shape[1:], x.dtype)
    return StateFrame(num=frame.num, data=jax.tree.map(shard, frame.data))


# ---------------------------------------------------------------------------
# Virtual-worker wrapper: simulate W workers on one device with vmap.  This is
# how tests and the paper-figure benchmarks execute the engine on CPU, and it
# is semantically identical to shard_map over a mesh axis of size W.
# ---------------------------------------------------------------------------

AXIS = "workers"


def run_virtual(sample_fn: SampleFn, check_fn: CheckFn, template: PyTree,
                init_carry: PyTree, seed: int, world: int, cfg: EpochConfig,
                frame_shards: int = 0) -> EpochState:
    from .frames import axis_collectives
    colls = axis_collectives(AXIS, world, frame_shards=frame_shards)

    def per_worker(key, wid):
        return run_worker(sample_fn, check_fn, template, init_carry, key, cfg,
                          colls=colls,
                          seed_scalar=jnp.asarray(seed, jnp.uint32),
                          worker_id=wid)

    keys = jax.random.split(jax.random.key(seed), world)
    wids = jnp.arange(world, dtype=jnp.int32)
    return jax.vmap(per_worker, axis_name=AXIS)(keys, wids)


def run_sharded(sample_fn: SampleFn, check_fn: CheckFn, template: PyTree,
                init_carry: PyTree, seed: int, mesh, axis: str,
                cfg: EpochConfig, frame_shards: int = 0) -> EpochState:
    """Run the engine over a real mesh axis with shard_map (production path).

    Every leaf of ``init_carry``/``template`` is treated as replicated;
    sampling randomness is decorrelated per worker via key splitting (or frame
    indices for INDEXED_FRAME).  Outputs are stacked per worker along a new
    leading axis of size W (scalars become ``(W,)``; replicated quantities
    like ``total``/``stop`` repeat identically — callers index ``[0]``).

    Collectives are built with ``grouped=True``: the SHARED_FRAME F < W path
    runs the paper's grouped reduce-scatter + cross-group all-reduce via
    ``axis_index_groups`` (real collectives, no psum+slice fallback).
    """
    from jax.sharding import PartitionSpec as P
    from .frames import axis_collectives

    world = mesh.shape[axis]
    colls = axis_collectives(axis, world, frame_shards=frame_shards,
                             grouped=True)

    def per_worker(keys, wids):
        st = run_worker(sample_fn, check_fn, template, init_carry,
                        keys[0], cfg, colls=colls,
                        seed_scalar=jnp.asarray(seed, jnp.uint32),
                        worker_id=wids[0])
        # add a per-worker leading dim so every leaf can carry P(axis)
        return jax.tree.map(lambda x: jnp.asarray(x)[None], st)

    keys = jax.random.split(jax.random.key(seed), world)
    wids = jnp.arange(world, dtype=jnp.int32)
    fn = jax.shard_map(per_worker, mesh=mesh,
                       in_specs=(P(axis), P(axis)),
                       out_specs=P(axis),
                       check_vma=False)
    return fn(keys, wids)
