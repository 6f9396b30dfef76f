"""Execution substrates — *where* the epoch engine's W workers run.

The engine (:func:`repro.core.epoch.run_worker`) is written against the
:class:`~repro.core.frames.Collectives` abstraction, so the same per-worker
program admits three executions:

SEQUENTIAL   W = 1, identity collectives — the correctness oracle.
VMAP         W virtual workers on one device via ``vmap(axis_name=...)``;
             collectives are simulated (psum = sum over the mapped axis).
             This is how tests and the paper-figure benchmarks run on CPU.
SHARD_MAP    W real devices on a mesh axis via ``jax.shard_map``;
             collectives lower to real all-reduce / reduce-scatter /
             all-gather, and the SHARED_FRAME
             F < W path uses the paper's grouped reduce-scatter +
             cross-group all-reduce (``axis_index_groups``) instead of the
             vmap psum+slice reference form.

The invariant the substrate-equivalence harness
(:func:`repro.core.conformance.run_substrate_equivalence`) enforces: for any
(instance, strategy, W, F) the three substrates produce **bit-identical**
``total.num`` and trimmed frame data.  Frames are integer pytrees, so real
collectives cannot diverge from the simulated semantics by reduction order.

On a single-device host, run tests with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (set before the first
jax import) to give SHARD_MAP real devices — exactly what the CI
``substrate-shardmap`` job does.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Any, Optional

import jax

PyTree = Any

WORKER_AXIS = "workers"


class Substrate(enum.Enum):
    """How the engine's W workers are executed (see module docstring)."""

    SEQUENTIAL = "sequential"
    VMAP = "vmap"
    SHARD_MAP = "shard_map"


def resolve_substrate(substrate: "Substrate | str | None",
                      world: int = 1) -> Substrate:
    """Normalize a substrate spec; ``None`` → the historical default
    (sequential at W=1, vmap otherwise)."""
    if substrate is None:
        return Substrate.SEQUENTIAL if world == 1 else Substrate.VMAP
    return Substrate(substrate) if isinstance(substrate, str) else substrate


def unavailable_reason(substrate: "Substrate | str",
                       world: int) -> Optional[str]:
    """Why ``substrate`` cannot run ``world`` workers here (None = it can)."""
    sub = resolve_substrate(substrate, world)
    if sub == Substrate.SEQUENTIAL and world != 1:
        return f"sequential substrate is the W=1 oracle (got W={world})"
    if sub == Substrate.SHARD_MAP:
        have = len(jax.devices())
        if have < world:
            return (f"shard_map needs ≥{world} devices, have {have} — set "
                    f"XLA_FLAGS=--xla_force_host_platform_device_count="
                    f"{world} before importing jax")
    return None


def available_substrates(world: int) -> tuple:
    """The substrates that can execute ``world`` workers on this host."""
    return tuple(s for s in Substrate
                 if unavailable_reason(s, world) is None)


def worker_mesh(world: int, axis: str = WORKER_AXIS, devices=None):
    """A 1-D mesh of ``world`` devices for the engine's worker axis.

    ``devices`` — an explicit device list (any subset of ``jax.devices()``,
    leading or not: the serving placement layer leases *disjoint* submeshes,
    so concurrent sessions must be buildable on e.g. devices ``[4..7]``).
    Default: the historical leading ``jax.devices()[:world]``.
    """
    from jax.sharding import AxisType
    if devices is None:
        reason = unavailable_reason(Substrate.SHARD_MAP, world)
        if reason is not None:
            raise RuntimeError(reason)
        devices = jax.devices()[:world]
    devices = list(devices)
    if len(devices) != world:
        raise ValueError(f"worker_mesh needs exactly world={world} devices, "
                         f"got {len(devices)}")
    return jax.make_mesh((world,), (axis,), axis_types=(AxisType.Auto,),
                         devices=devices)


def mesh_device_ids(mesh) -> tuple:
    """The flat device ids of a mesh, in mesh order — the part of a stepper
    cache key that distinguishes same-shape programs bound to different
    submeshes."""
    return tuple(d.id for d in mesh.devices.flat)


@dataclasses.dataclass(frozen=True)
class EpochStepper:
    """Single-epoch stepping of the engine on a substrate (serving path).

    ``init(seed)`` returns the primed epoch-0 state with every leaf stacked
    per worker (leading dim ``world``) — the same layout
    :func:`run_on_substrate` returns.  ``step(state, seed)`` advances exactly
    one epoch; the underlying program is jitted once per stepper and takes
    the seed as a traced scalar, so the serving scheduler can cache ONE
    stepper per session *shape* (instance config × strategy × W × F ×
    substrate × fold) and run any number of differently-seeded queries
    through it without recompiling.  ``readback(state)`` brings the
    host-side continuation predicate (all workers' verdicts are in
    lockstep) and the sampler's counters to the host in one transfer.

    The invariant that makes checkpoint/resume and scheduling sound:
    ``step^n(init(seed))`` is bit-identical to the fused ``while_loop`` run
    of :func:`run_on_substrate` — the inter-epoch state is a value pytree,
    so where it is materialized (device loop, host loop, or a checkpoint on
    disk) cannot change the trajectory.
    """

    substrate: "Substrate"
    world: int
    cfg: Any
    fold: Optional[int]
    init_fn: Any = dataclasses.field(repr=False)
    step_fn: Any = dataclasses.field(repr=False)

    def init(self, seed: int):
        return self.init_fn(seed)

    def step(self, state, seed: int):
        import jax.numpy as jnp
        return self.step_fn(state, jnp.asarray(seed, jnp.uint32))

    def readback(self, state) -> tuple:
        """``(active, counters)``: whether the query goes on, and the
        sampler's counters summed over workers.  The sampler's carry holds
        counters where it is a dict of integers (``{}`` otherwise)."""
        import numpy as np
        counters = state.carry if isinstance(state.carry, dict) else {}
        stop, epoch, counters = jax.device_get(
            (state.stop, state.epoch, counters))
        active = (not bool(np.reshape(stop, -1)[0])
                  and int(np.reshape(epoch, -1)[0]) < self.cfg.max_epochs)
        return active, {k: int(np.sum(v)) for k, v in counters.items()}

    def run(self, seed: int):
        """Host-driven run to completion (the stepping-path oracle)."""
        st = self.init(seed)
        while self.readback(st)[0]:
            st = self.step(st, seed)
        return st


def make_stepper(sample_fn, check_fn, template: PyTree, init_carry: PyTree,
                 world: int, cfg, *,
                 substrate: "Substrate | str | None" = None,
                 frame_shards: int = 0, fold: Optional[int] = None,
                 mesh=None, mesh_axis: Optional[str] = None) -> EpochStepper:
    """Build an :class:`EpochStepper` for one engine configuration.

    Key derivation matches the run-to-completion substrates exactly: the
    logical worker streams are ``jax.random.split(key(seed), world·k)``
    (k = fold or 1), reshaped ``(world, k)`` so physical worker p carries
    logical streams ``p·k … p·k+k−1`` — with ``fold=None`` this degenerates
    to the historical ``split(key(seed), world)`` per-worker streams.  With
    ``fold`` set, ``init_carry`` must already be stacked ``(k, ...)`` per
    logical stream (None is fine).
    """
    import jax.numpy as jnp

    from .epoch import AXIS, make_program
    from .frames import axis_collectives, sequential_collectives

    sub = resolve_substrate(
        substrate if substrate is not None
        else getattr(cfg, "substrate", None), world)
    reason = unavailable_reason(sub, world)
    if reason is not None:
        raise RuntimeError(f"substrate {sub.value!r}: {reason}")
    k = fold or 1

    def worker_keys(seed: int):
        keys = jax.random.split(jax.random.key(seed), world * k)
        return keys.reshape(world, k) if fold is not None \
            else keys.reshape(world)

    wids = jnp.arange(world, dtype=jnp.int32)

    if sub == Substrate.SEQUENTIAL:
        colls = sequential_collectives()
        axis = None
        mesh = None
    elif sub == Substrate.VMAP:
        colls = axis_collectives(AXIS, world, frame_shards=frame_shards)
        axis = AXIS
        mesh = None
    else:  # SHARD_MAP
        mesh = mesh if mesh is not None else worker_mesh(world)
        axis = mesh_axis if mesh_axis is not None else mesh.axis_names[0]
        if mesh.shape[axis] != world:
            raise ValueError(f"mesh axis {axis!r} has size "
                             f"{mesh.shape[axis]}, expected world={world}")
        colls = axis_collectives(axis, world, frame_shards=frame_shards,
                                 grouped=True)

    def make_prog(seed_arr):
        return make_program(sample_fn, check_fn, template, cfg, colls,
                            seed_scalar=seed_arr, fold=fold)

    if sub == Substrate.SEQUENTIAL:
        def init_raw(seed_arr, keys):
            st = make_prog(seed_arr).init(keys[0], jnp.int32(0), init_carry)
            return jax.tree.map(lambda x: jnp.asarray(x)[None], st)

        def step_raw(st, seed_arr):
            inner = jax.tree.map(lambda x: x[0], st)
            out = make_prog(seed_arr).body(inner, jnp.int32(0))
            return jax.tree.map(lambda x: jnp.asarray(x)[None], out)
    elif sub == Substrate.VMAP:
        def init_raw(seed_arr, keys):
            p = make_prog(seed_arr)
            return jax.vmap(lambda kk, w: p.init(kk, w, init_carry),
                            axis_name=axis)(keys, wids)

        def step_raw(st, seed_arr):
            return jax.vmap(make_prog(seed_arr).body, axis_name=axis)(st, wids)
    else:
        from jax.sharding import PartitionSpec as P

        def _mapped(fn):
            return jax.shard_map(fn, mesh=mesh,
                                 in_specs=(P(axis), P(axis)),
                                 out_specs=P(axis), check_vma=False)

        def init_raw(seed_arr, keys):
            p = make_prog(seed_arr)

            def per_worker(kk, ws):
                st = p.init(kk[0], ws[0], init_carry)
                return jax.tree.map(lambda x: jnp.asarray(x)[None], st)

            return _mapped(per_worker)(keys, wids)

        def step_raw(st, seed_arr):
            p = make_prog(seed_arr)

            def per_worker(stw, ws):
                out = p.body(jax.tree.map(lambda x: x[0], stw), ws[0])
                return jax.tree.map(lambda x: jnp.asarray(x)[None], out)

            return _mapped(per_worker)(st, wids)

    step_jit = jax.jit(step_raw)
    init_jit = jax.jit(init_raw)

    def init_fn(seed: int):
        return init_jit(jnp.asarray(seed, jnp.uint32), worker_keys(seed))

    return EpochStepper(substrate=sub, world=world, cfg=cfg, fold=fold,
                        init_fn=init_fn, step_fn=step_jit)


def run_on_substrate(sample_fn, check_fn, template: PyTree,
                     init_carry: PyTree, seed: int, world: int, cfg,
                     *, substrate: "Substrate | str | None" = None,
                     frame_shards: int = 0, mesh=None,
                     mesh_axis: Optional[str] = None):
    """Run the epoch engine on the chosen substrate.

    Returns an :class:`~repro.core.epoch.EpochState` whose leaves are stacked
    per worker along a new leading axis of size ``world`` on **every**
    substrate (sequential results gain a leading axis of 1), so callers can
    treat the three substrates uniformly.

    ``substrate=None`` defers to ``cfg.substrate``, then to the historical
    default (sequential at W=1, vmap otherwise).  The per-worker RNG streams
    (``jax.random.split(key(seed), world)``) and the INDEXED_FRAME frame
    indices are substrate-independent by construction — that is what makes
    bit-identity across substrates possible at all.
    """
    from .epoch import run_sharded, run_virtual, run_worker
    from .frames import sequential_collectives

    import jax.numpy as jnp

    sub = resolve_substrate(
        substrate if substrate is not None
        else getattr(cfg, "substrate", None), world)
    reason = unavailable_reason(sub, world)
    if reason is not None:
        raise RuntimeError(f"substrate {sub.value!r}: {reason}")

    if sub == Substrate.VMAP:
        return run_virtual(sample_fn, check_fn, template, init_carry, seed,
                           world, cfg, frame_shards=frame_shards)
    if sub == Substrate.SHARD_MAP:
        mesh = mesh if mesh is not None else worker_mesh(world)
        axis = mesh_axis if mesh_axis is not None else mesh.axis_names[0]
        if mesh.shape[axis] != world:
            raise ValueError(
                f"mesh axis {axis!r} has size {mesh.shape[axis]}, "
                f"expected world={world}")
        return run_sharded(sample_fn, check_fn, template, init_carry, seed,
                           mesh, axis, cfg, frame_shards=frame_shards)
    # SEQUENTIAL: same key derivation as the mapped substrates (split once,
    # take worker 0) so W=1 results are bit-identical across substrates.
    key = jax.random.split(jax.random.key(seed), 1)[0]
    st = run_worker(sample_fn, check_fn, template, init_carry, key, cfg,
                    colls=sequential_collectives(),
                    seed_scalar=jnp.asarray(seed, jnp.uint32),
                    worker_id=jnp.int32(0))
    return jax.tree.map(lambda x: jnp.asarray(x)[None], st)
