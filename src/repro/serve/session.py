"""Checkpointable adaptive-sampling sessions.

An :class:`AdaptiveSession` is one running query against the epoch engine,
driven one epoch at a time from the host (``core/substrate.make_stepper``).
Its full resumable state is the per-worker-stacked
:class:`~repro.core.epoch.EpochState` pytree — epoch index, τ, accumulated
frame totals (shards for SHARED_FRAME), pending delta frames, PRNG carry,
stop verdict — plus the frozen :class:`SessionSpec` (strategy / W / F /
substrate / seed / instance name).

The proof obligation of the serving layer: **save → restore → run ≡ run**,
bit-identically, for every strategy.  This is trivial for INDEXED_FRAME
(frames are pure functions of their index) and holds for LOCAL/SHARED
because frame snapshots are *values*, not memory locations — a checkpoint
written at an epoch boundary captures the entire cross-worker contract (the
consistent total plus each worker's not-yet-reduced pending delta), so the
resumed trajectory replays the identical sequence of collectives.

Checkpoints go through :mod:`repro.checkpoint.manager` (global-slice
chunked, CRC'd, atomic-rename) with the spec in the manifest ``meta`` —
``AdaptiveSession.restore(dir)`` needs nothing but the directory.
"""

from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint.manager import (latest_step, load_checkpoint, read_meta,
                                  save_checkpoint)
from ..core.adaptive import AdaptiveResult, result_from_state
from ..core.epoch import EpochConfig
from ..core.frames import FrameStrategy
from ..core.instances import BuiltInstance, get_instance
from ..core.substrate import EpochStepper, make_stepper
from ..runtime.spans import count, span

PyTree = Any


@dataclasses.dataclass(frozen=True)
class SessionSpec:
    """Frozen description of one query — everything needed to (re)build its
    engine program.  ``instance`` must be a registered workload name so a
    restore can rebuild the sampler from the manifest alone.

    ``logical_world`` is the worker count the sampling streams were *keyed*
    for; it differs from ``world`` only after an elastic re-shard
    (``world`` physical workers each fold ``logical_world/world`` logical
    streams — see :mod:`repro.serve.elastic`).  0 means "same as world".

    ``placement`` pins a ``shard_map`` session to specific device ids — the
    submesh the placement pool leased it (:mod:`repro.serve.placement`).
    ``None`` keeps the historical leading-devices mesh.  Recorded in the
    checkpoint manifest so a resume can re-lease equivalent devices.
    """

    instance: str
    strategy: str = "local"
    world: int = 1
    seed: int = 0
    substrate: Optional[str] = None
    frame_shards: int = 0
    logical_world: int = 0
    placement: Optional[Tuple[int, ...]] = None

    def __post_init__(self):
        FrameStrategy(self.strategy)  # validate early
        lw = self.logical_world or self.world
        if lw % self.world != 0:
            raise ValueError(
                f"world={self.world} must divide logical_world={lw}")
        if lw != self.world and \
                FrameStrategy(self.strategy) != FrameStrategy.SHARED_FRAME:
            raise ValueError("folded execution (logical_world != world) is "
                             "an elastic SHARED_FRAME feature")
        if self.placement is not None:
            object.__setattr__(self, "placement", tuple(self.placement))
            if self.substrate != "shard_map":
                raise ValueError("placement pins devices and is only "
                                 "meaningful for substrate='shard_map' "
                                 f"(got {self.substrate!r})")
            if len(self.placement) != self.world:
                raise ValueError(
                    f"placement names {len(self.placement)} device(s) for "
                    f"world={self.world}")

    @property
    def fold(self) -> Optional[int]:
        lw = self.logical_world or self.world
        return None if lw == self.world else lw // self.world

    @property
    def frame_strategy(self) -> FrameStrategy:
        return FrameStrategy(self.strategy)

    def stepper_key(self) -> tuple:
        """Cache key for compiled steppers: everything that changes the
        traced program *or the devices it is bound to*.  The seed is
        deliberately absent — it is a traced scalar of the step function, so
        differently-seeded queries of the same shape share one compilation.
        The placement (mesh device ids) and worker-axis name are present:
        two same-shape sessions on disjoint submeshes must NOT share a
        compiled stepper, or one of them would silently run on the other's
        devices."""
        from ..core.substrate import WORKER_AXIS
        return (self.instance, self.strategy, self.world, self.frame_shards,
                self.substrate, self.logical_world, self.placement,
                WORKER_AXIS)

    def as_meta(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_meta(cls, meta: Dict[str, Any]) -> "SessionSpec":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in meta.items() if k in fields})

    @classmethod
    def parse(cls, spec: str) -> "SessionSpec":
        """Parse the CLI grammar ``instance:strategy:world[:seed]`` (the one
        parser both ``launch.serve --pool`` and ``benchmarks.bench_serve``
        use)."""
        parts = spec.split(":")
        if not 2 <= len(parts) <= 4:
            raise ValueError(f"query spec {spec!r} is not "
                             f"instance:strategy:world[:seed]")
        return cls(instance=parts[0], strategy=parts[1],
                   world=int(parts[2]) if len(parts) > 2 else 1,
                   seed=int(parts[3]) if len(parts) > 3 else 0)


class StepperCache:
    """Shared (built instance, compiled stepper) per session shape.

    One scheduler owns one cache; all queries with the same
    :meth:`SessionSpec.stepper_key` reuse the same jitted single-epoch step,
    so admitting a query of an already-seen shape costs no compilation.
    """

    def __init__(self):
        self._cache: Dict[tuple, Tuple[BuiltInstance, EpochStepper]] = {}

    def get(self, spec: SessionSpec) -> Tuple[BuiltInstance, EpochStepper]:
        key = spec.stepper_key()
        if key not in self._cache:
            with span("session.build", instance=spec.instance):
                self._cache[key] = _build(spec)
        return self._cache[key]

    def __len__(self) -> int:
        return len(self._cache)


def _build(spec: SessionSpec) -> Tuple[BuiltInstance, EpochStepper]:
    inst = get_instance(spec.instance)
    lw = spec.logical_world or spec.world
    # build() pads SHARED frames for the LOGICAL world; every W' | lw then
    # divides the padded length, so any elastic width shards evenly.
    built = inst.build(world=lw, strategy=spec.frame_strategy)
    cfg = EpochConfig(strategy=spec.frame_strategy,
                      rounds_per_epoch=built.rounds_per_epoch,
                      max_epochs=built.max_epochs)
    k = spec.fold
    init_carry = built.init_carry
    if k is not None and init_carry is not None:
        init_carry = jax.tree.map(
            lambda x: jnp.stack([jnp.asarray(x)] * k), init_carry)
    mesh = None
    if spec.placement is not None:
        from ..core.substrate import worker_mesh
        from .placement import lease_devices
        mesh = worker_mesh(spec.world, devices=lease_devices(spec.placement))
    stepper = make_stepper(built.sample_fn, built.check_fn, built.template,
                           init_carry, spec.world, cfg,
                           substrate=spec.substrate,
                           frame_shards=spec.frame_shards, fold=k, mesh=mesh)
    return built, stepper


def _state_to_tree(state) -> PyTree:
    """Checkpoint form: typed PRNG keys become raw uint32 key data."""
    return state._replace(key=jax.random.key_data(state.key))


def _tree_to_state(tree):
    return tree._replace(key=jax.random.wrap_key_data(tree.key))


class AdaptiveSession:
    """One query: spec + engine state + the stepper that advances it.

    Lifecycle::

        s = AdaptiveSession.create(SessionSpec("kadabra", "shared", world=4))
        s.start()
        while not s.done:
            s.step()                  # one epoch (the scheduler's unit)
        estimate, result = s.result()

        s.save(ckpt_dir)              # any epoch boundary
        r = AdaptiveSession.restore(ckpt_dir)
        # r continues bit-identically to an uninterrupted s

    Host spans ``session.init`` and ``session.step`` (children
    ``session.dispatch`` and ``session.readback``) carry ``query``; each
    ends once the verdict is on the host, and counts what the sampler's
    counters gained in it.
    """

    def __init__(self, spec: SessionSpec, built: BuiltInstance,
                 stepper: EpochStepper):
        self.spec = spec
        self.built = built
        self.stepper = stepper
        self.state = None
        self.query = f"{spec.instance}:{spec.seed}"   # the spans' query id
        # seconds in session.init and session.step spans: from the dispatch
        # until the verdict is on the host
        self.wall_s = 0.0

    @property
    def state(self):
        return self._state

    @state.setter
    def state(self, state) -> None:
        self._state = state
        self._host = None             # (active, counters), read back once

    def _readback(self) -> tuple:
        if self._host is None:
            self._host = self.stepper.readback(self._state)
        return self._host

    @classmethod
    def create(cls, spec: SessionSpec,
               cache: Optional[StepperCache] = None) -> "AdaptiveSession":
        built, stepper = cache.get(spec) if cache is not None \
            else _build(spec)
        return cls(spec, built, stepper)

    def rebind_placement(self, placement: "Tuple[int, ...] | None",
                         cache: Optional[StepperCache] = None
                         ) -> "AdaptiveSession":
        """Re-bind this session to a different leased submesh (same shape).

        The inter-epoch state is a value pytree, so *which* devices execute
        the next epoch cannot change the trajectory — rebinding swaps the
        stepper (new mesh, possibly a fresh compile via the cache) and keeps
        the state; the next ``step()`` transfers it to the new devices.
        Used on resume/admission when the original devices are taken or
        gone and the pool leased equivalent ones.
        """
        placement = None if placement is None else tuple(placement)
        if placement == self.spec.placement:
            return self
        self.spec = dataclasses.replace(self.spec, placement=placement)
        self.built, self.stepper = cache.get(self.spec) \
            if cache is not None else _build(self.spec)
        return self

    # ------------------------------------------------------------- running
    def start(self) -> "AdaptiveSession":
        with span("session.init", query=self.query) as s:
            self.state = self.stepper.init(self.spec.seed)
            for name, value in self._readback()[1].items():
                count(name, value)
        self.wall_s += s.seconds
        return self

    @property
    def started(self) -> bool:
        return self.state is not None

    @property
    def done(self) -> bool:
        return self.started and not self._readback()[0]

    @property
    def epoch(self) -> int:
        assert self.started
        return int(np.asarray(self.state.epoch).reshape(-1)[0])

    @property
    def tau(self) -> int:
        """Samples in the *checked* consistent state (the paper's τ)."""
        assert self.started
        return int(np.asarray(self.state.total.num).reshape(-1)[0])

    def step(self) -> bool:
        """Advance one epoch; returns ``done``.  No-op once stopped."""
        assert self.started, "call start() (or restore) first"
        if self.done:
            return True
        before = self._readback()[1]
        with span("session.step", query=self.query) as s:
            with span("session.dispatch"):
                state = self.stepper.step(self.state, self.spec.seed)
            with span("session.readback"):
                self.state = state
                after = self._readback()[1]
            for name, value in after.items():
                count(name, value - before.get(name, 0))
        self.wall_s += s.seconds
        return self.done

    def run(self) -> "AdaptiveSession":
        while not self.done:
            self.step()
        return self

    def result(self) -> Tuple[np.ndarray, AdaptiveResult]:
        """(estimate, AdaptiveResult) from the current consistent state."""
        assert self.started
        res = result_from_state(self.state, strategy=self.spec.frame_strategy,
                                world=self.spec.world,
                                frame_shards=self.spec.frame_shards)
        est = self.built.estimate(self.built.trim(res.data),
                                  float(max(res.num, 1)))
        return est, res

    # -------------------------------------------------------- checkpointing
    def state_template(self) -> PyTree:
        """Shape/dtype skeleton of the checkpoint tree (no FLOPs)."""
        sds = jax.eval_shape(self.stepper.init_fn, self.spec.seed)
        return _state_to_tree_sds(sds)

    def save(self, directory: "str | Path") -> Path:
        """Atomic checkpoint at the current epoch boundary."""
        assert self.started, "nothing to save before start()"
        return save_checkpoint(
            _state_to_tree(self.state), directory, step=self.epoch,
            meta={"spec": self.spec.as_meta(), "kind": "adaptive-session",
                  "tau": self.tau, "wall_s": self.wall_s})

    @classmethod
    def restore(cls, directory: "str | Path", step: Optional[int] = None,
                cache: Optional[StepperCache] = None,
                placement: Any = "keep") -> "AdaptiveSession":
        """Rebuild from a checkpoint directory.  ``placement`` overrides the
        manifest's recorded device ids (pass ``None`` to drop the pin, a
        tuple to re-lease onto different devices) — the state layout is
        placement-independent, so the override is always sound; the default
        ``"keep"`` restores onto the recorded submesh."""
        directory = Path(directory)
        if step is None:
            step = latest_step(directory)
            if step is None:
                raise FileNotFoundError(f"no complete checkpoint in "
                                        f"{directory}")
        meta = read_meta(directory, step)
        spec = SessionSpec.from_meta(meta["spec"])
        if not (isinstance(placement, str) and placement == "keep"):
            spec = dataclasses.replace(
                spec, placement=None if placement is None
                else tuple(placement))
        session = cls.create(spec, cache=cache)
        tree, _meta = load_checkpoint(session.state_template(), directory,
                                      step)
        session.state = _tree_to_state(tree)
        # pre-preemption stepping time carries over so latency accounting
        # (and us_per_call > 0 in BENCH_serve rows) survives a resume.
        session.wall_s = float(meta.get("wall_s", 0.0))
        return session


def _state_to_tree_sds(sds):
    """eval_shape analog of :func:`_state_to_tree` (typed key SDS → raw)."""
    key_sds = jax.eval_shape(jax.random.key_data, sds.key)
    return sds._replace(key=key_sds)
