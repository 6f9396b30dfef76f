"""Elastic re-sharding: resume a session at a different worker width.

The paper's SHARED_FRAME strategy trades memory for bandwidth: each worker
keeps only a 1/F shard of the consistent state (Θ(n/F) instead of Θ(n)).
This module makes that trade-off *dynamic*: a SHARED_FRAME session started
at logical width W can be re-shard-resumed on W′ physical workers for any
W′ | W —

1. the consistent total is **reassembled** from the old per-worker shard
   layout round-robin across the redundant groups (PR 3's grouped-
   reassembly path, :func:`repro.core.adaptive.reassemble_shared`), then
   **re-scattered** into W′ contiguous shards of n/W′ each;
2. the W logical sampling streams (PRNG keys + carries) are *folded*
   k = W/W′ per physical worker (``core/epoch.make_program(fold=k)``), so
   every logical stream continues exactly where it left off;
3. pending delta frames are redistributed sum-preservingly (⊕ is
   commutative/associative over integer frames, and the next reduce-scatter
   only consumes the global sum).

Because the global per-epoch delta and the partition-independent stop
verdict are unchanged, the resumed run's (τ, estimate) is **bit-identical**
to the uninterrupted W-worker run — certified by
``tests/test_serve_session.py``.

Also home to the train-side :func:`elastic_restore`: restore a
model/optimizer checkpoint distributed per the *new* mesh's shardings.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import numpy as np

from ..checkpoint.manager import CheckpointManager
from ..core.adaptive import reassemble_shared
from ..core.frames import FrameStrategy
from .session import AdaptiveSession, SessionSpec, StepperCache

PyTree = Any


def elastic_restore(manager: CheckpointManager, tree_like: PyTree,
                    new_shardings: Optional[PyTree]
                    ) -> Optional[Tuple[int, PyTree, dict]]:
    """Restore the latest checkpoint distributed per ``new_shardings``
    (computed for the NEW mesh).  Returns (step, tree, meta) or None.

    Checkpoints are global-slice chunked (``checkpoint/manager.py``) and the
    data pipeline is stateless in ``(step, shard, n_shards)``, so changing
    the data-parallel world size between runs requires nothing beyond
    computing the new shardings and re-distributing."""
    return manager.restore_latest(tree_like, shardings=new_shardings)


def _redistribute(stacked: np.ndarray, new_world: int) -> np.ndarray:
    """Regroup per-worker leaves (P, ...) into (W′, ...) preserving the sum
    along axis 0 — old worker i's contribution lands on new worker i mod W′.
    Handles both down-scale (P > W′: fold-sum) and up-scale (P < W′:
    zero-pad)."""
    P = stacked.shape[0]
    pad = (-P) % new_world
    if pad:
        stacked = np.concatenate(
            [stacked, np.zeros((pad,) + stacked.shape[1:], stacked.dtype)])
    return stacked.reshape(-1, new_world, *stacked.shape[1:]).sum(
        axis=0, dtype=stacked.dtype)


def reshard_state(state, *, old_spec: SessionSpec, new_spec: SessionSpec,
                  template_state) -> Any:
    """Transform a SHARED_FRAME stacked :class:`EpochState` from the old
    physical layout to the new one (see module docstring for the algebra).
    ``template_state`` supplies the new layout's aux shapes (aux is
    recomputed at the next check; it is re-zeroed here)."""
    P = old_spec.world
    W2 = new_spec.world
    lw = old_spec.logical_world or old_spec.world
    F_old = old_spec.frame_shards or P

    def first(x):
        return np.asarray(x)[0]

    # 1. sampling streams: (P[, k_old]) keys → (lw,) logical → (W2[, k]).
    raw = np.asarray(jax.random.key_data(state.key))
    raw = raw.reshape(lw, *raw.shape[-1:])
    new_keys = raw.reshape(W2, lw // W2, -1) if W2 != lw \
        else raw.reshape(lw, -1)
    key = jax.random.wrap_key_data(jax.numpy.asarray(new_keys))

    def regroup_carry(x):
        a = np.asarray(x)
        a = a.reshape(lw, *a.shape[2:]) if a.ndim >= 2 and \
            a.shape[0] == P and old_spec.fold is not None else a
        assert a.shape[0] == lw, (a.shape, lw)
        return a.reshape(W2, lw // W2, *a.shape[1:]) if W2 != lw \
            else a
    carry = jax.tree.map(regroup_carry, state.carry) \
        if state.carry is not None else None

    # 2. consistent total: reassemble old shards → full → contiguous W′
    # blocks (the layout tiled psum_scatter produces).
    def rescatter(x):
        full = reassemble_shared(np.asarray(x), P, F_old)
        if full.ndim == 0:
            return np.broadcast_to(full, (W2,)).copy()
        assert full.shape[0] % W2 == 0, (full.shape, W2)
        return full.reshape(W2, full.shape[0] // W2, *full.shape[1:])
    total_data = jax.tree.map(rescatter, state.total.data)
    total_num = np.broadcast_to(first(state.total.num), (W2,)).copy()

    # 3. pending deltas: full-size per-worker frames; any sum-preserving
    # redistribution is equivalent under the next reduce-scatter.
    pending_data = jax.tree.map(
        lambda x: _redistribute(np.asarray(x), W2), state.pending.data)
    pending_num = _redistribute(np.asarray(state.pending.num), W2)

    # 4. replicated scalars re-tile; aux re-zeros in the new shard shape.
    def tile(x):
        return np.broadcast_to(first(x), (W2,) + np.asarray(x).shape[1:]).copy()

    aux = jax.tree.map(
        lambda s: np.zeros(s.shape, s.dtype), template_state.aux)
    return template_state.__class__(
        key=key, carry=carry,
        total=state.total.__class__(num=jax.numpy.asarray(total_num),
                                    data=jax.tree.map(jax.numpy.asarray,
                                                      total_data)),
        pending=state.pending.__class__(
            num=jax.numpy.asarray(pending_num),
            data=jax.tree.map(jax.numpy.asarray, pending_data)),
        stop=jax.numpy.asarray(tile(state.stop)),
        aux=jax.tree.map(jax.numpy.asarray, aux),
        epoch=jax.numpy.asarray(tile(state.epoch)),
        stop_epoch=jax.numpy.asarray(tile(state.stop_epoch)))


def reshard_session(session: AdaptiveSession, new_world: int, *,
                    substrate: Optional[str] = None,
                    placement: Optional[tuple] = None,
                    cache: Optional[StepperCache] = None) -> AdaptiveSession:
    """Resume ``session`` on ``new_world`` physical workers (SHARED_FRAME).

    ``new_world`` must divide the session's logical width; the returned
    session continues the identical logical trajectory — per-worker shard
    memory becomes Θ(n/W′) — and its final (τ, estimate) is bit-identical
    to the uninterrupted original run.

    ``placement`` pins the resharded session to specific device ids (a
    ``shard_map`` submesh — e.g. the leading half of the lease a
    pressure-driven shrink keeps, see :mod:`repro.serve.placement`); it
    implies ``substrate="shard_map"``.
    """
    spec = session.spec
    if spec.frame_strategy != FrameStrategy.SHARED_FRAME:
        raise ValueError("elastic re-sharding is defined for SHARED_FRAME "
                         f"sessions (got {spec.strategy!r})")
    if not session.started:
        raise ValueError("session has no state to reshard; start() it or "
                         "restore a checkpoint first")
    lw = spec.logical_world or spec.world
    if lw % new_world != 0:
        raise ValueError(f"new_world={new_world} must divide the session's "
                         f"logical world {lw}")
    if placement is not None:
        substrate = "shard_map"
    new_spec = dataclasses.replace(
        spec, world=new_world, logical_world=lw,
        frame_shards=0,            # one contiguous shard per new worker
        placement=None if placement is None else tuple(placement),
        substrate=substrate if substrate is not None else
        (None if new_world != spec.world else spec.substrate))
    resharded = AdaptiveSession.create(new_spec, cache=cache)
    resharded.state = reshard_state(
        session.state, old_spec=spec, new_spec=new_spec,
        template_state=resharded.state_template())
    resharded.wall_s = session.wall_s
    resharded.query = session.query
    return resharded
