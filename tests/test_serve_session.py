"""Serving sessions: checkpoint-resume bit-identity for all 5 strategies,
stepping-path ≡ fused-while-loop equivalence, and elastic W→W′ re-sharding
of SHARED_FRAME sessions.

The acceptance obligations of the serving subsystem:

* interrupt ANY strategy mid-run at an epoch boundary, checkpoint, restore,
  continue → (τ, data, estimate) are **bit-identical** to the uninterrupted
  run (trivial for INDEXED_FRAME, and required for LOCAL/SHARED because
  frame snapshots are values, not memory);
* an elastic W→W′ resume of a SHARED_FRAME session (W′ | W) yields the same
  (τ, estimate) as the uninterrupted W-worker run, while per-worker shard
  memory drops to Θ(n/W′).
"""

import dataclasses
import functools
import time

import jax
import numpy as np
import pytest

from repro.core.adaptive import run_adaptive
from repro.core.frames import FrameStrategy
from repro.core.instances import get_instance
from repro.serve import (AdaptiveSession, SessionSpec, StepperCache,
                         reshard_session)

INSTANCE = "wrs"            # fast: stops within a handful of epochs
ELASTIC_INSTANCE = "reachability"   # ≥3 epochs at W=4 — real mid-run
# (substrate, world) cells every host can run; shard_map joins at W=1 on a
# single device (real-collective lowering; W>1 runs under the CI serve-smoke
# job's forced-8-device flags through benchmarks.bench_serve).
CELLS = [("sequential", 1), ("vmap", 2), ("shard_map", 1)]

CACHE = StepperCache()      # share compiled steppers across all tests


@functools.lru_cache(maxsize=None)
def reference(instance, strategy, world, substrate, seed=0):
    """Uninterrupted session run (same stepper via the shared cache)."""
    spec = SessionSpec(instance, strategy, world=world, seed=seed,
                       substrate=substrate)
    s = AdaptiveSession.create(spec, cache=CACHE).start().run()
    est, res = s.result()
    return est, res


def _raw(x):
    if hasattr(x, "dtype") and jax.dtypes.issubdtype(x.dtype,
                                                     jax.dtypes.prng_key):
        x = jax.random.key_data(x)
    return np.asarray(x)


def tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(_raw(x), _raw(y))


@pytest.mark.parametrize("substrate,world", CELLS)
@pytest.mark.parametrize("strategy", [s.value for s in FrameStrategy])
def test_checkpoint_resume_bit_identical(tmp_path, strategy, substrate,
                                         world):
    """Interrupt mid-run at an epoch boundary → restore → finish: every
    field of the result matches the uninterrupted run bit-for-bit."""
    est_ref, res_ref = reference(INSTANCE, strategy, world, substrate)
    assert res_ref.epochs >= 2, "need a genuine mid-run epoch boundary"

    spec = SessionSpec(INSTANCE, strategy, world=world, substrate=substrate)
    s = AdaptiveSession.create(spec, cache=CACHE).start()
    s.step()                              # mid-run epoch boundary
    assert not s.done
    s.save(tmp_path)

    r = AdaptiveSession.restore(tmp_path, cache=CACHE)
    assert r.epoch == s.epoch and r.tau == s.tau
    tree_equal(r.state, s.state)          # the full pytree round-trips
    r.run()
    est, res = r.result()
    assert res.num == res_ref.num
    assert res.epochs == res_ref.epochs
    np.testing.assert_array_equal(est, est_ref)
    tree_equal(res.data, res_ref.data)


@pytest.mark.parametrize("strategy", [s.value for s in FrameStrategy])
def test_session_matches_fused_run_adaptive(strategy):
    """The host-driven stepping path must agree bit-for-bit with the fused
    while_loop path (run_adaptive) — same τ, data, and estimate."""
    world = 2
    est_s, res_s = reference(INSTANCE, strategy, world, "vmap")
    built = get_instance(INSTANCE).build(
        world=world, strategy=FrameStrategy(strategy))
    res_f = run_adaptive(built.sample_fn, built.check_fn, built.template,
                         strategy=strategy, world=world, seed=0,
                         rounds_per_epoch=built.rounds_per_epoch,
                         max_epochs=built.max_epochs, substrate="vmap")
    assert res_s.num == res_f.num
    tree_equal(res_s.data, res_f.data)
    est_f = built.estimate(built.trim(res_f.data), float(res_f.num))
    np.testing.assert_array_equal(est_s, est_f)


def test_restore_needs_only_the_directory(tmp_path):
    """The manifest meta carries the full spec: restore without any
    session object in hand."""
    spec = SessionSpec(INSTANCE, "local", world=2, seed=3, substrate="vmap")
    s = AdaptiveSession.create(spec, cache=CACHE).start()
    s.step()
    s.save(tmp_path)
    r = AdaptiveSession.restore(tmp_path)
    assert r.spec == spec
    assert r.epoch == s.epoch


def test_restore_empty_dir_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        AdaptiveSession.restore(tmp_path)


def test_spec_validation():
    with pytest.raises(ValueError):
        SessionSpec(INSTANCE, "warp")
    with pytest.raises(ValueError):
        SessionSpec(INSTANCE, "shared", world=3, logical_world=4)
    with pytest.raises(ValueError):
        SessionSpec(INSTANCE, "local", world=2, logical_world=4)
    assert SessionSpec(INSTANCE, "shared", world=2, logical_world=4).fold == 2
    assert SessionSpec(INSTANCE, "shared", world=2).fold is None


# ------------------------------------------------------------------ elastic

@pytest.mark.parametrize("new_world", [2, 1])
def test_elastic_reshard_matches_uninterrupted(new_world):
    """SHARED_FRAME W=4 → W′ resume: identical (τ, estimate, data) to the
    uninterrupted W=4 run, with per-worker shards of n/W′."""
    est_ref, res_ref = reference(ELASTIC_INSTANCE, "shared", 4, "vmap")

    spec = SessionSpec(ELASTIC_INSTANCE, "shared", world=4, substrate="vmap")
    s = AdaptiveSession.create(spec, cache=CACHE).start()
    s.step()                               # mid-run
    assert not s.done
    r = reshard_session(s, new_world, cache=CACHE)
    assert r.spec.world == new_world and r.spec.logical_world == 4
    # Θ(n/W′): each physical worker now holds 1/W′ of every vector leaf
    for leaf, old in zip(jax.tree.leaves(r.state.total.data),
                         jax.tree.leaves(s.state.total.data)):
        a, o = np.asarray(leaf), np.asarray(old)
        if o.ndim > 1:                     # vector leaves: (4, n/4) → (W′, n/W′)
            assert a.shape == (new_world, o.shape[1] * 4 // new_world)
    r.run()
    est, res = r.result()
    assert res.num == res_ref.num
    np.testing.assert_array_equal(est, est_ref)
    tree_equal(res.data, res_ref.data)


def test_elastic_chain_reshard():
    """4 → 2 → 1 re-shard chain continues the identical trajectory."""
    est_ref, res_ref = reference(ELASTIC_INSTANCE, "shared", 4, "vmap")
    s = AdaptiveSession.create(
        SessionSpec(ELASTIC_INSTANCE, "shared", world=4, substrate="vmap"),
        cache=CACHE).start()
    s.step()
    mid = reshard_session(s, 2, cache=CACHE)
    if not mid.done:
        mid.step()
    final = reshard_session(mid, 1, cache=CACHE)
    final.run()
    est, res = final.result()
    assert res.num == res_ref.num
    np.testing.assert_array_equal(est, est_ref)


def test_elastic_checkpoint_roundtrip(tmp_path):
    """A folded (resharded) session checkpoints and restores like any
    other — the spec's logical_world makes the layout self-describing."""
    est_ref, res_ref = reference(ELASTIC_INSTANCE, "shared", 4, "vmap")
    s = AdaptiveSession.create(
        SessionSpec(ELASTIC_INSTANCE, "shared", world=4, substrate="vmap"),
        cache=CACHE).start()
    s.step()
    r = reshard_session(s, 2, cache=CACHE)
    r.save(tmp_path)
    r2 = AdaptiveSession.restore(tmp_path, cache=CACHE)
    assert r2.spec.fold == 2
    r2.run()
    est, res = r2.result()
    assert res.num == res_ref.num
    np.testing.assert_array_equal(est, est_ref)


def test_elastic_rejects_invalid():
    s = AdaptiveSession.create(
        SessionSpec(INSTANCE, "local", world=2, substrate="vmap"),
        cache=CACHE).start()
    with pytest.raises(ValueError, match="SHARED_FRAME"):
        reshard_session(s, 1)
    sh = AdaptiveSession.create(
        SessionSpec(ELASTIC_INSTANCE, "shared", world=4, substrate="vmap"),
        cache=CACHE)
    with pytest.raises(ValueError, match="no state"):
        reshard_session(sh, 2)
    sh.start()
    with pytest.raises(ValueError, match="divide"):
        reshard_session(sh, 3)


def test_wall_s_covers_the_step_until_its_state_is_ready():
    """``wall_s`` counts a step until its verdict is on the host: a step
    whose device work takes 0.3 s after its dispatch has returned is
    charged all of it, and its state is ready when ``step()`` returns."""
    s = AdaptiveSession.create(SessionSpec(INSTANCE, "local", seed=11),
                               cache=CACHE).start()
    real = s.stepper.step_fn

    def late(x):
        time.sleep(0.3)
        return x

    delay = jax.jit(lambda x: jax.pure_callback(
        late, jax.ShapeDtypeStruct(x.shape, x.dtype), x))
    def step_fn(state, seed):
        out = real(state, seed)
        return out._replace(stop=delay(out.stop))

    s.stepper = dataclasses.replace(s.stepper, step_fn=step_fn)
    s.step()                      # compiles the delay
    wall0, t0 = s.wall_s, time.perf_counter()
    s.step()
    t1 = time.perf_counter()
    jax.block_until_ready(s.state)
    t2 = time.perf_counter()
    assert 0.3 <= s.wall_s - wall0 <= t1 - t0
    assert t2 - t1 < 0.1
