"""End-to-end system tests: training loop with checkpoint/restart + failure
injection, serve loop, sharded epoch engine on a mesh, and a subprocess
mini dry-run."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]


def run_cli(mod, *args, timeout=600):
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    return subprocess.run(
        [sys.executable, "-m", mod, *args], capture_output=True, text=True,
        env=env, timeout=timeout, cwd=ROOT)


def test_train_loop_end_to_end(tmp_path):
    r = run_cli("repro.launch.train", "--arch", "smollm-360m-reduced",
                "--steps", "8", "--batch", "4", "--seq", "32",
                "--micro", "2", "--ckpt-dir", str(tmp_path),
                "--ckpt-every", "4", "--log-every", "4")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "done" in r.stdout
    from repro.checkpoint import latest_step
    assert latest_step(tmp_path) == 8


def test_train_resume_after_preemption(tmp_path):
    r1 = run_cli("repro.launch.train", "--arch", "smollm-360m-reduced",
                 "--steps", "10", "--batch", "4", "--seq", "32",
                 "--micro", "1", "--ckpt-dir", str(tmp_path),
                 "--ckpt-every", "3", "--preempt-at", "5")
    assert r1.returncode == 0, r1.stderr[-2000:]
    assert "PREEMPTION" in r1.stdout
    r2 = run_cli("repro.launch.train", "--arch", "smollm-360m-reduced",
                 "--steps", "10", "--batch", "4", "--seq", "32",
                 "--micro", "1", "--ckpt-dir", str(tmp_path), "--resume")
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "resumed at step 5" in r2.stdout
    assert "done" in r2.stdout


def test_serve_generate_and_adaptive_eval():
    r = run_cli("repro.launch.serve", "--arch", "smollm-360m-reduced",
                "--batch", "2", "--prompt-len", "8", "--gen", "4")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "generated" in r.stdout
    r = run_cli("repro.launch.serve", "--arch", "smollm-360m-reduced",
                "--adaptive-eval", "--eps", "0.5", "--delta", "0.2",
                "--seq", "16", "--batch", "2")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "adaptive eval" in r.stdout


@pytest.mark.slow
def test_mini_dryrun_subprocess():
    """The real dry-run entrypoint on the smallest cell (512 virtual
    devices in a subprocess — must not leak into this process)."""
    r = run_cli("repro.launch.dryrun", "--arch", "smollm-360m",
                "--shape", "decode_32k", "--no-diff", timeout=900)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    assert "memory_analysis" in r.stdout
    assert len(jax.devices()) == 1  # flag must not leak


def test_dryrun_import_leaves_xla_flags(monkeypatch):
    """Only the dry-run's ``__main__`` forces 512 host devices."""
    import importlib
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
    importlib.reload(importlib.import_module("repro.launch.dryrun"))
    assert os.environ["XLA_FLAGS"] == "--xla_force_host_platform_device_count=1"


@pytest.mark.parametrize("build", ["worker_mesh", "make_mesh"])
def test_meshes_have_auto_axes(build):
    """jax.make_mesh defaults to Explicit axes; the engine's meshes are
    Auto so shard_map and jit shard as before."""
    from jax.sharding import AxisType
    if build == "worker_mesh":
        from repro.core.substrate import worker_mesh
        mesh = worker_mesh(1)
    else:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1, 1), ("data", "model"))
    assert set(mesh.axis_types) == {AxisType.Auto}


def test_sharded_epoch_engine_on_mesh():
    """run_sharded on a 1-device mesh (semantics identical to vmap path)."""
    from repro.core.epoch import EpochConfig, run_sharded
    from repro.core.frames import FrameStrategy, StateFrame
    from repro.core.stopping import HoeffdingCondition

    def sample_fn(key, carry):
        x = (jax.random.uniform(key, (4, 8)) < 0.5).astype(jnp.int32)
        return StateFrame(num=jnp.int32(4), data=x.sum(0)), carry

    mesh = jax.make_mesh((1,), ("workers",))
    cfg = EpochConfig(strategy=FrameStrategy.LOCAL_FRAME,
                      rounds_per_epoch=2, max_epochs=500)
    st = run_sharded(sample_fn, HoeffdingCondition(eps=0.1, delta=0.1),
                     jnp.zeros((8,), jnp.int32), None, 0, mesh, "workers",
                     cfg)
    assert bool(np.asarray(st.stop).reshape(-1)[0])
    assert int(np.asarray(st.total.num).reshape(-1)[0]) >= 149
