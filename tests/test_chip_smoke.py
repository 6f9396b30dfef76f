"""``chip_smoke.py`` at tiny sizes on the CPU: phases b and c pass, ``main``
refuses to run without a TPU, and the compile-cache helper keeps one fixed
directory unless ``JAX_COMPILATION_CACHE_DIR`` decides."""

import importlib.util
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.core import instances
from repro.core.instances import WeightedSamplingInstance
from repro.launch.compile_cache import CACHE_DIR, enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def registry(monkeypatch):
    """Instances the smoke phases register vanish after the test."""
    monkeypatch.setattr(instances, "_REGISTRY", dict(instances._REGISTRY))


def test_phase_kadabra_tiny(smoke):
    g = smoke.kadabra_instance(64, 256, batch=8, compute_oracle=False,
                               name="kadabra-smoke")
    out = smoke.phase_kadabra(g, world=4)
    assert 0 < out["tau"] <= out["omega"]


def test_phase_kadabra_against_brandes_tiny(smoke):
    g = smoke.kadabra_instance(32, 96, batch=8, compute_oracle=True,
                               name="kadabra-smoke-brandes")
    out = smoke.phase_kadabra(g, world=4)
    assert out["max_abs_err"] <= smoke.EPS


def test_phase_scheduler_tiny(smoke, registry):
    g = smoke.kadabra_instance(64, 256, batch=8, compute_oracle=False,
                               name="kadabra-smoke")
    out = smoke.phase_scheduler(g, WeightedSamplingInstance(), world=4)
    assert out["steppers"] == 2


def test_main_refuses_without_tpu(smoke, capsys):
    assert jax.default_backend() != "tpu"
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_fails(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_compile_cache_follows_env(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(ROOT / "elsewhere"))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = enable_compile_cache()
        second = enable_compile_cache()
        assert first == second == CACHE_DIR == ROOT / ".jax_cache"
        assert jax.config.jax_compilation_cache_dir == str(CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
