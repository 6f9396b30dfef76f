"""``bench/run.py`` refuses to run without TPU chips, and without the
program beside it; in both cases it prints no result line."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "g500-bc.1chip", "--seed", "3000000007",
        "--seconds", "1", "--trace", "0"]


def _run(cwd: Path, **env):
    return subprocess.run(
        [sys.executable, "bench/run.py", *ARGS], cwd=cwd, capture_output=True,
        text=True, timeout=300, env={**os.environ, **env})


def _no_result(out: str) -> bool:
    return not any(line.lstrip().startswith("{") for line in out.splitlines())


def test_cpu_only_exits_nonzero():
    r = _run(ROOT, JAX_PLATFORMS="cpu")
    assert r.returncode != 0
    assert _no_result(r.stdout)
    assert "needs TPU chips" in r.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path, JAX_PLATFORMS="cpu")
    assert r.returncode != 0
    assert _no_result(r.stdout)
    assert "No module named 'repro'" in r.stderr
