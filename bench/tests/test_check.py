"""The check on a tiny cell on the CPU: a sound run is correct; the
control and each fault the cells can have are not."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import check
from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]


def _four_workers(fault: str) -> dict:
    """A four-worker run on four virtual CPU devices, in a process of its
    own: the device count is fixed when JAX starts."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join([str(ROOT), str(ROOT / "src")])}
    r = subprocess.run([sys.executable, "-m", "bench.tests.tiny", "4", fault],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.splitlines()[-1])


def test_sound_run_is_correct():
    correct, checked = check.verdict(tiny.values(1))
    assert correct, checked
    assert checked["replay_l1"]["value"] == 0


def test_control_is_not_correct():
    """The reference in bfloat16, put in the program's place."""
    correct, checked = check.verdict(tiny.values(1, control=True))
    assert not correct, checked


@pytest.mark.parametrize("fault", ["frozen", "half_batch", "altered"])
def test_fault_is_not_correct(fault, monkeypatch):
    tiny.plant(fault, monkeypatch.setattr)
    correct, checked = check.verdict(tiny.values(1))
    assert not correct, checked


@pytest.mark.parametrize("fault", ["none", "control", *tiny.FAULTS[1:]])
def test_four_workers(fault):
    """The four-worker cell: sound, its control, and every fault."""
    correct, checked = check.verdict(_four_workers(fault))
    assert correct == (fault == "none"), checked
