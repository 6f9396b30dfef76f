"""The check on tiny cells on the CPU: a sound run is correct; the control
and each fault the cells can have are not.  Each runs on a tiny Graph500
graph and on a tiny random geometric graph (DIMACS10 rgg), whose paths are
some 20 steps long."""

import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import cell as cellmod, check
from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]
GRAPHS = tiny.GRAPHS


def _four_workers(graph: str, fault: str) -> dict:
    """A run on four virtual CPU devices, in a process of its own: the
    device count is fixed when JAX starts."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join([str(ROOT), str(ROOT / "src")])}
    r = subprocess.run([sys.executable, "-m", "bench.tests.tiny", "4",
                        fault, graph],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.splitlines()[-1])


@pytest.mark.parametrize("graph", GRAPHS)
def test_sound_run_is_correct(graph):
    correct, checked = check.verdict(tiny.values(**GRAPHS[graph]))
    assert correct, checked
    assert checked["replay_l1"]["value"] == 0


@pytest.mark.parametrize("graph", GRAPHS)
def test_control_is_not_correct(graph):
    """The reference in bfloat16, put in the program's place."""
    correct, checked = check.verdict(tiny.values(**GRAPHS[graph],
                                                 control=True))
    assert not correct, checked


@pytest.mark.parametrize("graph", GRAPHS)
@pytest.mark.parametrize("fault", ["frozen", "half_batch", "altered"])
def test_fault_is_not_correct(fault, graph, monkeypatch):
    tiny.plant(fault, monkeypatch.setattr)
    correct, checked = check.verdict(tiny.values(**GRAPHS[graph]))
    assert not correct, checked


@pytest.mark.parametrize("graph", GRAPHS)
@pytest.mark.parametrize("fault", ["none", "control", *tiny.FAULTS[1:]])
def test_four_workers(fault, graph):
    """The query on four chips, laid out by the harness as four workers
    under ``shard_map``: sound, its control, and every fault."""
    correct, checked = check.verdict(_four_workers(graph, fault))
    assert correct == (fault == "none"), checked


def test_cell_lays_out_one_worker_per_chip():
    one = cellmod.load("g500-bc.1chip")
    four = dataclasses.replace(one, chips=4)
    assert one.substrate == "sequential"
    assert four.substrate == "shard_map"
    assert "world" not in one.config and "substrate" not in one.config
