"""Substrate equivalence: sequential / vmap / shard_map executions of the
epoch engine must agree bit-for-bit on every (instance, strategy, W, F) cell.

Three layers of coverage:

* In-process grid over every registered instance at the world sizes this
  host can actually cross-check (W=1 everywhere — sequential, vmap, and a
  1-device shard_map mesh; larger W joins when the process has ≥ W devices,
  i.e. under the CI substrate job's
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
* A subprocess that forces 8 host devices and runs the grouped F < W cells
  under real shard_map collectives — so the single-device fast tier still
  exercises grouped reduce-scatter + cross-group all-reduce on every run.
* A lowering check (in the same subprocess) that the shard_map F < W path
  emits a real grouped ``reduce_scatter`` — not the vmap psum+slice
  reference form.
"""

import functools
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.core.conformance import (EQUIVALENCE_WORLDS, equivalence_grid,
                                    run_substrate_equivalence)
from repro.core.frames import FrameStrategy
from repro.core.substrate import (Substrate, available_substrates,
                                  unavailable_reason)

ROOT = Path(__file__).resolve().parents[1]
INSTANCES = ("kadabra", "triangles", "reachability", "wrs", "diameter",
             "gradvar")

# Only sweep worlds this process can cross-check on ≥ 2 substrates: W=1
# always; W>1 joins when shard_map has enough devices (the CI substrate job
# forces 8).  Running vmap-only cells would compare nothing.
WORLDS = tuple(w for w in EQUIVALENCE_WORLDS
               if w == 1 or len(jax.devices()) >= w)
REQUIRE_ALL = os.environ.get("SUBSTRATE_REQUIRE_ALL", "") == "1"


@functools.lru_cache(maxsize=None)
def report(name):
    return run_substrate_equivalence(name, worlds=WORLDS,
                                     require_all=REQUIRE_ALL)


def test_substrate_enum_availability():
    assert unavailable_reason(Substrate.VMAP, 8) is None
    assert unavailable_reason(Substrate.SEQUENTIAL, 2) is not None
    assert Substrate.SEQUENTIAL in available_substrates(1)
    assert Substrate.VMAP in available_substrates(64)
    many = len(jax.devices()) + 1
    assert Substrate.SHARD_MAP not in available_substrates(many)


def test_equivalence_grid_shape():
    cells = equivalence_grid((1, 2, 4, 8))
    assert len(cells) == len(FrameStrategy) * 4 + 3  # + SHARED F=W/2 cells
    assert (FrameStrategy.SHARED_FRAME, 8, 4) in cells
    assert (FrameStrategy.SHARED_FRAME, 1, 0) in cells


@pytest.mark.parametrize("world", WORLDS)
@pytest.mark.parametrize("strategy", list(FrameStrategy),
                         ids=lambda s: s.name)
@pytest.mark.parametrize("instance", INSTANCES)
def test_cell_bit_identical_across_substrates(instance, strategy, world):
    rep = report(instance)
    cells = [c for c in rep.cells
             if c.strategy == strategy and c.world == world]
    assert cells, "grid must cover the cell"
    for cell in cells:  # includes the SHARED F=W/2 cell where it exists
        assert cell.ok, "\n".join(cell.failures)
        assert cell.compared >= (1 if world == 1 else 0)


@pytest.mark.parametrize("instance", INSTANCES)
def test_w1_oracle_joins_comparison(instance):
    """At W=1 all three substrates run and agree (the sequential oracle is
    part of the comparison, not just vmap vs vmap)."""
    rep = report(instance)
    for cell in rep.cells:
        if cell.world != 1:
            continue
        assert "sequential" in cell.ran and "vmap" in cell.ran
        assert "shard_map" in cell.ran  # 1-device mesh works everywhere
        assert cell.ok, "\n".join(cell.failures)


# --------------------------------------------------------------- subprocess
# Real grouped collectives need >1 device; force 8 virtual host devices in a
# child process (the flag must precede the first jax import and must not
# leak into this one — see tests/test_system.py).

_GROUPED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
assert len(jax.devices()) == 8

from repro.core.conformance import run_substrate_equivalence
from repro.core.frames import FrameStrategy

rep = run_substrate_equivalence(
    "reachability",
    strategies=[FrameStrategy.LOCAL_FRAME, FrameStrategy.SHARED_FRAME],
    worlds=(4,), require_all=True)
print(rep.summary())
assert rep.ok, rep.failures
cells = {(c.strategy, c.world, c.frame_shards): c for c in rep.cells}
grouped = cells[(FrameStrategy.SHARED_FRAME, 4, 2)]
assert "shard_map" in grouped.ran and "vmap" in grouped.ran

# Lowering proof: the F < W shard_map path must emit a grouped
# reduce-scatter (axis_index_groups), not the psum+slice reference form.
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core.frames import StateFrame, axis_collectives
from repro.core.substrate import worker_mesh

mesh = worker_mesh(4)
colls = axis_collectives("workers", 4, frame_shards=2, grouped=True)

def scatter(x):
    f = StateFrame(num=jnp.int32(1), data=x[0])
    out = colls.scatter_frames(f)
    return out.data[None]

fn = jax.shard_map(scatter, mesh=mesh, in_specs=P("workers"),
                   out_specs=P("workers"), check_vma=False)
text = jax.jit(fn).lower(jnp.zeros((4, 8), jnp.int32)).as_text()
assert "reduce_scatter" in text, "grouped path must lower to reduce_scatter"
print("GROUPED_SUBSTRATE_OK")
"""


@pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="grouped F<W lowering needs ≥4 devices (CI substrate job)")
def test_grouped_lowering_emits_reduce_scatter():
    """In-process version of the subprocess lowering proof: the shard_map
    F < W path must be the grouped reduce-scatter, not psum+slice."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core.frames import StateFrame, axis_collectives
    from repro.core.substrate import worker_mesh

    mesh = worker_mesh(4)
    colls = axis_collectives("workers", 4, frame_shards=2, grouped=True)

    def scatter(x):
        out = colls.scatter_frames(StateFrame(num=jnp.int32(1), data=x[0]))
        return out.data[None]

    fn = jax.shard_map(scatter, mesh=mesh, in_specs=P("workers"),
                       out_specs=P("workers"), check_vma=False)
    text = jax.jit(fn).lower(jnp.zeros((4, 8), jnp.int32)).as_text()
    assert "reduce_scatter" in text


@pytest.mark.skipif(
    len(jax.devices()) >= 8,
    reason="parent already runs the grouped W>1 cells in-process (CI "
           "substrate-shardmap job) — the subprocess would just repeat them")
def test_grouped_collectives_under_forced_multidevice():
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _GROUPED_SCRIPT],
                       capture_output=True, text=True, env=env,
                       timeout=600, cwd=ROOT)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    assert "GROUPED_SUBSTRATE_OK" in r.stdout
