"""Compiles for a described TPU v5e (no chip attached): the programs of the
main path at the sizes ``chip_smoke.py`` runs, so that what the chip's
compiler refuses fails here first.

The topology is described inside a module fixture — never while a module is
imported — because only one process at a time may load the TPU library.
The persistent compilation cache is off around these compiles: an entry
written for a described chip cannot be read back without one.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

from repro.configs.adaptive_instances import BENCH
from repro.core.epoch import EpochConfig, run_sharded
from repro.core.frames import FrameStrategy
from repro.core.instances import KadabraInstance
from repro.graphs import erdos_renyi
from repro.graphs.kadabra import Preprocessed, init_counters, make_sample_fn
from repro.sampling.alias import AliasTable, make_weighted_sample_fn

# G of chip_smoke.py: its graph, and the BFS level / path-length bounds
# that preprocessing derives for it (diameter bound 10 → 11 vertices).
G_VERTICES, G_EDGES, G_SEED, G_VD = 1 << 17, 1 << 21, 1, 11


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def test_kadabra_round_fits_one_chip(one_chip):
    """One sampling round on G with 32 concurrent samples (W = 4 workers ×
    batch 8) stays well inside the chip's 16 GB."""
    g = erdos_renyi(G_VERTICES, G_EDGES, seed=G_SEED)

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    g_spec = jax.tree.map(spec, g)
    comps = jax.ShapeDtypeStruct((g.n,), jnp.int32, sharding=one_chip)
    seed = jax.ShapeDtypeStruct((), jnp.uint32, sharding=one_chip)

    def sample_round(g, comps, seed):
        pre = Preprocessed(omega=1.0, vd_upper=G_VD, components=comps,
                           diam_levels=G_VD)
        sample_fn = make_sample_fn(g, pre, 8)
        keys = jax.random.split(jax.random.key(seed), 4)
        frames, _ = jax.vmap(lambda k: sample_fn(k, init_counters()))(keys)
        return frames.data

    compiled = jax.jit(sample_round).lower(g_spec, comps, seed).compile()
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes + ma.argument_size_in_bytes < 12 * 10**9


def test_wrs_round_compiles(one_chip):
    """The alias draw of a ``wrs-m`` round (2^16-entry table, 4096 draws)."""
    inst = BENCH["wrs-m"]
    n = inst.n_items

    def spec(dtype):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)

    key = jax.ShapeDtypeStruct((), jnp.uint32, sharding=one_chip)

    def sample_round(prob, alias, values_q, seed):
        table = AliasTable(n=n, prob=prob, alias=alias)
        sample_fn = make_weighted_sample_fn(table, values_q, inst.batch)
        frame, _ = sample_fn(jax.random.key(seed), None)
        return frame.data

    compiled = jax.jit(sample_round).lower(
        spec(jnp.float32), spec(jnp.int32), spec(jnp.int32), key).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 10**9


@pytest.mark.parametrize("strategy,frame_shards", [
    (FrameStrategy.LOCAL_FRAME, 0),
    (FrameStrategy.SHARED_FRAME, 2),
])
def test_run_sharded_compiles_on_four_chips(topo, strategy, frame_shards):
    """``run_sharded`` over a 2x2 mesh at conformance size; F = 2 is the
    grouped reduction."""
    mesh = Mesh(np.asarray(topo.devices[:4]), ("workers",),
                axis_types=(AxisType.Auto,))
    built = KadabraInstance().build(world=4, strategy=strategy)
    cfg = EpochConfig(strategy=strategy,
                      rounds_per_epoch=built.rounds_per_epoch,
                      max_epochs=built.max_epochs)

    def run():
        st = run_sharded(built.sample_fn, built.check_fn, built.template,
                         built.init_carry, 0, mesh, "workers", cfg,
                         frame_shards=frame_shards)
        return st.total.num

    compiled = jax.jit(run).lower().compile()
    assert "all-reduce" in compiled.as_text()
