"""Sharding policy (divisibility fallback) + HLO collective parser +
roofline math."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.analysis.hlo import collective_bytes, parse_shape_bytes
from repro.analysis.roofline import (combine_layer_diff, model_flops,
                                     roofline_terms)
from repro.models import SHAPES, get_config
from repro.models.layers import ShardingRules


def rules_16():
    return ShardingRules(
        rules={"vocab": ("model",), "heads": ("model",), "ffn": ("model",),
               "embed": ("data",), "batch": ("data",)},
        mesh_shape={"data": 16, "model": 16})


def test_divisibility_fallback():
    r = rules_16()
    # 15 heads don't divide 16 → replicated (3-D head-major params make the
    # check hit the head COUNT, not the fused H·hd dim); 2560 ffn → sharded
    spec = r.spec_for_shape((960, 15, 64), ("embed", "heads", None))
    assert spec == P("data", None, None)
    spec = r.spec_for_shape((960, 2560), ("embed", "ffn"))
    assert spec == P("data", "model")
    # divisible head count shards normally
    spec = r.spec_for_shape((6144, 48, 128), ("embed", "heads", None))
    assert spec == P("data", "model", None)


def test_axis_used_once():
    r = ShardingRules(rules={"a": ("model",), "b": ("model",)},
                      mesh_shape={"model": 4})
    spec = r.spec_for_shape((8, 8), ("a", "b"))
    # 'model' must not be assigned to two dims of one tensor
    assert spec in (P("model", None), P(None, "model"))


def test_multi_axis_dim():
    r = ShardingRules(rules={"embed": ("pod", "data")},
                      mesh_shape={"pod": 2, "data": 16})
    assert r.spec_for_shape((64,), ("embed",)) == P(("pod", "data"))
    # 33 not divisible by 2 → fully replicated
    assert r.spec_for_shape((33,), ("embed",)) == P(None)


def test_parse_shape_bytes():
    assert parse_shape_bytes("f32[128]") == 512
    assert parse_shape_bytes("bf16[2,3]{1,0}") == 12
    assert parse_shape_bytes("pred[] s8[10]") == 11  # 1-byte scalar + 10
    assert parse_shape_bytes("u32[4,4]") == 64


def test_collective_bytes_on_real_hlo():
    mesh = jax.make_mesh((1,), ("x",))

    def f(a):
        return jax.lax.psum(a, "x")

    g = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("x"), out_specs=P()))
    txt = g.lower(jnp.ones((8, 128), jnp.float32)).compile().as_text()
    out = collective_bytes(txt)
    # single-device psum may be optimized away; at minimum the parser
    # must not crash and must return the dict shape
    assert "total" in out and "count" in out


def test_collective_bytes_synthetic():
    hlo = """
  %ar = f32[1024]{0} all-reduce(f32[1024]{0} %x), replica_groups={}
  %ag.s = (f32[256]{0}, f32[1024]{0}) all-gather-start(f32[256]{0} %y)
  %ag.d = f32[1024]{0} all-gather-done((f32[256]{0}, f32[1024]{0}) %ag.s)
  %cp = bf16[64,64]{1,0} collective-permute(bf16[64,64]{1,0} %z)
"""
    out = collective_bytes(hlo)
    assert out["all-reduce"] == 4096
    assert out["all-gather"] == 1024          # operand of -start
    assert out["collective-permute"] == 8192
    assert out["count"] == 3                  # -done skipped


def test_roofline_terms_dominance():
    t = roofline_terms(flops_per_dev=197e12, bytes_per_dev=1e9,
                       coll_bytes_per_dev=1e9, chips=256)
    assert t.compute_s == pytest.approx(1.0)
    assert t.dominant == "compute"
    t = roofline_terms(flops_per_dev=1e12, bytes_per_dev=819e9 * 2,
                       coll_bytes_per_dev=1e9, chips=256)
    assert t.dominant == "memory"


def test_layer_differencing():
    base = {"flops": 100.0, "bytes": 10.0}
    two = {"flops": 160.0, "bytes": 14.0}
    out = combine_layer_diff(base, two, 11)
    assert out["flops"] == pytest.approx(100 + 60 * 10)
    assert out["bytes"] == pytest.approx(10 + 4 * 10)


def test_model_flops_forms():
    cfg = get_config("mistral-large-123b")
    tr = model_flops(cfg, SHAPES["train_4k"])
    pf = model_flops(cfg, SHAPES["prefill_32k"])
    dc = model_flops(cfg, SHAPES["decode_32k"])
    n = cfg.param_count()
    assert tr == pytest.approx(6 * n * 4096 * 256, rel=1e-6)
    assert pf == pytest.approx(2 * n * 32768 * 32, rel=1e-6)
    assert dc == pytest.approx(2 * n * 128, rel=1e-6)
    # MoE: active < total
    moe = get_config("qwen3-moe-235b-a22b")
    assert moe.active_param_count() < 0.25 * moe.param_count()
