"""Weighted random sampling: alias-table exactness, the alias draw vs the
rule evaluated in NumPy, and the relative-error stopping rule."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core.frames import StateFrame
from repro.core.stopping import RelativeErrorCondition
from repro.sampling import (alias_draw, alias_draw_probabilities,
                            build_alias_table, make_weighted_sample_fn,
                            weighted_mean_exact)


# ----------------------------------------------------------------- alias table
def test_alias_table_exact_probabilities():
    """Vose invariant: prob[i] + Σ_{j: alias[j]=i}(1−prob[j]) = n·wᵢ/Σw."""
    rng = np.random.default_rng(0)
    w = rng.pareto(1.5, size=257) + 1e-4
    table = build_alias_table(w)
    p = alias_draw_probabilities(table)
    np.testing.assert_allclose(p, w / w.sum(), rtol=1e-5, atol=1e-9)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 64), st.integers(0, 2 ** 31 - 1))
def test_alias_table_exact_probabilities_property(n, seed):
    rng = np.random.default_rng(seed)
    w = rng.uniform(0.0, 10.0, size=n) + 1e-6
    p = alias_draw_probabilities(build_alias_table(w))
    np.testing.assert_allclose(p, w / w.sum(), rtol=1e-5, atol=1e-9)
    assert abs(p.sum() - 1.0) < 1e-6


def test_alias_table_degenerate_and_invalid():
    t = build_alias_table(np.asarray([3.0]))
    np.testing.assert_allclose(alias_draw_probabilities(t), [1.0])
    # a zero-weight item must never be drawn
    t = build_alias_table(np.asarray([1.0, 0.0, 1.0]))
    p = alias_draw_probabilities(t)
    assert p[1] < 1e-12
    with pytest.raises(ValueError):
        build_alias_table(np.zeros(4))
    with pytest.raises(ValueError):
        build_alias_table(np.asarray([1.0, -2.0]))
    with pytest.raises(ValueError):
        build_alias_table(np.asarray([1.0, np.inf]))
    with pytest.raises(ValueError):
        build_alias_table(np.zeros(0))


# ----------------------------------------------------------------- alias draw
@pytest.mark.parametrize("n,b", [(7, 64), (256, 1000), (33, 4096), (5, 3)])
def test_alias_draw_matches_numpy_rule(n, b):
    """The jitted draw equals the alias rule evaluated in NumPy."""
    rng = np.random.default_rng(n * b)
    table = build_alias_table(rng.pareto(1.2, size=n) + 1e-4)
    k1, k2 = jax.random.split(jax.random.key(b))
    u1 = jax.random.uniform(k1, (b,))
    u2 = jax.random.uniform(k2, (b,))
    got = np.asarray(jax.jit(alias_draw)(table.prob, table.alias, u1, u2))
    prob, alias = np.asarray(table.prob), np.asarray(table.alias)
    u1, u2 = np.asarray(u1), np.asarray(u2)
    bucket = np.minimum((u1 * np.float32(n)).astype(np.int32), n - 1)
    exp = np.where(u2 < prob[bucket], bucket, alias[bucket])
    np.testing.assert_array_equal(got, exp)
    assert np.all(got >= 0) and np.all(got < n)


def test_alias_draw_empirical_distribution():
    """Large-sample frequencies match the weights (4σ binomial bands)."""
    rng = np.random.default_rng(1)
    w = rng.pareto(1.5, size=16) + 0.05
    table = build_alias_table(w)
    b = 200_000
    k1, k2 = jax.random.split(jax.random.key(0))
    u1 = jax.random.uniform(k1, (b,))
    u2 = jax.random.uniform(k2, (b,))
    idx = np.asarray(alias_draw(table.prob, table.alias, u1, u2))
    freq = np.bincount(idx, minlength=16) / b
    p = w / w.sum()
    sigma = np.sqrt(p * (1 - p) / b)
    assert np.all(np.abs(freq - p) < 4.0 * sigma + 1e-4)


# ------------------------------------------------------------------ sample fn
def test_weighted_sample_fn_frame_contents():
    rng = np.random.default_rng(2)
    w = rng.pareto(1.5, size=32) + 1e-3
    values_q = jnp.asarray(rng.integers(8, 32, size=32), jnp.int32)
    table = build_alias_table(w)
    fn = make_weighted_sample_fn(table, values_q, batch=512, pad_to=32)
    frame, _ = fn(jax.random.key(3), None)
    hist = np.asarray(frame.data["hist"])
    assert int(frame.num) == 512 and hist.sum() == 512
    # moments must equal the histogram-weighted sums exactly (integer frames)
    v = np.asarray(values_q, np.int64)
    assert int(frame.data["s1"]) == int((hist * v).sum())
    assert int(frame.data["s2"]) == int((hist * v * v).sum())


def test_weighted_mean_exact_matches_definition():
    w = np.asarray([1.0, 3.0])
    vq = np.asarray([8, 16])
    got = weighted_mean_exact(w, vq, value_scale=32)
    assert abs(got - (0.25 * 8 / 32 + 0.75 * 16 / 32)) < 1e-12


# ------------------------------------------------------- relative-error rule
def _moment_frame(num, mean, var, scale=1.0):
    s1 = mean * num * scale
    s2 = (var + mean ** 2) * num * scale ** 2
    return StateFrame(num=jnp.int32(num),
                      data={"s1": jnp.float32(s1), "s2": jnp.float32(s2),
                            "hist": jnp.zeros((4,), jnp.int32)})


def test_relative_error_condition_stops_on_tight_mean():
    cond = RelativeErrorCondition(rtol=0.05, delta=0.1)
    assert not bool(cond(_moment_frame(50, 0.5, 0.05))[0])
    assert bool(cond(_moment_frame(200_000, 0.5, 0.05))[0])


def test_relative_error_condition_scale_invariance():
    """Quantized frames (s1=Σxq, s2=Σxq²) give the same verdict and mean."""
    plain = RelativeErrorCondition(rtol=0.05, delta=0.1)
    scaled = RelativeErrorCondition(rtol=0.05, delta=0.1, scale=32.0)
    fa = _moment_frame(5000, 0.5, 0.02)
    fb = _moment_frame(5000, 0.5, 0.02, scale=32.0)
    sa, aa = plain(fa)
    sb, ab = scaled(fb)
    assert bool(sa) == bool(sb)
    np.testing.assert_allclose(float(aa["mean"]), float(ab["mean"]),
                               rtol=1e-5)


def test_relative_error_condition_max_samples_cap():
    cond = RelativeErrorCondition(rtol=1e-9, delta=0.1, max_samples=1000)
    assert not bool(cond(_moment_frame(999, 0.5, 0.1))[0])
    assert bool(cond(_moment_frame(1000, 0.5, 0.1))[0])
