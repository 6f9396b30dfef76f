"""Alias tables (Walker/Vose) and the weighted-mean ADS workload.

Weighted random sampling per Hübschle-Schneider & Sanders ("Parallel
Weighted Random Sampling"): an alias table turns n arbitrary positive
weights into O(1)-time draws — bucket ``i = ⌊u₁·n⌋`` is kept with
probability ``prob[i]`` and redirected to ``alias[i]`` otherwise.
Construction is the two-stack Vose method, O(n) and exact in float64.

The ADS instance on top estimates the weighted mean μ = Σᵢ pᵢ·xᵢ of a
bounded value vector x under the weight distribution p ∝ w, stopping on
*relative* standard error (:class:`~repro.core.stopping.RelativeErrorCondition`)
— the adaptive-sampling analog of H&S's fixed-size batches.

Frame layout (all-integer so every strategy, INDEXED_FRAME bit-identity
included, reduces exactly):

    frame.num  — number of draws
    frame.data — {"s1": Σ xq   (int32 scalar),
                  "s2": Σ xq²  (int32 scalar),
                  "hist": (n_pad,) int32 per-item draw counts (vector leaf
                          so SHARED_FRAME exercises a real reduce-scatter)}

Values are quantized to integers ``xq ∈ [0, value_scale)`` with
``x = xq / value_scale``; int32 moment sums stay exact as long as
``num · (value_scale−1)² < 2³¹`` (the BENCH presets cap ``max_samples``
accordingly).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.frames import StateFrame

VALUE_SCALE = 32


@dataclasses.dataclass(frozen=True)
class AliasTable:
    """Walker alias table: draw ⌊u₁·n⌋, keep w.p. ``prob``, else ``alias``."""

    n: int
    prob: jax.Array    # (n,) float32 — acceptance threshold per bucket
    alias: jax.Array   # (n,) int32   — redirect target per bucket


def build_alias_table(weights: np.ndarray) -> AliasTable:
    """Vose's O(n) two-stack construction (float64 host-side, then cast)."""
    w = np.asarray(weights, np.float64).reshape(-1)
    if w.size == 0:
        raise ValueError("alias table needs at least one weight")
    if not np.all(np.isfinite(w)) or np.any(w < 0.0):
        raise ValueError("weights must be finite and non-negative")
    total = float(w.sum())
    if total <= 0.0:
        raise ValueError("weights must not all be zero")
    n = w.size
    scaled = w / total * n
    prob = np.ones(n, np.float64)
    alias = np.arange(n, dtype=np.int64)
    small = [i for i in range(n) if scaled[i] < 1.0]
    large = [i for i in range(n) if scaled[i] >= 1.0]
    while small and large:
        s = small.pop()
        g = large.pop()
        prob[s] = scaled[s]
        alias[s] = g
        scaled[g] = (scaled[g] + scaled[s]) - 1.0
        (small if scaled[g] < 1.0 else large).append(g)
    # leftovers are ≈1 up to rounding: keep with probability 1
    for i in small + large:
        prob[i] = 1.0
        alias[i] = i
    return AliasTable(n=n, prob=jnp.asarray(prob, jnp.float32),
                      alias=jnp.asarray(alias, jnp.int32))


def alias_draw_probabilities(table: AliasTable) -> np.ndarray:
    """Exact per-item draw probability implied by the table:

    P(i) = (prob[i] + Σ_{j: alias[j]=i} (1 − prob[j])) / n

    Used by tests to verify construction (must equal wᵢ/Σw up to the f32
    cast of ``prob``).
    """
    prob = np.asarray(table.prob, np.float64)
    alias = np.asarray(table.alias)
    p = prob.copy()
    np.add.at(p, alias, 1.0 - prob)
    return p / table.n


def weighted_mean_exact(weights: np.ndarray, values_q: np.ndarray,
                        value_scale: int = VALUE_SCALE) -> float:
    """Exact estimand μ = Σᵢ pᵢ·(xqᵢ/scale) — the workload oracle (O(n))."""
    w = np.asarray(weights, np.float64)
    x = np.asarray(values_q, np.float64) / float(value_scale)
    return float((w * x).sum() / w.sum())


def alias_draw(prob: jax.Array, alias: jax.Array, u1: jax.Array,
               u2: jax.Array) -> jax.Array:
    """Batched alias-table draws: keep bucket ⌊u₁·n⌋ w.p. ``prob``, else
    ``alias``.  Two XLA gathers on every backend (a TPU Pallas kernel cannot
    index a VMEM table with a vector of indices)."""
    n = prob.shape[0]
    bucket = jnp.minimum((u1 * n).astype(jnp.int32), n - 1)
    return jnp.where(u2 < prob[bucket], bucket, alias[bucket])


def make_weighted_sample_fn(table: AliasTable, values_q: jax.Array,
                            batch: int, *, pad_to: Optional[int] = None):
    """Build SAMPLE() — one vectorized round of ``batch`` alias draws.

    Uniforms only *select* integer indices, so the accumulated frame is
    integer-exact and identical across strategies for identical keys.
    """
    n = table.n
    n_pad = pad_to or n
    values_q = jnp.asarray(values_q, jnp.int32)

    def sample_fn(key: jax.Array, carry) -> Tuple[StateFrame, jax.Array]:
        k1, k2 = jax.random.split(key)
        u1 = jax.random.uniform(k1, (batch,))
        u2 = jax.random.uniform(k2, (batch,))
        idx = alias_draw(table.prob, table.alias, u1, u2)
        xq = values_q[idx]
        hist = jax.ops.segment_sum(jnp.ones((batch,), jnp.int32), idx,
                                   num_segments=n_pad)
        data = {"s1": jnp.sum(xq), "s2": jnp.sum(xq * xq), "hist": hist}
        return StateFrame(num=jnp.int32(batch), data=data), carry

    return sample_fn


def weighted_frame_template(n: int, pad_to: Optional[int] = None):
    n_pad = pad_to or n
    return {"s1": jnp.zeros((), jnp.int32), "s2": jnp.zeros((), jnp.int32),
            "hist": jnp.zeros((n_pad,), jnp.int32)}
