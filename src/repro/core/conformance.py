"""Cross-strategy conformance harness.

Runs one registered ADS instance under every (or a chosen subset of)
:class:`~repro.core.frames.FrameStrategy` × virtual world size and asserts
the paper's invariants, turning "does strategy/kernel change X break any
workload?" into a one-line check:

    report = run_conformance("triangles")
    assert report.ok, report.summary()

Invariants checked per cell (strategy, W):

1. **Termination** — the engine stops before ``max_epochs`` (Alg. 1 must
   terminate once the static ω-style bound holds).
2. **Sample-count consistency** (Prop. 1) — the checked state is ``⊕`` over
   an *integral* set of per-worker sample prefixes: ``total.num`` is a whole
   number of epoch frames (× all W workers for the frame strategies whose
   reductions always fold complete epochs).
3. **(ε, δ) accuracy** — the estimate agrees with the exact oracle within
   the instance tolerance ε and with the W=1 sequential oracle run within
   2ε (fixed seeds keep this deterministic).

Cross-cell invariants:

4. **INDEXED_FRAME determinism** (§D.2) — bit-identical ``total`` (num and
   trimmed data) for every W.
5. **SHARED_FRAME reassembly** (§3.2) — the reduce-scattered shards, glued
   back together, equal the replicated LOCAL_FRAME total at the same
   (seed, W) — hardware reduce-scatter ≡ fetch-add.

Substrate equivalence (:func:`run_substrate_equivalence`): every
(strategy × W × F) cell must produce **bit-identical** τ, trimmed data, and
estimate under the sequential / vmap / shard_map execution substrates
(:mod:`repro.core.substrate`), so collectives changes — in particular the
grouped F < W reduce-scatter that only exists under shard_map — can never
silently diverge from the simulated semantics the rest of the suite runs on.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from .frames import FrameStrategy
from .instances import AdaptiveInstance, get_instance, run_instance
from .substrate import Substrate, unavailable_reason

DEFAULT_WORLDS = (1, 2, 4)
EQUIVALENCE_WORLDS = (1, 2, 4, 8)


@dataclasses.dataclass
class CellResult:
    instance: str
    strategy: FrameStrategy
    world: int
    num: int
    stopped: bool
    err_oracle: float
    err_sequential: float
    failures: List[str]
    estimate: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0))

    @property
    def ok(self) -> bool:
        return not self.failures


@dataclasses.dataclass
class ConformanceReport:
    instance: str
    cells: List[CellResult]
    cross_failures: List[str]

    @property
    def ok(self) -> bool:
        return not self.cross_failures and all(c.ok for c in self.cells)

    @property
    def failures(self) -> List[str]:
        out = [f for c in self.cells for f in c.failures]
        return out + list(self.cross_failures)

    def summary(self) -> str:
        lines = [f"conformance[{self.instance}]: "
                 f"{sum(c.ok for c in self.cells)}/{len(self.cells)} cells ok"]
        for c in self.cells:
            tag = "ok " if c.ok else "FAIL"
            lines.append(f"  {tag} {c.strategy.name:13s} W={c.world} "
                         f"τ={c.num:6d} err={c.err_oracle:.4f}"
                         + ("" if c.ok else f"  <- {'; '.join(c.failures)}"))
        lines += [f"  CROSS FAIL: {f}" for f in self.cross_failures]
        return "\n".join(lines)


def _tree_equal(a, b) -> bool:
    la = jax.tree.leaves(a)
    lb = jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


def run_conformance(instance: "str | AdaptiveInstance", *,
                    strategies: Optional[Sequence[FrameStrategy]] = None,
                    worlds: Sequence[int] = DEFAULT_WORLDS,
                    seed: int = 0) -> ConformanceReport:
    """Sweep one instance over strategies × worlds and check all invariants."""
    inst = get_instance(instance) if isinstance(instance, str) else instance
    strategies = list(strategies) if strategies is not None \
        else list(FrameStrategy)

    # W=1 sequential oracle: BARRIER at W=1 checks after every epoch — the
    # reference Algorithm 1 execution.
    ref_est, ref_res, _ = run_instance(inst, strategy=FrameStrategy.BARRIER,
                                       world=1, seed=seed)

    cells: List[CellResult] = []
    indexed: Dict[int, Tuple[int, object]] = {}
    local: Dict[int, Tuple[int, object]] = {}
    shared: Dict[int, Tuple[int, object]] = {}

    for strat in strategies:
        for world in worlds:
            est, res, built = run_instance(inst, strategy=strat, world=world,
                                           seed=seed)
            failures: List[str] = []
            where = f"{built.name}/{strat.name}/W={world}"

            if not res.stopped:
                failures.append(f"{where}: did not stop "
                                f"within {built.max_epochs} epochs")

            # Prop. 1: τ = Σ over integral per-worker frame prefixes.
            spf = built.samples_per_round * (
                1 if strat == FrameStrategy.LOCK else built.rounds_per_epoch)
            unit = spf if strat == FrameStrategy.INDEXED_FRAME \
                else spf * world
            if res.num <= 0 or res.num % unit != 0:
                failures.append(f"{where}: τ={res.num} is not a whole number "
                                f"of {unit}-sample frame sets")

            err_o = float(np.max(np.abs(est - built.oracle)))
            if err_o > built.eps:
                failures.append(f"{where}: oracle error {err_o:.4f} "
                                f"> ε={built.eps:.4f}")
            err_s = float(np.max(np.abs(est - ref_est)))
            if err_s > 2.0 * built.eps:
                failures.append(f"{where}: deviates from W=1 sequential "
                                f"oracle by {err_s:.4f} > 2ε")

            trimmed = built.trim(res.data)
            if strat == FrameStrategy.INDEXED_FRAME:
                indexed[world] = (res.num, trimmed)
            elif strat == FrameStrategy.LOCAL_FRAME:
                local[world] = (res.num, trimmed)
            elif strat == FrameStrategy.SHARED_FRAME:
                shared[world] = (res.num, trimmed)

            cells.append(CellResult(
                instance=built.name, strategy=strat, world=world,
                num=res.num, stopped=res.stopped, err_oracle=err_o,
                err_sequential=err_s, failures=failures, estimate=est))

    cross: List[str] = []
    if len(indexed) > 1:
        w0 = min(indexed)
        num0, data0 = indexed[w0]
        for w, (num, data) in sorted(indexed.items()):
            if num != num0:
                cross.append(f"INDEXED_FRAME τ differs across worlds: "
                             f"W={w0}→{num0}, W={w}→{num}")
            if not _tree_equal(data, data0):
                cross.append(f"INDEXED_FRAME data differs: W={w0} vs W={w}")
    for w in sorted(set(local) & set(shared)):
        num_l, data_l = local[w]
        num_s, data_s = shared[w]
        if num_l != num_s:
            cross.append(f"W={w}: SHARED τ={num_s} ≠ LOCAL τ={num_l}")
        if not _tree_equal(data_l, data_s):
            cross.append(f"W={w}: SHARED shard reassembly ≠ LOCAL total")

    # Every cell ran freshly built closures, so none of the sweep's compiled
    # programs can be hit again; JAX would keep them, and the memory
    # mappings of their code, for the life of the process.  A few sweeps in
    # one process reach the kernel's limit on mappings, and the next
    # compile then crashes.
    jax.clear_caches()
    name = inst.name if not isinstance(instance, str) else instance
    return ConformanceReport(instance=name, cells=cells, cross_failures=cross)


def run_all(*, strategies: Optional[Sequence[FrameStrategy]] = None,
            worlds: Sequence[int] = DEFAULT_WORLDS,
            seed: int = 0) -> Dict[str, ConformanceReport]:
    """Conformance across every registered instance.

    ``seed`` flows into every cell *and* the W=1 sequential reference run of
    each per-instance sweep, so a multi-seed certification is simply
    ``{s: run_all(seed=s) for s in seeds}`` — no cell ever silently runs at
    a default seed.
    """
    from .instances import available_instances
    return {name: run_conformance(name, strategies=strategies, worlds=worlds,
                                  seed=seed)
            for name in available_instances()}


# ---------------------------------------------------------------------------
# Substrate equivalence: sequential / vmap / shard_map must agree bit-for-bit.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SubstrateCell:
    """One (strategy, W, F) cell compared across execution substrates."""

    instance: str
    strategy: FrameStrategy
    world: int
    frame_shards: int             # paper's F (0 → W)
    num: int                      # reference (vmap) τ
    ran: List[str]                # substrate values that executed
    skipped: Dict[str, str]       # substrate value -> why it could not run
    failures: List[str]

    @property
    def ok(self) -> bool:
        return not self.failures

    @property
    def compared(self) -> int:
        """How many substrates were actually cross-checked against vmap."""
        return max(0, len(self.ran) - 1)


@dataclasses.dataclass
class SubstrateReport:
    instance: str
    cells: List[SubstrateCell]

    @property
    def ok(self) -> bool:
        return all(c.ok for c in self.cells)

    @property
    def failures(self) -> List[str]:
        return [f for c in self.cells for f in c.failures]

    def summary(self) -> str:
        lines = [f"substrate-equivalence[{self.instance}]: "
                 f"{sum(c.ok for c in self.cells)}/{len(self.cells)} cells ok"]
        for c in self.cells:
            tag = "ok " if c.ok else "FAIL"
            F = c.frame_shards or c.world
            lines.append(
                f"  {tag} {c.strategy.name:13s} W={c.world} F={F} "
                f"τ={c.num:6d} ran={','.join(c.ran)}"
                + (f" skipped={sorted(c.skipped)}" if c.skipped else "")
                + ("" if c.ok else f"  <- {'; '.join(c.failures)}"))
        return "\n".join(lines)


def equivalence_grid(worlds: Sequence[int] = EQUIVALENCE_WORLDS,
                     strategies: Optional[Sequence[FrameStrategy]] = None,
                     ) -> List[Tuple[FrameStrategy, int, int]]:
    """The (strategy, W, F) cells of the substrate-equivalence suite: the
    full strategy × W grid at F = W, plus the SHARED_FRAME F = W/2 cells
    that exercise the grouped reduce-scatter + cross-group all-reduce."""
    strategies = list(strategies) if strategies is not None \
        else list(FrameStrategy)
    cells = [(s, w, 0) for s in strategies for w in worlds]
    if FrameStrategy.SHARED_FRAME in strategies:
        cells += [(FrameStrategy.SHARED_FRAME, w, w // 2)
                  for w in worlds if w >= 2]
    return cells


def run_substrate_equivalence(
        instance: "str | AdaptiveInstance", *,
        strategies: Optional[Sequence[FrameStrategy]] = None,
        worlds: Sequence[int] = EQUIVALENCE_WORLDS,
        substrates: Optional[Sequence[Substrate]] = None,
        seed: int = 0,
        require_all: bool = False) -> SubstrateReport:
    """Run one instance's (strategy × W × F) grid on every substrate that can
    execute here and demand bit-identical τ, trimmed data, and estimate.

    vmap is the reference substrate (always available; it is what the rest of
    the test suite certifies).  The sequential oracle joins at W=1; shard_map
    joins wherever ``len(jax.devices()) ≥ W``.  A substrate that cannot run
    is recorded in ``cell.skipped`` — or failed outright with
    ``require_all=True`` (the CI substrate job sets it so a mis-provisioned
    runner cannot silently skip the whole point of the suite).
    """
    inst = get_instance(instance) if isinstance(instance, str) else instance
    subs = list(substrates) if substrates is not None else list(Substrate)

    cells: List[SubstrateCell] = []
    for strat, world, F in equivalence_grid(worlds, strategies):
        runs: Dict[str, Tuple[int, object, np.ndarray]] = {}
        skipped: Dict[str, str] = {}
        failures: List[str] = []
        where = f"{inst.name}/{strat.name}/W={world}/F={F or world}"
        for sub in subs:
            reason = unavailable_reason(sub, world)
            if reason is not None:
                skipped[sub.value] = reason
                if require_all and sub != Substrate.SEQUENTIAL:
                    failures.append(f"{where}: required substrate "
                                    f"{sub.value} unavailable: {reason}")
                continue
            est, res, built = run_instance(
                inst, strategy=strat, world=world, seed=seed,
                substrate=sub.value, frame_shards=F)
            runs[sub.value] = (res.num, built.trim(res.data), est)

        ref_key = Substrate.VMAP.value
        if ref_key not in runs:
            failures.append(f"{where}: reference substrate {ref_key} did "
                            f"not run")
            num0 = -1
        else:
            num0, data0, est0 = runs[ref_key]
            for key, (num, data, est) in runs.items():
                if key == ref_key:
                    continue
                if num != num0:
                    failures.append(f"{where}: τ differs — {key}={num}, "
                                    f"{ref_key}={num0}")
                if not _tree_equal(data, data0):
                    failures.append(f"{where}: trimmed data differs — "
                                    f"{key} vs {ref_key}")
                if not np.array_equal(est, est0):
                    failures.append(f"{where}: estimate differs — "
                                    f"{key} vs {ref_key}")

        cells.append(SubstrateCell(
            instance=inst.name, strategy=strat, world=world, frame_shards=F,
            num=num0, ran=sorted(runs), skipped=skipped, failures=failures))
    return SubstrateReport(instance=inst.name, cells=cells)
