"""The comparison that decides ``correct``: what the timed path produced
against the plain reference (``reference.py``).

Each number has its limit in ``limits.json``; the run is correct when every
number is at most its limit.  The numbers:

graph_mismatch       entries of the program's CSR arrays and sizes that
                     differ from the reference's graph
preprocess_mismatch  components, vertex-diameter bound, BFS level budget
                     and omega that differ from the reference's
tau_mismatch         |tau - the samples of the frames reduced into it|, and
                     |tau - batch * rounds * W * (frames reduced)|, summed
reduce_mismatch      sum over workers of |the worker's total - the sum over
                     workers of every frame reduced into it|
replay_l1            sum of |program - reference| over the per-vertex counts
                     of frames drawn from the seed among the window's, each
                     replayed sample by sample from the seed
verdict_mismatch     workers whose stop verdict differs from the reference's
bound_gap            largest relative gap of max f and max g, the stopping
                     check's bounds on the final state, to the reference's
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from . import reference as ref

LIMITS = json.loads((Path(__file__).resolve().parent / "limits.json")
                    .read_text())
REPLAYED_FRAMES = 4


def replay_choice(seed: int, window_frames: list, world: int) -> list:
    """(query, frame, worker) of the frames to replay, drawn from the seed:
    REPLAYED_FRAMES window frames, the workers taken in turn."""
    rng = np.random.default_rng([seed, 7])
    picks = rng.choice(len(window_frames),
                       size=min(REPLAYED_FRAMES, len(window_frames)),
                       replace=False)
    return [(*window_frames[i], k % world) for k, i in enumerate(picks)]


def graph_mismatch(ref_g: dict, program_graph) -> int:
    p = {k: np.asarray(getattr(program_graph, k))
         for k in ("indptr", "indices_padded", "src", "dst")}
    bad = sum(int(getattr(program_graph, k) != ref_g[k])
              for k in ("n", "m_arcs", "max_degree"))
    n = ref_g["n"]
    want = {"indptr": ref_g["indptr"], "src": ref_g["src"],
            "dst": ref_g["dst"],
            "indices_padded": np.concatenate(
                [ref_g["dst"], np.full(ref_g["max_degree"], n)])}
    for k, w in want.items():
        got = p[k]
        if got.shape != w.shape:
            bad += max(got.size, w.size)
        else:
            bad += int(np.count_nonzero(got != w))
    return bad


def preprocess_mismatch(ref_pre: dict, pre) -> int:
    comps = np.asarray(pre.components)
    bad = int(np.count_nonzero(comps != comps[0])) if ref_pre["connected"] \
        else 1
    bad += int(pre.vd_upper != ref_pre["vd_upper"])
    bad += int(pre.diam_levels != ref_pre["diam_levels"])
    bad += int(abs(pre.omega - ref_pre["omega"]) > 1e-9 * ref_pre["omega"])
    return bad


class Reference:
    """The reference's graph and preprocessing for a finished run."""

    def __init__(self, win):
        cfg = win.cell.config
        # one worker per chip
        self.world, self.batch = win.cell.chips, int(cfg["batch"])
        self.rounds = win.instance.rounds_per_epoch
        self.eps, self.delta = float(cfg["eps"]), float(cfg["delta"])
        self.g = ref.build_graph(win.n, win.edges)
        self.pre = ref.preprocess(self.g, self.eps, self.delta)

    def frame(self, qseed: int, worker: int, frame: int,
              dtype=np.float64) -> np.ndarray:
        return ref.frame(self.g, self.pre, qseed, self.world, worker, frame,
                         rounds=self.rounds, batch=self.batch, dtype=dtype)


def compare(win, reference: Reference, *, control: bool = False) -> dict:
    """``{name: value}`` for a finished run ``win`` (``harness.Window``).

    With ``control`` the bfloat16 reference takes the program's place where
    the program computes in floating point: it produces the replayed frames
    and the stopping bounds.
    """
    world, batch, rounds = reference.world, reference.batch, reference.rounds
    eps, delta = reference.eps, reference.delta
    ref_g, ref_pre = reference.g, reference.pre
    out = {"graph_mismatch": graph_mismatch(ref_g, win.instance.graph),
           "preprocess_mismatch": preprocess_mismatch(ref_pre,
                                                      win.instance.pre)}

    tau_bad = reduce_bad = verdict_bad = 0
    gap = 0.0
    for q in win.queries:
        frames, final = q["frames"], q["final"]
        reduced = frames[:-1]       # the last frame is still pending
        nums = sum(int(np.sum(num)) for num, _ in reduced)
        sums = sum((np.asarray(d, np.int64).sum(axis=0) for _, d in reduced),
                   np.zeros(win.n, np.int64))
        for w in range(world):
            tau = int(final["num"][w])
            tau_bad += abs(tau - nums) + abs(tau - batch * rounds * world
                                             * len(reduced))
            reduce_bad += int(np.abs(np.asarray(final["data"][w], np.int64)
                                     - sums).sum())
        want = ref.kadabra_bounds(sums, nums, eps, delta, ref_pre["omega"])
        got = ref.kadabra_bounds(sums, nums, eps, delta, ref_pre["omega"],
                                 ref.BF16) if control else None
        for w in range(world):
            f = got["max_f"] if got else float(final["max_f"][w])
            g = got["max_g"] if got else float(final["max_g"][w])
            stop = got["stop"] if got else bool(final["stop"][w])
            verdict_bad += int(stop != want["stop"])
            gap = max(gap, abs(f - want["max_f"]) / want["max_f"],
                      abs(g - want["max_g"]) / want["max_g"])
    out["tau_mismatch"] = tau_bad
    out["reduce_mismatch"] = reduce_bad

    l1 = 0
    for qi, fi, w in replay_choice(win.seed, win.window_frames, world):
        q = win.queries[qi]
        want = reference.frame(q["seed"], w, fi)
        got = (reference.frame(q["seed"], w, fi, ref.BF16) if control
               else np.asarray(q["frames"][fi][1][w], np.int64))
        l1 += int(np.abs(got - want).sum())
    out["replay_l1"] = l1
    out["verdict_mismatch"] = verdict_bad
    out["bound_gap"] = gap
    return out


def verdict(values: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}})."""
    checked = {k: {"value": v, "limit": LIMITS[k]} for k, v in values.items()}
    return all(v <= LIMITS[k] for k, v in values.items()), checked
