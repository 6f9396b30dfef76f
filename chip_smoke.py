#!/usr/bin/env python3
"""Smoke run of the adaptive-sampling engine on a TPU, through its normal
entry points.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four chips: substrate equivalence

One process runs every phase and is the only one that touches JAX.

a. Device check: every device must be a TPU, or the script exits non-zero.
b. KADABRA through ``run_instance`` (LOCAL_FRAME, W = 4) on G, a
   2^17-vertex, 2^21-edge Erdős–Rényi graph: it must stop with τ ≤ ω and
   estimates in [0, 1].  Then the same path on a 2^10-vertex graph against
   the exact Brandes betweenness: max |b̃ − b| ≤ ε.
c. ``EpochScheduler``, ticked as ``launch.serve --pool`` ticks it: two
   KADABRA queries on G with different seeds and one ``wrs-m`` query.  All
   retire, the KADABRA queries share one compiled stepper, and the ``wrs``
   estimate lies within its ``rtol`` of the exact weighted mean.
d. Compile and run wall time of each phase, labelled with the device.
   These lines are informational, not metrics.
e. The last line of stdout is one JSON object:
   ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

``--four-chips`` runs only this phase: KADABRA on G at W = 4 under
``shard_map`` over four chips and under ``vmap`` on one of them, for
LOCAL_FRAME and SHARED_FRAME with F = 2.  τ and the count vector must be
bit-identical between the two substrates.

A failed check raises, so the script exits non-zero and prints no JSON line.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.adaptive_instances import BENCH  # noqa: E402
from repro.core.frames import FrameStrategy  # noqa: E402
from repro.core.instances import (KadabraInstance,  # noqa: E402
                                  WeightedSamplingInstance, register_instance,
                                  run_instance)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.serve import EpochScheduler, SessionSpec  # noqa: E402

EPS = 0.05
DELTA = 0.1
WORLD = 4
G_VERTICES, G_EDGES = 1 << 17, 1 << 21
ORACLE_VERTICES, ORACLE_EDGES = 1 << 10, 1 << 12
BATCH = 8            # per worker: 32 concurrent samples at W = 4

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or incomplete result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


@contextlib.contextmanager
def timed(device: str, label: str):
    """Print the phase's wall time split into compile and run."""
    spent = [0.0]

    def on_event(event: str, secs: float, **_):
        if event in _COMPILE_EVENTS:
            spent[0] += secs

    jax.monitoring.register_event_duration_secs_listener(on_event)
    t0 = time.perf_counter()
    try:
        yield
    finally:
        wall = time.perf_counter() - t0
        jax.monitoring.unregister_event_duration_listener(on_event)
        print(f"[chip_smoke] {device} | {label}: compile {spent[0]:.1f} s, "
              f"run {wall - spent[0]:.1f} s", flush=True)


def kadabra_instance(n_vertices: int, n_edges: int, *, batch: int,
                     compute_oracle: bool, name: str) -> KadabraInstance:
    return KadabraInstance(name=name, n_vertices=n_vertices, n_edges=n_edges,
                           eps=EPS, delta=DELTA, batch=batch,
                           compute_oracle=compute_oracle)


def check_kadabra(est: np.ndarray, tau: int, stopped: bool, omega: float,
                  what: str) -> None:
    check(stopped, f"{what}: did not stop")
    check(tau <= omega, f"{what}: τ={tau} exceeds ω={omega}")
    check(bool(np.all(np.isfinite(est))), f"{what}: non-finite estimate")
    check(bool(np.all((est >= 0.0) & (est <= 1.0))),
          f"{what}: estimate outside [0, 1]")


def phase_kadabra(inst: KadabraInstance, *, world: int) -> dict:
    """KADABRA through ``run_instance`` (LOCAL_FRAME); checks the result."""
    est, res, built = run_instance(inst, strategy=FrameStrategy.LOCAL_FRAME,
                                   world=world, seed=0)
    check_kadabra(est, res.num, res.stopped, built.check_fn.omega, inst.name)
    out = {"tau": res.num, "omega": built.check_fn.omega,
           "epochs": res.epochs}
    if inst.compute_oracle:
        err = float(np.max(np.abs(est - built.oracle)))
        check(err <= inst.eps,
              f"{inst.name}: max |b̃ − b| = {err} exceeds ε = {inst.eps}")
        out["max_abs_err"] = err
    print(f"[chip_smoke] {inst.name} (n={inst.n_vertices}): {out}",
          flush=True)
    return out


def phase_scheduler(kadabra: KadabraInstance,
                    wrs: WeightedSamplingInstance, *, world: int) -> dict:
    """Two KADABRA queries and one ``wrs`` query through the scheduler."""
    register_instance(kadabra, overwrite=True)
    register_instance(wrs, overwrite=True)
    sched = EpochScheduler(max_in_flight=3)
    kq = [sched.submit(SessionSpec(kadabra.name, "local", world, seed))
          for seed in (1, 2)]
    wq = sched.submit(SessionSpec(wrs.name, "local", world, 0))
    while not sched.idle:
        ev = sched.tick()
        for qid in ev.retired:
            r = sched.results[qid]
            print(f"[chip_smoke] tick {ev.tick}: retired {qid} τ={r.tau} "
                  f"epochs={r.epochs}", flush=True)
    check(set(sched.results) == set(kq) | {wq},
          f"not every query retired: {sorted(sched.results)}")
    check(len(sched.cache) == 2,
          f"expected one stepper per shape (2), got {len(sched.cache)}")
    omega = kadabra.build(world=world).check_fn.omega
    for qid in kq:
        r = sched.results[qid]
        check_kadabra(r.estimate, r.tau, r.stopped, omega, qid)
    r = sched.results[wq]
    mu = float(wrs.build(world=world).oracle[0])
    got = float(r.estimate[0])
    check(r.stopped, f"{wq}: did not stop")
    check(abs(got - mu) <= wrs.rtol * mu,
          f"{wq}: estimate {got} not within rtol={wrs.rtol} of {mu}")
    out = {"ticks": sched.tick_count, "steppers": len(sched.cache),
           "wrs_estimate": got, "wrs_oracle": mu}
    print(f"[chip_smoke] scheduler: {out}", flush=True)
    return out


def phase_substrates(inst: KadabraInstance, *, world: int) -> dict:
    """shard_map vs vmap at W = ``world``: τ and counts bit-identical."""
    out = {}
    for strategy, shards in ((FrameStrategy.LOCAL_FRAME, 0),
                             (FrameStrategy.SHARED_FRAME, 2)):
        runs = {}
        for substrate in ("shard_map", "vmap"):
            _, res, built = run_instance(inst, strategy=strategy, world=world,
                                         seed=0, substrate=substrate,
                                         frame_shards=shards)
            check(res.stopped, f"{strategy.value}/{substrate}: did not stop")
            runs[substrate] = (res.num, np.asarray(built.trim(res.data)))
        (tau_s, counts_s), (tau_v, counts_v) = runs["shard_map"], runs["vmap"]
        label = f"{strategy.value} F={shards or world}"
        check(tau_s == tau_v, f"{label}: τ {tau_s} (shard_map) != "
                              f"{tau_v} (vmap)")
        check(np.array_equal(counts_s, counts_v),
              f"{label}: count vectors differ between shard_map and vmap")
        out[label] = tau_s
        print(f"[chip_smoke] {label}: shard_map ≡ vmap, τ={tau_s}",
              flush=True)
    return out


def tpu_devices(needed: int) -> list:
    """All devices, which must be at least ``needed`` TPU chips."""
    devices = jax.devices()
    platforms = sorted({d.platform for d in devices})
    if platforms != ["tpu"]:
        raise SystemExit(f"chip_smoke: no TPU found (platforms {platforms})")
    if len(devices) < needed:
        raise SystemExit(f"chip_smoke: needs {needed} TPU chip(s), "
                         f"found {len(devices)}")
    return devices


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip substrate-equivalence phase")
    args = ap.parse_args(argv)

    devices = tpu_devices(4 if args.four_chips else 1)
    d0 = devices[0]
    device = f"{d0.platform} {d0.device_kind} x{len(devices)}"
    print(f"[chip_smoke] devices: platform={d0.platform} "
          f"kind={d0.device_kind} count={len(devices)}", flush=True)
    enable_compile_cache()

    g = kadabra_instance(G_VERTICES, G_EDGES, batch=BATCH,
                         compute_oracle=False, name="kadabra-g")
    if args.four_chips:
        with timed(device, "shard_map vs vmap, KADABRA on G"):
            phase_substrates(g, world=WORLD)
    else:
        with timed(device, "KADABRA on G via run_instance"):
            phase_kadabra(g, world=WORLD)
        small = kadabra_instance(ORACLE_VERTICES, ORACLE_EDGES, batch=BATCH,
                                 compute_oracle=True, name="kadabra-brandes")
        with timed(device, "KADABRA vs brandes_exact"):
            phase_kadabra(small, world=WORLD)
        with timed(device, "EpochScheduler: 2 x KADABRA on G + wrs-m"):
            phase_scheduler(g, BENCH["wrs-m"], world=WORLD)

    print(json.dumps({"ok": True,
                      "device": {"platform": d0.platform,
                                 "kind": d0.device_kind,
                                 "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
