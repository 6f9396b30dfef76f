#!/usr/bin/env python3
"""The readings the check's limits are set from (not a run of the
benchmark, which never runs the control).

    python3 bench/readings.py --workload <cell> --seconds <s> \\
        --seeds 11,12,... --control-seeds 21,22,23

One process sets the cell up once; then for each seed it starts a query,
measures a short window at the cell's own load and compares what the
program produced with the reference (the lower readings); for each control
seed it does the same with the control, the reference in bfloat16, in the
program's place (the upper readings).  One JSON line per seed on stdout.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)

    sys.path[:1] = [str(ROOT), str(ROOT / "src")]
    from bench import run
    run.use_checkout_cache()
    from bench import cell as cellmod, check, harness

    cell = cellmod.load(args.workload)
    devices = run.tpu_devices(cell.chips)
    setup = harness.set_up(cell)
    reference = None
    for kind, seeds in (("program", args.seeds), ("control", args.control_seeds)):
        for seed in (int(s) for s in seeds.split(",") if s):
            win = harness.measure(setup, devices, seed=seed,
                                  seconds=args.seconds,
                                  t_process=time.perf_counter())
            if reference is None:
                reference = check.Reference(win)
            t0 = time.perf_counter()
            values = check.compare(win, reference, control=kind == "control")
            print(json.dumps({"kind": kind, "seed": seed, "epochs": win.epochs,
                              "check_s": time.perf_counter() - t0,
                              "values": values}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
