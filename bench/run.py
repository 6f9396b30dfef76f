#!/usr/bin/env python3
"""The chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the configuration's graph on the host, registers the query's
instance, preprocesses, starts the session and runs one warm-up epoch;
then the window steps the query epoch by epoch for ``--seconds``.  With
``--trace 0`` the last line of stdout holds the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  Either way the run is checked against the plain reference
(``check.py``); every number compared is printed beside its limit, as the
last lines on stderr and under ``checked`` in the result line.

Without TPU chips, or with fewer than the cell asks for, it exits non-zero
and prints no result.
"""

from __future__ import annotations

import time

_T_IMPORT = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def process_start() -> float:
    """The process's start on the ``time.perf_counter`` clock."""
    try:
        fields = Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()
        started = int(fields[19]) / os.sysconf("SC_CLK_TCK")
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - started
        return time.perf_counter() - age
    except (OSError, IndexError, ValueError):
        return _T_IMPORT


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def tpu_devices(chips: int) -> list:
    import jax
    devices = jax.devices()
    platforms = sorted({d.platform for d in devices})
    if platforms != ["tpu"]:
        raise SystemExit(f"bench: needs TPU chips, found platforms {platforms}")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chip(s), found "
                         f"{len(devices)}")
    return devices[:chips]


def peaks(device_kind: str) -> dict:
    table = json.loads((ROOT / "bench" / "peaks.json").read_text())
    if device_kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {device_kind!r} "
                         "in bench/peaks.json")
    return table[device_kind]


def use_checkout_cache() -> None:
    """JAX's persistent compilation cache at ``.jax_cache`` in the checkout,
    unbounded and holding every program however short its compile, so that
    a later run in the checkout compiles nothing; the TPU runtime's logs
    under ``TMPDIR``.  Set before JAX is imported: JAX reads these once,
    and the first compile fixes the cache.  The program takes the cache
    directory from ``JAX_COMPILATION_CACHE_DIR``."""
    os.environ.update(JAX_COMPILATION_CACHE_DIR=str(CACHE_DIR),
                      JAX_COMPILATION_CACHE_MAX_SIZE="-1",
                      JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    # the TPU runtime logs to /tmp/tpu_logs unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))


def main(argv=None) -> int:
    t_process = process_start()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    use_checkout_cache()
    sys.path[:1] = [str(ROOT), str(ROOT / "src")]
    from bench import cell as cellmod, check, harness, report
    from repro.launch.compile_cache import enable_compile_cache
    cell = cellmod.load(args.workload)
    devices = tpu_devices(cell.chips)
    kind = devices[0].device_kind
    peak_table = peaks(kind)
    enable_compile_cache()
    trace_dir = Path(tempfile.mkdtemp(prefix="bench-trace-")) \
        if args.trace else None
    try:
        win = harness.run(cell, devices, seed=args.seed, seconds=args.seconds,
                          trace_dir=trace_dir, t_process=t_process)
        for k, v in win.setup.items():
            log(f"setup {k}: {v}")
        log(f"window: {win.seconds} s, {win.epochs} epochs, {win.samples} "
            f"samples, {win.compiles_in_window} programs compiled or loaded")
        log(f"memory_peak_bytes {win.memory_peak_bytes} of "
            f"{peak_table['hbm_bytes']} HBM; runtime counters "
            f"{win.memory_stats}")
        if win.step_memory:
            log(f"step program memory_analysis {win.step_memory}")
        if args.trace:
            metrics, extra = report.per_layer(win, trace_dir)
        else:
            metrics, extra = report.end_to_end(win), {}
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    t0 = time.perf_counter()
    values = check.compare(win, check.Reference(win))
    correct, checked = check.verdict(values)
    log(f"check took {time.perf_counter() - t0} s")
    for name, c in checked.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    result = {
        "correct": correct, "attempted": win.epochs, "failed": 0,
        "metrics": metrics,
        "device": {"platform": devices[0].platform, "kind": kind,
                   "count": len(devices),
                   "memory_peak_bytes": win.memory_peak_bytes,
                   **extra.pop("device", {})},
        **extra,
        "checked": checked,
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
