"""Multi-pod dry-run (deliverable e).

For every (architecture × input shape × mesh) cell:

    with mesh:
        lowered  = jax.jit(step, in_shardings=…, out_shardings=…).lower(**specs)
        compiled = lowered.compile()
        print(compiled.memory_analysis())   # proves it fits
        print(compiled.cost_analysis())     # FLOPs/bytes for §Roofline

plus (single-pod only) two small *unrolled* layer-differencing compiles that
correct ``cost_analysis``'s count-scan-body-once behaviour (DESIGN.md §6).
Results land in ``benchmarks/results/dryrun/<cell>.json``.

Usage:
    python -m repro.launch.dryrun --arch smollm-360m --shape train_4k
    python -m repro.launch.dryrun --all            # every applicable cell
    python -m repro.launch.dryrun --all --multipod # 2-pod mesh pass
"""

import argparse
import dataclasses
import json
import os
import time
import traceback
from pathlib import Path

import jax

RESULTS = Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"


def _cost_dict(compiled, chips: int) -> dict:
    from repro.analysis.hlo import collective_bytes
    ca = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll_bytes": float(coll.get("total", 0)),
        "coll_detail": {k: v for k, v in coll.items()
                        if k not in ("total", "count")},
        "coll_count": coll.get("count", 0),
    }


def _mem_dict(compiled) -> dict:
    ma = compiled.memory_analysis()
    return {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "peak_bytes": int(ma.argument_size_in_bytes
                          + ma.output_size_in_bytes
                          + ma.temp_size_in_bytes
                          - ma.alias_size_in_bytes),
    }


def _lower_compile(cfg, shape, mesh, verbose=True, flags=None):
    from repro.launch.specs import input_specs
    from repro.launch.steps import step_for

    kwargs, shardings, rules, model = input_specs(cfg, shape, mesh,
                                                  flags=flags)
    step = step_for(model, shape.kind)
    order = list(kwargs)  # dict order matches step signatures
    args = tuple(kwargs[k] for k in order)
    in_sh = tuple(shardings[k] for k in order)
    # donation: train updates (params, opt_state) in place; decode updates
    # the cache in place — halves the resident footprint and lets XLA fuse
    # the cache one-hot update into the donated buffer.
    donate = {"train": (0, 1), "decode": (1,), "prefill": ()}[shape.kind]
    t0 = time.time()
    with jax.set_mesh(mesh):
        lowered = jax.jit(step, in_shardings=in_sh,
                          donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    if verbose:
        print(f"  lowered {t_lower:.1f}s, compiled {t_compile:.1f}s")
        print(f"  memory_analysis: {compiled.memory_analysis()}")
        ca = compiled.cost_analysis()
        print(f"  cost_analysis: flops={ca.get('flops', 0):.4g} "
              f"bytes={ca.get('bytes accessed', 0):.4g}")
    return compiled, dict(t_lower=t_lower, t_compile=t_compile)


def _diff_variants(cfg):
    """(base_cfg, two_cfg[, extra]) unrolled variants for layer-differencing."""
    def rep(**kw):
        return dataclasses.replace(cfg, scan_layers=False, grad_accum=1, **kw)
    if cfg.family == "encdec":
        return [("base", rep(n_layers=1, enc_layers=1)),
                ("dec2", rep(n_layers=2, enc_layers=1)),
                ("enc2", rep(n_layers=1, enc_layers=2))]
    if cfg.family == "hybrid":
        return [("base", rep(n_layers=3)), ("two", rep(n_layers=6))]
    return [("base", rep(n_layers=1)), ("two", rep(n_layers=2))]


def _corrected_cost(cfg, shape, mesh, flags=None) -> dict:
    """Layer-differenced flops/bytes/coll_bytes for the full depth."""
    from repro.analysis.roofline import combine_layer_diff
    chips = mesh.devices.size
    costs = {}
    for tag, vcfg in _diff_variants(cfg):
        compiled, _ = _lower_compile(vcfg, shape, mesh, verbose=False,
                                     flags=flags)
        costs[tag] = _cost_dict(compiled, chips)
    keys = ("flops", "bytes", "coll_bytes")
    def pick(c):
        return {k: c[k] for k in keys}
    if cfg.family == "encdec":
        dec = {k: costs["dec2"][k] - costs["base"][k] for k in keys}
        enc = {k: costs["enc2"][k] - costs["base"][k] for k in keys}
        used_dec = cfg.n_layers if shape.kind != "prefill" else cfg.n_layers
        out = {k: costs["base"][k]
               + max(dec[k], 0.0) * (cfg.n_layers - 1)
               + max(enc[k], 0.0) * (cfg.enc_layers - 1) for k in keys}
        # decode never runs the encoder; enc diff is ~0 there by construction
        return out
    if cfg.family == "hybrid":
        per_unit = {k: (costs["two"][k] - costs["base"][k]) for k in keys}
        return {k: costs["base"][k]
                + max(per_unit[k], 0.0) * (cfg.n_layers - 3) / 3.0
                for k in keys}
    return combine_layer_diff(pick(costs["base"]), pick(costs["two"]),
                              cfg.n_layers)


OPTS = {
    # §Perf hillclimb configurations (dryrun --opt): explicit beyond-baseline
    # changes per arch; everything else inherits the baseline.
    # (sort dispatch was tried and REFUTED for the jit/GSPMD path — see
    # EXPERIMENTS.md §Perf iterations 1–2; kept in the code base behind
    # cfg.moe_dispatch="sort" as the shard_map-migration starting point.)
    "qwen3-moe-235b-a22b": dict(moe_group=128),  # capacity C 40→16: one-hot
                                                 # dispatch tensors ÷4
    "smollm-360m": dict(grad_accum=1),  # 256-row batch divides 256-way DP;
                                        # policy-level: dp_over_model
    "mistral-large-123b": dict(grad_accum=32),
    "internlm2-20b": dict(grad_accum=4),
}
OPT_FLAGS = {
    "smollm-360m": dict(dp_over_model=True, zero1=True),
}


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             with_diff: bool = True, out_dir: Path = RESULTS,
             opt: bool = False) -> dict:
    import dataclasses as _dc
    from repro.launch.mesh import make_production_mesh
    from repro.launch.sharding import default_flags
    from repro.models import SHAPES, cell_is_applicable, get_config
    from repro.analysis.roofline import roofline_terms, model_flops

    cfg = get_config(arch)
    flags = None
    if opt:
        cfg = _dc.replace(cfg, **OPTS.get(arch, {}))
        if arch in OPT_FLAGS:
            flags = _dc.replace(default_flags(cfg), **OPT_FLAGS[arch])
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    cell = f"{arch}__{shape_name}__{mesh_name}" + ("__opt" if opt else "")
    print(f"[dryrun] {cell}")
    ok, why = cell_is_applicable(cfg, shape)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "applicable": ok, "skip_reason": why}
    if ok:
        mesh = make_production_mesh(multi_pod=multi_pod)
        chips = mesh.devices.size
        compiled, times = _lower_compile(cfg, shape, mesh, flags=flags)
        rec["memory"] = _mem_dict(compiled)
        rec["raw_cost"] = _cost_dict(compiled, chips)
        rec["times"] = times
        rec["chips"] = chips
        rec["fits_16gb"] = rec["memory"]["peak_bytes"] <= 16 * 1024 ** 3
        if with_diff and not multi_pod:
            corrected = _corrected_cost(cfg, shape, mesh, flags=flags)
            rec["corrected_cost"] = corrected
            terms = roofline_terms(
                flops_per_dev=corrected["flops"],
                bytes_per_dev=corrected["bytes"],
                coll_bytes_per_dev=corrected["coll_bytes"],
                chips=chips, cfg=cfg, shape=shape)
            rec["roofline"] = terms.as_dict()
            print(f"  roofline: compute={terms.compute_s:.4f}s "
                  f"memory={terms.memory_s:.4f}s "
                  f"collective={terms.collective_s:.4f}s "
                  f"dominant={terms.dominant} "
                  f"useful={terms.useful_ratio:.2f}")
        rec["model_flops"] = model_flops(cfg, shape)
    else:
        print(f"  SKIP: {why}")
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"{cell}.json").write_text(json.dumps(rec, indent=1))
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-diff", action="store_true")
    ap.add_argument("--opt", action="store_true",
                    help="apply the §Perf hillclimb config for this arch")
    ap.add_argument("--resume", action="store_true",
                    help="skip cells whose result JSON already exists")
    args = ap.parse_args()

    from repro.models import SHAPES, all_configs

    cells = []
    if args.all:
        for arch in sorted(all_configs()):
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    failures = []
    for arch, shape in cells:
        mesh_name = "2x16x16" if args.multipod else "16x16"
        suffix = "__opt" if args.opt else ""
        out = RESULTS / f"{arch}__{shape}__{mesh_name}{suffix}.json"
        if args.resume and out.exists():
            print(f"[dryrun] {out.stem} (cached)")
            continue
        try:
            run_cell(arch, shape, args.multipod, with_diff=not args.no_diff,
                     opt=args.opt)
        except Exception as e:  # noqa: BLE001 — record & continue
            traceback.print_exc()
            failures.append((arch, shape, repr(e)))
            RESULTS.mkdir(parents=True, exist_ok=True)
            out.write_text(json.dumps(
                {"arch": arch, "shape": shape, "mesh": mesh_name,
                 "applicable": True, "error": repr(e)}, indent=1))
    if failures:
        print(f"\n{len(failures)} FAILED cells:")
        for f in failures:
            print("  ", f)
        return 1
    print("\nall cells OK")
    return 0


if __name__ == "__main__":
    # before the first backend use; importing this module changes nothing
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    raise SystemExit(main())
