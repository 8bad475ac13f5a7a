import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import (jax locks the device
# count at first init). Tests may shrink the placeholder fleet:
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

# Multi-pod dry-run (deliverable e): .lower().compile() every
# (architecture × input-shape × mesh) cell on the production meshes and
# record memory_analysis / cost_analysis / collective schedule for §Roofline.
#
# Usage:
#   python -m repro.launch.dryrun --arch qwen2.5-3b --shape train_4k
#   python -m repro.launch.dryrun --all --mesh both --out-dir results/dryrun

import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax

from repro.configs.base import SHAPES
from repro.configs.registry import get_config, list_archs
from repro.launch.mesh import (make_debug_mesh, make_production_mesh,
                               mesh_num_chips)
from repro.launch.roofline import (HBM_PER_CHIP, compute_roofline,
                                   extrapolate_linear, model_flops_for)
from repro.launch.steps import lower_cell


def _is_scanned(cfg) -> bool:
    from repro.models.transformer import _use_scan
    if cfg.is_encdec:
        return cfg.scan_layers
    return _use_scan(cfg)


def _reduced_cfg(cfg, r: int):
    """Unrolled r-repetition variant for roofline FD calibration."""
    pat = len(cfg.block_pattern)
    kw = dict(n_layers=pat * r, scan_layers=False)
    if cfg.is_encdec:
        assert cfg.n_enc_layers == cfg.n_layers, \
            "FD calibration assumes enc/dec layer counts match"
        kw["n_enc_layers"] = r
    return dataclasses.replace(cfg, **kw)


def _calibrated_costs(cfg, shape, mesh, optimizer):
    """(flops_per_chip, bytes_per_chip, meta): XLA counts while bodies once,
    so lower UNROLLED variants at n_rep∈{1,2} and extrapolate linearly."""
    n_rep_full = cfg.n_layers // len(cfg.block_pattern)
    pts = []
    for r in (1, 2):
        lo, _ = lower_cell(_reduced_cfg(cfg, r), shape, mesh,
                           optimizer=optimizer)
        cost = lo.compile().cost_analysis() or {}
        pts.append((r, float(cost.get("flops", 0.0) or 0.0),
                    float(cost.get("bytes accessed", 0.0) or 0.0)))
    (n1, f1, b1), (n2, f2, b2) = pts
    flops = extrapolate_linear(n1, f1, n2, f2, n_rep_full)
    byts = extrapolate_linear(n1, b1, n2, b2, n_rep_full)
    meta = {"method": "fd_unrolled", "points": pts, "n_rep_full": n_rep_full}
    return flops, byts, meta


def _memory_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for k in ("generated_code_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "alias_size_in_bytes",
              "temp_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def applicable(cfg, shape_name: str) -> tuple[bool, str]:
    shape = SHAPES[shape_name]
    if shape_name in cfg.skip_shapes:
        return False, "config skip_shapes (full attention at 500k / enc-dec)"
    return True, ""


def run_cell(arch: str, shape_name: str, mesh, mesh_name: str,
             optimizer: str = "adamw", verbose: bool = True) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    chips = mesh_num_chips(mesh)
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                 "chips": chips, "kind": shape.kind,
                 "optimizer": optimizer if shape.kind == "train" else None}
    ok, why = applicable(cfg, shape_name)
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = why
        return rec

    t0 = time.perf_counter()
    lowered, plan = lower_cell(cfg, shape, mesh, optimizer=optimizer)
    rec["lower_s"] = round(time.perf_counter() - t0, 2)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.perf_counter() - t1, 2)

    mem = _memory_dict(compiled)
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    raw_flops = float(cost.get("flops", 0.0) or 0.0)
    raw_bytes = float(cost.get("bytes accessed", 0.0) or 0.0)
    if _is_scanned(cfg):
        flops, byts, calib = _calibrated_costs(cfg, shape, mesh, optimizer)
        calib["raw_full_compile"] = {"flops": raw_flops, "bytes": raw_bytes}
    else:
        flops, byts = raw_flops, raw_bytes
        calib = {"method": "direct_unrolled"}
    mf = model_flops_for(cfg, shape)
    roof = compute_roofline(flops, byts, hlo, chips, model_flops=mf,
                            calibration=calib)

    state_per_chip = plan.state_bytes / chips
    arg_per_chip = mem.get("argument_size_in_bytes", 0)
    temp_per_chip = mem.get("temp_size_in_bytes", 0)
    rec.update({
        "status": "ok",
        "memory_analysis": mem,
        "state_bytes_total": plan.state_bytes,
        "state_bytes_per_chip_fully_sharded": state_per_chip,
        "bytes_per_chip": arg_per_chip + temp_per_chip,
        "fits_v5e_hbm": bool((arg_per_chip + temp_per_chip) <= HBM_PER_CHIP)
        if mem else None,
        "roofline": roof.as_dict(),
    })
    if verbose:
        print(f"--- {arch} × {shape_name} × {mesh_name} "
              f"({chips} chips, {shape.kind}) ---")
        print("memory_analysis:", json.dumps(mem))
        print("cost_analysis(per-chip, calibrated): flops=%.3e bytes=%.3e" %
              (roof.flops_per_chip, roof.bytes_per_chip))
        print("roofline: compute=%.3es memory=%.3es collective=%.3es "
              "dominant=%s useful_flops=%.2f" %
              (roof.compute_s, roof.memory_s, roof.collective_s,
               roof.dominant, roof.useful_flops_ratio or float("nan")))
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, choices=list_archs() + [None])
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both", "debug",
                             "debug-multi"])
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "sgd"])
    ap.add_argument("--out-dir", default=None)
    ap.add_argument("--fail-fast", action="store_true")
    args = ap.parse_args(argv)

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("pod16x16", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("pods2x16x16", make_production_mesh(multi_pod=True)))
    if args.mesh == "debug":
        meshes.append(("debug2x4", make_debug_mesh(multi_pod=False)))
    if args.mesh == "debug-multi":
        meshes.append(("debug2x2x2", make_debug_mesh(multi_pod=True)))

    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]

    results, failures = [], 0
    for mesh_name, mesh in meshes:
        for arch in archs:
            for shape_name in shapes:
                try:
                    rec = run_cell(arch, shape_name, mesh, mesh_name,
                                   optimizer=args.optimizer)
                except Exception as e:
                    failures += 1
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_name, "status": "error",
                           "error": f"{type(e).__name__}: {e}"}
                    print(f"!!! {arch} × {shape_name} × {mesh_name} FAILED:",
                          rec["error"], file=sys.stderr)
                    if args.fail_fast:
                        traceback.print_exc()
                        return 1
                results.append(rec)
                if args.out_dir:
                    os.makedirs(args.out_dir, exist_ok=True)
                    fn = f"{arch.replace('.', '_')}__{shape_name}__{mesh_name}.json"
                    with open(os.path.join(args.out_dir, fn), "w") as f:
                        json.dump(rec, f, indent=1)

    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    print(f"\ndry-run: {n_ok} ok, {n_skip} skipped, {failures} failed, "
          f"{len(results)} cells")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
