"""Benchmark harness — one module per paper table/figure:

  table1_speedup       Table 1  (SKR vs GMRES, dataset × precond × tol)
  table2_sort_ablation Table 2  (sort ablation + δ metric)
  convergence_fig11    Fig 11/12 (accuracy-vs-cost ladders + slope fits)
  stability_fig13      Fig 13   (max-iteration saturation fractions)
  parallel_e22         Table 31 (chunk-parallel SKR, both engines)
  batched_solver       lockstep batched vs per-system chunked datagen
  mixed_precision      fp32-inner + fp64 refinement vs fp64 baseline
                       (precision-policy tentpole; lockstep engine)
  trajectory_recycle   time-dependent stepping: recycled vs cold-start
                       (heat, convdiff-t, wave M≠I), sequential vs lockstep
                       engines, adaptive-Δt step counts vs fixed
  sharded_datagen      multi-device sharded pipeline: per-device throughput
                       at 1/2/4/8 virtual CPU devices (subprocess sweep)
  table33_no_training  Table 33 (FNO on SKR vs GMRES data)
  label_expansion      few-solves-many-labels: labels/s vs expansion K
                       (DiffOAS f' = A u' waves; poisson/darcy/heat) +
                       FNO quality gates at equal label count (full mode)
  streaming_datagen    online streaming scheduler: mid-flight slot refill
                       vs wave-padding baseline on Poisson traces
                       (utilization, p50/p99 latency, label parity)

Each run also writes a machine-readable ``results/BENCH_<name>.json``
artifact (name, wall time, headline metrics = whatever the bench's ``run``
returns, plus a ``provenance`` block — git SHA, timestamp, jax/jaxlib
versions, device kind/count — so a committed artifact is traceable to the
box and tree that produced it) so the perf trajectory is tracked across
PRs.

``--telemetry`` runs every bench under the observability layer
(``repro.obs``): each artifact gains a ``telemetry`` block (lockstep
utilization, occupancy counters) and the trace exports
``results/TRACE_<name>.json`` (Chrome/Perfetto — load in
chrome://tracing) + ``results/TELEMETRY_<name>.jsonl``.

``python -m benchmarks.run [--quick] [--only NAME] [--telemetry]``
"""
from __future__ import annotations

import argparse
import datetime
import json
import os
import subprocess
import tempfile
import time

from benchmarks import (batched_solver, convergence_fig11, label_expansion,
                        mixed_precision, parallel_e22, sharded_datagen,
                        stability_fig13, streaming_datagen,
                        table1_speedup, table2_sort_ablation,
                        table33_no_training, trajectory_recycle)

BENCHES = [
    ("table1_speedup", table1_speedup.run),
    ("table2_sort_ablation", table2_sort_ablation.run),
    ("convergence_fig11", convergence_fig11.run),
    ("stability_fig13", stability_fig13.run),
    ("parallel_e22", parallel_e22.run),
    ("batched_solver", batched_solver.run),
    ("mixed_precision", mixed_precision.run),
    ("trajectory_recycle", trajectory_recycle.run),
    ("sharded_datagen", sharded_datagen.run),
    ("table33_no_training", table33_no_training.run),
    ("label_expansion", label_expansion.run),
    ("streaming_datagen", streaming_datagen.run),
]

RESULTS_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "results")


def _provenance() -> dict:
    """Run provenance stamped into every artifact: enough to trace a
    committed BENCH_*.json back to the tree and box that produced it.
    Consumers (trend.py, check_regression.py) treat the block as optional —
    artifacts written before it existed keep loading."""
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10).stdout.strip()
    except (OSError, subprocess.SubprocessError):
        sha = ""
    import jax
    import jaxlib

    devs = jax.devices()
    return {"git_sha": sha or "unknown",
            "timestamp": datetime.datetime.now(
                datetime.timezone.utc).isoformat(timespec="seconds"),
            "jax_version": jax.__version__,
            "jaxlib_version": jaxlib.__version__,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs),
            "platform": devs[0].platform}


def _jsonable(obj):
    """Best-effort conversion of a bench's return value to JSON types."""
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return obj
    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if hasattr(obj, "item"):  # numpy scalars
        return obj.item()
    if hasattr(obj, "tolist"):  # numpy arrays
        return obj.tolist()
    return str(obj)


def _write_artifact(name: str, wall_s: float, quick: bool, metrics,
                    provenance=None, telemetry=None):
    """Atomic artifact publish: write to a UNIQUE tmp file in results/ (same
    filesystem), then `os.replace`. A fixed tmp name would let two
    concurrent runs of the same bench interleave writes and publish a
    truncated JSON; mkstemp gives every writer its own file and the rename
    is atomic, so `benchmarks/trend.py` never sees a half-written artifact."""
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"BENCH_{name}.json")
    fd, tmp = tempfile.mkstemp(dir=RESULTS_DIR, prefix=f"BENCH_{name}.",
                               suffix=".tmp")
    try:
        os.fchmod(fd, 0o644)  # mkstemp defaults to 0600; keep artifacts
        doc = {"name": name, "wall_s": round(wall_s, 3),
               "quick": quick, "metrics": _jsonable(metrics)}
        if provenance:
            doc["provenance"] = _jsonable(provenance)
        if telemetry:
            doc["telemetry"] = _jsonable(telemetry)
        with os.fdopen(fd, "w") as f:  # world-readable like plain open()
            json.dump(doc, f, indent=2)
            f.write("\n")
        os.replace(tmp, path)  # atomic publish
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    print(f"[artifact: {os.path.relpath(path)}]")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="reduced grids/tols for CI-speed runs")
    ap.add_argument("--only", default=None,
                    choices=[n for n, _ in BENCHES])
    ap.add_argument("--no-artifacts", action="store_true",
                    help="skip writing results/BENCH_<name>.json")
    ap.add_argument("--telemetry", action="store_true",
                    help="run under repro.obs: telemetry block per "
                         "artifact + results/TRACE_<name>.json / "
                         "TELEMETRY_<name>.jsonl exports")
    args = ap.parse_args(argv)

    prov = _provenance()
    failed = []
    for name, fn in BENCHES:
        if args.only and name != args.only:
            continue
        if args.telemetry:
            from repro import obs
            obs.enable()   # fresh buffers per bench
        t0 = time.perf_counter()
        print(f"\n{'=' * 72}\n== {name}\n{'=' * 72}")
        metrics = fn(quick=args.quick)
        wall = time.perf_counter() - t0
        print(f"[{name}: {wall:.1f}s]")
        telemetry = None
        if args.telemetry:
            from repro import obs
            telemetry = obs.summary()
            if not args.no_artifacts:
                os.makedirs(RESULTS_DIR, exist_ok=True)
                trace = os.path.join(RESULTS_DIR, f"TRACE_{name}.json")
                jsonl = os.path.join(RESULTS_DIR, f"TELEMETRY_{name}.jsonl")
                obs.export_chrome_trace(trace)
                obs.export_jsonl(jsonl)
                print(f"[trace: {os.path.relpath(trace)}]")
            obs.disable()
        if not args.no_artifacts:
            _write_artifact(name, wall, args.quick, metrics,
                            provenance=prov, telemetry=telemetry)
        # benches may publish an acceptance verdict under metrics["ok"]
        # (e.g. mixed_precision's speedup/accuracy gate) — propagate it so
        # CI's quick-verify job actually fails on a regression
        if isinstance(metrics, dict) and metrics.get("ok") is False:
            failed.append(name)
    if failed:
        print(f"\nFAILED acceptance gates: {', '.join(failed)}")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
