"""Chip benchmark of SKR datagen: one cell of BENCHMARK.json, run once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up compiles (or loads from the compile cache at <checkout>/.jax_cache)
every program the cell's shapes use, by a warm-up job cut after two rows.
The window then runs jobs, each sampled from the seed and the job's number,
until --seconds have passed, and finishes the row in flight. Every label it
emitted is then checked against the plain reference (bench/reference.py).
With --trace 0 the result holds the cell's end-to-end metrics; with
--trace 1 its per-layer metrics, read from the program's spans, the solver's
counts and a profiler trace of a short slice in the middle of the window.

The last line of stdout is one JSON object (correct, attempted, failed,
metrics, device, breakdown with --trace 1, and last `checks`: each number
compared with its limit). `correct` holds only when every label the window's
rows owed was emitted, flagged ok, and meets tol by the reference. Without a TPU, or with fewer chips than the cell
asks for, the run exits 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _paths():
    """Import the benchmark as the package `bench` and the program from
    <checkout>/src; the script's own directory leaves sys.path so that its
    `trace.py` cannot shadow the standard library's."""
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != here]
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def chips(entry: dict):
    """JAX's devices when they are TPU chips, as many as the cell asks for;
    otherwise None, after saying what JAX sees."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < entry["chips"]:
        print(f"bench: cell {entry['name']} needs {entry['chips']} TPU "
              f"chip(s); JAX sees {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return None
    return devs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    _paths()
    # the compile cache lives in the checkout, whatever the environment says
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    try:
        import repro  # noqa: F401  (turns on jax_enable_x64)
    except ImportError as e:
        print(f"bench: the program is not in this checkout ({e})",
              file=sys.stderr)
        return 3
    from bench import harness
    from repro import compile_cache

    man = harness.manifest()
    entry, config, traffic = harness.cell(args.workload, man)
    devs = chips(entry)
    if devs is None:
        return 3
    compile_cache.enable(ROOT)

    rec = harness.run(config, traffic, args.seed, args.seconds,
                      bool(args.trace), T_START)
    rec["device_kind"] = devs[0].device_kind
    c, d = rec["compiles"], rec["dispatches"]
    print(f"bench: set-up {rec['setup_s']:.3f} s, {c['setup']} programs "
          f"({c['setup_cache_hits']} from the cache); window "
          f"{rec['window_s']:.3f} s, {rec['jobs']} job(s), {rec['rows']} "
          f"rows, {len(d)} solver dispatches, "
          f"{c['window']} compilations in the window"
          + (f", profiler {rec['profiler_s']:.3f} s" if args.trace else ""),
          file=sys.stderr)
    checks = harness.check(rec)
    print(f"bench: {rec['labels']} labels; the program's own residuals and "
          f"the reference's differ by at most {rec['residual_gap']!r} "
          "relative", file=sys.stderr)
    tr = None
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": entry["chips"],
              "memory_peak_bytes": rec["memory_peak_bytes"]}
    out = {}
    if args.trace:
        from bench import trace as xtrace

        tr = xtrace.read(xtrace.find(harness.TRACE_DIR))
        summ = xtrace.summarize(tr)
        rec["trace_summary"] = summ
        device.update(busy_s=summ.busy_s, window_s=summ.window_s)
        out["breakdown"] = {"device_ops": summ.top(summ.op_s),
                            "idle_gaps": summ.top(summ.gaps_s)}
    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in harness.metric_names(man, entry, group):
        got = harness.read_metric(m["name"], rec, tr)
        if got is None:
            continue
        extra = got if isinstance(got, dict) else {"value": got}
        metrics[m["name"]] = {"value": extra.pop("value"), "unit": m["unit"],
                              **extra}
    line = {"correct": harness.passed(checks), "attempted": rec["labels_due"],
            "failed": checks["failed"]["value"] + checks["missing"]["value"],
            "metrics": metrics, "device": device, **out, "checks": checks}
    for k, v in line["checks"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r}) "
              f"{'ok' if v['value'] <= v['limit'] else 'OVER'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
