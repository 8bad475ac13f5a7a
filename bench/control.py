"""The control of the benchmark's output check, and the program's own
readings beside it, on several seeds in one process.

    python bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

For each seed it runs the cell as `run.py` does (set-up once), checks the
labels the window emitted, and then checks them again with every label
replaced by the plain reference's own solve in float32, the precision
below the configuration's fp64 labels. The check has to fail the control. One JSON line per seed:
{"seed", "program": {number: reading}, "control": {number: reading}}.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_labels(jobs, ems, dtype):
    """The emitted labels replaced by the reference's solves in `dtype`."""
    import numpy as np

    from bench import reference

    out = []
    for em in ems:
        em = dict(em)
        a, b = reference.darcy_system(
            em["input"], jobs.config["family_params"]["source"])
        em["label"] = reference.solve(a, b, dtype)
        em["ok"] = np.ones(len(em["ok"]), bool)
        out.append(em)
    return out


def readings(checks):
    return {k: v["value"] for k, v in checks.items() if isinstance(v, dict)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    here = os.path.dirname(os.path.abspath(__file__))
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != here]
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    import numpy as np

    import repro  # noqa: F401
    from bench import harness
    from repro import compile_cache

    compile_cache.enable(ROOT)
    _, config, traffic = harness.cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        rec = harness.run(config, traffic, seed, args.seconds, False,
                          time.perf_counter())
        jobs, ems = rec["job_kind"], rec["emitted"]
        t0 = time.perf_counter()
        ctrl = jobs.check(control_labels(jobs, ems, np.float32))
        control_s = time.perf_counter() - t0
        prog = harness.check(rec)
        prog["gap"] = dict(value=rec["residual_gap"])
        print(json.dumps({"seed": seed, "labels": rec["labels"],
                          "window_compiles": rec["compiles"]["window"],
                          "program": readings(prog),
                          "control": readings(ctrl),
                          "control_s": control_s}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
