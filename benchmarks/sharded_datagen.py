"""Multi-device sharded datagen: per-device throughput scaling.

Runs the unified pipeline's `sharded` engine (chunk-chain axis of the
lockstep `BatchedGCRODRSolver` sharded over a 1-D `data` mesh) at device
counts 1/2/4/8 and reports dataset throughput for a steady family (poisson
systems) and a trajectory family (heat implicit steps). The device count is
fixed at JAX init, so each count runs in a SUBPROCESS with
`XLA_FLAGS=--xla_force_host_platform_device_count=N` — the same recipe the
CI multi-device smoke job and `tests/test_pipeline.py` use.

HONESTY NOTE: on this box the "devices" are VIRTUAL CPU devices sharing the
same physical cores, so the committed ratios measure what sharding COSTS
(SPMD partitioning + cross-device collectives + per-shard dispatch) at
fixed total compute, not real multi-chip speedup — near-flat throughput
across device counts is the success criterion here; real scaling needs one
accelerator per shard. The 1-device row is the plain batched engine (the
sharded engine degenerates to it when no mesh is available).

Run:  PYTHONPATH=src python -m benchmarks.sharded_datagen [--quick]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

DEVICE_COUNTS = (1, 2, 4, 8)
CHAINS = 8          # divides every device count above


def _worker(args) -> dict:
    """One measurement at the CURRENT process's device count."""
    import jax

    from repro.core.skr import SKRConfig, generate_dataset_chunked
    from repro.core.trajectory import (TrajConfig,
                                       generate_trajectories_chunked)
    from repro.pde.registry import get_family, get_timedep_family
    from repro.solvers.types import KrylovConfig

    kc = KrylovConfig(m=30, k=10, tol=1e-6, maxiter=10_000)
    out = {"devices": len(jax.devices())}

    fam = get_family("poisson", nx=args.nx, ny=args.nx)
    cfg = SKRConfig(krylov=kc, sort_method="greedy", precond="jacobi")
    generate_dataset_chunked(fam, jax.random.PRNGKey(999), args.num, cfg,
                             workers=CHAINS, engine="sharded")  # warmup
    t0 = time.perf_counter()
    chunks = generate_dataset_chunked(fam, jax.random.PRNGKey(0), args.num,
                                      cfg, workers=CHAINS, engine="sharded")
    wall = time.perf_counter() - t0
    out["poisson_wall_s"] = round(wall, 3)
    out["poisson_systems_per_s"] = round(args.num / wall, 2)
    out["poisson_converged"] = int(sum(c.stats.num_converged for c in chunks))

    tfam = get_timedep_family("heat", nx=args.nx, ny=args.nx, nt=args.nt,
                              dt=5e-2)
    tcfg = TrajConfig(krylov=kc, sort_method="greedy", precond="jacobi")
    generate_trajectories_chunked(tfam, jax.random.PRNGKey(999), args.ntraj,
                                  tcfg, workers=CHAINS, engine="sharded")
    t0 = time.perf_counter()
    tchunks = generate_trajectories_chunked(tfam, jax.random.PRNGKey(0),
                                            args.ntraj, tcfg, workers=CHAINS,
                                            engine="sharded")
    wall = time.perf_counter() - t0
    steps = args.ntraj * args.nt
    out["heat_wall_s"] = round(wall, 3)
    out["heat_steps_per_s"] = round(steps / wall, 2)
    out["heat_converged"] = int(sum(c.stats.num_converged for c in tchunks))
    return out


def _spawn(ndev: int, quick: bool, extra_args: list[str]) -> dict:
    env = dict(os.environ)
    # the sweep's device count goes LAST: XLA gives the last duplicate flag
    # precedence, so an inherited --xla_force_host_platform_device_count in
    # the caller's XLA_FLAGS must not override the row being measured
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={ndev}"
                        ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, "-m", "benchmarks.sharded_datagen", "--worker"]
    if quick:
        cmd.append("--quick")
    cmd += extra_args
    out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=1800)
    if out.returncode != 0:
        raise RuntimeError(f"worker (devices={ndev}) failed:\n"
                           f"{out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def run(quick: bool = False):
    import jax

    from benchmarks.common import CSV

    # one process per chip: a parent that holds an accelerator would keep
    # it while the CPU-forced children measured something else entirely
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            "sharded_datagen sweeps virtual CPU devices in subprocesses; "
            f"its parent must run on the CPU, not {jax.default_backend()!r} "
            "(set JAX_PLATFORMS=cpu)")
    rows = {}
    for ndev in DEVICE_COUNTS:
        rows[ndev] = _spawn(ndev, quick, [])
    base = rows[DEVICE_COUNTS[0]]

    csv = CSV(["devices", "poisson_wall_s", "poisson_systems_per_s",
               "heat_wall_s", "heat_steps_per_s", "vs_1dev_poisson",
               "vs_1dev_heat"])
    for ndev, r in rows.items():
        csv.row(ndev, r["poisson_wall_s"], r["poisson_systems_per_s"],
                r["heat_wall_s"], r["heat_steps_per_s"],
                f"{r['poisson_systems_per_s'] / base['poisson_systems_per_s']:.2f}x",
                f"{r['heat_steps_per_s'] / base['heat_steps_per_s']:.2f}x")
    csv.emit("Sharded datagen throughput vs virtual-CPU device count "
             f"({CHAINS} chunk chains; 1-device row = plain batched engine)")
    print("  NOTE: virtual devices share the same physical cores — these "
          "ratios track sharding OVERHEAD at fixed compute, not multi-chip "
          "speedup.")

    return {
        "chains": CHAINS,
        "note": ("virtual CPU devices share physical cores: ratios measure "
                 "SPMD sharding overhead at fixed total compute; near-flat "
                 "is good, real scaling needs one accelerator per shard"),
        "per_devices": {str(k): v for k, v in rows.items()},
        "scaling_vs_1dev": {
            "poisson": {str(k): round(v["poisson_systems_per_s"]
                                      / base["poisson_systems_per_s"], 3)
                        for k, v in rows.items()},
            "heat": {str(k): round(v["heat_steps_per_s"]
                                   / base["heat_steps_per_s"], 3)
                     for k, v in rows.items()},
        },
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--worker", action="store_true",
                    help="internal: measure at THIS process's device count "
                         "and print one JSON line")
    ap.add_argument("--nx", type=int, default=None)
    ap.add_argument("--num", type=int, default=None)
    ap.add_argument("--ntraj", type=int, default=None)
    ap.add_argument("--nt", type=int, default=None)
    args = ap.parse_args(argv)

    if args.nx is None:
        args.nx = 16 if args.quick else 24
    if args.num is None:
        args.num = 16 if args.quick else 32
    if args.ntraj is None:
        args.ntraj = 8
    if args.nt is None:
        args.nt = 4 if args.quick else 6

    if args.worker:
        print(json.dumps(_worker(args)))
        return 0
    run(quick=args.quick)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
