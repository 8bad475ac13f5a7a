"""Smoke run of the SKR datagen main path on a TPU.

    python chip_smoke.py              one chip: phases K, A, B, C
    python chip_smoke.py --chips 4    four chips: phase A's job on the
                                      sharded engine over the 4 devices,
                                      against the batched engine on one
    python chip_smoke.py --rehearse   the same phases at tiny sizes on the
                                      CPU, kernels interpreted (add
                                      XLA_FLAGS=--xla_force_host_platform_device_count=4
                                      for --chips 4)

Phases, through the public entry points, families at their default widths:

  K  each main-path Pallas kernel as the solver calls it (vmapped over 8
     chains, fp32) against its jnp reference; the compiled lockstep Arnoldi
     cycle must hold a `tpu_custom_call`
  A  darcy 64x64, 64 systems, KrylovConfig defaults (m=40, k=15, tol=1e-8),
     Jacobi, fp64, no kernels: generate_dataset_chunked(workers=8,
     engine="batched")
  B  the same job on the compiled kernels (use_kernel=True,
     inner_dtype="float32"); its labels must agree with A's
  C  heat 32x32 trajectories through generate_trajectories_chunked(
     workers=8, engine="batched")

Every label of A, B and C is checked on the host by its true residual
||b - A u|| / ||b|| in fp64 against the phase's tol, from the sampled
systems and a plain numpy 5-point stencil; a label flagged label_ok=False
fails as well. Each phase runs twice: the first call compiles, the second
is timed. Any failure exits non-zero. The compile cache is
JAX_COMPILATION_CACHE_DIR when set, else <repo>/.jax_cache. The last line
of stdout is one JSON object naming the device; without a TPU (and without
--rehearse) the script exits 2 and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# (darcy nx, systems, heat nx, trajectories, heat steps, chains)
FULL = dict(nx=64, num=64, heat_nx=32, ntraj=16, nt=10, workers=8)
TINY = dict(nx=16, num=16, heat_nx=16, ntraj=8, nt=3, workers=4)
# phase B against A: both meet tol by true residual, so they differ by at
# most ~2·tol·cond(A) relative; this bound leaves that room at these grids
AGREE = 1e-4


class CompileCounter:
    """XLA compilations (cache hits included) and their seconds, read from
    jax.monitoring."""

    def __init__(self):
        import jax

        self.n = self.hits = 0
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snap(self):
        return self.n, self.secs, self.hits


def stencil_residual(coeffs, u, b):
    """||b - A u|| / ||b|| per system, numpy fp64. coeffs (N, 5, nx, ny)
    stacked [c, n, s, w, e] (pde/dia.py), u and b (N, nx, ny)."""
    import numpy as np

    c, u, b = (np.asarray(a, np.float64) for a in (coeffs, u, b))
    up = np.zeros_like(u)
    up[:, 1:] = u[:, :-1]
    down = np.zeros_like(u)
    down[:, :-1] = u[:, 1:]
    left = np.zeros_like(u)
    left[:, :, 1:] = u[:, :, :-1]
    right = np.zeros_like(u)
    right[:, :, :-1] = u[:, :, 1:]
    au = (c[:, 0] * u + c[:, 1] * up + c[:, 2] * down + c[:, 3] * left
          + c[:, 4] * right)
    num = np.linalg.norm((b - au).reshape(len(u), -1), axis=1)
    den = np.linalg.norm(b.reshape(len(u), -1), axis=1)
    return num / np.where(den > 0, den, 1.0)


def timed(counter, fn):
    """Run fn twice: (result of the timed call, record of both calls)."""
    n0, s0, h0 = counter.snap()
    t0 = time.perf_counter()
    fn()
    first = time.perf_counter() - t0
    n1, s1, h1 = counter.snap()
    t0 = time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    n2, _, _ = counter.snap()
    return out, dict(first_call_s=first, compile_s=s1 - s0, compiles=n1 - n0,
                     cache_hits=h1 - h0, wall_s=wall, compiles_timed=n2 - n1)


def phase_steady(counter, sizes, seed, *, use_kernel, engine="batched",
                 observe=None):
    """Phases A / B: darcy at its default grid through the chunked
    pipeline. `observe(solver)` sees the solver after every lockstep row.
    Returns (labels in sample order, passed, record)."""
    import jax
    import numpy as np
    from repro.core import pipeline
    from repro.core.skr import (SKRConfig, SteadyWork,
                                generate_dataset_chunked)
    from repro.pde.registry import get_family
    from repro.solvers.types import KrylovConfig

    nx, num, workers = sizes["nx"], sizes["num"], sizes["workers"]
    fam = get_family("darcy", nx=nx, ny=nx)
    kc = KrylovConfig(inner_dtype="float32") if use_kernel else KrylovConfig()
    cfg = SKRConfig(krylov=kc, precond="jacobi", use_kernel=use_kernel)
    key = jax.random.PRNGKey(seed)

    if observe is None:
        def run():
            return generate_dataset_chunked(fam, key, num, cfg,
                                            workers=workers, engine=engine)
    else:
        class Observed(SteadyWork):
            def execute_row(self, solver, t, idx, prepared):
                super().execute_row(solver, t, idx, prepared)
                observe(solver)

        def run():   # generate_dataset_chunked's body, observed
            return pipeline.run_chunked(Observed(fam, cfg), key, num,
                                        workers, engine)

    chunks, rec = timed(counter, run)
    u = np.zeros((num, nx, nx))
    ok = np.zeros(num, bool)
    for c in chunks:
        u[c.order] = c.solutions
        ok[c.order] = c.label_ok
    batch = fam.sample_batch(key, num)
    res = stencil_residual(batch.op.coeffs, u, batch.b)
    rec.update(labels=num,
               iterations=sum(c.stats.total_iterations for c in chunks),
               max_true_res=float(res.max()), tol=kc.tol,
               over_tol=int((res > kc.tol).sum()), flagged=int((~ok).sum()))
    passed = bool((res <= kc.tol).all() and ok.all() and np.isfinite(u).all())
    return u, passed, rec


def phase_heat(counter, sizes, seed):
    """Phase C: heat trajectories; every implicit step's label is checked
    against the step system rebuilt by the family (θ-scheme, fixed Δt)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.trajectory import TrajConfig, generate_trajectories_chunked
    from repro.pde.registry import get_timedep_family
    from repro.solvers.types import KrylovConfig

    nx, num = sizes["heat_nx"], sizes["ntraj"]
    fam = get_timedep_family("heat", nx=nx, ny=nx, nt=sizes["nt"])
    cfg = TrajConfig(krylov=KrylovConfig(), precond="jacobi")
    key = jax.random.PRNGKey(seed + 1)
    chunks, rec = timed(counter, lambda: generate_trajectories_chunked(
        fam, key, num, cfg, workers=sizes["workers"], engine="batched"))

    traj = np.zeros((num, fam.nt + 1, nx, nx))
    ok = np.zeros(num, bool)
    for c in chunks:
        traj[c.order] = c.trajectories
        ok[c.order] = c.label_ok
    specs = fam.sample_specs(key, num)
    step = fam.step_fn_batched()
    res = []
    for s in range(fam.nt):
        a, b = step(specs.latent, jnp.asarray(traj[:, s]), s * fam.dt,
                    (s + 1) * fam.dt)
        res.append(stencil_residual(a, traj[:, s + 1], b))
    res = np.concatenate(res)
    tol = cfg.krylov.tol
    rec.update(labels=int(res.size), trajectories=num,
               iterations=sum(c.stats.total_iterations for c in chunks),
               max_true_res=float(res.max()), tol=tol,
               over_tol=int((res > tol).sum()), flagged=int((~ok).sum()))
    return bool((res <= tol).all() and ok.all() and np.isfinite(traj).all()), rec


def phase_kernels(sizes, seed):
    """Phase K: each main-path kernel as the solver calls it, against its
    jnp reference, plus the compiled cycle program's custom call."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref
    from repro.pde.dia import DIA
    from repro.solvers.arnoldi import arnoldi_cycle_batched
    from repro.solvers.operator import PreconditionedOp, StencilOp
    from repro.solvers.precond import JacobiPrecond
    from repro.solvers.types import KrylovConfig

    kc = KrylovConfig()
    bsz, nx = 8, sizes["nx"]
    n, m1, k = nx * nx, kc.m + 1, kc.k
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 2), 16))
    f32 = jnp.float32

    def rnd(*shape):
        return jax.random.normal(next(keys), shape, f32)

    coeffs, x = rnd(bsz, 5, nx, nx), rnd(bsz, nx, nx)
    v, w, vin = rnd(bsz, m1, n), rnd(bsz, n), rnd(bsz, n)
    c_rows = rnd(bsz, k, n)
    inv_diag = 1.0 + 0.1 * rnd(bsz, n) ** 2
    mask = jnp.broadcast_to((jnp.arange(m1) < m1 // 2).astype(f32),
                            (bsz, m1))
    kp1 = 9
    dia = DIA(offsets=(-nx, -1, 0, 1, nx), data=rnd(bsz, 5, n))
    xs = rnd(bsz * kp1, n)

    def vm(fn, **kw):
        return jax.vmap(lambda *a: fn(*a, **kw))

    cases = {
        "stencil5_matvec": (vm(ops.stencil5_matvec, use_kernel=True),
                            vm(ref.stencil5_matvec), (coeffs, x)),
        "fused_orthog": (vm(ops.fused_orthog, use_kernel=True),
                         vm(ref.fused_orthog), (v, w, mask)),
        "arnoldi_step": (vm(ops.arnoldi_step, use_kernel=True),
                         vm(ref.arnoldi_step),
                         (coeffs, inv_diag, c_rows, v, vin, mask)),
        "dia_spmv_strided": (
            lambda d, y: ops.dia_spmv(d, y, op_stride=kp1, use_kernel=True),
            lambda d, y: ops.dia_spmv(d, y, op_stride=kp1), (dia, xs)),
    }
    out, ok = {}, True
    for name, (kern, want_fn, args) in cases.items():
        hlo = jax.jit(kern).lower(*args).compile().as_text()
        got = jax.tree_util.tree_leaves(jax.jit(kern)(*args))
        want = jax.tree_util.tree_leaves(jax.jit(want_fn)(*args))
        err = 0.0
        for g, r in zip(got, want):
            scale = max(float(jnp.abs(r).max()), 1.0)
            err = max(err, float(jnp.abs(g - r).max()) / scale)
        custom = "tpu_custom_call" in hlo
        out[name] = dict(max_scaled_err=err, tpu_custom_call=custom)
        ok &= err <= 1e-5 and (custom or jax.default_backend() != "tpu")

    # the lockstep Arnoldi cycle as phase B dispatches it: fp32 Jacobi-
    # preconditioned stencil operators with the kernel path on
    st = StencilOp(jnp.abs(coeffs), use_kernel=True)
    opb = PreconditionedOp(st, JacobiPrecond(inv_diag))
    cycle = jax.jit(lambda *a: arnoldi_cycle_batched(*a, m=kc.m - k,
                                                     use_kernel=True))
    hlo = cycle.lower(opb, c_rows, w,
                      jnp.full((bsz,), 1e-4, f32)).compile().as_text()
    fused = "tpu_custom_call" in hlo and "arnoldi_step" in hlo
    out["cycle_program_fused_kernel"] = fused
    ok &= fused or jax.default_backend() != "tpu"
    return ok, out


def report(name, ok, rec):
    print(f"phase {name}: {'ok' if ok else 'FAILED'} {json.dumps(rec)}",
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU, kernels interpreted")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    from repro import compile_cache

    backend = jax.default_backend()
    want = "cpu" if args.rehearse else "tpu"
    if backend != want:
        print(f"chip_smoke: JAX backend is {backend!r}, this run needs "
              f"{want!r}", file=sys.stderr)
        return 2
    devs = jax.devices()
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)} "
              f"device(s)", file=sys.stderr)
        return 2
    cache = compile_cache.enable(ROOT)
    print(f"compile cache: {cache}", flush=True)
    sizes = TINY if args.rehearse else FULL
    counter = CompileCounter()
    ok = True

    if args.chips == 4:
        # the chain axis of every lockstep row's solution must span 4 chips
        spans = []
        u_s, ok_s, rec_s = phase_steady(
            counter, sizes, args.seed, use_kernel=False, engine="sharded",
            observe=lambda s: spans.append(
                len(s.x_device.sharding.device_set)))
        rec_s["row_solution_devices"] = sorted(set(spans))
        ok_s &= set(spans) == {4}
        report("A-sharded", ok_s, rec_s)
        u_b, ok_b, rec_b = phase_steady(counter, sizes, args.seed,
                                        use_kernel=False)
        report("A-batched", ok_b, rec_b)
        diff = rel_diff(u_s, u_b)
        print(f"sharded vs batched labels: max rel diff {diff!r} "
              f"(limit {AGREE})", flush=True)
        ok = ok_s and ok_b and diff <= AGREE
    else:
        ok_k, rec_k = phase_kernels(sizes, args.seed)
        report("K", ok_k, rec_k)
        u_a, ok_a, rec_a = phase_steady(counter, sizes, args.seed,
                                        use_kernel=False)
        report("A", ok_a, rec_a)
        u_b, ok_b, rec_b = phase_steady(counter, sizes, args.seed,
                                        use_kernel=True)
        rec_b["max_rel_diff_vs_A"] = rel_diff(u_b, u_a)
        ok_b &= rec_b["max_rel_diff_vs_A"] <= AGREE
        report("B", ok_b, rec_b)
        ok_c, rec_c = phase_heat(counter, sizes, args.seed)
        report("C", ok_c, rec_c)
        ok = ok_k and ok_a and ok_b and ok_c
    if not ok:
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


def rel_diff(u, ref):
    """Largest per-label ||u - ref|| / ||ref||."""
    import numpy as np

    num = np.linalg.norm((u - ref).reshape(len(u), -1), axis=1)
    den = np.linalg.norm(ref.reshape(len(u), -1), axis=1)
    return float((num / np.maximum(den, 1e-300)).max())


if __name__ == "__main__":
    sys.exit(main())
