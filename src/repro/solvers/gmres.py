"""Restarted GMRES — the paper's baseline (PETSc KSPGMRES semantics:
relative-residual tolerance, restart length m, right preconditioning so the
tracked residual is the true residual).

Precision policy: `cfg.inner_dtype="float32"` routes through
`_gmres_solve_mixed` — an fp64 outer iterative-refinement loop whose
correction systems are solved by THIS solver on the fp32-casted operator
(`cast_operator`). The fp64 default takes the historical code path
unchanged (bitwise regression-tested)."""
from __future__ import annotations

import dataclasses
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels import ops as kops
from repro.kernels.precision import matmul as mm
from repro.obs.telemetry import KrylovTelemetry
from repro.solvers.arnoldi import arnoldi_cycle
from repro.solvers.hostlinalg import hessenberg_lstsq
from repro.solvers.operator import (PreconditionedOp, apply_op, as_operator,
                                    cast_operator)
from repro.solvers.types import KrylovConfig, SolveStats


@jax.jit
def _residual_norms(op, b, z):
    """Initial residual AND both norms in ONE dispatch (the x0 path used to
    pay two host syncs before the first cycle; warm-started solves now issue
    a single device round-trip)."""
    r = b - apply_op(op, z)
    return r, jnp.linalg.norm(b), jnp.linalg.norm(r)


@jax.jit
def _fused_update(op, b, z, v, y):
    """z += Vᵀy (y zero-padded to the cycle width) + true residual — one
    dispatch instead of a host V copy + host matmul + residual dispatch."""
    z = z + mm(v[:-1].T, y)
    r = b - apply_op(op, z)
    return z, r, jnp.linalg.norm(r)


@jax.jit
def _ir_accum(base, b, x, d):
    """Outer refinement step: x += d (upcast) and the TRUE fp64 residual of
    the UNpreconditioned operator — one dispatch per outer pass."""
    x = x + d.astype(b.dtype)
    r = b - apply_op(base, x)
    return x, r, jnp.linalg.norm(r)


_downcast32 = jax.jit(lambda r: r.astype(jnp.float32))


def gmres_solve(op: PreconditionedOp, b, cfg: KrylovConfig, x0=None,
                use_kernel: bool = False, stall_break: bool = False):
    """Returns (x, SolveStats). `op` must be a PreconditionedOp; `b` flat.

    stall_break: break out (instead of spinning to maxiter) when full cycles
    at the restart cap stop reducing the residual — used by the
    mixed-precision outer loop for its inner fp32 correction solves, where
    the fp32 round-off floor is an expected exit, not a failure.
    """
    if cfg.inner_dtype == "float32":
        return _gmres_solve_mixed(op, b, cfg, x0=x0, use_kernel=use_kernel)
    t0 = time.perf_counter()
    n = int(b.shape[0])
    b = jnp.asarray(b)
    z = jnp.zeros(n, b.dtype) if x0 is None else jnp.asarray(x0)
    stats = SolveStats()
    if x0 is None:
        r = b
        bnorm = rnorm = float(jnp.linalg.norm(b))   # one sync, not two
    else:
        r, bn, rn = _residual_norms(op, b, z)
        bnorm, rnorm = (float(v) for v in jax.device_get((bn, rn)))
    stats.host_syncs += 1
    stats.dispatches += 1
    if bnorm == 0.0:
        return np.zeros(n), SolveStats(converged=True, rel_residual=0.0,
                                       wall_time_s=time.perf_counter() - t0)
    tol_abs = cfg.tol * bnorm
    empty_c = jnp.zeros((0, n), b.dtype)

    # Adaptive restart (anti-stagnation): restarted GMRES at a FIXED m can
    # stall on indefinite operators (Helmholtz) — the restart discards the
    # small-eigenvalue information every cycle. When a full cycle reduces the
    # residual by less than 2× we double m up to m_cap; each growth retraces
    # the jitted cycle once (new static shape), which converged runs never pay.
    m = cfg.m
    m_cap = min(n, cfg.m_max if cfg.m_max else 8 * cfg.m)
    no_prog = 0
    # free per-cycle telemetry: rnorm is already a host float every cycle
    hist = [] if obs.enabled() else None
    while True:
        if rnorm <= tol_abs:
            stats.converged = True
            break
        if stats.iterations >= cfg.maxiter:
            break
        cyc = arnoldi_cycle(op, empty_c, r, tol_abs, m=m,
                            orthog=cfg.orthog, use_kernel=use_kernel,
                            h_acc=cfg.cgs2_acc)
        j = int(cyc.j_used)
        stats.host_syncs += 2      # j_used + Hessenberg pull
        stats.dispatches += 1      # arnoldi_cycle
        if j == 0:
            break  # stagnation
        h = np.asarray(cyc.h)[: j + 1, :j]
        y = np.zeros(m, dtype=h.dtype)   # device-dtype padded factor
        y[:j] = hessenberg_lstsq(h, rnorm)
        rprev = rnorm
        z, r, rn = _fused_update(op, b, z, cyc.v, jnp.asarray(y))
        rnorm = float(rn)
        if hist is not None:
            hist.append(rnorm)
        stats.host_syncs += 2      # rn + breakdown flag
        stats.dispatches += 1
        stats.iterations += j
        stats.matvecs += j + 1
        stats.cycles += 1
        stats.breakdown = bool(cyc.breakdown)
        if stats.breakdown and rnorm > tol_abs:
            break  # exact breakdown but not converged: stop honestly
        grew = j == m and rnorm > tol_abs and rnorm > 0.5 * rprev and m < m_cap
        if grew:
            m = min(2 * m, m_cap)
        if stall_break:
            no_prog = no_prog + 1 if rnorm > 0.99 * rprev else 0
            if grew:
                no_prog = 0  # a longer cycle deserves a fresh shot
            elif no_prog >= 3:
                break  # round-off floor reached — hand back to the outer loop

    x = np.asarray(op.from_z(z))
    stats.host_syncs += 1
    stats.dispatches += 1
    stats.rel_residual = rnorm / bnorm
    stats.wall_time_s = time.perf_counter() - t0
    if hist is not None:
        stats.telemetry = KrylovTelemetry(res_hist=np.asarray(hist))
    return x, stats


def _ir_refine(op: PreconditionedOp, b, cfg: KrylovConfig, solve32, solve64,
               x0=None):
    """The fp64 iterative-refinement outer loop shared by the mixed GMRES
    and GCRO-DR drivers (the lockstep engine has its own per-chain-masked
    variant in solvers/batched.py).

    Invariants: `b`, the accumulated solution `x`, and every residual of
    record are fp64; each outer pass solves the correction system A·d = r
    through a callback — `solve32(r, tol_rel, iter_budget)` on the
    fp32-casted operator, or `solve64(...)` in full precision once fp32
    stagnates (a pass reducing ‖r‖ by < 2×, an overflow rollback, or
    `ir_max_outer` exhausted) — then re-derives the TRUE fp64 residual, so
    `cfg.tol` is always reachable. Callbacks own everything solver-specific
    (operator twins, recycle-carry transplants).
    """
    t0 = time.perf_counter()
    n = int(b.shape[0])
    b = jnp.asarray(b, jnp.float64)
    stats = SolveStats()
    if x0 is None:
        x = jnp.zeros(n, b.dtype)
        r = b
        bnorm = rnorm = float(jnp.linalg.norm(b))
        stats.host_syncs += 1
        stats.dispatches += 1
    else:
        # x0 follows the plain-path contract (z-space guess): x = M⁻¹ x0
        x = jnp.asarray(op.from_z(jnp.asarray(x0)))
        r, bn, rn = _residual_norms(op, b, jnp.asarray(x0))
        bnorm, rnorm = (float(v) for v in jax.device_get((bn, rn)))
        stats.host_syncs += 1
        stats.dispatches += 1
    if bnorm == 0.0:
        return np.zeros(n), SolveStats(converged=True, rel_residual=0.0,
                                       wall_time_s=time.perf_counter() - t0)
    tol_abs = cfg.tol * bnorm
    fallback = False
    # outer-pass telemetry (kind="outer"): the TRUE fp64 residual after
    # each refinement pass — already host floats, so recording is free
    hist = [] if obs.enabled() else None

    while rnorm > tol_abs and stats.iterations < cfg.maxiter:
        budget = cfg.maxiter - stats.iterations
        if not fallback and stats.outer_refinements < cfg.ir_max_outer:
            # ---- fp32 correction pass --------------------------------------
            tol_i = min(0.5, max(cfg.inner_tol, 0.25 * tol_abs / rnorm))
            d, st_in = solve32(r, tol_i, budget)
            stats.outer_refinements += 1
        else:
            # ---- fp64 fallback: finish the job in full precision -----------
            tol_i = min(0.5, max(0.5 * tol_abs / rnorm, 1e-14))
            d, st_in = solve64(r, tol_i, budget)
            stats.fp64_fallback = True
        stats.merge_inner(st_in)
        rprev, x_prev, r_prev = rnorm, x, r
        x, r, rn = _ir_accum(op.base, b, x, jnp.asarray(d))
        stats.matvecs += 1
        rnorm = float(rn)
        stats.host_syncs += 1      # outer residual norm
        stats.dispatches += 2      # _ir_accum + the d upcast transfer
        if not np.isfinite(rnorm) or rnorm > rprev:
            # fp32 overflow OR a diverging correction (finite but worse —
            # near-singular operators can blow up the inner solve): roll the
            # pass back so the next pass solves against the clean residual
            # instead of chasing the corrupted one with a tol scaled by it
            x, r, rnorm = x_prev, r_prev, rprev
        if hist is not None:
            hist.append(rnorm)
        if not (rnorm <= 0.5 * rprev):   # pass made no real progress
            if fallback or stats.fp64_fallback:
                break                    # fp64 cycles are stuck too — stop
            fallback = True              # fp32 stagnated → switch to fp64

    stats.converged = rnorm <= tol_abs
    stats.rel_residual = rnorm / bnorm
    stats.wall_time_s = time.perf_counter() - t0
    if hist is not None:
        stats.telemetry = KrylovTelemetry(res_hist=np.asarray(hist),
                                          kind="outer")
    return np.asarray(x), stats


def _gmres_solve_mixed(op: PreconditionedOp, b, cfg: KrylovConfig, x0=None,
                       use_kernel: bool = False):
    """fp64 iterative refinement over fp32 restarted-GMRES correction
    solves (`_ir_refine` with plain-GMRES callbacks)."""
    op32 = cast_operator(op, jnp.float32)

    def solve32(r, tol_i, budget):
        cfg_in = dataclasses.replace(cfg, inner_dtype="float64", tol=tol_i,
                                     maxiter=budget)
        return gmres_solve(op32, _downcast32(r), cfg_in,
                           use_kernel=use_kernel, stall_break=True)

    def solve64(r, tol_i, budget):
        cfg_in = dataclasses.replace(cfg, inner_dtype="float64", tol=tol_i,
                                     maxiter=budget)
        return gmres_solve(op, r, cfg_in, use_kernel=use_kernel)

    return _ir_refine(op, jnp.asarray(b), cfg, solve32, solve64, x0=x0)


def solve_gmres(problem_op, b_field, cfg: KrylovConfig, precond=None,
                use_kernel: bool = False):
    """Convenience wrapper over field-form problems (Stencil5 + (nx,ny) b)."""
    kops.check_solver_request(cfg, use_kernel)
    base = as_operator(problem_op, use_kernel=use_kernel)
    op = PreconditionedOp(base, precond)
    x, stats = gmres_solve(op, jnp.asarray(b_field).reshape(-1), cfg)
    return x.reshape(b_field.shape), stats
