"""GCRO-DR — Generalized Conjugate Residual with inner Orthogonalization and
Deflated Restarting (Parks et al. 2006; paper App. B.2 Algorithm 2), the
recycling engine of SKR.

The solver is STATEFUL across a sequence of systems: after system i it keeps
Ỹ_k = U_k (the approximate invariant subspace of the smallest harmonic Ritz
values) and re-biorthogonalizes it against A^(i+1) (Alg. 2 lines 2-7 /
App. B.1). GMRES is exactly the k=0 special case — asserted in tests.

Device/host split (§Perf iter 4): Arnoldi cycles AND all O(m·n) update
algebra run as fused jitted dispatches with PADDED static shapes (y, P, Q
zero-padded to the full cycle width, so early-exit cycles reuse the same
executable); only the O(m³) eigen/LS/QR cleanup runs on host — the same
split PETSc uses, but with ~4 device round-trips per cycle instead of ~15.

The padded static shapes are also what makes the fused steps below vmap
cleanly: `solvers/batched.py` lifts each of them over a leading chain axis
to advance B independent recycling chains in lockstep (App. E.2.2).

Precision policy: `cfg.inner_dtype="float32"` routes `solve` through an
fp64 outer iterative-refinement loop (`_solve_mixed`): every Arnoldi cycle,
preconditioner apply and recycle-space update runs in fp32 on the casted
operator while the operator/RHS of record — and the emitted labels — stay
fp64. The recycle carry U_k is STORED fp32 (half the checkpoint/HBM
footprint; it only seeds the next search space, accuracy is owned by the
outer loop). The fp64 default takes the historical code path unchanged.
"""
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
from repro.solvers.gmres import (_downcast32, _ir_refine, _residual_norms,
                                 gmres_solve)
from repro.solvers.hostlinalg import (harmonic_ritz_deflated,
                                      harmonic_ritz_first_cycle,
                                      hessenberg_lstsq, right_tri_solve)
from repro.solvers.operator import (PreconditionedOp, apply_op, as_operator,
                                    cast_operator)
from repro.solvers.types import KrylovConfig, SolveStats

_apply_cols = jax.jit(jax.vmap(apply_op, in_axes=(None, 1), out_axes=1))


# --------------------------------------------------------------------------
# fused device steps (shapes static per (n, m, k) — compiled once/sequence)
# --------------------------------------------------------------------------

@jax.jit
def _warm_start(u, au_q, z, r):
    """Alg. 2 lines 6-7 given Q from qr(A·U_old): project the initial
    residual onto range(C)ᶜ and absorb the correction into z."""
    ctr = mm(au_q.T, r)
    z = z + mm(u, ctr)
    r = r - mm(au_q, ctr)
    return z, r, jnp.linalg.norm(r)


@jax.jit
def _fresh_update(op, b, z, v, y):
    """z += Vᵀy (y zero-padded to m); recompute the true residual."""
    z = z + mm(v[:-1].T, y)
    r = b - apply_op(op, z)
    return z, r, jnp.linalg.norm(r)


@jax.jit
def _fresh_cu(v, h, p, q):
    """First recycle space: Ỹ = V P, C = V_{m+1} Q (P, Q zero-padded)."""
    yk = mm(v[:-1].T, p)
    c = mm(v.T, q)
    return c, yk


@jax.jit
def _rhs_and_dnorm(c, u, v, r):
    """Ŵᴴr pieces + ‖U columns‖ for the host-side LS solve."""
    return mm(c.T, r), mm(v, r), jnp.linalg.norm(u, axis=0)


@jax.jit
def _deflated_update(op, b, z, ut, v, y_k, y_m):
    """z += Û y_k + V y_m (zero-padded); true residual + Ŵᴴ V̂ pencil."""
    z = z + mm(ut, y_k) + mm(v[:-1].T, y_m)
    r = b - apply_op(op, z)
    # Ŵ = [C V_{m+1}] is produced by the caller as (c, v); the pencil
    # Ŵᴴ V̂ is assembled on host from these small blocks.
    return z, r, jnp.linalg.norm(r)


@jax.jit
def _whv_blocks(c, ut, v):
    """Small blocks of Ŵᴴ V̂: Ŵ = [c, Vrows], V̂ = [ut, Vrows[:-1]]."""
    cu = mm(c.T, ut)                   # (k, k)
    cv = mm(c.T, v[:-1].T)             # (k, m)
    vu = mm(v, ut)                     # (m+1, k)
    vv = mm(v, v[:-1].T)               # (m+1, m)
    return cu, cv, vu, vv


@jax.jit
def _next_cu(ut, v, c, p_k, p_m, q_c, q_v):
    """C' = Ŵ Q, Ỹ = V̂ P from padded host factors."""
    yk = mm(ut, p_k) + mm(v[:-1].T, p_m)
    c_new = mm(c, q_c) + mm(v.T, q_v)
    return c_new, yk


class GCRODRSolver:
    """Sequence-stateful GCRO-DR. One instance per sorted sequence.

    Usage:
        solver = GCRODRSolver(cfg)
        for problem in sorted_sequence:
            x, stats = solver.solve(op_i, b_i)
    """

    def __init__(self, cfg: KrylovConfig, use_kernel: bool = False,
                 stall_break: bool = False):
        kops.check_solver_request(cfg, use_kernel)
        self.cfg = cfg
        self.use_kernel = use_kernel
        # stall_break: break out of no-progress cycles instead of spinning to
        # maxiter — set by the mixed-precision outer loop on its inner fp32
        # solvers, where hitting the fp32 round-off floor is an expected exit
        self.stall_break = stall_break
        self.u_carry: np.ndarray | None = None  # (n, k) recycle space
        self.systems_solved = 0
        self._inner: GCRODRSolver | None = None   # fp32 correction solver
        self._inner64: GCRODRSolver | None = None  # fp64 fallback solver

    # -- resumable-datagen support (core/skr.py checkpoints this) --------
    def state_dict(self) -> dict:
        return {"u_carry": self.u_carry, "systems_solved": self.systems_solved}

    def load_state_dict(self, state: dict):
        self.u_carry = state["u_carry"]
        self.systems_solved = int(state["systems_solved"])

    def reset(self):
        self.u_carry = None
        self.systems_solved = 0
        self._inner = None
        self._inner64 = None

    # --------------------------------------------------------------------
    def _refresh_space(self, last_cycle, k: int, mi: int, stats=None):
        """Harmonic-Ritz recycle-space refresh from a deflated cycle
        (Alg. 2 lines 29-33). Returns (C', U') or None on rank trouble."""
        j, g, ut, cyc, c_dev = last_cycle
        if stats is not None:  # 4 block pulls + _whv_blocks/_next_cu launches
            stats.host_syncs += 4
            stats.dispatches += 2
        cu, cv, vu, vv = [np.asarray(a)
                          for a in _whv_blocks(c_dev, ut, cyc.v)]
        whv = np.zeros((k + j + 1, k + j))
        whv[:k, :k] = cu
        whv[:k, k:] = cv[:, :j]
        whv[k:, :k] = vu[: j + 1]
        whv[k:, k:] = vv[: j + 1, :j]
        p = harmonic_ritz_deflated(g, whv, k)
        if p.shape[1] != k:
            return None
        q, rr = np.linalg.qr(g @ p)
        diag = np.abs(np.diag(rr))
        if diag.min() <= 1e-12 * max(diag.max(), 1e-300):
            return None
        # host factors ship in the DEVICE dtype (fp32 inner cycles must not
        # silently re-widen the recycle space; f64 path: no-op casts)
        dt = ut.dtype
        p_m = np.zeros((mi, k))
        p_m[:j] = p[k:]
        q_v = np.zeros((mi + 1, k))
        q_v[: j + 1] = q[k:]
        c_new, yk = _next_cu(ut, cyc.v, c_dev,
                             jnp.asarray(p[:k], dt), jnp.asarray(p_m, dt),
                             jnp.asarray(q[:k], dt), jnp.asarray(q_v, dt))
        return c_new, mm(yk, jnp.asarray(np.linalg.inv(rr), dt))

    def _solve_mixed(self, op: PreconditionedOp, b, x0=None):
        """fp64 iterative refinement over fp32 GCRO-DR correction solves
        (`_ir_refine` with recycling callbacks).

        The fp32 inner solver keeps the sequence-stateful recycle carry —
        in fp32, across passes AND across systems; an fp64-fallback pass
        borrows the carry upcast and hands its refreshed space back
        downcast, so the chain survives precision switches.
        """
        cfg = self.cfg
        op32 = cast_operator(op, jnp.float32)
        if self._inner is None:
            self._inner = GCRODRSolver(cfg, use_kernel=self.use_kernel,
                                       stall_break=True)
        inner = self._inner
        # the carry rides the PUBLIC u_carry (checkpointed by core/skr.py),
        # STORED fp32 — downcast whatever precision last produced it
        inner.u_carry = (np.asarray(self.u_carry, np.float32)
                         if self.u_carry is not None else None)

        def solve32(r, tol_i, budget):
            inner.cfg = dataclasses.replace(cfg, inner_dtype="float64",
                                            tol=tol_i, maxiter=budget)
            return inner.solve(op32, _downcast32(r))

        def solve64(r, tol_i, budget):
            if self._inner64 is None:
                self._inner64 = GCRODRSolver(cfg, use_kernel=self.use_kernel)
            self._inner64.cfg = dataclasses.replace(
                cfg, inner_dtype="float64", tol=tol_i, maxiter=budget)
            self._inner64.u_carry = (np.asarray(inner.u_carry, np.float64)
                                     if inner.u_carry is not None else None)
            d, st_in = self._inner64.solve(op, r)
            if self._inner64.u_carry is not None:
                inner.u_carry = np.asarray(self._inner64.u_carry, np.float32)
            return d, st_in

        x, stats = _ir_refine(op, jnp.asarray(b), cfg, solve32, solve64,
                              x0=x0)
        if inner.u_carry is not None:
            self.u_carry = np.asarray(inner.u_carry, np.float32)
        self.systems_solved += 1
        return x, stats

    def solve(self, op: PreconditionedOp, b, x0=None):
        cfg = self.cfg
        if cfg.k == 0:
            x, stats = gmres_solve(op, b, cfg, x0=x0,
                                   use_kernel=self.use_kernel,
                                   stall_break=self.stall_break)
            self.systems_solved += 1
            return x, stats
        if cfg.inner_dtype == "float32":
            return self._solve_mixed(op, b, x0=x0)

        t0 = time.perf_counter()
        n = int(b.shape[0])
        b = jnp.asarray(b)
        z = jnp.zeros(n, b.dtype) if x0 is None else jnp.asarray(x0)
        stats = SolveStats()
        if x0 is None:
            r = b
            bnorm = rnorm = float(jnp.linalg.norm(b))  # ONE host sync
            stats.host_syncs += 1
            stats.dispatches += 1
        else:
            r, bn_d, rn_d = _residual_norms(op, b, z)  # one fused dispatch
            bnorm, rnorm = (float(v) for v in jax.device_get((bn_d, rn_d)))
            stats.host_syncs += 1
            stats.dispatches += 1
        if bnorm == 0.0:
            stats.converged = True
            stats.rel_residual = 0.0
            stats.wall_time_s = time.perf_counter() - t0
            self.systems_solved += 1
            return np.zeros(n), stats
        tol_abs = cfg.tol * bnorm

        c_dev = None  # (n, k) device
        u_dev = None
        k = cfg.k

        # ---- warm start: re-biorthogonalize the carried recycle space ----
        if self.u_carry is not None and self.u_carry.shape[1] == k \
                and rnorm > tol_abs:
            u_old = jnp.asarray(self.u_carry)
            au = _apply_cols(op, u_old)                      # (n, k)
            stats.matvecs += k
            q, rr = jnp.linalg.qr(au)                        # reduced QR
            rr_np = np.asarray(rr)
            stats.host_syncs += 1          # R factor pull
            stats.dispatches += 2          # _apply_cols + qr
            diag = np.abs(np.diag(rr_np))
            if diag.min() > 1e-12 * max(diag.max(), 1e-300):
                c_dev = q
                u_dev = mm(u_old, jnp.asarray(
                    np.linalg.inv(rr_np)))                   # U R⁻¹
                z, r, rn = _warm_start(u_dev, c_dev, z, r)
                rnorm = float(rn)
                stats.host_syncs += 1
                stats.dispatches += 1

        empty_c = jnp.zeros((0, n), b.dtype)
        dt = b.dtype        # host factors ship back in the device dtype
        last_cycle = None   # (j, g, ut, cyc, c) of the latest deflated cycle
        no_prog = 0         # consecutive no-progress cycles (stall_break)
        # per-cycle convergence telemetry is FREE here: the sequential
        # driver already pulls rnorm to host every cycle (contrast the
        # lockstep engine's device rings in solvers/batched.py)
        hist = [] if obs.enabled() else None
        dims = [] if hist is not None else None

        while True:
            if rnorm <= tol_abs:
                stats.converged = True
                break
            if stats.iterations >= cfg.maxiter:
                break
            if self.stall_break and no_prog >= 3:
                break  # round-off floor — hand back to the outer IR loop
            rprev = rnorm

            if c_dev is None:
                # ---- fresh GMRES(m) cycle + first recycle space (l.9-18) --
                m = cfg.m
                cyc = arnoldi_cycle(op, empty_c, r, tol_abs, m=m,
                                    orthog=cfg.orthog, use_kernel=self.use_kernel,
                                    h_acc=cfg.cgs2_acc)
                j = int(cyc.j_used)
                stats.host_syncs += 2      # j_used + Hessenberg pull
                stats.dispatches += 1      # arnoldi_cycle
                if j == 0:
                    break
                h = np.asarray(cyc.h)                       # (m+1, m) small
                y = np.zeros(m, dtype=h.dtype)
                y[:j] = hessenberg_lstsq(h[: j + 1, :j], rnorm)
                z, r, rn = _fresh_update(op, b, z, cyc.v, jnp.asarray(y))
                rnorm = float(rn)
                stats.host_syncs += 1
                stats.dispatches += 1
                stats.iterations += j
                stats.matvecs += j + 1
                stats.cycles += 1
                no_prog = no_prog + 1 if rnorm > 0.99 * rprev else 0
                k_eff = min(k, j - 1)
                if k_eff >= 1:
                    p = harmonic_ritz_first_cycle(h, j, k_eff)
                    if p.shape[1] == k:
                        q, rr = np.linalg.qr(h[: j + 1, :j] @ p)
                        diag = np.abs(np.diag(rr))
                        if diag.min() > 1e-12 * max(diag.max(), 1e-300):
                            p_pad = np.zeros((m, k), dtype=h.dtype)
                            p_pad[:j] = p
                            q_pad = np.zeros((m + 1, k), dtype=h.dtype)
                            q_pad[: j + 1] = q
                            stats.dispatches += 1
                            c_dev, yk = _fresh_cu(cyc.v, cyc.h,
                                                  jnp.asarray(p_pad),
                                                  jnp.asarray(q_pad))
                            u_dev = mm(yk, jnp.asarray(np.linalg.inv(rr), dt))
                if hist is not None:
                    hist.append(rnorm)
                    dims.append(k if c_dev is not None else 0)
                continue

            # ---- deflated cycle (Alg. 2 lines 19-33) ----------------------
            mi = cfg.m - k
            cyc = arnoldi_cycle(op, c_dev.T, r, tol_abs, m=mi,
                                orthog=cfg.orthog, use_kernel=self.use_kernel,
                                h_acc=cfg.cgs2_acc)
            j = int(cyc.j_used)
            stats.host_syncs += 1
            stats.dispatches += 1          # arnoldi_cycle
            if j == 0:
                break
            ctr, vr, dnorm = _rhs_and_dnorm(c_dev, u_dev, cyc.v, r)
            stats.host_syncs += 5          # h, b, dnorm, ctr, vr pulls
            stats.dispatches += 1
            h = np.asarray(cyc.h)[: j + 1, :j]               # effective block
            bb = np.asarray(cyc.b)[:, :j]
            dnorm_np = np.maximum(np.asarray(dnorm, np.float64), 1e-300)
            ut = u_dev / dnorm                               # device Ũ_k

            # host pencil at the EFFECTIVE width j (padded columns would
            # feed spurious θ≈0 null directions to the harmonic-Ritz eig);
            # host LS runs in f64 regardless — factors ship back in dt
            g = np.zeros((k + j + 1, k + j))
            g[:k, :k] = np.diag(1.0 / dnorm_np)
            g[:k, k:] = bb
            g[k:, k:] = h
            rhs = np.concatenate([np.asarray(ctr),
                                  np.asarray(vr)[: j + 1]])
            y, *_ = np.linalg.lstsq(g, rhs, rcond=None)
            y_m = np.zeros(mi)
            y_m[:j] = y[k:]

            z, r, rn = _deflated_update(op, b, z, ut, cyc.v,
                                        jnp.asarray(y[:k], dt),
                                        jnp.asarray(y_m, dt))
            rnorm = float(rn)
            stats.host_syncs += 2          # rn + breakdown flag below
            stats.dispatches += 1          # _deflated_update
            stats.iterations += j
            stats.matvecs += j + 1
            stats.cycles += 1
            no_prog = no_prog + 1 if rnorm > 0.99 * rprev else 0

            # next recycle space from the harmonic Ritz pencil — either
            # every cycle (paper-faithful) or deferred to the last cycle
            last_cycle = (j, g, ut, cyc, c_dev)
            if cfg.ritz_refresh == "cycle":
                refreshed = self._refresh_space(last_cycle, k, mi, stats)
                if refreshed is not None:
                    c_dev, u_dev = refreshed
            if hist is not None:
                hist.append(rnorm)
                dims.append(k)
            if bool(cyc.breakdown) and rnorm > tol_abs:
                break

        if cfg.ritz_refresh == "final" and last_cycle is not None:
            refreshed = self._refresh_space(last_cycle, k, cfg.m - k, stats)
            if refreshed is not None:
                _, u_dev = refreshed

        x = np.asarray(op.from_z(z))
        stats.host_syncs += 1
        stats.dispatches += 1
        stats.rel_residual = rnorm / bnorm
        stats.wall_time_s = time.perf_counter() - t0
        if hist is not None:
            stats.telemetry = KrylovTelemetry(
                res_hist=np.asarray(hist),
                defl_dim=np.asarray(dims, np.int32))
        # carry Ỹ_k = U_k to the next system (Alg. 2 line 34)
        if u_dev is not None:
            self.u_carry = np.asarray(u_dev)
            stats.host_syncs += 1
        self.systems_solved += 1
        return x, stats


def solve_gcrodr(problem_op, b_field, cfg: KrylovConfig, precond=None,
                 solver: GCRODRSolver | None = None, use_kernel: bool = False):
    """Field-form convenience wrapper; pass a shared `solver` to recycle."""
    solver = solver or GCRODRSolver(cfg, use_kernel=use_kernel)
    base = as_operator(problem_op, use_kernel=use_kernel)
    op = PreconditionedOp(base, precond)
    x, stats = solver.solve(op, jnp.asarray(b_field).reshape(-1))
    return x.reshape(b_field.shape), stats, solver
