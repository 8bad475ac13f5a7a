"""The jitted (deflated) Arnoldi cycle — the hot loop shared by GMRES and
GCRO-DR.

One call runs up to `m` Arnoldi steps of the operator (I − C Cᴴ)·A with
progressive Givens residual tracking and early exit (`lax.while_loop`), so a
solver cycle is ONE device dispatch regardless of where it converges. The
m×m eigen/LS cleanup happens on host (numpy) between cycles — O(m³) ≲ µs —
the same device/host split PETSc uses (DESIGN §4.3).

Key GCRO-DR fact exploited here: because Ĝ's recycled block [[D_k, B]] has
nonsingular diagonal D_k, the least-squares residual of
min‖Ŵᴴr − Ĝ y‖ equals the residual of the Hessenberg-only subproblem
min‖β e₁ − H̄ y₂‖ — so the SAME Givens recurrence gives the exact residual
for both GMRES (k=0) and GCRO-DR (k>0), and early exit is exact.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.kernels.precision import matmul as mm
from repro.solvers.operator import PreconditionedOp, StencilOp, apply_op
from repro.solvers.precond import JacobiPrecond


def _fusable(op, orthog: str) -> bool:
    """True when the whole inner iteration (precond → stencil matvec →
    C-projection → CGS2) can route through the single-launch fused kernel
    (kernels/arnoldi_step.py). Decided at trace time from the operator
    pytree structure — other operator/preconditioner kinds keep the
    composed per-op kernel path unchanged."""
    return (orthog == "cgs2"
            and isinstance(op, PreconditionedOp)
            and isinstance(op.base, StencilOp)
            and (op.precond is None or isinstance(op.precond, JacobiPrecond)))


class CycleResult(NamedTuple):
    # per chain; `arnoldi_cycle_batched` adds a leading chain axis to each
    v: jax.Array          # (m+1, n) orthonormal basis (rows)
    h: jax.Array          # (m+1, m) Hessenberg (raw, un-rotated)
    b: jax.Array          # (k, m)   B = Cᴴ A V block (k may be 0)
    j_used: jax.Array     # int32 — Arnoldi steps actually taken
    res_est: jax.Array    # float — exact LS residual after j_used steps
    breakdown: jax.Array  # bool — lucky breakdown hit


def _givens_apply(cs, sn, col, j):
    """Apply rotations 0..j-1 to col, then form rotation j. Returns updated
    (cs, sn, col, denom)."""

    def body(i, c):
        t = cs[i] * c[i] + sn[i] * c[i + 1]
        c = c.at[i + 1].set(-sn[i] * c[i] + cs[i] * c[i + 1])
        return c.at[i].set(t)

    col = jax.lax.fori_loop(0, j, body, col)
    a, bb = col[j], col[j + 1]
    denom = jnp.sqrt(a * a + bb * bb)
    safe = jnp.maximum(denom, jnp.finfo(col.dtype).tiny)
    cs_j = jnp.where(denom > 0, a / safe, 1.0)
    sn_j = jnp.where(denom > 0, bb / safe, 0.0)
    cs = cs.at[j].set(cs_j)
    sn = sn.at[j].set(sn_j)
    col = col.at[j].set(denom).at[j + 1].set(0.0)
    return cs, sn, col


def _mgs(v, w, j, m):
    """Modified Gram-Schmidt (paper-faithful): sequential projections."""

    def body(i, carry):
        w, h = carry
        active = (i <= j).astype(w.dtype)
        hi = active * mm(v[i], w)
        w = w - hi * v[i]
        return w, h.at[i].set(hi)

    h0 = jnp.zeros((m + 1,), w.dtype)
    return jax.lax.fori_loop(0, m + 1, body, (w, h0))


def _keep(live, new, old):
    """Per chain: `new` where the chain is still stepping, else `old`."""
    return jnp.where(live.reshape(live.shape + (1,) * (new.ndim - 1)), new,
                     old)


def arnoldi_cycle_batched(ops, c_rows, r0, tol_abs, *, m: int,
                          orthog: str = "cgs2",
                          use_kernel: bool = False,
                          h_acc: str = "native") -> CycleResult:
    """B independent (deflated) Arnoldi cycles in lockstep: ≤ m steps of
    (I − C Cᴴ)·A per chain from its r0. Traced into the program that calls
    it (the lockstep cycle programs, `arnoldi_cycle`), so its device ops
    carry that program's name.

    ops     : operator pytree (PreconditionedOp) with a leading batch axis on
              every leaf — applied via apply_op
    c_rows  : (B, k, n) rows C_kᴴ (k == 0 for plain GMRES)
    r0      : (B, n) current residuals (⊥ range(C) for an exact res_est)
    tol_abs : (B,) absolute residual targets (rtol·‖b‖ from the caller)
    h_acc   : "native" accumulates the CGS2 coefficients in r0's dtype;
              "float64" keeps fp32 basis STORAGE but fp64 ACCUMULATION in
              the fused orthogonalization (KrylovConfig.cgs2_acc).
    Returns a CycleResult whose fields carry a leading B axis.

    Every chain starts at step 0, so one scalar step counter `j` serves
    them all: step j writes basis row j + 1 of every chain in place (one
    (B, n) slab), and a chain that has stopped (converged, broken down, or
    entered with tol_abs = +inf — the lockstep "mask out" knob) keeps its
    small carries and its rows as they were, so per-chain `j_used` and
    `res_est` are exact. The loop runs while any chain steps. On the fused
    kernel's path the basis is held in the kernel's (nx, ny) tile layout
    for the whole sweep and flattened once at its end.

    Every array in the cycle carries r0.dtype — the precision-policy layer
    runs this whole dispatch in fp32 by handing in a casted operator and an
    fp32 residual; nothing below assumes f64.
    """
    with jax.named_scope("skr/arnoldi"):
        bsz, n = r0.shape
        k = c_rows.shape[1]
        dt = r0.dtype
        tiny = jnp.finfo(dt).tiny
        acc_dtype = jnp.float64 if h_acc == "float64" else None
        # fp64 cycles of a kernel solver (its fp64 replay/fallback) run on
        # jnp where the kernels take no fp64 (a TPU)
        use_kernel = use_kernel and kops.kernels_take(dt, acc_dtype)
        # fused single-launch inner iteration (tentpole kernel): Jacobi
        # apply + stencil matvec + C-projection + CGS2 in one dispatch.
        # Routed ONLY when the kernel path is requested AND the operator
        # matches — the unfused composition below serves every other
        # configuration.
        fuse = use_kernel and _fusable(ops, orthog)
        # a basis row: a flat (n,) vector, or the fused kernel's (nx, ny)
        # field, so that no step relays out the basis or C for the kernel
        row = ops.base.grid if fuse else (n,)
        inv_diag = None
        if fuse:
            inv_diag = (jnp.ones_like(r0) if ops.precond is None
                        else ops.precond.inv_diag).reshape((bsz,) + row)
            c_rows = c_rows.reshape((bsz, k) + row)
        beta = jnp.linalg.norm(r0, axis=1)
        safe_beta = jnp.maximum(beta, tiny)

        v = jnp.zeros((bsz, m + 1) + row, dt).at[:, 0].set(
            (r0 / safe_beta[:, None]).reshape((bsz,) + row))
        small = (jnp.zeros((bsz, m + 1, m), dt),      # h
                 jnp.zeros((bsz, k, m), dt),          # b
                 jnp.zeros((bsz, m), dt),             # cs
                 jnp.zeros((bsz, m), dt),             # sn
                 jnp.zeros((bsz, m + 1), dt).at[:, 0].set(beta),   # g
                 beta,                                # res
                 jnp.zeros((bsz,), bool))             # brk

        def stepping(small):
            return (small[5] > tol_abs) & ~small[6]

        def chain_step(op, c, v, vj, mask, idg, sbeta, h, b, cs, sn, g,
                       _res, _brk, j):
            """One chain's step j: its new basis row and small carries."""
            # phase scopes name their whole path (solvers/batched.py lists
            # them); the rest of the step is the basis bookkeeping
            if fuse:
                with jax.named_scope("skr/arnoldi/matvec"):
                    w, hcol, bj = kops.arnoldi_step(
                        op.base.coeffs, idg, c, v, vj, mask,
                        use_kernel=True, acc_dtype=acc_dtype)
            else:
                with jax.named_scope("skr/arnoldi/matvec"):
                    w = apply_op(op, vj)
                if k > 0:
                    with jax.named_scope("skr/arnoldi/orthog"):
                        bj = mm(c, w)
                        w = w - mm(c.T, bj)
                with jax.named_scope("skr/arnoldi/orthog"):
                    if orthog == "cgs2":
                        w, hcol = kops.fused_orthog(
                            v, w, mask, use_kernel=use_kernel,
                            acc_dtype=acc_dtype)
                    else:
                        w, hcol = _mgs(v, w, j, m)
            if k > 0:
                b = b.at[:, j].set(bj)
            hj1 = jnp.linalg.norm(w)
            brk = hj1 < 1e-14 * sbeta
            vj1 = w / jnp.maximum(hj1, tiny)
            hcol = hcol.at[j + 1].set(hj1)
            h = h.at[:, j].set(hcol)
            # Progressive Givens on a copy of the new column → exact LS
            # residual.
            cs, sn, col = _givens_apply(cs, sn, hcol, j)
            gj = g[j]
            g = g.at[j].set(cs[j] * gj).at[j + 1].set(-sn[j] * gj)
            return vj1, (h, b, cs, sn, g, jnp.abs(g[j + 1]), brk)

        def cond(carry):
            j, _, small, _ = carry
            return (j < m) & stepping(small).any()

        def body(carry):
            j, v, small, used = carry
            live = stepping(small)
            vj = jax.lax.dynamic_index_in_dim(v, j, axis=1, keepdims=False)
            mask = jnp.broadcast_to((jnp.arange(m + 1) <= j).astype(dt),
                                    (bsz, m + 1))
            vj1, new = jax.vmap(chain_step, in_axes=(0,) * 14 + (None,))(
                ops, c_rows, v, vj, mask, inv_diag, safe_beta, *small, j)
            small = tuple(_keep(live, a, o) for a, o in zip(new, small))
            # the new row in place: one (B, 1, ...) slab at the shared step.
            # A stopped chain's row j + 1 lies past its j_used, so it is
            # still zero (reading it back would make XLA copy the basis)
            v = jax.lax.dynamic_update_index_in_dim(
                v, _keep(live, vj1, 0.0)[:, None], j + 1, axis=1)
            return j + 1, v, small, jnp.where(live, j + 1, used)

        init = (jnp.int32(0), v, small, jnp.zeros((bsz,), jnp.int32))
        _, v, small, used = jax.lax.while_loop(cond, body, init)
        h, b, _, _, _, res, brk = small
        return CycleResult(v=v.reshape(bsz, m + 1, n), h=h, b=b, j_used=used,
                           res_est=res, breakdown=brk)


@partial(jax.jit, static_argnames=("m", "orthog", "use_kernel", "h_acc"))
def arnoldi_cycle(op, c_rows, r0, tol_abs, *, m: int, orthog: str = "cgs2",
                  use_kernel: bool = False,
                  h_acc: str = "native") -> CycleResult:
    """One chain's cycle (the sequential solvers): `arnoldi_cycle_batched`
    on a leading axis of one. c_rows (k, n), r0 (n,), tol_abs scalar; the
    result carries no batch axis."""
    cyc = arnoldi_cycle_batched(
        jax.tree.map(lambda x: x[None], op), c_rows[None], r0[None],
        jnp.reshape(tol_abs, (1,)), m=m, orthog=orthog,
        use_kernel=use_kernel, h_acc=h_acc)
    return jax.tree.map(lambda x: x[0], cyc)
