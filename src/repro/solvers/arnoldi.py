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
    v: jax.Array          # (m+1, n) orthonormal basis (rows)
    h: jax.Array          # (m+1, m) Hessenberg (raw, un-rotated)
    b: jax.Array          # (k, m)   B = Cᴴ A V block (k may be 0)
    j_used: jax.Array     # int — Arnoldi steps actually taken
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


def _arnoldi_cycle_impl(op, c_rows, r0, tol_abs, *, m: int, orthog: str = "cgs2",
                        use_kernel: bool = False,
                        h_acc: str = "native") -> CycleResult:
    """Run ≤ m deflated Arnoldi steps starting from r0.

    op      : operator pytree (PreconditionedOp) — applied via apply_op
    c_rows  : (k, n) rows = C_kᴴ (k == 0 for plain GMRES)
    r0      : (n,) current residual (must be ⊥ range(C) for exact res_est)
    tol_abs : absolute residual target (rtol·‖b‖ computed by the caller)
    h_acc   : "native" accumulates the CGS2 coefficients in r0's dtype;
              "float64" keeps fp32 basis STORAGE but fp64 ACCUMULATION in
              the fused orthogonalization (KrylovConfig.cgs2_acc).

    Every array in the cycle carries r0.dtype — the precision-policy layer
    runs this whole dispatch in fp32 by handing in a casted operator and an
    fp32 residual; nothing below assumes f64.
    """
    with jax.named_scope("skr/arnoldi"):
        n = r0.shape[0]
        acc_dtype = jnp.float64 if h_acc == "float64" else None
        k = c_rows.shape[0]
        dt = r0.dtype
        # fp64 cycles of a kernel solver (its fp64 replay/fallback) run on
        # jnp where the kernels take no fp64 (a TPU)
        use_kernel = use_kernel and kops.kernels_take(dt, acc_dtype)
        beta = jnp.linalg.norm(r0)
        safe_beta = jnp.maximum(beta, jnp.finfo(dt).tiny)

        v = jnp.zeros((m + 1, n), dt).at[0].set(r0 / safe_beta)
        h = jnp.zeros((m + 1, m), dt)
        b = jnp.zeros((k, m), dt)
        cs = jnp.zeros((m,), dt)
        sn = jnp.zeros((m,), dt)
        g = jnp.zeros((m + 1,), dt).at[0].set(beta)

        def cond(carry):
            v, h, b, cs, sn, g, j, res, brk = carry
            return (j < m) & (res > tol_abs) & (~brk)

        # fused single-launch inner iteration (tentpole kernel): Jacobi
        # apply + stencil matvec + C-projection + CGS2 in one dispatch.
        # Routed ONLY when the kernel path is requested AND the operator
        # matches — the unfused composition below stays byte-for-byte for
        # every other configuration.
        fuse = use_kernel and _fusable(op, orthog)
        if fuse:
            inv_diag = (jnp.ones_like(r0) if op.precond is None
                        else op.precond.inv_diag)

        def body(carry):
            v, h, b, cs, sn, g, j, res, brk = carry
            # phase scopes name their whole path (solvers/batched.py
            # lists them); the rest of the body is the basis bookkeeping
            if fuse:
                mask = (jnp.arange(m + 1) <= j).astype(dt)
                with jax.named_scope("skr/arnoldi/matvec"):
                    w, hcol, bj = kops.arnoldi_step(
                        op.base.coeffs, inv_diag, c_rows, v, v[j], mask,
                        use_kernel=True, acc_dtype=acc_dtype)
                b_new = b.at[:, j].set(bj) if k > 0 else b
            else:
                with jax.named_scope("skr/arnoldi/matvec"):
                    w = apply_op(op, v[j])
                if k > 0:
                    with jax.named_scope("skr/arnoldi/orthog"):
                        bj = mm(c_rows, w)
                        w = w - mm(c_rows.T, bj)
                    b_new = b.at[:, j].set(bj)
                else:
                    b_new = b
                with jax.named_scope("skr/arnoldi/orthog"):
                    if orthog == "cgs2":
                        mask = (jnp.arange(m + 1) <= j).astype(dt)
                        w, hcol = kops.fused_orthog(
                            v, w, mask, use_kernel=use_kernel,
                            acc_dtype=acc_dtype)
                    else:
                        w, hcol = _mgs(v, w, j, m)
            hj1 = jnp.linalg.norm(w)
            brk_new = hj1 < 1e-14 * safe_beta
            # the new row by a select: under vmap a write at the per-chain
            # j + 1 is a scatter over chains, whose scoped VMEM on a TPU
            # outgrows the chip's 16 MiB at 128² in fp64
            row = jnp.arange(m + 1)[:, None] == j + 1
            vj1 = w / jnp.maximum(hj1, jnp.finfo(dt).tiny)
            v = jnp.where(row, vj1[None], v)
            hcol = hcol.at[j + 1].set(hj1)
            h = h.at[:, j].set(hcol)
            # Progressive Givens on a copy of the new column → exact LS
            # residual.
            cs, sn, col = _givens_apply(cs, sn, hcol, j)
            gj = g[j]
            g = g.at[j].set(cs[j] * gj).at[j + 1].set(-sn[j] * gj)
            res = jnp.abs(g[j + 1])
            return (v, h, b_new, cs, sn, g, j + 1, res, brk_new)

        init = (v, h, b, cs, sn, g, jnp.array(0), beta, jnp.array(False))
        v, h, b, cs, sn, g, j, res, brk = jax.lax.while_loop(cond, body,
                                                             init)
        return CycleResult(v=v, h=h, b=b, j_used=j, res_est=res,
                           breakdown=brk)


arnoldi_cycle = partial(jax.jit,
                        static_argnames=("m", "orthog", "use_kernel", "h_acc"))(
    _arnoldi_cycle_impl)


@partial(jax.jit, static_argnames=("m", "orthog", "use_kernel", "h_acc"))
def arnoldi_cycle_batched(ops, c_rows, r0, tol_abs, *, m: int,
                          orthog: str = "cgs2",
                          use_kernel: bool = False,
                          h_acc: str = "native") -> CycleResult:
    """B independent (deflated) Arnoldi cycles as ONE lockstep dispatch.

    ops     : operator pytree with a leading batch axis on every leaf
    c_rows  : (B, k, n); r0 : (B, n); tol_abs : (B,) per-chain absolute target
    Returns a CycleResult whose fields carry a leading B axis.

    Early-exit semantics: the vmapped `lax.while_loop` runs until EVERY chain
    has met its own stop condition; chains that finish early are frozen by the
    batching rule (their carry is masked), so per-chain `j_used`/`res_est` are
    exact. A chain entering with ‖r0‖ ≤ tol_abs takes 0 steps — passing
    tol_abs = +inf freezes a chain entirely (the lockstep "mask out" knob).
    """
    fn = partial(_arnoldi_cycle_impl, m=m, orthog=orthog, use_kernel=use_kernel,
                 h_acc=h_acc)
    return jax.vmap(fn)(ops, c_rows, r0, tol_abs)
