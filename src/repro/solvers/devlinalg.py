"""Batched ON-DEVICE small dense linear algebra for the lockstep engine.

Device counterparts of `hostlinalg.py`'s LAPACK drivers (which stay the
reference oracle, regression-tested against this module): stacked Hessenberg
least squares via batched QR with an SVD min-norm fallback, the padded
harmonic-Ritz pencils, the recycle-space refresh factors, and stacked
masked triangular inverses. Everything here is pure `jnp` on TPU-supported
primitives (matmul, QR, SVD, triangular solve; the TPU has no fp64 LU, so
square solves go through QR) so a GCRO-DR cycle — Arnoldi sweep, LS
update, the pencil of the next recycle space — traces into ONE device
program (solvers/batched.py). The pencils' eigensolve runs on the host
(`hostlinalg.ritz_*_padded`): a TPU emulates fp64 with loops of f32 pairs,
and an iterative eigensolve of (k+m)² matrices there is serial latency.

Ragged widths (the lockstep reality: every chain runs its own j ≤ m Arnoldi
steps) are handled by PADDING, not loops:

* LS blocks pad dead columns c ≥ j with unit columns e_{row_below_block};
  they are orthogonal to the live block, so one stacked QR block-decouples
  and the padded solution entries come out EXACTLY zero (the engines'
  padded-update no-op convention).
* Eigen pencils pad with a BIG diagonal (first-cycle) or decouple to a zero
  block (deflated), so padded eigendirections are never among the wanted
  ones and the extracted subspace lives entirely in the live block.

Rank trouble is gated, never raised: every driver returns an `ok` mask (or
blends in a fallback solution, or non-finite pencil entries the host gates
on) and the caller keeps the previous recycle space for gated chains —
mirroring hostlinalg's try/except + pivot-gate behavior chain-by-chain.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import precision
from repro.kernels.precision import matmul as mm

# conditioning gate shared with hostlinalg._stack_well_conditioned
_RTOL = 1e-12


def _tiny(dt) -> float:
    return float(jnp.finfo(dt).tiny)


def _big(dt) -> float:
    """Pencil-padding diagonal: large enough that padded eigenvalues are
    never among the smallest |θ|, small enough that its reciprocal and
    products stay representable (fp32-safe)."""
    return 1e30 if dt == jnp.float64 else 1e12


def _col_mask(j, width):
    """(B, 1, width) float mask of live columns c < j[i]."""
    return (jnp.arange(width)[None, :] < j[:, None])[:, None, :]


def _row_mask(j, height):
    """(B, height, 1) float mask of live rows r <= j[i]."""
    return (jnp.arange(height)[None, :] <= j[:, None])[:, :, None]


def _unit_pad_cols(a, j, row_offset: int):
    """Replace dead columns c >= j[i] of stacked (B, R, C) blocks with unit
    columns e_{row_offset + c + 1}.

    The unit rows sit strictly below the live block (which occupies rows
    < row_offset + j + 1 in every live column), so the padded columns are
    orthogonal to the live ones and mutually orthonormal: a stacked QR
    block-decouples and any LS solution is exactly zero in the padded
    coordinates.
    """
    bsz, rows, cols = a.shape
    live = _col_mask(j, cols)
    unit = (jnp.arange(rows)[:, None]
            == (jnp.arange(cols) + row_offset + 1)[None, :])
    return jnp.where(live, a, unit[None].astype(a.dtype))


def _diag_ok(r):
    """(B,) gate: every stacked upper-triangular factor safely invertible
    (hostlinalg._stack_well_conditioned, per chain instead of all-or-none)."""
    diag = jnp.abs(jnp.diagonal(r, axis1=-2, axis2=-1))
    floor = _RTOL * jnp.maximum(diag.max(axis=-1), _tiny(r.dtype))
    return (diag.min(axis=-1) > floor) & jnp.isfinite(diag).all(axis=-1)


def tri_inv_stacked(r, want):
    """Masked batched inverse of stacked upper-triangular factors.

    r: (B, k, k) R factors from a stacked QR; want: (B,) bool — chains that
    asked for the inverse. Returns (inv_r, ok): ok = want & well-conditioned;
    gated-out chains get the identity (a harmless right-multiply that the
    caller masks away). Replaces the per-chain `np.nonzero(want)` +
    `np.linalg.inv` host loop of the old warm-start path.
    """
    ok = want & _diag_ok(r)
    k = r.shape[-1]
    eye = jnp.eye(k, dtype=r.dtype)
    safe = jnp.where(ok[:, None, None], r, eye[None])
    inv = jax.lax.linalg.triangular_solve(safe, jnp.broadcast_to(
        eye[None], safe.shape), left_side=True, lower=False)
    return inv, ok


def _qr_solve(a, b):
    """Stacked a⁻¹·b via Householder QR + triangular solve — the TPU's
    fp64 square solve (it implements no fp64 LU). A singular `a` yields
    non-finite entries, like an LU solve; callers gate on that."""
    q, r = jnp.linalg.qr(a)
    return jax.lax.linalg.triangular_solve(
        r, mm(q.swapaxes(-1, -2), b), left_side=True, lower=False)


def _svd_lstsq(a, rhs):
    """Stacked min-norm LS via SVD pinv — the rank-deficient fallback,
    matching np.linalg.lstsq(rcond=None) cutoff semantics in the storage
    dtype. The SVD itself runs in fp64 (the result is cast back): in JAX 0.9
    an fp32 SVD with vectors aborts the TPU compiler; fp64 compiles."""
    dt, wide = a.dtype, jnp.float64
    u, s, vt = jnp.linalg.svd(a.astype(wide), full_matrices=False)
    eps = jnp.finfo(dt).eps
    cut = s[..., :1] * max(a.shape[-2:]) * eps
    sinv = jnp.where(s > cut, 1.0 / jnp.maximum(s, _tiny(wide)), 0.0)
    utb = jnp.einsum("bij,bi->bj", u, rhs.astype(wide))
    return jnp.einsum("bji,bj->bi", vt, sinv * utb).astype(dt)


def lstsq_stacked(a, rhs):
    """Stacked argmin_y ‖rhs_i − A_i y‖ on PRE-PADDED blocks.

    a: (B, R, C) with dead columns already unit-padded (`_unit_pad_cols`),
    rhs: (B, R) with dead rows zeroed. One stacked QR solves the whole
    batch; chains whose R factor trips the conditioning gate are blended
    with the stacked SVD min-norm solution instead (the hostlinalg
    np.linalg.lstsq fallback, without leaving the device). The SVD runs
    only in a call where some chain trips the gate (`lax.cond`): a healthy
    cycle pays for the QR alone.
    """
    q, r = jnp.linalg.qr(a)
    ok = _diag_ok(r)
    qtb = precision.einsum("bij,bi->bj", q, rhs)
    eye = jnp.eye(r.shape[-1], dtype=r.dtype)
    safe = jnp.where(ok[:, None, None], r, eye[None])
    y_qr = jax.lax.linalg.triangular_solve(
        safe, qtb[..., None], left_side=True, lower=False)[..., 0]
    return jax.lax.cond(
        ok.all(), lambda: y_qr,
        lambda: jnp.where(ok[:, None], y_qr, _svd_lstsq(a, rhs)))


def hessenberg_lstsq_stacked(h, j, beta):
    """Stacked argmin_y ‖β_i e₁ − H_i y‖ over B chains, on device.

    h: (B, m+1, m) raw Hessenbergs; j: (B,) effective widths (0 = frozen
    chain); beta: (B,) residual norms. Returns y (B, m) zero-padded —
    columns c ≥ j[i] come out exactly zero (unit-column padding), so rows
    with j[i] == 0 stay all-zero: the padded-update no-op convention.
    Oracle: hostlinalg.hessenberg_lstsq_stacked.
    """
    bsz, _, m = h.shape
    hp = _unit_pad_cols(h, j, row_offset=0)
    rhs = jnp.zeros((bsz, m + 1), h.dtype).at[:, 0].set(
        beta.astype(h.dtype))
    return lstsq_stacked(hp, rhs)


# ---------------------------------------------------------------------------
# harmonic-Ritz pencils (their eigensolve runs on the host:
# hostlinalg.ritz_first_cycle_padded, hostlinalg.ritz_deflated_padded)
# ---------------------------------------------------------------------------


def first_cycle_pencil_stacked(h, j):
    """The padded fresh-cycle pencils A (B, m, m) = H_m + h²_{m+1,m} H_m⁻ᴴ
    e_m e_mᴴ at the per-chain widths j (Alg. 2 line 14), dead rows and
    columns a BIG diagonal, so that their eigenvalues are never among the
    smallest |θ|. A chain with a singular H_m gets non-finite entries."""
    bsz, _, m = h.shape
    dt = h.dtype
    big = _big(dt)
    live = _col_mask(j, m) & _row_mask(j - 1, m)   # (B, m, m) live block
    hm = h[:, :m, :] * live
    hm = hm + (jnp.eye(m, dtype=bool)[None] & ~live) * big
    # e_m at the per-chain last live column (j-1); j=0 chains are gated out
    jm1 = jnp.clip(j - 1, 0, m - 1)
    em = jax.nn.one_hot(jm1, m, dtype=dt)
    h2 = h[jnp.arange(bsz), jnp.clip(j, 0, m), jm1]   # h[j, j-1] per chain
    corr = _qr_solve(hm.swapaxes(1, 2), em[..., None])[..., 0]
    return hm + (h2 ** 2)[:, None, None] * corr[:, :, None] * em[:, None, :]


def assemble_g_stacked(dnorm, bb, h, j):
    """Padded deflated-cycle Ĝ (B, k+mi+1, k+mi): [[D_k, B], [0, H̄]] with
    dead Arnoldi columns unit-padded (rows below the live block), ready for
    one stacked QR. dnorm: (B, k) ‖U col‖; bb: (B, k, mi); h: (B, mi+1, mi);
    j: (B,) effective widths."""
    bsz, k = dnorm.shape
    mi = h.shape[-1]
    dt = h.dtype
    live_c = _col_mask(j, mi)
    live_r = _row_mask(j, mi + 1)
    g = jnp.zeros((bsz, k + mi + 1, k + mi), dt)
    dsafe = jnp.maximum(dnorm, _tiny(dt))
    g = g.at[:, :k, :k].set(jnp.eye(k, dtype=dt)[None] / dsafe[:, None, :])
    g = g.at[:, :k, k:].set(bb * live_c)
    g = g.at[:, k:, k:].set(h * live_c * live_r)
    # unit columns for dead Arnoldi directions, rooted below the live block
    unit = (jnp.arange(mi + 1)[:, None]
            == (jnp.arange(mi) + 1)[None, :]).astype(dt)
    g = g.at[:, k:, k:].add(jnp.where(live_c, 0.0, unit[None]))
    return g


def assemble_whv_stacked(cu, cv, vu, vv, j):
    """Padded Ŵᴴ V̂ (B, k+mi+1, k+mi) from the small device blocks
    (gcrodr._whv_blocks): dead rows/columns zeroed so the pencil
    block-decouples against the padded Ĝ."""
    bsz, k, _ = cu.shape
    mi = vv.shape[-1]
    dt = cu.dtype
    live_c = _col_mask(j, mi)
    live_r = _row_mask(j, mi + 1)
    whv = jnp.zeros((bsz, k + mi + 1, k + mi), dt)
    whv = whv.at[:, :k, :k].set(cu)
    whv = whv.at[:, :k, k:].set(cv * live_c)
    whv = whv.at[:, k:, :k].set(vu * live_r)
    whv = whv.at[:, k:, k:].set(vv * live_c * live_r)
    return whv


def deflated_pencil_stacked(g, whv):
    """M = (ĜᴴĜ)⁻¹ ĜᴴŴᴴV̂ (B, k+mi, k+mi) from the padded blocks of
    `assemble_*_stacked`: block diagonal with a ZERO dead block. A chain
    with a singular ĜᴴĜ gets non-finite entries."""
    a1 = mm(g.swapaxes(1, 2), g)                 # SPD (+ identity dead block)
    a2 = mm(g.swapaxes(1, 2), whv)
    return _qr_solve(a1, a2)


def refresh_factors(gp, want):
    """Stacked QR of Ĝ·P (or H̄·P on fresh cycles) + gated R inverse — the
    recycle-space renormalization C' = Ŵ Q, U' = V̂ P R⁻¹ (Alg. 2 l.31-33).

    gp: (B, R, k) stacked products; want: (B,) chains refreshing. Returns
    (q, inv_rr, ok): gated-out chains get q = 0, inv_rr = I (masked away by
    the caller's select).
    """
    q, rr = jnp.linalg.qr(gp)
    inv_rr, ok = tri_inv_stacked(rr, want)
    okb = ok[:, None, None]
    return jnp.where(okb, q, 0.0), inv_rr, ok
