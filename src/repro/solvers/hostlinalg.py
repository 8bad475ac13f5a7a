"""Small host-side dense linear algebra between Arnoldi cycles (m ≲ 200:
microseconds on host, no TPU-side nonsymmetric eig exists — DESIGN §4.3)."""
from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import scipy.linalg


def hessenberg_lstsq(h: np.ndarray, beta: float) -> np.ndarray:
    """argmin_y ‖β e₁ − H y‖ for the (j+1, j) Hessenberg block."""
    g = np.zeros(h.shape[0])
    g[0] = beta
    y, *_ = np.linalg.lstsq(h, g, rcond=None)
    return y


def real_spanning_basis(evals: np.ndarray, evecs: np.ndarray, k: int) -> np.ndarray:
    """k-column real orthonormal basis spanning the invariant subspace of the
    eigenvectors with SMALLEST |λ| (harmonic Ritz selection, Alg. 2 l.14/29).

    Complex conjugate pairs contribute their real/imag parts; rank-revealing
    pivoted QR picks k independent directions. Returns (n, k_eff), k_eff ≤ k.
    """
    finite = np.isfinite(evals)
    evals = np.where(finite, evals, np.inf)
    order = np.argsort(np.abs(evals))
    cand = []
    for idx in order[: 2 * k + 2]:
        if not np.isfinite(evals[idx]):
            continue
        v = evecs[:, idx]
        cand.append(np.real(v))
        if abs(np.imag(evals[idx])) > 1e-12 * max(1.0, abs(evals[idx])):
            cand.append(np.imag(v))
        if len(cand) >= 2 * k:
            break
    if not cand:
        return np.zeros((evecs.shape[0], 0))
    m = np.stack(cand, axis=1)
    q, r, _ = scipy.linalg.qr(m, mode="economic", pivoting=True)
    diag = np.abs(np.diag(r))
    rank = int(np.sum(diag > 1e-12 * max(diag[0], 1e-300)))
    return q[:, : min(k, rank)]


def _first_cycle_pencil(h: np.ndarray, j: int):
    """(H_m + h²_{m+1,m} H_m⁻ᴴ e_m e_mᴴ) — the fresh-cycle harmonic-Ritz
    pencil (Alg. 2 line 14); None when H_m is singular."""
    hm = h[:j, :j]
    h2 = h[j, j - 1] ** 2
    em = np.zeros((j, 1))
    em[-1, 0] = 1.0
    try:
        corr = h2 * np.linalg.solve(hm.T, em)  # H⁻ᵀ e_m (real arithmetic)
    except np.linalg.LinAlgError:
        return None
    return hm + corr @ em.T


def harmonic_ritz_first_cycle(h: np.ndarray, j: int, k: int) -> np.ndarray:
    """Harmonic Ritz vectors from a fresh GMRES cycle (Alg. 2 line 14):
    eig of (H_m + h²_{m+1,m} H_m⁻ᴴ e_m e_mᴴ). Returns P (j, k_eff)."""
    a = _first_cycle_pencil(h, j)
    if a is None:
        return np.zeros((j, 0))
    evals, evecs = np.linalg.eig(a)
    return real_spanning_basis(evals, evecs, k)


def harmonic_ritz_deflated(g: np.ndarray, whv: np.ndarray, k: int) -> np.ndarray:
    """Harmonic Ritz from a deflated cycle (Alg. 2 line 29):
    Ĝᴴ Ĝ z = θ Ĝᴴ Ŵᴴ V̂ z. Returns P (k+j, k_eff)."""
    a1 = g.T @ g
    a2 = g.T @ whv
    try:
        evals, evecs = scipy.linalg.eig(a1, a2)
    except (scipy.linalg.LinAlgError, ValueError):
        return np.zeros((g.shape[1], 0))
    return real_spanning_basis(evals, evecs, k)


def right_tri_solve(u: np.ndarray, r: np.ndarray) -> np.ndarray:
    """U R⁻¹ for upper-triangular R (Alg. 2: U_k = Ỹ_k R⁻¹)."""
    return scipy.linalg.solve_triangular(r.T, u.T, lower=True).T


# --------------------------------------------------------------------------
# Stacked Hessenberg least squares — the oracle of the device's
# (devlinalg.hessenberg_lstsq_stacked, tests/test_devlinalg.py). It takes B
# chains' blocks at per-chain EFFECTIVE widths j[i] and uses one LAPACK call
# on the whole stack whenever the widths agree; ragged widths fall back to a
# per-chain loop.
# --------------------------------------------------------------------------


def _well_conditioned(r: np.ndarray, rtol: float = 1e-12) -> np.ndarray:
    """(B,) per-factor gate: each R factor of a stacked QR safely
    invertible (devlinalg._diag_ok)."""
    diag = np.abs(np.diagonal(r, axis1=-2, axis2=-1))
    return ((diag.min(axis=-1) > rtol * np.maximum(diag.max(axis=-1), 1e-300))
            & np.isfinite(diag).all(axis=-1))


def _stack_well_conditioned(r: np.ndarray, rtol: float = 1e-12) -> bool:
    """True when every R factor in a stacked QR is safely invertible —
    gate for the fast solve path (lstsq fallback handles the rest)."""
    return bool(_well_conditioned(r, rtol).all())


def hessenberg_lstsq_stacked(h: np.ndarray, j: np.ndarray,
                             beta: np.ndarray) -> np.ndarray:
    """Stacked argmin_y ‖β_i e₁ − H_i y‖ over B chains.

    h: (B, m+1, m) raw Hessenbergs; j: (B,) effective widths (0 = frozen
    chain); beta: (B,) residual norms. Returns y (B, m), zero-padded — rows
    with j[i] == 0 stay zero (the padded-update no-op convention).
    """
    h = np.asarray(h)
    j = np.asarray(j, dtype=int)
    beta = np.asarray(beta, dtype=float)
    bsz, _, m = h.shape
    y = np.zeros((bsz, m))
    act = np.nonzero(j > 0)[0]
    if act.size == 0:
        return y
    ji = int(j[act[0]])
    if np.all(j[act] == ji):
        blocks = h[act][:, : ji + 1, :ji]
        q, r = np.linalg.qr(blocks)               # one stacked QR
        if _stack_well_conditioned(r):
            rhs = q[:, 0, :] * beta[act, None]    # Qᵀ(β e₁) = β·(first row)
            y[act[:, None], np.arange(ji)[None, :]] = \
                np.linalg.solve(r, rhs[..., None])[..., 0]
            return y
        # near-breakdown column somewhere in the stack → per-chain lstsq
    for i in act:
        ji = int(j[i])
        y[i, :ji] = hessenberg_lstsq(h[i, : ji + 1, :ji], beta[i])
    return y


# --------------------------------------------------------------------------
# Padded-stack harmonic-Ritz drivers — the host half of the lockstep
# engine's cycles (solvers/batched.py). They take the padded (B, s, s)
# stacks exactly as the device programs build them
# (devlinalg.first_cycle_pencil_stacked, devlinalg.deflated_pencil_stacked)
# and return a (B, s, k) fp64 basis zero outside each chain's live rows, and
# an ok gate: the invariant subspace of each chain's live block, by stacked
# LAPACK eig in fp64 whatever the cycle's dtype.
#
# A tall stack is cut into contiguous blocks of whole chains, solved side by
# side on a thread pool (numpy's LAPACK gufuncs release the GIL). LAPACK
# solves each matrix of a stack on its own, so a block gives its chains the
# bits the whole stack gives them; only the wall time changes.
# --------------------------------------------------------------------------

_RTOL = 1e-12          # the rank and conditioning gate of devlinalg
# Fewest chains a block holds. On a TPU v5e host (13 CPUs),
# ritz_deflated_padded on 64 random 40 x 40 pencils at k = 15 took 37.8 ms
# inline, 12.4 ms in 4 blocks, 9.5 ms in 8 blocks of 8 and 10.1-10.6 ms in
# 10 to 13 smaller ones: below 8 chains a block's handoff outweighs its
# share of the LAPACK. Stacks under 16 chains stay on the calling thread.
MIN_BLOCK_ROWS = 8
_POOL = None
_POOL_LOCK = threading.Lock()
_LAST = threading.local()


def _cpus() -> int:
    return len(os.sched_getaffinity(0))


def _pool() -> ThreadPoolExecutor:
    """The process's one pool of block workers, made at first use. They
    run numpy only: no JAX array reaches them."""
    global _POOL
    with _POOL_LOCK:
        if _POOL is None:
            _POOL = ThreadPoolExecutor(max_workers=_cpus(),
                                       thread_name_prefix="ritz")
        return _POOL


def _map_blocks(fn, rows: np.ndarray):
    """[(block, fn(block))] over contiguous blocks of the row indices
    `rows`: as many blocks as the CPUs this process may use, each of at
    least MIN_BLOCK_ROWS rows; one block (fn(rows), on the calling thread)
    below two. The calling thread solves the first block itself."""
    nb = min(_cpus(), rows.size // MIN_BLOCK_ROWS)
    _LAST.blocks = max(nb, 1)
    if nb < 2:
        return [(rows, fn(rows))]
    blocks = np.array_split(rows, nb)
    futs = [_pool().submit(fn, blk) for blk in blocks[1:]]
    first = fn(blocks[0])
    return list(zip(blocks, [first] + [f.result() for f in futs]))


def last_blocks() -> int:
    """Blocks the calling thread's last padded Ritz or refresh call ran in
    (1: inline)."""
    return getattr(_LAST, "blocks", 1)


def _eig_stacked(a: np.ndarray):
    """Stacked eig of (R, s, s). Returns (evals, evecs, ok): a row LAPACK
    cannot diagonalize gets ok False and zeros."""
    try:
        w, v = np.linalg.eig(a)
        return w, v, np.ones(len(a), bool)
    except np.linalg.LinAlgError:   # some row did not converge: one by one
        w = np.zeros(a.shape[:2], complex)
        v = np.zeros(a.shape, complex)
        ok = np.zeros(len(a), bool)
        for i, ai in enumerate(a):
            try:
                w[i], v[i] = np.linalg.eig(ai)
                ok[i] = True
            except np.linalg.LinAlgError:
                pass
        return w, v, ok


def _spanning_basis(evecs, order, live, k: int):
    """Orthonormal real basis (R, s, k) of the span of the eigenvectors
    `order` (R, k) picks, rows outside `live` (R, s) zeroed: their real and
    imaginary parts (a complex pair spans two real directions), then the k
    leading left singular vectors. ok where that span has rank k."""
    sel = np.take_along_axis(evecs, order[:, None, :], axis=2)
    cand = np.concatenate([sel.real, sel.imag], axis=2) * live[:, :, None]
    u, sv, _ = np.linalg.svd(cand, full_matrices=False)
    ok = sv[:, k - 1] > _RTOL * np.maximum(sv[:, 0], 1e-300)
    return u[:, :, :k], ok


def _ritz_rows(pencils, rows, live, k: int, largest: bool):
    """Bases (R, s, k) and ok (R,) of the chains `rows`."""
    w, v, eig_ok = _eig_stacked(pencils[rows])
    mag = np.abs(w)
    order = np.argsort(-mag if largest else mag, axis=1, kind="stable")
    pr, span_ok = _spanning_basis(v, order[:, :k], live[rows], k)
    return pr, eig_ok & span_ok


def _ritz_padded(pencils, rows, live, k: int, largest: bool):
    bsz, s, _ = pencils.shape
    p = np.zeros((bsz, s, k))
    ok = np.zeros(bsz, bool)
    _LAST.blocks = 1
    if rows.size == 0:
        return p, ok
    for blk, (pr, okr) in _map_blocks(
            lambda r: _ritz_rows(pencils, r, live, k, largest), rows):
        ok[blk] = okr
        p[blk] = np.where(okr[:, None, None], pr, 0.0)
    return p, ok


def ritz_first_cycle_padded(a: np.ndarray, j: np.ndarray, k: int):
    """Fresh-cycle harmonic-Ritz bases from the padded pencils A (B, m, m)
    (BIG dead diagonal): the k smallest-|θ| eigenvectors' real span in each
    chain's live rows r < j. Returns (p (B, m, k), ok): ok needs j > k, a
    finite pencil (H_m nonsingular), a converged eig and rank k."""
    a = np.asarray(a, np.float64)
    j = np.asarray(j)
    rows = np.nonzero((j > k) & np.isfinite(a).all(axis=(1, 2)))[0]
    live = np.arange(a.shape[-1])[None, :] < j[:, None]
    return _ritz_padded(a, rows, live, k, largest=False)


def ritz_deflated_padded(mm: np.ndarray, j: np.ndarray, k: int):
    """Deflated-cycle harmonic-Ritz bases from the padded M = (ĜᴴĜ)⁻¹
    ĜᴴŴᴴV̂ (B, k+mi, k+mi), dead block zero: the dominant (largest |μ| =
    smallest |θ|) k-dimensional invariant subspace in each chain's live rows
    r < k + j. Chains with j = 0 took no step and are not solved (the
    refresh masks them). Returns (p (B, k+mi, k), ok): ok needs a finite M
    (ĜᴴĜ nonsingular), a converged eig and rank k."""
    mm = np.asarray(mm, np.float64)
    j = np.asarray(j)
    k_mi = mm.shape[-1]
    rows = np.nonzero((j > 0) & np.isfinite(mm).all(axis=(1, 2)))[0]
    live = np.arange(k_mi)[None, :] < (k + j)[:, None]
    return _ritz_padded(mm, rows, live, k, largest=True)


def _refresh_rows(gp, want):
    q, rr = np.linalg.qr(gp)
    ok = want & _well_conditioned(rr, _RTOL)
    eye = np.eye(rr.shape[-1])
    inv_rr = np.linalg.inv(np.where(ok[:, None, None], rr, eye))
    return np.where(ok[:, None, None], q, 0.0), inv_rr, ok


def refresh_factors_stacked(gp: np.ndarray, want: np.ndarray):
    """Host twin of devlinalg.refresh_factors: stacked QR of the (B, R, k)
    products + gated R inverse, in blocks of chains as the Ritz bases.
    Returns (q, inv_rr, ok): ok = want and R well conditioned; gated chains
    get q = 0, inv_rr = I."""
    gp = np.asarray(gp)
    want = np.asarray(want)
    parts = _map_blocks(lambda rows: _refresh_rows(gp[rows], want[rows]),
                        np.arange(len(gp)))
    # the blocks are contiguous and in order
    return tuple(map(np.concatenate, zip(*(res for _, res in parts))))
