"""devlinalg vs hostlinalg parity: the on-device stacked drivers against
their host oracles — stacked QR least squares (uniform + ragged widths,
ill-conditioned and rank-deficient fallback), masked triangular inverses,
and the harmonic-Ritz extraction: the device-built padded pencils through
the host's stacked LAPACK drivers (first-cycle and deflated pencils, gapped
spectra where LAPACK's subspace is well defined)."""
import numpy as np
import pytest
import scipy.linalg

import jax
import jax.numpy as jnp

from repro.solvers import devlinalg as dl
from repro.solvers import hostlinalg as hl

jax.config.update("jax_enable_x64", True)


def _hessenberg_stack(bsz, m, j, rng, last_row=1.0):
    """Raw (B, m+1, m) stacks with the Arnoldi structure: live columns
    c < j[i] upper-Hessenberg, everything else exactly zero."""
    h = np.zeros((bsz, m + 1, m))
    for i in range(bsz):
        ji = int(j[i])
        blk = np.triu(rng.standard_normal((ji + 1, ji)), k=-1)
        for c in range(ji):
            blk[c + 1, c] = abs(blk[c + 1, c]) + 0.1
        if ji > 0:
            blk[ji, ji - 1] = last_row
        h[i, : ji + 1, :ji] = blk
    return h


def _angle(p, q):
    """sin of the largest principal angle between the two column spans."""
    qp = np.linalg.qr(p)[0]
    qq = np.linalg.qr(q)[0]
    s = np.clip(np.linalg.svd(qp.T @ qq, compute_uv=False), 0.0, 1.0)
    return float(np.sqrt(1.0 - s.min() ** 2))


# ------------------------------------------------------------- LS drivers

@pytest.mark.parametrize("widths", [(8, 8, 8), (8, 5, 2), (6, 0, 8)])
def test_hessenberg_lstsq_matches_host(widths):
    rng = np.random.default_rng(3)
    j = np.asarray(widths)
    m = 8
    h = _hessenberg_stack(len(j), m, j, rng)
    beta = rng.uniform(0.5, 2.0, len(j))
    want = hl.hessenberg_lstsq_stacked(h, j, beta)
    got = np.asarray(dl.hessenberg_lstsq_stacked(
        jnp.asarray(h), jnp.asarray(j), jnp.asarray(beta)))
    np.testing.assert_allclose(got, want, rtol=1e-10, atol=1e-12)
    # padded coordinates are EXACTLY zero (the no-op update convention)
    for i, ji in enumerate(j):
        np.testing.assert_array_equal(got[i, ji:], 0.0)


def test_hessenberg_lstsq_rank_deficient_falls_back():
    """A numerically rank-deficient live block trips the QR gate; the SVD
    path must return the np.linalg.lstsq min-norm solution."""
    rng = np.random.default_rng(5)
    m, j = 6, np.asarray([6, 6])
    h = _hessenberg_stack(2, m, j, rng)
    h[1, :, 3] = h[1, :, 2] * (1 + 1e-15)      # chain 1: duplicated column
    beta = np.asarray([1.3, 0.7])
    got = np.asarray(dl.hessenberg_lstsq_stacked(
        jnp.asarray(h), jnp.asarray(j), jnp.asarray(beta)))
    for i in range(2):
        e1 = np.zeros(m + 1)
        e1[0] = beta[i]
        want, *_ = np.linalg.lstsq(h[i], e1, rcond=None)
        np.testing.assert_allclose(got[i], want, rtol=1e-8, atol=1e-10)
    # the healthy chain still resolves through the same blended call
    assert np.linalg.norm(got[0]) > 0


def test_hessenberg_lstsq_ill_conditioned_stack():
    """Graded singular values across 12 decades: QR path where safe, SVD
    blend where not — always finite, always oracle-close."""
    rng = np.random.default_rng(11)
    m = 10
    j = np.asarray([10, 10])
    h = _hessenberg_stack(2, m, j, rng)
    h[1] *= np.logspace(0, -12, m)[None, :]    # kill conditioning of chain 1
    beta = np.asarray([1.0, 1.0])
    got = np.asarray(dl.hessenberg_lstsq_stacked(
        jnp.asarray(h), jnp.asarray(j), jnp.asarray(beta)))
    assert np.isfinite(got).all()
    e1 = np.zeros(m + 1)
    e1[0] = 1.0
    for i in range(2):
        want, *_ = np.linalg.lstsq(h[i], e1, rcond=None)
        np.testing.assert_allclose(h[i] @ got[i], h[i] @ want,
                                   rtol=1e-6, atol=1e-9)


def test_tri_inv_stacked_masked_gate():
    rng = np.random.default_rng(7)
    k = 5
    r = np.triu(rng.standard_normal((3, k, k))) + 3 * np.eye(k)
    r[2, 2, 2] = 1e-15                          # chain 2: gate must trip
    want = np.asarray([True, False, True])
    inv, ok = dl.tri_inv_stacked(jnp.asarray(r), jnp.asarray(want))
    ok = np.asarray(ok)
    assert ok.tolist() == [True, False, False]
    np.testing.assert_allclose(np.asarray(inv[0]), np.linalg.inv(r[0]),
                               rtol=1e-12, atol=1e-12)
    np.testing.assert_array_equal(np.asarray(inv[1]), np.eye(k))
    np.testing.assert_array_equal(np.asarray(inv[2]), np.eye(k))


# ----------------------------------------------------- harmonic-Ritz, fresh

def _gapped_hessenberg(m, k, rng, gap=8.0, subdiag=1e-3):
    """(m+1, m) Hessenberg whose first-cycle pencil has a clean |λ| gap at
    index k (small h[m, m-1] keeps the rank-1 correction a perturbation)."""
    lam = np.concatenate([rng.uniform(0.5, 1.0, k),
                          rng.uniform(0.5, 1.0, m - k) * gap])
    v = scipy.linalg.qr(rng.standard_normal((m, m)))[0]
    a = v @ np.diag(lam) @ v.T
    hm = scipy.linalg.hessenberg(a)
    h = np.zeros((m + 1, m))
    h[:m] = hm
    h[m, m - 1] = subdiag
    return h


def _smallest_eig_span(a, k):
    """LAPACK reference: real basis of the k smallest-|λ| invariant
    subspace (well-defined here: the test pencils are gapped and real)."""
    evals, evecs = np.linalg.eig(a)
    order = np.argsort(np.abs(evals))[:k]
    return np.real(evecs[:, order]), np.sort(np.abs(evals))


def _first_cycle_host(h, j, k):
    """The fresh cycle's split: the device builds the padded pencils, the
    host driver takes their eigenvectors."""
    a = dl.first_cycle_pencil_stacked(jnp.asarray(h), jnp.asarray(j))
    return hl.ritz_first_cycle_padded(np.asarray(a), j, k)


@pytest.mark.parametrize("widths", [(10, 10), (10, 7), (10, 7, 4)])
def test_harmonic_ritz_first_cycle_matches_lapack_on_gapped(widths):
    """The host driver on the device-built padded pencils vs LAPACK on each
    chain's unpadded pencil, at mixed widths: same invariant subspace AND
    same smallest-|θ| Ritz values, an orthonormal basis exactly zero
    outside the live rows."""
    rng = np.random.default_rng(17)
    k, m = 3, 10
    j = np.asarray(widths)
    h = np.zeros((len(j), m + 1, m))
    for i, ji in enumerate(j):
        h[i, : ji + 1, :ji] = _gapped_hessenberg(ji, k, rng)
    p, ok = _first_cycle_host(h, j, k)
    assert ok.all()
    for i, ji in enumerate(j):
        a = hl._first_cycle_pencil(h[i], int(ji))
        span, absev = _smallest_eig_span(a, k)
        assert _angle(p[i, :ji], span) < 1e-7, i
        np.testing.assert_array_equal(p[i, ji:], 0.0)
        np.testing.assert_allclose(p[i].T @ p[i], np.eye(k), atol=1e-12)
        # Ritz-value parity on the host driver's space
        pq = p[i, :ji]
        theta = np.sort(np.abs(np.linalg.eigvals(pq.T @ a @ pq)))
        np.testing.assert_allclose(theta, absev[:k], rtol=1e-8)


def test_harmonic_ritz_first_cycle_gates_short_and_singular():
    """Too-short chains and singular H_m (non-finite pencils from the
    device's QR solve) are gated with a zero basis; a graded but solvable
    H_m is not."""
    rng = np.random.default_rng(19)
    k, m = 3, 8
    j = np.asarray([8, 2, 8, 8])               # chain 1: j <= k → no space
    h = _hessenberg_stack(4, m, j, rng)
    h[2, :m, :] = 0.0                          # chain 2: singular H_m
    h[2, m, m - 1] = 1.0
    h[3] *= np.logspace(0, -9, m + 1)[:, None]  # chain 3: graded
    p, ok = _first_cycle_host(h, j, k)
    assert ok.tolist() == [True, False, False, True]
    assert np.isfinite(p).all()
    np.testing.assert_array_equal(p[~ok], 0.0)


# -------------------------------------------------- harmonic-Ritz, deflated

def _deflated_pencil_stack(bsz, k, mi, j, rng, gap=8.0):
    """Random well-conditioned Ĝ stacks plus Ŵᴴ V̂ = Ĝ·W with W orthogonal
    -diagonalized gapped spectrum, so M = (ĜᵀĜ)⁻¹ĜᵀŴᴴV̂ = W has a clean
    smallest-|θ| subspace LAPACK and subspace iteration must agree on."""
    g = np.zeros((bsz, k + mi + 1, k + mi))
    whv = np.zeros((bsz, k + mi + 1, k + mi))
    for i in range(bsz):
        ji = int(j[i])
        s = k + ji
        gi = rng.standard_normal((s + 1, s)) + 2 * np.eye(s + 1, s)
        # |mu| large on the first k directions → theta = 1/mu smallest
        mu = np.concatenate([rng.uniform(0.5, 1.0, k) * gap,
                             rng.uniform(0.5, 1.0, s - k)])
        v = scipy.linalg.qr(rng.standard_normal((s, s)))[0]
        w = v @ np.diag(mu) @ v.T
        g[i, : s + 1, :s] = gi
        whv[i, : s + 1, :s] = gi @ w
        # dead columns get unit pads as assemble_g_stacked does, so ĜᵀĜ
        # stays nonsingular for short chains (whv dead block stays zero)
        for c in range(s, k + mi):
            g[i, c + 1, c] = 1.0
    return g, whv


def _deflated_host(g, whv, j, k):
    """The deflated cycle's split: the device builds M, the host driver
    takes its dominant subspace."""
    mm = dl.deflated_pencil_stacked(jnp.asarray(g), jnp.asarray(whv))
    return hl.ritz_deflated_padded(np.asarray(mm), j, k)


@pytest.mark.parametrize("widths", [(6, 6), (6, 3), (6, 3, 1)])
def test_harmonic_ritz_deflated_matches_lapack_on_gapped(widths):
    rng = np.random.default_rng(23)
    k, mi = 3, 6
    j = np.asarray(widths)
    g, whv = _deflated_pencil_stack(len(j), k, mi, j, rng)
    p, ok = _deflated_host(g, whv, j, k)
    assert ok.all()
    for i, ji in enumerate(j):
        s = k + int(ji)
        ge = g[i, : s + 1, :s]
        we = whv[i, : s + 1, :s]
        mm = np.linalg.solve(ge.T @ ge, ge.T @ we)   # θ smallest = μ largest
        evals, evecs = np.linalg.eig(mm)
        order = np.argsort(np.abs(evals))[::-1][:k]
        span = np.real(evecs[:, order])
        assert _angle(p[i, :s], span) < 1e-7, i
        np.testing.assert_array_equal(p[i, s:], 0.0)
        np.testing.assert_allclose(p[i].T @ p[i], np.eye(k), atol=1e-12)
        # the sequential solver's driver stays inside the dominant
        # 2k-candidate span (its pivoted-QR pick among near-equal
        # candidates is order-arbitrary)
        p_seq = hl.harmonic_ritz_deflated(ge, we, k)
        assert p_seq.shape[1] == k
        order2k = np.argsort(np.abs(evals))[::-1][: 2 * k]
        span2k = np.linalg.qr(np.real(evecs[:, order2k]))[0]
        assert _angle(p_seq, span2k @ (span2k.T @ p_seq)) < 1e-6, i


def test_harmonic_ritz_deflated_gates_singular_pencil():
    """A singular ĜᵀĜ (non-finite M from the device's QR solve) is gated
    with a zero basis, a graded but solvable Ĝ is not, and a chain that
    took no step (j = 0) is left alone."""
    rng = np.random.default_rng(37)
    k, mi = 3, 6
    j = np.asarray([6, 6, 4, 0])
    g, whv = _deflated_pencil_stack(4, k, mi, j, rng)
    g[1] = 0.0                                 # chain 1: ĜᵀĜ singular
    whv[1] = 0.0
    g[2] *= np.logspace(0, -6, k + mi)[None, :]   # chain 2: graded Ĝ
    p, ok = _deflated_host(g, whv, j, k)
    assert ok.tolist() == [True, False, True, False]
    assert np.isfinite(p).all()
    np.testing.assert_array_equal(p[~ok], 0.0)


# --------------------------------------------------- assemblers vs gcrodr

def test_assemblers_match_host_blocks():
    """assemble_g/whv reproduce the exact host-side block layout of the
    sequential solver's deflated pencil at every live width."""
    rng = np.random.default_rng(29)
    k, mi = 2, 5
    j = np.asarray([5, 3])
    bsz = len(j)
    dnorm = rng.uniform(0.5, 2.0, (bsz, k))
    bb = rng.standard_normal((bsz, k, mi))
    h = _hessenberg_stack(bsz, mi, j, rng)
    cu = rng.standard_normal((bsz, k, k))
    cv = rng.standard_normal((bsz, k, mi))
    vu = rng.standard_normal((bsz, mi + 1, k))
    vv = rng.standard_normal((bsz, mi + 1, mi))
    g = np.asarray(dl.assemble_g_stacked(jnp.asarray(dnorm), jnp.asarray(bb),
                                         jnp.asarray(h), jnp.asarray(j)))
    whv = np.asarray(dl.assemble_whv_stacked(
        jnp.asarray(cu), jnp.asarray(cv), jnp.asarray(vu), jnp.asarray(vv),
        jnp.asarray(j)))
    for i, ji in enumerate(j):
        ji = int(ji)
        g_host = np.zeros((k + ji + 1, k + ji))
        g_host[:k, :k] = np.diag(1.0 / dnorm[i])
        g_host[:k, k:] = bb[i][:, :ji]
        g_host[k:, k:] = h[i][: ji + 1, :ji]
        np.testing.assert_allclose(g[i, : k + ji + 1, : k + ji], g_host,
                                   rtol=1e-15, atol=0)
        whv_host = np.zeros((k + ji + 1, k + ji))
        whv_host[:k, :k] = cu[i]
        whv_host[:k, k:] = cv[i][:, :ji]
        whv_host[k:, :k] = vu[i][: ji + 1]
        whv_host[k:, k:] = vv[i][: ji + 1, :ji]
        np.testing.assert_allclose(whv[i, : k + ji + 1, : k + ji], whv_host,
                                   rtol=1e-15, atol=0)
        # dead columns of g are unit vectors rooted below the live block
        for c in range(ji, mi):
            col = g[i, :, k + c]
            assert col[k + c + 1] == 1.0 and np.abs(col).sum() == 1.0
        np.testing.assert_array_equal(whv[i, :, k + ji:], 0.0)
        np.testing.assert_array_equal(whv[i, k + ji + 1:, :], 0.0)


# ------------------------------------------------------- refresh factors

def test_host_refresh_factors_match_the_device():
    """The host twin of refresh_factors: the same Q, R⁻¹ and gate."""
    rng = np.random.default_rng(41)
    gp = rng.standard_normal((3, 11, 4))
    gp[2, :, 3] = gp[2, :, 2]                # chain 2: rank deficient
    want = np.asarray([True, False, True])
    q, inv, ok = hl.refresh_factors_stacked(gp, want)
    q_d, inv_d, ok_d = map(np.asarray,
                           dl.refresh_factors(jnp.asarray(gp),
                                              jnp.asarray(want)))
    np.testing.assert_array_equal(ok, ok_d)
    assert ok.tolist() == [True, False, False]
    np.testing.assert_allclose(np.abs(q[0]), np.abs(q_d[0]), atol=1e-12)
    np.testing.assert_allclose(np.abs(inv[0]), np.abs(inv_d[0]),
                               rtol=1e-10, atol=1e-12)
    np.testing.assert_array_equal(q[1:], 0.0)
    np.testing.assert_array_equal(inv[1:], np.broadcast_to(np.eye(4),
                                                           (2, 4, 4)))


# ------------------------------------------- blocked host eigensolves

_MARK = 7.25          # a pencil holding it at [0, 0] makes LAPACK "fail"


def _host_solves(a, j_first, mm, j_defl, gp, k):
    p1, ok1 = hl.ritz_first_cycle_padded(a, j_first, k)
    p2, ok2 = hl.ritz_deflated_padded(mm, j_defl, k)
    q, inv, ok3 = hl.refresh_factors_stacked(gp, ok1)
    return p1, ok1, p2, ok2, q, inv, ok3


@pytest.mark.parametrize("eig_fails", [False, True],
                         ids=["eig-ok", "eig-raises"])
@pytest.mark.parametrize("bsz", [1, 8, 17, 64])
def test_blocked_host_solves_match_the_inline_call(bsz, eig_fails,
                                                    monkeypatch):
    """The padded Ritz bases and the refresh factors, split into chain
    blocks on the pool, give every chain the bits of one inline stacked
    call: at ragged widths, with a j = 0 chain, a non-finite pencil, a
    rank-deficient product and a pencil whose eig raises (the one-by-one
    fallback then runs inside its block only)."""
    rng = np.random.default_rng(53 + bsz)
    k, m = 3, 10
    j_first = rng.integers(k + 1, m + 1, bsz)
    j_defl = rng.integers(1, m - k + 1, bsz)
    a = np.zeros((bsz, m, m))
    mm = np.zeros((bsz, m, m))
    for i in range(bsz):
        jf, s = int(j_first[i]), k + int(j_defl[i])
        a[i, :jf, :jf] = rng.standard_normal((jf, jf))
        a[i, range(jf, m), range(jf, m)] = 1e8      # the dead diagonal
        mm[i, :s, :s] = rng.standard_normal((s, s))
    gp = rng.standard_normal((bsz, m + 1, k))
    if bsz > 1:
        j_first[1] = j_defl[1] = 0                 # chain 1 took no step
        a[-1, 0, 1] = mm[-1, 1, 0] = np.nan        # last: non-finite pencil
        gp[0, :, 2] = gp[0, :, 1]                  # chain 0: rank deficient
    if eig_fails:
        bad = bsz // 2
        j_first[bad] = m
        a[bad, 0, 0] = mm[bad, 0, 0] = _MARK
        real_eig = np.linalg.eig

        def eig(x):
            if (np.asarray(x)[..., 0, 0] == _MARK).any():
                raise np.linalg.LinAlgError("no convergence")
            return real_eig(x)

        monkeypatch.setattr(np.linalg, "eig", eig)
    monkeypatch.setattr(hl, "_cpus", lambda: 4)
    monkeypatch.setattr(hl, "MIN_BLOCK_ROWS", 10**9)
    inline = _host_solves(a, j_first, mm, j_defl, gp, k)
    assert hl.last_blocks() == 1
    monkeypatch.setattr(hl, "MIN_BLOCK_ROWS", 2)
    blocked = _host_solves(a, j_first, mm, j_defl, gp, k)
    assert hl.last_blocks() == (4 if bsz >= 8 else 1)
    for got, want in zip(blocked, inline):
        assert np.array_equal(got, want)
    live = np.ones(bsz, bool)
    live[[1, -1] if bsz > 1 else []] = False
    if eig_fails:
        live[bad] = False
    assert np.array_equal(inline[1], live) and np.array_equal(inline[3], live)
