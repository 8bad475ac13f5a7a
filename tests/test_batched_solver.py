"""Lockstep batched GCRO-DR: per-chain equivalence with the sequential
solver, the k=0 ≡ vmapped-GMRES special case, chunked-datagen engine
equivalence + padding semantics, and the batched DIA-SpMV kernel."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.skr import (SKRConfig, SKRGenerator, generate_dataset,
                            generate_dataset_chunked)
from repro.pde.dia import DIA, Stencil5
from repro.pde.registry import get_family, list_families
from repro.solvers.batched import BatchedGCRODRSolver
from repro.solvers.gcrodr import GCRODRSolver
from repro.solvers.gmres import gmres_solve
from repro.solvers.operator import PreconditionedOp, StencilOp
from repro.solvers.precond import (JacobiPrecond, make_preconditioner,
                                   make_preconditioner_batched)
from repro.solvers.types import KrylovConfig

# tol 1e-9 leaves the batched-vs-sequential float-reassociation drift
# (vmapped matmuls + eig-selection sensitivity in the recycle refresh)
# comfortably under the 1e-8 equivalence budget asserted below
KC = KrylovConfig(m=30, k=10, tol=1e-9, maxiter=6000)


def _chains(family="poisson", nx=12, num=6, chains=2, seed=3, precond="jacobi"):
    """Sample `num` systems and split them into `chains` equal chunks."""
    fam = get_family(family, nx=nx, ny=nx)
    batch = fam.sample_batch(jax.random.PRNGKey(seed), num)
    coeffs = jnp.asarray(batch.op.coeffs)
    b_all = np.asarray(batch.b).reshape(num, -1)
    per = num // chains
    subs = [list(range(w * per, (w + 1) * per)) for w in range(chains)]
    return coeffs, b_all, subs


def _solve_sequential(coeffs, b_all, subs, cfg, precond="jacobi"):
    out = {}
    for sub in subs:
        solver = GCRODRSolver(cfg)
        for i in sub:
            st5 = Stencil5(coeffs[i])
            pre = make_preconditioner(precond, st5)
            op = PreconditionedOp(StencilOp(st5.coeffs), pre)
            x, st = solver.solve(op, b_all[i])
            out[i] = (x, st)
    return out


def _solve_batched(coeffs, b_all, subs, cfg, precond="jacobi"):
    out = {}
    solver = BatchedGCRODRSolver(cfg)
    for t in range(len(subs[0])):
        idx = np.array([sub[t] for sub in subs])
        st5 = Stencil5(coeffs).take(jnp.asarray(idx))
        pre = make_preconditioner_batched(precond, st5)
        ops = PreconditionedOp(StencilOp(st5.coeffs), pre)
        xs, stats = solver.solve_batch(ops, jnp.asarray(b_all[idx]))
        for w, i in enumerate(idx):
            out[int(i)] = (xs[w], stats[w])
    return out


@pytest.mark.parametrize("family", list_families())
def test_batched_matches_sequential_per_chain(family):
    """Acceptance: per-chain solutions agree with the existing GCRODRSolver
    to <= 1e-8 relative error, chains keep independent recycle carries."""
    coeffs, b_all, subs = _chains(family=family)
    seq = _solve_sequential(coeffs, b_all, subs, KC)
    bat = _solve_batched(coeffs, b_all, subs, KC)
    for i in seq:
        x_seq, st_seq = seq[i]
        x_bat, st_bat = bat[i]
        assert st_seq.converged and st_bat.converged, (i, st_seq, st_bat)
        rel = (np.linalg.norm(x_bat - x_seq)
               / max(np.linalg.norm(x_seq), 1e-300))
        assert rel <= 1e-8, (i, rel)
        # same trajectory family: iteration counts stay in the same regime
        assert st_bat.iterations <= max(1.5 * st_seq.iterations,
                                        st_seq.iterations + KC.m), i


def test_batched_k0_equals_vmapped_gmres():
    """k=0 lockstep == restarted GMRES per chain (paper §4.2 batched)."""
    cfg = dataclasses.replace(KC, k=0)
    coeffs, b_all, subs = _chains(num=4, chains=4)
    bat = _solve_batched(coeffs, b_all, subs, cfg)
    for i in range(4):
        st5 = Stencil5(coeffs[i])
        pre = make_preconditioner("jacobi", st5)
        op = PreconditionedOp(StencilOp(st5.coeffs), pre)
        x_ref, st_ref = gmres_solve(op, jnp.asarray(b_all[i]), cfg)
        x_bat, st_bat = bat[i]
        assert st_ref.converged and st_bat.converged
        np.testing.assert_allclose(np.asarray(x_bat), np.asarray(x_ref),
                                   rtol=1e-6, atol=1e-10)


def test_batched_fused_kernel_path_matches_default():
    """use_kernel=True routes the whole inner iteration through the fused
    arnoldi_step Pallas kernel (interpret mode on CPU); solutions must agree
    with the composed-jnp default path to the lockstep equivalence budget."""
    coeffs, b_all, subs = _chains(num=4, chains=2)
    ref_out = _solve_batched(coeffs, b_all, subs, KC)
    out = {}
    solver = BatchedGCRODRSolver(KC, use_kernel=True)
    for t in range(len(subs[0])):
        idx = np.array([sub[t] for sub in subs])
        st5 = Stencil5(coeffs).take(jnp.asarray(idx))
        pre = make_preconditioner_batched("jacobi", st5)
        ops = PreconditionedOp(StencilOp(st5.coeffs), pre)
        xs, stats = solver.solve_batch(ops, jnp.asarray(b_all[idx]))
        for w, i in enumerate(idx):
            out[int(i)] = (xs[w], stats[w])
    for i in ref_out:
        x_ref, st_ref = ref_out[i]
        x_ker, st_ker = out[i]
        assert st_ref.converged and st_ker.converged, (i, st_ref, st_ker)
        rel = (np.linalg.norm(x_ker - x_ref)
               / max(np.linalg.norm(x_ref), 1e-300))
        assert rel <= 1e-8, (i, rel)


def test_batched_zero_rhs_is_padding_noop():
    """A zero RHS row (padded chain) converges at 0 iterations with x = 0
    and leaves the chain's recycle carry untouched."""
    coeffs, b_all, subs = _chains(num=4, chains=2)
    solver = BatchedGCRODRSolver(KC)
    idx = np.array([0, 1])
    st5 = Stencil5(coeffs).take(jnp.asarray(idx))
    pre = make_preconditioner_batched("jacobi", st5)
    ops = PreconditionedOp(StencilOp(st5.coeffs), pre)
    solver.solve_batch(ops, jnp.asarray(b_all[idx]))
    carry_before = solver.u_carry.copy()
    b_pad = b_all[idx].copy()
    b_pad[1] = 0.0
    xs, stats = solver.solve_batch(ops, jnp.asarray(b_pad))
    assert stats[1].converged and stats[1].iterations == 0
    np.testing.assert_array_equal(xs[1], np.zeros_like(xs[1]))
    np.testing.assert_array_equal(solver.u_carry[1], carry_before[1])
    assert stats[0].converged and stats[0].iterations > 0


@pytest.mark.parametrize("family", list_families())
def test_chunked_engines_agree_with_padding(family):
    """batched == sequential engine through the full datagen path, with a
    worker count that does NOT divide num (uneven chunks exercise the
    zero-RHS padding)."""
    fam = get_family(family, nx=12, ny=12)
    cfg = SKRConfig(krylov=KC, precond="jacobi")
    key = jax.random.PRNGKey(5)
    seq = generate_dataset_chunked(fam, key, 8, cfg, workers=3,
                                   engine="sequential")
    bat = generate_dataset_chunked(fam, key, 8, cfg, workers=3,
                                   engine="batched")
    assert len(seq) == len(bat) == 3
    for cs, cb in zip(seq, bat):
        np.testing.assert_array_equal(cs.order, cb.order)
        assert cs.solutions.shape == cb.solutions.shape
        for pos in range(len(cs.order)):
            rel = (np.linalg.norm(cb.solutions[pos] - cs.solutions[pos])
                   / max(np.linalg.norm(cs.solutions[pos]), 1e-300))
            assert rel <= 1e-8, (pos, rel)
        assert cs.stats.num_converged == len(cs.order)
        assert cb.stats.num_converged == len(cb.order)


def test_chunked_workers1_bitwise_stable():
    """workers=1 routes through the sequential per-system loop and is
    BITWISE identical to the plain generator on the same key."""
    fam = get_family("poisson", nx=12, ny=12)
    cfg = SKRConfig(krylov=KC, precond="jacobi")
    key = jax.random.PRNGKey(7)
    whole = generate_dataset(fam, key, 6, cfg)
    chunks = generate_dataset_chunked(fam, key, 6, cfg, workers=1)
    assert len(chunks) == 1
    ch = chunks[0]
    np.testing.assert_array_equal(ch.order, whole.order)
    for pos, i in enumerate(ch.order.tolist()):
        np.testing.assert_array_equal(ch.solutions[pos], whole.solutions[i])


def test_batched_solver_rejects_final_refresh():
    cfg = dataclasses.replace(KC, ritz_refresh="final")
    with pytest.raises(NotImplementedError):
        BatchedGCRODRSolver(cfg)


# ------------------------------------------------------------ batched kernel

@pytest.mark.parametrize("bsz,n", [(2, 64), (4, 256), (3, 1000)])
def test_batched_dia_kernel_matches_ref(bsz, n):
    from repro.kernels import ops, ref

    rng = np.random.default_rng(n + bsz)
    offsets = (-8, -1, 0, 1, 8)
    data = jnp.asarray(rng.standard_normal((bsz, len(offsets), n)))
    x = jnp.asarray(rng.standard_normal((bsz, n)))
    dia = DIA(offsets=offsets, data=data)
    got = ops.dia_spmv(dia, x, use_kernel=True, interpret=True)
    want = ref.dia_spmv(offsets, data, x)
    assert got.shape == (bsz, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-12, atol=1e-12)


def test_batched_dia_kernel_matches_per_system_kernel():
    """One batched launch == B single launches (same kernel semantics)."""
    from repro.kernels.dia_spmv import (dia_spmv_batched_pallas,
                                        dia_spmv_pallas)

    rng = np.random.default_rng(0)
    offsets = (-3, 0, 3)
    bsz, n = 3, 128
    data = jnp.asarray(rng.standard_normal((bsz, len(offsets), n)))
    x = jnp.asarray(rng.standard_normal((bsz, n)))
    got = dia_spmv_batched_pallas(offsets, data, x, interpret=True)
    for i in range(bsz):
        want = dia_spmv_pallas(offsets, data[i], x[i], interpret=True)
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want),
                                   rtol=1e-12, atol=1e-12)


def test_stencil5_take_batched_indexing():
    rng = np.random.default_rng(1)
    coeffs = jnp.asarray(rng.standard_normal((5, 5, 8, 8)))
    st = Stencil5(coeffs)
    sub = st.take(jnp.asarray([3, 1]))
    assert sub.coeffs.shape == (2, 5, 8, 8)
    np.testing.assert_array_equal(np.asarray(sub.coeffs[0]),
                                  np.asarray(coeffs[3]))
    one = st.take(2)
    assert one.coeffs.shape == (5, 8, 8)


# ------------------------------------------ the eigensolve on the host

# Σ iterations of each row below when the eigensolve ran on the device
# (48 sweeps of batched subspace iteration; CPU, fp64)
_DEVICE_EIG_ROW_ITERS = (239, 238, 226)


def test_rows_converge_with_one_sync_per_cycle():
    """Fresh then warm-started fp64 rows, the eigensolve on the host, under
    the transfer guard: every label meets tol by its true residual, one
    blocking sync per cycle (host_syncs = 2 + cycles), and each row's
    iterations within 5 % of what the device eigensolve took."""
    chains, rows = 4, 3
    coeffs, b_all, subs = _chains(family="darcy", nx=16, num=chains * rows,
                                  chains=chains, seed=9)
    solver = BatchedGCRODRSolver(KC)
    for t in range(rows):
        idx = np.array([sub[t] for sub in subs])
        st5 = Stencil5(coeffs).take(jnp.asarray(idx))
        pre = make_preconditioner_batched("jacobi", st5)
        ops = PreconditionedOp(StencilOp(st5.coeffs), pre)
        b = b_all[idx]
        with jax.transfer_guard("disallow"):
            xs, stats = solver.solve_batch(ops, b)
        for w in range(chains):
            a = np.asarray(st5.take(w).to_dense())
            res = np.linalg.norm(b[w] - a @ xs[w]) / np.linalg.norm(b[w])
            assert stats[w].converged and res <= KC.tol * (1 + 1e-6), (w, res)
        cycles = max(s.cycles for s in stats)
        assert all(s.host_syncs == 2 + cycles for s in stats)
        it_h = sum(s.iterations for s in stats)
        it_d = _DEVICE_EIG_ROW_ITERS[t]
        assert abs(it_h - it_d) <= 0.05 * it_d, (t, it_h, it_d)


def _sweep_chains(dt, k, use_kernel, nx=8):
    """Four chains for the lockstep Arnoldi sweep that stop at different
    steps: tol_abs = +inf (0 steps), a loose tolerance (an early stop), 0
    (all m steps), and an operator that Jacobi makes the identity with r0
    on one grid point, where C vanishes (a lucky breakdown at step 1)."""
    rng = np.random.default_rng(7)
    n = nx * nx
    c = np.zeros((4, 5, nx, nx))
    c[:3, 0] = 4.0 + rng.random((3, nx, nx))
    c[:3, 1:] = -(0.5 + 0.5 * rng.random((3, 4, nx, nx)))
    c[3, 0] = 2.0
    c_rows = rng.standard_normal((4, k, n))
    c_rows[:, :, 5] = 0.0
    if k:
        c_rows = np.stack([np.linalg.qr(x.T)[0].T for x in c_rows])
    r0 = rng.standard_normal((4, n))
    r0 -= np.einsum("bkn,bk->bn", c_rows,
                    np.einsum("bkn,bn->bk", c_rows, r0))
    r0[3] = 0.0
    r0[3, 5] = 3.0
    tol = np.array([np.inf, 0.05 * np.linalg.norm(r0[1]), 0.0, 0.0])
    ops = PreconditionedOp(StencilOp(jnp.asarray(c, dt),
                                     use_kernel=use_kernel),
                           JacobiPrecond(jnp.asarray(1.0 / c[:, 0], dt)
                                         .reshape(4, n)))
    return ops, jnp.asarray(c_rows, dt), jnp.asarray(r0, dt), \
        jnp.asarray(tol, dt)


@pytest.mark.parametrize("k", [0, 2], ids=["k0", "k2"])
@pytest.mark.parametrize("path", ["fp64-jnp", "fp32-fused"])
def test_lockstep_sweep_matches_one_chain_at_a_time(path, k):
    """The lockstep Arnoldi sweep (one step counter for all chains, rows
    written in place) gives each chain what the same sweep gives it alone:
    the same steps and breakdown flag, the same basis, Hessenberg and
    C-block, and rows past the chain's last step still zero. On the fp64
    jnp path and on the fp32 fused kernel (interpreted)."""
    from repro.solvers.arnoldi import arnoldi_cycle, arnoldi_cycle_batched

    fused = path == "fp32-fused"
    dt, rtol = (jnp.float32, 1e-6) if fused else (jnp.float64, 1e-13)
    m = 6
    ops, c_rows, r0, tol = _sweep_chains(dt, k, fused)
    got = arnoldi_cycle_batched(ops, c_rows, r0, tol, m=m,
                                use_kernel=fused)
    j = np.asarray(got.j_used)
    assert j[0] == 0 and 0 < j[1] < m and j[2] == m and j[3] == 1, j
    assert np.asarray(got.breakdown).tolist() == [False, False, False, True]

    def rel(a, b):
        a, b = np.float64(a), np.float64(b)
        return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-300)

    for i in range(4):
        one = arnoldi_cycle(jax.tree.map(lambda x: x[i], ops), c_rows[i],
                            r0[i], tol[i], m=m, use_kernel=fused)
        assert int(one.j_used) == j[i]
        assert bool(one.breakdown) == bool(got.breakdown[i])
        v, v1 = np.asarray(got.v[i]), np.asarray(one.v)
        assert rel(v, v1) <= rtol
        # h and b are the V- and C-blocks of one projection of A·V
        hb = np.concatenate([np.asarray(got.h[i]), np.asarray(got.b[i])])
        hb1 = np.concatenate([np.asarray(one.h), np.asarray(one.b)])
        assert rel(hb, hb1) <= rtol
        assert not v[j[i] + 1:].any() and not v1[j[i] + 1:].any()
