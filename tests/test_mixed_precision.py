"""Precision-policy layer: fp32 inner cycles + fp64 iterative refinement.

Covers the tentpole's contract end to end: fp64-tolerance parity of the
fp32-inner path on every registered steady + time-dependent family, the
stagnation fallback to fp64 on an ill-conditioned (near-resonant)
Helmholtz operator, bitwise regression of the fp64 default path, fp32
carry / fp64 label dtypes, and dtype polymorphism of the kernels in both
the ref and interpret-mode Pallas paths (incl. the padded-tail fallback
and the f32-storage/f64-accum CGS2 knob)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.pde.dia import DIA, Stencil5
from repro.pde.registry import (get_family, get_timedep_family,
                                list_families, list_timedep_families)
from repro.solvers.batched import BatchedGCRODRSolver
from repro.solvers.gcrodr import GCRODRSolver
from repro.solvers.gmres import gmres_solve, solve_gmres
from repro.solvers.operator import (PreconditionedOp, StencilOp, as_operator,
                                    cast_operator)
from repro.solvers.precond import (make_preconditioner,
                                   make_preconditioner_batched)
from repro.solvers.types import KrylovConfig

CFG = KrylovConfig(m=30, k=10, tol=1e-8, maxiter=10_000)
CFG32 = dataclasses.replace(CFG, inner_dtype="float32")


def _true_rel_res(prob, x):
    a = prob.op.to_dense()
    b = np.asarray(prob.b, np.float64).reshape(-1)
    return np.linalg.norm(b - a @ np.asarray(x).reshape(-1)) / np.linalg.norm(b)


# ------------------------------------------------------ fp64-parity, steady

@pytest.mark.parametrize("family", list_families())
def test_gmres_fp32_inner_reaches_fp64_tolerance(family):
    """The outer refinement loop owns the accuracy: the final TRUE fp64
    relative residual of the fp32-inner path sits at cfg.tol on every
    registered steady family, and labels come back fp64."""
    fam = get_family(family, nx=16, ny=16)
    p = fam.sample(jax.random.PRNGKey(0))
    x32, st32 = solve_gmres(p.op, p.b, CFG32)
    assert st32.converged, (family, st32)
    assert st32.outer_refinements >= 1
    assert np.asarray(x32).dtype == np.float64
    assert _true_rel_res(p, x32) <= CFG.tol * 1.01
    x64, _ = solve_gmres(p.op, p.b, CFG)
    np.testing.assert_allclose(np.asarray(x32), np.asarray(x64), rtol=1e-4,
                               atol=1e-7)


@pytest.mark.parametrize("family", list_families())
def test_gcrodr_fp32_inner_sequence_parity(family):
    """Recycling chain under the mixed policy: every system of a sequence
    converges to the fp64 tolerance and the carry is STORED fp32."""
    fam = get_family(family, nx=16, ny=16)
    solver = GCRODRSolver(CFG32)
    for s in range(3):
        p = fam.sample(jax.random.PRNGKey(s))
        pre = make_preconditioner("jacobi", p.op)
        op = PreconditionedOp(as_operator(p.op), pre)
        x, st = solver.solve(op, jnp.asarray(p.b).reshape(-1))
        assert st.converged, (family, s, st)
        assert _true_rel_res(p, x) <= CFG.tol * 1.01
        assert np.asarray(x).dtype == np.float64       # labels fp64
    assert solver.u_carry is not None
    assert solver.u_carry.dtype == np.float32          # carry fp32


@pytest.mark.parametrize("family", list_timedep_families())
def test_timedep_fp32_inner_trajectory_parity(family):
    """θ-scheme marching with fp32 inner cycles matches the fp64 engine to
    solver tolerance on every registered time-dependent family."""
    from repro.core.trajectory import TrajConfig, march_trajectory

    fam = get_timedep_family(family, nx=12, ny=12, nt=4)
    spec = fam.sample_spec(jax.random.PRNGKey(0))
    kc = dataclasses.replace(CFG, tol=1e-9)
    t64, s64 = march_trajectory(fam, spec, TrajConfig(krylov=kc,
                                                      precond="jacobi"))
    kc32 = dataclasses.replace(kc, inner_dtype="float32")
    t32, s32 = march_trajectory(fam, spec, TrajConfig(krylov=kc32,
                                                      precond="jacobi"))
    assert s32.num_converged == s32.num
    assert t32.dtype == np.float64
    scale = np.abs(t64).max()
    np.testing.assert_allclose(t32, t64, atol=1e-6 * scale)


@pytest.mark.parametrize("family", list_families())
def test_batched_fp32_inner_matches_fp64_lockstep(family):
    """Lockstep mixed engine: per-chain solutions agree with the fp64
    lockstep engine to solver tolerance; per-chain carries stored fp32."""
    fam = get_family(family, nx=12, ny=12)
    batch = fam.sample_batch(jax.random.PRNGKey(3), 4)
    coeffs = jnp.asarray(batch.op.coeffs)
    b_all = np.asarray(batch.b).reshape(4, -1)
    outs = {}
    for tag, cfg in (("f64", CFG), ("f32", CFG32)):
        solver = BatchedGCRODRSolver(cfg)
        xs = []
        for t in range(2):
            idx = np.array([2 * w + t for w in range(2)])
            st5 = Stencil5(coeffs).take(jnp.asarray(idx))
            pre = make_preconditioner_batched("jacobi", st5)
            opsb = PreconditionedOp(StencilOp(st5.coeffs), pre)
            x, sts = solver.solve_batch(opsb, jnp.asarray(b_all[idx]))
            assert all(s.converged for s in sts), (tag, t)
            xs.append(x)
        outs[tag] = np.concatenate(xs)
        if tag == "f32":
            assert solver.u_carry.dtype == np.float32
            assert all(s.outer_refinements >= 1 for s in sts)
    rel = (np.linalg.norm(outs["f32"] - outs["f64"], axis=1)
           / np.linalg.norm(outs["f64"], axis=1))
    assert (rel <= 1e-6).all(), rel


def test_batched_fp32_zero_rhs_padding_noop():
    """Padded chains stay a no-op under the mixed policy: 0 iterations,
    x = 0, recycle carry untouched."""
    fam = get_family("poisson", nx=12, ny=12)
    batch = fam.sample_batch(jax.random.PRNGKey(5), 2)
    coeffs = jnp.asarray(batch.op.coeffs)
    b_all = np.asarray(batch.b).reshape(2, -1)
    st5 = Stencil5(coeffs).take(jnp.asarray([0, 1]))
    pre = make_preconditioner_batched("jacobi", st5)
    opsb = PreconditionedOp(StencilOp(st5.coeffs), pre)
    solver = BatchedGCRODRSolver(CFG32)
    solver.solve_batch(opsb, jnp.asarray(b_all))
    before = solver.u_carry.copy()
    b_pad = b_all.copy()
    b_pad[1] = 0.0
    xs, sts = solver.solve_batch(opsb, jnp.asarray(b_pad))
    assert sts[1].converged and sts[1].iterations == 0
    np.testing.assert_array_equal(xs[1], np.zeros_like(xs[1]))
    np.testing.assert_array_equal(solver.u_carry[1], before[1])
    assert sts[0].converged and sts[0].iterations > 0


def test_batched_fp32_carry_stays_on_device_between_passes():
    """The fp32 inner solver keeps its recycle carry on the device: a call
    uploads it only when the public carry is not the one it stored last,
    and fetches it once, with the iterate. Forcing the upload changes no
    bit of the labels or of the carry."""
    from repro import obs

    fam = get_family("poisson", nx=12, ny=12)
    batch = fam.sample_batch(jax.random.PRNGKey(6), 6)
    coeffs = jnp.asarray(batch.op.coeffs)
    b_all = np.asarray(batch.b).reshape(6, -1)
    rows = []
    for t in range(3):
        idx = np.array([3 * w + t for w in range(2)])
        st5 = Stencil5(coeffs).take(jnp.asarray(idx))
        pre = make_preconditioner_batched("jacobi", st5)
        rows.append((PreconditionedOp(StencilOp(st5.coeffs), pre),
                     jnp.asarray(b_all[idx])))
    carry_bytes = 2 * 144 * CFG.k * 4           # (B, n, k) fp32
    outs = {}
    for forced in (False, True):
        solver = BatchedGCRODRSolver(CFG32)
        xs, h2d, d2h = [], [], []
        for t, (opsb, b) in enumerate(rows):
            if forced:
                solver._carry_mirror = None
            obs.enable(krylov_capacity=0)
            try:
                x, sts = solver.solve_batch(opsb, b)
                c = obs.summary()["counters"]
            finally:
                obs.disable()
            assert all(s.converged for s in sts), (forced, t)
            h2d.append(c["hostlink.h2d_bytes"])
            d2h.append(c["hostlink.d2h_bytes"])
            if t:
                assert max(s.outer_refinements for s in sts) >= 2
            assert isinstance(solver._inner.u_carry, jax.Array)
            assert solver.u_carry.dtype == np.float32
            assert solver.u_carry.flags.writeable
            xs.append(x)
        outs[forced] = (np.concatenate(xs), solver.u_carry.copy())
        if forced:
            assert h2d[2] - h2d_kept[2] == carry_bytes
            assert d2h == d2h_kept
        else:
            h2d_kept, d2h_kept = h2d, d2h
            # the first call has no carry to send; each later call fetches
            # it once and never sends it back
            assert min(d2h) >= carry_bytes
    np.testing.assert_array_equal(outs[False][0], outs[True][0])
    np.testing.assert_array_equal(outs[False][1], outs[True][1])


# ------------------------------------------------------ stagnation fallback

def _near_resonant_helmholtz(nx=12, kappa=1e8):
    """Helmholtz operator shifted to within ‖A‖/kappa of resonance — fp32
    cycles cannot contract the residual (κ·eps_f32 ≫ 1)."""
    fam = get_family("helmholtz", nx=nx, ny=nx)
    p = fam.sample(jax.random.PRNGKey(0))
    a = np.asarray(p.op.to_dense())
    evals = np.linalg.eigvalsh(0.5 * (a + a.T))
    mu = evals[np.argmin(np.abs(evals))]
    eps = np.abs(evals).max() / kappa
    coeffs = p.op.coeffs.at[Stencil5.C].add(-mu + eps)
    return Stencil5(coeffs), p.b


def test_fp32_stagnation_falls_back_to_fp64():
    """Ill-conditioned helmholtz: the fp32 passes stagnate, the solver must
    flag the fallback AND still converge to the fp64 tolerance."""
    op_ill, b = _near_resonant_helmholtz()
    n = int(np.asarray(b).size)
    cfg = KrylovConfig(m=n + 8, k=12, tol=1e-8, maxiter=20_000,
                       inner_dtype="float32")
    solver = GCRODRSolver(cfg)
    op = PreconditionedOp(as_operator(op_ill), None)
    x, st = solver.solve(op, jnp.asarray(b).reshape(-1))
    assert st.converged, st
    assert st.fp64_fallback
    assert st.outer_refinements >= 1
    ad = op_ill.to_dense()
    bv = np.asarray(b).reshape(-1)
    res = np.linalg.norm(bv - ad @ np.asarray(x)) / np.linalg.norm(bv)
    assert res <= cfg.tol * 1.01


# ------------------------------------------------- fp64-default regression

def test_batched_fp32_stall_on_a_recycled_space_retries_cold():
    """Lockstep mixed engine: a chain whose fp32 pass fails to halve its
    residual while it started from a recycled space (here one whose columns
    are nearly parallel) gets one more fp32 pass with its carry dropped,
    instead of sending the whole batch to fp64; every chain still meets
    tol by its true fp64 residual and the chain owns a fresh space after."""
    cfg = KrylovConfig(m=30, k=10, tol=1e-8, maxiter=20_000,
                       inner_dtype="float32")
    fam = get_family("poisson", nx=12, ny=12)
    batch = fam.sample_batch(jax.random.PRNGKey(2), 4)
    coeffs = jnp.asarray(batch.op.coeffs)
    b_all = np.asarray(batch.b).reshape(4, -1)

    def row(idx):
        st5 = Stencil5(coeffs).take(jnp.asarray(idx))
        pre = make_preconditioner_batched("jacobi", st5)
        return (PreconditionedOp(StencilOp(st5.coeffs), pre),
                jnp.asarray(b_all[idx]))

    solver = BatchedGCRODRSolver(cfg)
    solver.solve_batch(*row([0, 1]))
    assert solver.carry_ok.all()
    u = np.array(solver.u_carry)
    noise = np.random.default_rng(0).standard_normal((u.shape[1], cfg.k - 1))
    u[0][:, 1:] = u[0][:, :1] + 1e-6 * noise
    solver.u_carry = u
    x, sts = solver.solve_batch(*row([2, 3]))
    assert not any(s.fp64_fallback for s in sts)
    assert sts[0].outer_refinements == sts[1].outer_refinements + 1
    assert solver.carry_ok.all()
    for w, i in enumerate([2, 3]):
        a = Stencil5(coeffs[i]).to_dense()
        res = np.linalg.norm(b_all[i] - a @ x[w]) / np.linalg.norm(b_all[i])
        assert sts[w].converged and res <= cfg.tol * 1.01, (w, res)


def test_fp64_default_path_bitwise_identical():
    """inner_dtype="float64" (and the default) must take the historical
    code path: bitwise-identical solutions and identical iterate counts."""
    fam = get_family("poisson", nx=16, ny=16)
    p = fam.sample(jax.random.PRNGKey(1))
    x_def, st_def = solve_gmres(p.op, p.b, CFG)
    x_f64, st_f64 = solve_gmres(
        p.op, p.b, dataclasses.replace(CFG, inner_dtype="float64"))
    np.testing.assert_array_equal(np.asarray(x_def), np.asarray(x_f64))
    assert st_def.iterations == st_f64.iterations
    assert st_f64.outer_refinements == 0 and not st_f64.fp64_fallback

    op = PreconditionedOp(as_operator(p.op), None)
    b = jnp.asarray(p.b).reshape(-1)
    x1, st1 = GCRODRSolver(CFG).solve(op, b)
    x2, st2 = GCRODRSolver(
        dataclasses.replace(CFG, inner_dtype="float64")).solve(op, b)
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))
    assert st1.iterations == st2.iterations


def test_cast_operator_preserves_structure():
    fam = get_family("darcy", nx=12, ny=12)
    p = fam.sample(jax.random.PRNGKey(0))
    pre = make_preconditioner("bjacobi", p.op)
    op = PreconditionedOp(as_operator(p.op), pre)
    op32 = cast_operator(op, jnp.float32)
    assert op32.base.coeffs.dtype == jnp.float32
    assert op32.precond.inv_blocks.dtype == jnp.float32
    # same treedef (static structure untouched)
    assert (jax.tree_util.tree_structure(op)
            == jax.tree_util.tree_structure(op32))
    v = jnp.ones(op.n, jnp.float32)
    assert op32.apply(v).dtype == jnp.float32


# ------------------------------------------- kernel dtype polymorphism

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
@pytest.mark.parametrize("use_kernel", [False, True])
def test_stencil5_matvec_dtype_polymorphic(dtype, use_kernel):
    key = jax.random.PRNGKey(0)
    coeffs = jax.random.normal(key, (5, 16, 16), dtype)
    x = jax.random.normal(jax.random.fold_in(key, 1), (16, 16), dtype)
    y = ops.stencil5_matvec(coeffs, x, use_kernel=use_kernel, interpret=True)
    assert y.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 1e-12
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(ref.stencil5_matvec(
            coeffs.astype(jnp.float64), x.astype(jnp.float64))),
        rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
@pytest.mark.parametrize("batched", [False, True])
def test_dia_spmv_dtype_polymorphic(dtype, batched):
    rng = np.random.default_rng(0)
    offsets = (-8, -1, 0, 1, 8)
    n = 200
    shape = (3, len(offsets), n) if batched else (len(offsets), n)
    data = jnp.asarray(rng.standard_normal(shape), dtype)
    x = jnp.asarray(rng.standard_normal(shape[:-2] + (n,)), dtype)
    dia = DIA(offsets=offsets, data=data)
    y = ops.dia_spmv(dia, x, use_kernel=True, interpret=True)
    assert y.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 1e-12
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(ref.dia_spmv(offsets, data, x)),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("n", [521, 1000, 4096])  # prime / ragged / aligned
def test_fused_orthog_padded_tail_matches_ref(n):
    """The padded-tail fallback (prime-ish n must NOT degrade to a 1-element
    block) is exact: zero columns contribute nothing."""
    key = jax.random.PRNGKey(n)
    m = 12
    v = jax.random.normal(key, (m, n))
    w = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    mask = (jnp.arange(m) < 9).astype(w.dtype)
    got_w, got_h = ops.fused_orthog(v, w, mask, use_kernel=True,
                                    interpret=True)
    want_w, want_h = ref.fused_orthog(v, w, mask)
    np.testing.assert_allclose(np.asarray(got_w), np.asarray(want_w),
                               rtol=1e-10, atol=1e-10)
    np.testing.assert_allclose(np.asarray(got_h), np.asarray(want_h),
                               rtol=1e-10, atol=1e-10)


def test_fused_orthog_grid_cap_raises():
    from repro.kernels.fused_orthog import fused_orthog_pallas

    v = jnp.zeros((4, 1 << 22))
    w = jnp.zeros(1 << 22)
    mask = jnp.ones(4)
    with pytest.raises(ValueError, match="sanity cap"):
        fused_orthog_pallas(v, w, mask, interpret=True, block_n=128)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_fused_orthog_f64_accum_knob(use_kernel):
    """cgs2_acc="float64": fp32 storage, fp64 accumulation — at least as
    close to the fp64 oracle as the all-fp32 run, and fp32 outputs."""
    key = jax.random.PRNGKey(7)
    m, n = 16, 512
    v64 = jnp.linalg.qr(jax.random.normal(key, (n, m)))[0].T
    w64 = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    mask = jnp.ones((m,), jnp.float32)
    v32, w32 = v64.astype(jnp.float32), w64.astype(jnp.float32)
    ww, hw = ops.fused_orthog(v32, w32, mask, use_kernel=use_kernel,
                              interpret=True, acc_dtype=jnp.float64)
    assert ww.dtype == jnp.float32 and hw.dtype == jnp.float32
    w_ref, h_ref = ref.fused_orthog(v64, w64, mask.astype(jnp.float64))
    np.testing.assert_allclose(np.asarray(hw), np.asarray(h_ref), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(ww), np.asarray(w_ref), atol=1e-5)


def test_arnoldi_cgs2_acc64_converges():
    """End-to-end: the f64-accum knob through a mixed-precision solve."""
    fam = get_family("poisson", nx=12, ny=12)
    p = fam.sample(jax.random.PRNGKey(0))
    cfg = dataclasses.replace(CFG32, cgs2_acc="float64")
    x, st = solve_gmres(p.op, p.b, cfg)
    assert st.converged
    assert _true_rel_res(p, x) <= cfg.tol * 1.01


# ------------------------------------------------- chunked-datagen parity

def test_chunked_datagen_fp32_inner_labels_match():
    """generate_dataset_chunked with the mixed policy: fp64 labels at
    solver tolerance, both engines, carry checkpoint-compatible."""
    from repro.core.skr import SKRConfig, generate_dataset_chunked

    fam = get_family("poisson", nx=12, ny=12)
    kc = dataclasses.replace(CFG, tol=1e-9)
    key = jax.random.PRNGKey(7)
    base = generate_dataset_chunked(
        fam, key, 6, SKRConfig(krylov=kc, precond="jacobi"), workers=2,
        engine="batched")
    mixed = generate_dataset_chunked(
        fam, key, 6,
        SKRConfig(krylov=dataclasses.replace(kc, inner_dtype="float32"),
                  precond="jacobi"),
        workers=2, engine="batched")
    for cb, cm in zip(base, mixed):
        np.testing.assert_array_equal(cb.order, cm.order)
        assert cm.solutions.dtype == np.float64
        assert cm.stats.num_converged == len(cm.order)
        for pos in range(len(cb.order)):
            rel = (np.linalg.norm(cm.solutions[pos] - cb.solutions[pos])
                   / max(np.linalg.norm(cb.solutions[pos]), 1e-300))
            assert rel <= 1e-6, (pos, rel)


# ----------------------------------------------- fp32 products on a TPU

def _lockstep_programs(dtype, bsz=4, nx=16, m=8, k=3):
    """The jaxprs of the lockstep solver's programs (entry, fresh and
    deflated cycles, both refreshes) in `dtype`: the kernel path for fp32,
    as a kernel solver's fp32 passes run; jnp for fp64."""
    from repro.solvers import batched as bt
    from repro.solvers.precond import JacobiPrecond

    n, fp32 = nx * nx, dtype == jnp.float32

    def sds(*shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt)

    ops_ = PreconditionedOp(StencilOp(sds(bsz, 5, nx, nx), use_kernel=fp32),
                            JacobiPrecond(sds(bsz, n)))
    mask = sds(bsz, dt=bool)
    eargs = (ops_, sds(bsz, n), sds(bsz, n), sds(bsz, n, k), sds(bsz, n, k),
             sds(bsz, n, k), mask, mask, sds(), sds(dt=jnp.int32), sds())
    ekw = dict(k=k, use_carry=True, pad_given=True, stall_break=fp32)
    kw = dict(k=k, orthog="cgs2", use_kernel=fp32, h_acc="native",
              stall_break=fp32)
    fkw, dkw = dict(kw, m=m, can_grow=False), dict(kw, mi=m - k)
    s, aux, _ = jax.eval_shape(lambda *a: bt._entry(*a, **ekw), *eargs)
    sf, _, pf = jax.eval_shape(lambda *a: bt._fresh_cycle(*a, **fkw),
                               ops_, s, aux)
    sd, _, pd = jax.eval_shape(lambda *a: bt._deflated_cycle(*a, **dkw),
                               ops_, s, aux)
    jx = jax.make_jaxpr
    return {
        "entry": jx(lambda *a: bt._entry(*a, **ekw))(*eargs),
        "fresh": jx(lambda *a: bt._fresh_cycle(*a, **fkw))(ops_, s, aux),
        "deflated": jx(lambda *a: bt._deflated_cycle(*a, **dkw))(ops_, s,
                                                                  aux),
        "fresh_refresh": jx(lambda *a: bt._fresh_refresh(*a, k=k))(
            sf, pf["v"], pf["h"], sds(bsz, m, k), sds(bsz, m + 1, k),
            sds(bsz, k, k), mask),
        "deflated_refresh": jx(lambda *a: bt._deflated_refresh(*a, k=k))(
            sd, pd["g"], pd["ut"], pd["v"], pd["step"], sds(bsz, m, k),
            mask),
    }


def _dots(jaxpr):
    """(operand dtype, precision) of every dot_general in a jaxpr and the
    jaxprs nested in it."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append((eqn.invars[0].aval.dtype, eqn.params["precision"]))
        for p in eqn.params.values():
            for q in (p if isinstance(p, (tuple, list)) else (p,)):
                if isinstance(q, ClosedJaxpr):
                    out += _dots(q.jaxpr)
                elif isinstance(q, Jaxpr):
                    out += _dots(q)
    return out


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64],
                         ids=["fp32", "fp64"])
def test_lockstep_products_run_at_their_precision(dtype):
    """Every fp32 product of the fp32 lockstep programs asks for HIGHEST:
    at the TPU's default an fp32 dot is one bf16 pass, and the fp32
    correction passes then fail to halve the fp64 residual on the chip.
    The fp64 programs keep the default (no precision) on every product."""
    highest = jax.lax.Precision.HIGHEST
    for name, jaxpr in _lockstep_programs(dtype).items():
        dots = [p for dt, p in _dots(jaxpr.jaxpr) if dt == dtype]
        assert dots, name
        for prec in dots:
            if dtype == jnp.float32:
                assert prec is not None and all(
                    q == highest for q in (prec if isinstance(prec, tuple)
                                           else (prec,))), (name, prec)
            else:
                assert prec is None, (name, prec)


def test_fp32_lockstep_pass_stops_three_cycles_after_its_best():
    """A stall-breaking fp32 lockstep solve asked for a tolerance below
    fp32's round-off floor stops each chain at its first run of 3 cycles
    that leave its best residual within 1 %: a cycle that only wins back a
    rise at the floor does not restart the count (per-cycle residuals from
    the device telemetry)."""
    from repro import obs
    from repro.pde.dia import Stencil5

    bsz = 4
    batch = get_family("darcy", nx=32, ny=32).sample_batch(
        jax.random.PRNGKey(3), bsz)
    st5 = Stencil5(jnp.asarray(batch.op.coeffs))
    op = cast_operator(PreconditionedOp(
        StencilOp(st5.coeffs), make_preconditioner_batched("jacobi", st5)),
        jnp.float32)
    b = jnp.asarray(np.asarray(batch.b).reshape(bsz, -1), jnp.float32)
    cfg = KrylovConfig(m=20, k=5, tol=1e-9, maxiter=5000)
    obs.enable(krylov_capacity=256)
    try:
        _, stats = BatchedGCRODRSolver(cfg, stall_break=True).solve_batch(
            op, b)
    finally:
        obs.disable()
    for st, best in zip(stats, np.linalg.norm(np.asarray(b), axis=1)):
        assert st.breakdown
        run = 0
        for cycle, res in enumerate(st.telemetry.res_hist, start=1):
            run = run + 1 if res > 0.99 * best else 0
            best = min(best, res)
            if run == 3:
                break
        assert st.cycles == cycle, (st.cycles, cycle)


def test_darcy128_f32k_settings_match_the_plain_reference():
    """The benchmark's darcy-128-f32k settings at 32 x 32 on 8 chains
    (kernels interpreted), through the pipeline: every label meets tol by
    the plain reference's true residual and agrees with its fp64 direct
    solve; every refinement pass ran in fp32, as the counters say."""
    import json
    import os

    from bench import reference
    from repro import obs
    from repro.core import pipeline
    from repro.core.skr import SKRConfig, SteadyWork

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "bench", "configs",
                           "darcy-128-f32k.json")) as f:
        conf = json.load(f)
    params = dict(conf["family_params"], nx=32, ny=32)
    fam = get_family(conf["family"], **params)
    cfg = SKRConfig(krylov=KrylovConfig(**conf["krylov"]),
                    sort_method=conf["sort_method"], precond=conf["precond"],
                    use_kernel=conf["use_kernel"], strict_labels="flag")
    obs.enable(krylov_capacity=0)
    try:
        chunks = pipeline.run_chunked(SteadyWork(fam, cfg),
                                      jax.random.PRNGKey(15), 16, 8,
                                      "batched")
        counters = obs.registry().snapshot()["counters"]
    finally:
        obs.disable()
    k_field = np.concatenate([c.inputs for c in chunks])
    labels = np.concatenate([c.solutions for c in chunks])
    a, b = reference.darcy_system(k_field, params["source"])
    res = reference.stencil_residual(a, labels, b)
    assert res.max() <= conf["label_residual_limit"], res.max()
    direct = reference.solve(a, b, np.float64)
    rel = (np.linalg.norm((labels - direct).reshape(len(labels), -1), axis=1)
           / np.linalg.norm(direct.reshape(len(direct), -1), axis=1))
    assert rel.max() <= 1e-6, rel.max()

    solved = [c.stats.solved for c in chunks]
    assert not any(s.fp64_fallback for st in solved for s in st)
    assert counters.get("mixed.passes_fp64", 0.0) == 0.0
    rows = max(len(st) for st in solved)
    assert counters["mixed.dispatches"] == rows
    # a pass runs while any chain of the row still needs one
    assert counters["mixed.passes_fp32"] == sum(
        max(st[t].outer_refinements for st in solved if t < len(st))
        for t in range(rows))
