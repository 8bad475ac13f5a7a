"""Per-kernel validation: every Pallas kernel swept over shapes/dtypes in
interpret=True mode against the pure-jnp ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops, ref


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


# ------------------------------------------------------------ stencil5

@pytest.mark.parametrize("nx,ny", [(8, 8), (16, 32), (33, 17), (64, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_stencil5_kernel_matches_ref(nx, ny, dtype):
    key = jax.random.PRNGKey(nx * 100 + ny)
    coeffs = _rand(key, (5, nx, ny), dtype)
    x = _rand(jax.random.fold_in(key, 1), (nx, ny), dtype)
    got = ops.stencil5_matvec(coeffs, x, use_kernel=True, interpret=True)
    want = ref.stencil5_matvec(coeffs, x)
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == jnp.float32 else \
        dict(rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **tol)


def test_stencil5_kernel_batched():
    key = jax.random.PRNGKey(0)
    coeffs = _rand(key, (3, 5, 16, 16), jnp.float64)
    x = _rand(jax.random.fold_in(key, 1), (3, 16, 16), jnp.float64)
    got = ops.stencil5_matvec(coeffs, x, use_kernel=True, interpret=True)
    want = ref.stencil5_matvec(coeffs, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-12)


def test_stencil5_matches_dense_matrix():
    """Kernel ≡ explicit sparse matrix assembled from the same stencil."""
    from repro.pde.dia import Stencil5

    key = jax.random.PRNGKey(3)
    coeffs = _rand(key, (5, 12, 12), jnp.float64)
    from repro.pde.dia import zero_boundary_neighbors

    coeffs = zero_boundary_neighbors(coeffs)
    st5 = Stencil5(coeffs)
    a = st5.to_dense()
    x = _rand(jax.random.fold_in(key, 1), (12, 12), jnp.float64)
    got = ops.stencil5_matvec(coeffs, x, use_kernel=True, interpret=True)
    want = (a @ np.asarray(x).reshape(-1)).reshape(12, 12)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-12)


# ------------------------------------------------------------ dia spmv

@pytest.mark.parametrize("n", [64, 256, 1000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_dia_spmv_kernel_matches_ref(n, dtype):
    from repro.pde.dia import DIA

    key = jax.random.PRNGKey(n)
    offsets = (-8, -1, 0, 1, 8)
    data = _rand(key, (len(offsets), n), dtype)
    x = _rand(jax.random.fold_in(key, 1), (n,), dtype)
    dia = DIA(offsets=offsets, data=data)
    got = ops.dia_spmv(dia, x, use_kernel=True, interpret=True)
    want = ref.dia_spmv(offsets, data, x)
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == jnp.float32 else \
        dict(rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **tol)


@given(st.integers(16, 128), st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_property_dia_spmv_matches_dense(n, seed):
    from repro.pde.dia import DIA

    rng = np.random.default_rng(seed)
    offsets = (-3, -1, 0, 1, 3)
    data = rng.standard_normal((5, n))
    x = rng.standard_normal(n)
    dia = DIA(offsets=offsets, data=jnp.asarray(data))
    a = dia.to_dense()
    got = ops.dia_spmv(dia, jnp.asarray(x), use_kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), a @ x, rtol=1e-10,
                               atol=1e-10)


# ----------------------------------------- dia spmv: broadcast operators
# (label expansion's dispatch shape: K+1 vectors per operator via index
#  arithmetic — `op_stride` — or an explicit per-vector `op_index` gather)

@pytest.mark.parametrize("nops,stride", [(1, 4), (3, 5), (4, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_dia_spmv_strided_matches_ref(nops, stride, dtype):
    from repro.pde.dia import DIA

    n = 144
    key = jax.random.PRNGKey(nops * 10 + stride)
    offsets = (-12, -1, 0, 1, 12)
    data = _rand(key, (nops, len(offsets), n), dtype)
    x = _rand(jax.random.fold_in(key, 1), (nops * stride, n), dtype)
    dia = DIA(offsets=offsets, data=data)
    got = ops.dia_spmv(dia, x, op_stride=stride, use_kernel=True,
                       interpret=True)
    want = ref.dia_spmv(offsets, data[:, None], x.reshape(nops, stride, n)
                        ).reshape(nops * stride, n)
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == jnp.float32 else \
        dict(rtol=1e-12, atol=1e-12)
    assert got.shape == (nops * stride, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **tol)


@pytest.mark.parametrize("use_kernel", [False, True], ids=["ref", "pallas"])
def test_dia_spmv_strided_equals_materialized(use_kernel):
    """op_stride broadcast ≡ repeating every operator stride times."""
    from repro.pde.dia import DIA

    nops, stride, n = 3, 4, 100
    key = jax.random.PRNGKey(7)
    offsets = (-10, -1, 0, 1, 10)
    data = _rand(key, (nops, 5, n), jnp.float64)
    x = _rand(jax.random.fold_in(key, 1), (nops * stride, n), jnp.float64)
    got = ops.dia_spmv(DIA(offsets=offsets, data=data), x, op_stride=stride,
                       use_kernel=use_kernel, interpret=True)
    rep = jnp.repeat(data, stride, axis=0)
    want = ops.dia_spmv(DIA(offsets=offsets, data=rep), x,
                        use_kernel=use_kernel, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-13, atol=1e-13)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_dia_spmv_gather_matches_ref(dtype):
    from repro.pde.dia import DIA

    nops, bsz, n = 4, 9, 121
    key = jax.random.PRNGKey(21)
    offsets = (-11, -1, 0, 1, 11)
    data = _rand(key, (nops, len(offsets), n), dtype)
    x = _rand(jax.random.fold_in(key, 1), (bsz, n), dtype)
    op_index = jnp.asarray(np.random.default_rng(0).integers(0, nops, bsz))
    dia = DIA(offsets=offsets, data=data)
    got = ops.dia_spmv(dia, x, op_index=op_index, use_kernel=True,
                       interpret=True)
    want = ref.dia_spmv(offsets, data[op_index], x)
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == jnp.float32 else \
        dict(rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **tol)


def test_dia_spmv_broadcast_args_are_exclusive():
    from repro.pde.dia import DIA

    data = jnp.zeros((2, 5, 64))
    dia = DIA(offsets=(-8, -1, 0, 1, 8), data=data)
    x = jnp.zeros((4, 64))
    with pytest.raises(ValueError):
        ops.dia_spmv(dia, x, op_stride=2, op_index=jnp.zeros(4, jnp.int32))


# -------------------------------------------------------- fused orthog

@pytest.mark.parametrize("m,n", [(8, 128), (16, 256), (40, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_fused_orthog_kernel_matches_ref(m, n, dtype):
    key = jax.random.PRNGKey(m + n)
    v = _rand(key, (m, n), dtype)
    w = _rand(jax.random.fold_in(key, 1), (n,), dtype)
    mask = (jnp.arange(m) < m // 2).astype(dtype)
    got_w, got_h = ops.fused_orthog(v, w, mask, use_kernel=True,
                                    interpret=True)
    want_w, want_h = ref.fused_orthog(v, w, mask)
    # tolerances scale with the output magnitude (random non-orthonormal
    # bases amplify CGS2 values; the solver always feeds orthonormal rows)
    tol = 1e-5 if dtype == jnp.float32 else 1e-12
    for got, want in ((got_w, want_w), (got_h, want_h)):
        scale = max(float(np.abs(np.asarray(want)).max()), 1.0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=tol * scale)


def test_fused_orthog_produces_orthogonal_result():
    key = jax.random.PRNGKey(7)
    m, n = 12, 512
    v = jnp.linalg.qr(jax.random.normal(key, (n, m)))[0].T  # orthonormal rows
    w = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    mask = jnp.ones((m,))
    w2, _ = ops.fused_orthog(v, w, mask, use_kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(v @ w2), np.zeros(m), atol=1e-10)


# ------------------------------------------------------- arnoldi step

def _arnoldi_inputs(key, nx, ny, m, k, dtype):
    n = nx * ny
    coeffs = _rand(key, (5, nx, ny), dtype)
    inv_diag = 1.0 + 0.1 * _rand(jax.random.fold_in(key, 1), (n,), dtype) ** 2
    c_rows = _rand(jax.random.fold_in(key, 2), (k, n), dtype)
    v = _rand(jax.random.fold_in(key, 3), (m + 1, n), dtype)
    vin = _rand(jax.random.fold_in(key, 4), (n,), dtype)
    mask = (jnp.arange(m + 1) < m // 2 + 1).astype(dtype)
    return coeffs, inv_diag, c_rows, v, vin, mask


@pytest.mark.parametrize("nx,ny", [(8, 8), (16, 32), (33, 17)])
@pytest.mark.parametrize("k", [0, 6])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_arnoldi_step_kernel_matches_ref(nx, ny, k, dtype):
    key = jax.random.PRNGKey(nx * 100 + ny + k)
    args = _arnoldi_inputs(key, nx, ny, 10, k, dtype)
    got = ops.arnoldi_step(*args, use_kernel=True, interpret=True)
    want = ref.arnoldi_step(*args)
    tol = 1e-5 if dtype == jnp.float32 else 1e-12
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        if w.size == 0:
            continue  # bj when k == 0
        scale = max(float(np.abs(np.asarray(w)).max()), 1.0)
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=tol * scale)


def test_arnoldi_step_kernel_fp64_accumulation():
    # fp32 storage + fp64 CGS2 accumulation (KrylovConfig.cgs2_acc)
    key = jax.random.PRNGKey(11)
    args = _arnoldi_inputs(key, 16, 16, 12, 4, jnp.float32)
    got = ops.arnoldi_step(*args, use_kernel=True, interpret=True,
                           acc_dtype=jnp.float64)
    want = ref.arnoldi_step(*args, acc_dtype=jnp.float64)
    for g, w in zip(got, want):
        assert g.dtype == jnp.float32
        scale = max(float(np.abs(np.asarray(w)).max()), 1.0)
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5 * scale)


def test_arnoldi_step_kernel_small_block_rows():
    # force multiple row tiles so the halo/neighbor-tile path is exercised
    from repro.kernels.arnoldi_step import arnoldi_step_pallas

    key = jax.random.PRNGKey(3)
    args = _arnoldi_inputs(key, 24, 8, 9, 3, jnp.float64)
    got = arnoldi_step_pallas(*args, interpret=True, block_rows=8)
    want = ref.arnoldi_step(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-10, atol=1e-10)


def test_arnoldi_step_kernel_vmaps():
    # the lockstep engine calls it under jax.vmap — batching rule must hold
    key = jax.random.PRNGKey(17)
    batched = [jnp.stack([a, a * 0.5 + 0.1])
               for a in _arnoldi_inputs(key, 8, 8, 6, 2, jnp.float64)]
    fn = lambda *a: ops.arnoldi_step(*a, use_kernel=True, interpret=True)
    got = jax.vmap(fn)(*batched)
    for i in range(2):
        want = ref.arnoldi_step(*(a[i] for a in batched))
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g[i]), np.asarray(w),
                                       rtol=1e-10, atol=1e-10)

