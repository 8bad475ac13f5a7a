"""Pure-jnp oracles for every Pallas kernel (the ground truth the kernel
tests assert against, and the default CPU execution path)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import precision
from repro.kernels.precision import matmul as mm


def stencil5_matvec(coeffs: jax.Array, x: jax.Array) -> jax.Array:
    """y[i,j] = c·x[i,j] + n·x[i-1,j] + s·x[i+1,j] + w·x[i,j-1] + e·x[i,j+1]."""
    xp = jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(1, 1), (1, 1)])
    return (
        coeffs[..., 0, :, :] * x
        + coeffs[..., 1, :, :] * xp[..., :-2, 1:-1]
        + coeffs[..., 2, :, :] * xp[..., 2:, 1:-1]
        + coeffs[..., 3, :, :] * xp[..., 1:-1, :-2]
        + coeffs[..., 4, :, :] * xp[..., 1:-1, 2:]
    )


def dia_spmv(offsets, data: jax.Array, x: jax.Array) -> jax.Array:
    """y[i] = Σ_d data[d, i] · x[i + offsets[d]], zero-padded."""
    n = data.shape[-1]
    y = jnp.zeros(jnp.broadcast_shapes(data[..., 0, :].shape, x.shape), x.dtype)
    for d, off in enumerate(offsets):
        row = data[..., d, :]
        if off == 0:
            y = y + row * x
        elif off > 0:
            y = y.at[..., : n - off].add(row[..., : n - off] * x[..., off:])
        else:
            y = y.at[..., -off:].add(row[..., -off:] * x[..., : n + off])
    return y


def fused_orthog(v_basis: jax.Array, w: jax.Array, mask: jax.Array,
                 acc_dtype=None):
    """Two-pass classical Gram-Schmidt (CGS2) against masked rows of v_basis.

    v_basis: (m, n) row basis (rows beyond the active count are arbitrary,
    masked out); w: (n,); mask: (m,) float {0,1}.
    acc_dtype: None accumulates in the storage dtype; a wider dtype (e.g.
    jnp.float64 under fp32 storage) widens ONLY the dot-product
    accumulation (operands stay in storage dtype — the same semantics as
    the Pallas kernel's widened h scratch) and casts the results back —
    the mixed-precision robustness knob.
    Returns (w_orth, h_total) — h_total: (m,) combined coefficients.
    """
    if acc_dtype is not None and jnp.dtype(acc_dtype) != w.dtype:
        acc = jnp.dtype(acc_dtype)
        hp = precision.of(v_basis, w)
        h1 = mask.astype(acc) * jnp.matmul(v_basis, w, precision=hp,
                                           preferred_element_type=acc)
        w1 = w - mm(v_basis.T, h1.astype(w.dtype))
        h2 = mask.astype(acc) * jnp.matmul(v_basis, w1, precision=hp,
                                           preferred_element_type=acc)
        w2 = w1 - mm(v_basis.T, h2.astype(w.dtype))
        return w2, (h1 + h2).astype(w.dtype)
    h1 = mask * mm(v_basis, w)
    w1 = w - mm(v_basis.T, h1)
    h2 = mask * mm(v_basis, w1)
    w2 = w1 - mm(v_basis.T, h2)
    return w2, h1 + h2


def arnoldi_step(coeffs: jax.Array, inv_diag: jax.Array, c_rows: jax.Array,
                 v_basis: jax.Array, vin: jax.Array, mask: jax.Array,
                 acc_dtype=None):
    """One (deflated) Arnoldi inner iteration, unfused: Jacobi apply →
    stencil matvec → C-deflation projection → CGS2. The composition the
    fused kernel (arnoldi_step.py) replaces with a single launch.

    Returns (w_orth (n,), hcol (m+1,), bj (k,))."""
    nx, ny = coeffs.shape[-2:]
    u = inv_diag * vin
    w = stencil5_matvec(coeffs, u.reshape(nx, ny)).reshape(-1)
    bj = mm(c_rows, w)
    w = w - mm(c_rows.T, bj)
    w, h = fused_orthog(v_basis, w, mask, acc_dtype=acc_dtype)
    return w, h, bj

