"""jit'd dispatch wrappers for the Pallas kernels.

Every op has a pure-jnp reference path (ref.py, the default) and a Pallas
path (`use_kernel=True`). The solver layers call THESE wrappers so
the kernel routing is a config flag, not a code change. The backend decides
how a kernel runs, here and nowhere else (`interpret_mode`): compiled by
Mosaic on a TPU, in the Pallas interpreter on the CPU. A caller may still
pass `interpret=` explicitly (the CPU tests do).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref

FP64_ON_TPU = (
    "the Pallas kernels run fp32 on a TPU (Mosaic has no fp64): with "
    "use_kernel=True set KrylovConfig(inner_dtype='float32', "
    "cgs2_acc='native'), or keep fp64 with use_kernel=False")


def interpret_mode(*dtypes) -> bool:
    """How a kernel over operands of `dtypes` runs on this backend: False
    (compiled) on a TPU, True (interpreted) on the CPU. Any other backend,
    and an fp64 operand on a TPU, is an error — never a silent fallback."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend != "tpu":
        raise RuntimeError(f"Pallas kernels run on a TPU (compiled) or the "
                           f"CPU (interpreted), not on {backend!r}")
    if not kernels_take(*dtypes):
        raise TypeError(FP64_ON_TPU)
    return False


def kernels_take(*dtypes) -> bool:
    """Whether the kernel path can run operands of `dtypes` on this backend
    (fp64 only in the CPU interpreter). The solver's dispatch points
    (`StencilOp`/`DIAOp.apply`, the Arnoldi cycle) ask this, so the fp64
    work of a kernel solver (the mixed-precision residual replay and fp64
    fallback cycles, the fp64 retry rung) runs on jnp on a TPU."""
    wide = any(d is not None and jnp.dtype(d) == jnp.float64 for d in dtypes)
    return not (wide and jax.default_backend() == "tpu")


def check_solver_request(cfg, use_kernel: bool) -> None:
    """The one check of a solver's kernel request, made where a solver is
    built from its KrylovConfig: on a TPU, `use_kernel=True` with fp64
    inner storage or fp64 CGS2 accumulation raises instead of running its
    cycles on jnp."""
    acc = "float64" if cfg.cgs2_acc == "float64" else None
    if use_kernel and not kernels_take(cfg.inner_dtype, acc):
        raise TypeError(FP64_ON_TPU)


def _resolve(interpret, *dtypes) -> bool:
    return interpret_mode(*dtypes) if interpret is None else interpret


def stencil5_matvec(coeffs: jax.Array, x: jax.Array, *, use_kernel: bool = False,
                    interpret: bool | None = None) -> jax.Array:
    """(…, 5, nx, ny) coeffs × (…, nx, ny) field → (…, nx, ny)."""
    if use_kernel:
        from repro.kernels.stencil_matvec import stencil5_matvec_pallas

        fn = functools.partial(stencil5_matvec_pallas,
                               interpret=_resolve(interpret, coeffs.dtype,
                                                  x.dtype))
        if x.ndim > 2:  # batched: map over leading dims
            for _ in range(x.ndim - 2):
                fn = jax.vmap(fn)
        return fn(coeffs, x)
    return ref.stencil5_matvec(coeffs, x)


def dia_spmv(dia, x: jax.Array, *, use_kernel: bool = False,
             interpret: bool | None = None, op_stride: int | None = None,
             op_index: jax.Array | None = None) -> jax.Array:
    """DIA sparse matvec on flat (…, n) vectors.

    A matched batch (data (B, ndiag, n) against x (B, n)) routes through the
    single-launch batched kernel — one explicit dispatch for all B operators.
    NOTE: this branch fires only for direct matched-batch calls at this
    boundary; inside `jax.vmap` (the lockstep solver's cycles) tracer shapes
    are per-chain, and it is Pallas's own vmap batching rule that lifts the
    single kernel to an equivalent batched grid.

    Broadcastable operator stacks (a SMALLER data (A, ndiag, n) against a
    LARGER x (B, n), the label-expansion fan-out) never materialize per-row
    operator copies:
      op_stride=s  uniform fan-out, B = A·s, y[b] = data[b // s] @ x[b]
                   (index arithmetic in the kernel's BlockSpec; the ref
                   path broadcasts a (A, 1, …) reshape)
      op_index     arbitrary (B,) int assignment, y[b] = data[op_index[b]]
                   @ x[b] (in-kernel dynamic slice; ref path gathers)
    The two are mutually exclusive; with neither, shapes must match or
    broadcast as before.
    """
    if op_stride is not None and op_index is not None:
        raise ValueError("op_stride and op_index are mutually exclusive")
    if use_kernel:
        from repro.kernels.dia_spmv import (dia_spmv_batched_pallas,
                                            dia_spmv_gather_pallas,
                                            dia_spmv_pallas,
                                            dia_spmv_strided_pallas)

        data = dia.data
        interpret = _resolve(interpret, data.dtype, x.dtype)
        if op_stride is not None:
            return dia_spmv_strided_pallas(dia.offsets, data, x,
                                           op_stride=op_stride,
                                           interpret=interpret)
        if op_index is not None:
            return dia_spmv_gather_pallas(dia.offsets, data, x, op_index,
                                          interpret=interpret)
        if data.ndim == 3 and x.ndim == 2 and data.shape[0] == x.shape[0]:
            return dia_spmv_batched_pallas(dia.offsets, data, x,
                                           interpret=interpret)
        fn = functools.partial(dia_spmv_pallas, dia.offsets, interpret=interpret)
        if x.ndim > 1:
            for _ in range(x.ndim - 1):
                fn = jax.vmap(fn)
        return fn(data, x)
    if op_stride is not None:
        nops = dia.data.shape[0]
        n = dia.data.shape[-1]
        y = ref.dia_spmv(dia.offsets, dia.data[:, None],
                         x.reshape(nops, op_stride, n))
        return y.reshape(nops * op_stride, n)
    if op_index is not None:
        return ref.dia_spmv(dia.offsets, dia.data[op_index], x)
    return ref.dia_spmv(dia.offsets, dia.data, x)


def fused_orthog(v_basis: jax.Array, w: jax.Array, mask: jax.Array, *,
                 use_kernel: bool = False, interpret: bool | None = None,
                 acc_dtype=None):
    """CGS2 projection: orthogonalize w against the masked rows of v_basis.

    Returns (w_orth, h) with h the combined projection coefficients —
    the Arnoldi inner-loop hot spot after the matvec (DESIGN §4.4).
    Dtype-polymorphic: runs in the storage dtype of (v_basis, w); pass
    acc_dtype (e.g. jnp.float64 under fp32 storage) to widen ONLY the
    accumulation (KrylovConfig.cgs2_acc="float64").
    """
    if use_kernel:
        from repro.kernels.fused_orthog import fused_orthog_pallas

        return fused_orthog_pallas(
            v_basis, w, mask, acc_dtype=acc_dtype,
            interpret=_resolve(interpret, v_basis.dtype, w.dtype, acc_dtype))
    return ref.fused_orthog(v_basis, w, mask, acc_dtype=acc_dtype)


def arnoldi_step(coeffs: jax.Array, inv_diag: jax.Array, c_rows: jax.Array,
                 v_basis: jax.Array, vin: jax.Array, mask: jax.Array, *,
                 use_kernel: bool = False, interpret: bool | None = None,
                 acc_dtype=None):
    """One fused (deflated) Arnoldi inner iteration: Jacobi apply + 5-point
    stencil matvec + C-projection + CGS2 as ONE launch (the lockstep hot
    loop's whole inner body — see kernels/arnoldi_step.py).

    Returns (w_orth (n,), hcol (m+1,), bj (k,)); the kernel path also
    takes its vectors as (nx, ny) fields, and then returns w_orth as one.
    k = 0 (plain GMRES) is handled by zero-row padding inside the kernel
    wrapper."""
    if use_kernel:
        from repro.kernels.arnoldi_step import arnoldi_step_pallas

        return arnoldi_step_pallas(
            coeffs, inv_diag, c_rows, v_basis, vin, mask, acc_dtype=acc_dtype,
            interpret=_resolve(interpret, coeffs.dtype, v_basis.dtype,
                               acc_dtype))
    return ref.arnoldi_step(coeffs, inv_diag, c_rows, v_basis, vin, mask,
                            acc_dtype=acc_dtype)

