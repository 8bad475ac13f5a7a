"""Pallas TPU kernel: fused CGS2 orthogonalization (the Arnoldi inner loop's
second hot spot after the matvec).

TPU adaptation (DESIGN §4.4): paper-faithful MGS is a chain of m dependent
dot/axpy pairs — latency-bound. CGS2 reshapes the work into two matmul pairs
(h = V·w; w −= Vᵀ·h, twice) with equivalent robustness (Giraud et al. 2005).
This kernel fuses both passes into ONE launch: a 3-phase sequential grid
with the projection coefficients held in VMEM scratch, so the intermediate
half-orthogonalized vector never round-trips to HBM.

  phase 0: accumulate h1 += V[:, tile] · w[tile]         (per column tile)
  phase 1: w1[tile] = w[tile] − Vᵀh1; accumulate h2 += V · w1
  phase 2: w2[tile] = w1[tile] − Vᵀh2; emit h = h1 + h2
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import ZERO, padded_tiles


def _kernel(v_ref, w_ref, mask_ref, wout_ref, h_ref, h1_s, h2_s):
    phase = pl.program_id(0)
    t = pl.program_id(1)
    nt = pl.num_programs(1)
    v = v_ref[...]        # (m1, bn) storage dtype
    mask = mask_ref[...]  # (m1, 1)
    acc = h1_s.dtype      # accumulation dtype (== storage unless widened)

    # V·w and Vᵀh as broadcast-multiply + reduce on the vector unit: 2-D
    # shapes Mosaic lowers, exact in the storage dtype (an fp32 MXU pass
    # would round through bf16)
    def proj(w):          # (1, bn) → (m1, 1)
        return jnp.sum(v * w, axis=1, keepdims=True)

    def expand(h):        # (m1, 1) → (1, bn)
        return jnp.sum(h.astype(v.dtype) * v, axis=0, keepdims=True)

    @pl.when(jnp.logical_and(phase == 0, t == 0))
    def _init():
        h1_s[...] = jnp.zeros_like(h1_s)
        h2_s[...] = jnp.zeros_like(h2_s)

    @pl.when(phase == 0)
    def _p0():
        h1_s[...] += (mask * proj(w_ref[...])).astype(acc)

    @pl.when(phase == 1)
    def _p1():
        w1 = w_ref[...] - expand(h1_s[...])
        wout_ref[...] = w1
        h2_s[...] += (mask * proj(w1)).astype(acc)

    @pl.when(phase == 2)
    def _p2():
        wout_ref[...] = wout_ref[...] - expand(h2_s[...])
        @pl.when(t == nt - 1)
        def _emit():
            h_ref[...] = (h1_s[...] + h2_s[...]).astype(h_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block_n",
                                             "acc_dtype"))
def fused_orthog_pallas(v_basis: jax.Array, w: jax.Array, mask: jax.Array, *,
                        interpret: bool, block_n: int = 2048,
                        acc_dtype=None):
    """v_basis (m1, n), w (n,), mask (m1,) → (w_orth (n,), h (m1,)).

    Ragged n is handled by padding up to a multiple of the block size with
    ZERO columns (a masked tail): zero basis columns contribute nothing to
    h, and the padded slice of w_orth is discarded. This keeps the block
    size at the requested tile (the old fallback shrank bn until it divided
    n — degrading to bn = 1, one grid step per element, for prime-ish n).

    acc_dtype: widen ONLY the h accumulation scratch (fp32 storage / fp64
    accumulate); outputs stay in w.dtype.
    """
    m1, n = v_basis.shape
    bn, n_pad, nt = padded_tiles(n, block_n, "fused_orthog", steps_factor=3)
    if n_pad != n:
        v_basis = jnp.pad(v_basis, ((0, 0), (0, n_pad - n)))
        w = jnp.pad(w, (0, n_pad - n))
    acc = jnp.dtype(acc_dtype) if acc_dtype is not None else w.dtype

    wout, h = pl.pallas_call(
        _kernel,
        grid=(3, nt),
        in_specs=[
            pl.BlockSpec((m1, bn), lambda p, t: (ZERO, t)),
            pl.BlockSpec((1, bn), lambda p, t: (ZERO, t)),
            pl.BlockSpec((m1, 1), lambda p, t: (ZERO, ZERO)),
        ],
        out_specs=[
            pl.BlockSpec((1, bn), lambda p, t: (ZERO, t)),
            pl.BlockSpec((m1, 1), lambda p, t: (ZERO, ZERO)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n_pad), w.dtype),
            jax.ShapeDtypeStruct((m1, 1), w.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((m1, 1), acc),
            pltpu.VMEM((m1, 1), acc),
        ],
        interpret=interpret,
        name="fused_orthog",
    )(v_basis, w.reshape(1, n_pad), mask.reshape(m1, 1))
    return wout[0, :n], h[:, 0]
