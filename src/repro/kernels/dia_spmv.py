"""Pallas TPU kernel: DIA (diagonal-format) SpMV.

General banded companion to the stencil kernel (used for flattened /
non-stencil operators). The wrapper pre-pads x by the maximum |offset| so
every in-kernel load is in range: per output tile the kernel reads one
x slice per diagonal and accumulates coeff·slice — unit-stride VPU work,
no gather (DESIGN §4.1).

One kernel body serves the four entry points. The grid is (B, n∕bn):
dimension 0 walks the vectors, dimension 1 the output tiles, and the
data BlockSpec's index map picks the operator of vector b — b itself
(single, batched), b // op_stride (strided) or op_index[b] from scalar
prefetch (gather). Each padded x row sits whole in VMEM as one (1, ·)
block (solver vectors are ≤ O(100k)).

Dtype-polymorphic: the accumulator and output carry
result_type(data, x) — fp32 operands stay fp32 end to end (the
mixed-precision inner cycles), nothing assumes f64. Ragged n is padded up
to a multiple of the block size with zero diagonals/entries (masked tail)
instead of shrinking the block to a divisor of n, which degraded to a
one-element grid step for prime-ish n.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import LANE, ZERO, padded_tiles, round_up


def _kernel(*refs, offsets, pad, bn):
    # a leading scalar-prefetch ref (gather) only feeds the index maps
    data_ref, xpad_ref, o_ref = refs[-3:]
    t = pl.program_id(1)
    win = bn + LANE
    acc = jnp.zeros((1, bn), o_ref.dtype)
    for d, off in enumerate(offsets):
        # x[i + off] for the tile's i: Mosaic loads lane-aligned windows
        # only, so load the aligned window that holds the slice and rotate
        # the static remainder r to lane 0
        q, r = divmod(pad + off, LANE)
        start = pl.multiple_of(t * bn + q * LANE, LANE)
        xs = xpad_ref[:, pl.ds(start, win)]
        if r:
            xs = pltpu.roll(xs, np.int32(win - r), 1)
        acc = acc + data_ref[d:d + 1, :] * xs[:, :bn]
    o_ref[...] = acc


def _launch(offsets, data, x, *, op_of, op_index=None, interpret: bool,
            block_n: int, what: str):
    """y[b] = data[op_of(b)] @ x[b] for data (A, ndiag, n), x (B, n).

    Zero-padding by max|offset| encodes the boundary (matches DIA semantics:
    contributions from out-of-range columns vanish). Out-of-range data
    entries must already be zero — true for all assemblers in pde/."""
    _, ndiag, n = data.shape
    bsz = x.shape[0]
    pad = max(1, max(abs(o) for o in offsets))
    bn, n_pad, nt = padded_tiles(n, block_n, what, steps_factor=bsz)
    if n_pad != n:
        data = jnp.pad(data, ((0, 0), (0, 0), (0, n_pad - n)))
    # right pad: room for the last tile's widest aligned window
    xlen = n_pad + round_up(2 * pad, LANE) + LANE
    xpad = jnp.pad(x, ((0, 0), (pad, xlen - pad - n)))[:, None, :]
    out_dtype = jnp.result_type(data.dtype, x.dtype)
    prefetch = () if op_index is None else (op_index.astype(jnp.int32),)

    y = pl.pallas_call(
        functools.partial(_kernel, offsets=tuple(offsets), pad=pad, bn=bn),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(bsz, nt),
            in_specs=[
                pl.BlockSpec((None, ndiag, bn),
                             lambda b, t, *s: (op_of(b, *s), ZERO, t)),
                pl.BlockSpec((None, 1, xlen),
                             lambda b, t, *s: (b, ZERO, ZERO)),
            ],
            out_specs=pl.BlockSpec((None, 1, bn),
                                   lambda b, t, *s: (b, ZERO, t)),
        ),
        out_shape=jax.ShapeDtypeStruct((bsz, 1, n_pad), out_dtype),
        interpret=interpret,
        name="dia_spmv",
    )(*prefetch, data, xpad)
    return y[:, 0, :n]


@functools.partial(jax.jit, static_argnames=("offsets", "interpret", "block_n"))
def dia_spmv_pallas(offsets, data: jax.Array, x: jax.Array, *,
                    interpret: bool, block_n: int = 1024) -> jax.Array:
    """offsets: static tuple; data (ndiag, n); x (n,) → y (n,) — the
    batched launch at B = 1."""
    return _launch(offsets, data[None], x[None], op_of=lambda b: b,
                   interpret=interpret, block_n=block_n, what="dia_spmv")[0]


@functools.partial(jax.jit, static_argnames=("offsets", "interpret", "block_n"))
def dia_spmv_batched_pallas(offsets, data: jax.Array, x: jax.Array, *,
                            interpret: bool,
                            block_n: int = 1024) -> jax.Array:
    """B stencil/band operators applied in ONE kernel launch.

    offsets: static tuple shared by the batch; data (B, ndiag, n);
    x (B, n) → y (B, n). Amortizes the launch across the whole batch
    instead of issuing B separate dispatches. This is the explicit
    single-launch form of what Pallas's vmap batching rule produces when the
    lockstep solver vmaps the single kernel; use it for direct matched-batch
    SpMV at the ops boundary.
    """
    return _launch(offsets, data, x, op_of=lambda b: b, interpret=interpret,
                   block_n=block_n, what="dia_spmv_batched")


@functools.partial(jax.jit, static_argnames=("offsets", "op_stride",
                                             "interpret", "block_n"))
def dia_spmv_strided_pallas(offsets, data: jax.Array, x: jax.Array, *,
                            op_stride: int, interpret: bool,
                            block_n: int = 1024) -> jax.Array:
    """A operators, each applied to `op_stride` consecutive x rows.

    offsets: static tuple; data (A, ndiag, n); x (A·op_stride, n) →
    y (A·op_stride, n), with y[b] = data[b // op_stride] @ x[b]. The
    label-expansion shape: one anchor operator re-labels its K+1 perturbed
    solutions without `DIA.take` ever materializing K+1 operator copies —
    the broadcast is PURE INDEX ARITHMETIC in the BlockSpec index_map
    (`b // op_stride`), so the same operator block is simply fetched for
    each of its op_stride batch rows.
    """
    nops = data.shape[0]
    bsz = x.shape[0]
    if bsz != nops * op_stride:
        raise ValueError(f"strided batch mismatch: {nops} operators x "
                         f"stride {op_stride} != {bsz} vectors")
    stride = np.int32(op_stride)   # int32 index-map arithmetic for Mosaic
    return _launch(offsets, data, x, op_of=lambda b: jax.lax.div(b, stride),
                   interpret=interpret, block_n=block_n,
                   what="dia_spmv_strided")


@functools.partial(jax.jit, static_argnames=("offsets", "interpret",
                                             "block_n"))
def dia_spmv_gather_pallas(offsets, data: jax.Array, x: jax.Array,
                           op_index: jax.Array, *, interpret: bool,
                           block_n: int = 1024) -> jax.Array:
    """Arbitrary operator-per-vector assignment: y[b] = data[op_index[b]] @
    x[b].

    offsets: static tuple; data (A, ndiag, n); x (B, n); op_index (B,)
    int — the general companion of the strided path for non-uniform
    fan-out (ragged expansion waves, mixed re-label batches). `op_index`
    rides in scalar prefetch (SMEM) and feeds the data BlockSpec's index
    map, so only each vector's own operator tiles are fetched.
    """
    return _launch(offsets, data, x, op_of=lambda b, idx: idx[b],
                   op_index=op_index, interpret=interpret, block_n=block_n,
                   what="dia_spmv_gather")
