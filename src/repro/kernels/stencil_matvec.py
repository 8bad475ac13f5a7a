"""Pallas TPU kernel: variable-coefficient 5-point stencil matvec.

The paper's inner-loop hot spot (>90% of solve time is SpMV +
orthogonalization). TPU adaptation of CSR SpMV (DESIGN §4.1): the operator
lives in field form (5, nx, ny); the matvec is 5 shifted elementwise
multiplies — pure VPU work, unit-stride, no gather.

Tiling: grid over row-tiles (bx, ny). Halo rows come from neighbor-tile
input blocks selected by a clamped index_map; the first/last tiles mask the
out-of-range halo. The whole working set per step is (5+3)·bx·ny elements —
sized to sit comfortably in VMEM (bx chosen so ≤ ~2 MB at f32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import ZERO, row_block


def _kernel(c_ref, x_ref, xup_ref, xdn_ref, o_ref, *, nx_tiles: int):
    t = pl.program_id(0)
    c = c_ref[...]          # (5, bx, ny)
    x = x_ref[...]          # (bx, ny)
    bx, ny = x.shape

    # north neighbor of row r is x[r-1]; row 0 needs the last row of the
    # previous tile (zero for the first tile).
    prev_last = jnp.where(t > 0, xup_ref[bx - 1, :], jnp.zeros_like(x[0]))
    up = jnp.concatenate([prev_last[None, :], x[:-1, :]], axis=0)

    next_first = jnp.where(t < nx_tiles - 1, xdn_ref[0, :], jnp.zeros_like(x[0]))
    down = jnp.concatenate([x[1:, :], next_first[None, :]], axis=0)

    zcol = jnp.zeros((bx, 1), x.dtype)
    left = jnp.concatenate([zcol, x[:, :-1]], axis=1)
    right = jnp.concatenate([x[:, 1:], zcol], axis=1)

    o_ref[...] = (c[0] * x + c[1] * up + c[2] * down + c[3] * left + c[4] * right)


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def stencil5_matvec_pallas(coeffs: jax.Array, x: jax.Array, *,
                           interpret: bool, block_rows: int = 64) -> jax.Array:
    """coeffs (5, nx, ny) × x (nx, ny) → (nx, ny).

    Dtype-polymorphic: output/accumulation carry result_type(coeffs, x) —
    fp32 operands (mixed-precision inner cycles) never silently widen."""
    nx, ny = x.shape
    bx = row_block(nx, block_rows)
    nt = nx // bx
    out_dtype = jnp.result_type(coeffs.dtype, x.dtype)

    return pl.pallas_call(
        functools.partial(_kernel, nx_tiles=nt),
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((5, bx, ny), lambda t: (ZERO, t, ZERO)),
            pl.BlockSpec((bx, ny), lambda t: (t, ZERO)),
            # clamped neighbor tiles supply the halo rows
            pl.BlockSpec((bx, ny), lambda t: (jnp.maximum(t - 1, 0), ZERO)),
            pl.BlockSpec((bx, ny), lambda t: (jnp.minimum(t + 1, nt - 1), ZERO)),
        ],
        out_specs=pl.BlockSpec((bx, ny), lambda t: (t, ZERO)),
        out_shape=jax.ShapeDtypeStruct((nx, ny), out_dtype),
        interpret=interpret,
        name="stencil5_matvec",
    )(coeffs, x, x, x)
