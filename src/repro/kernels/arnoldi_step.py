"""Pallas TPU kernel: one fused (deflated) Arnoldi inner iteration.

The lockstep hot loop runs, per Arnoldi step: Jacobi preconditioner apply →
5-point stencil matvec → C-deflation projection → two-pass CGS2 against the
growing basis. Unfused, that is four kernel launches with w round-tripping
through HBM between each. This kernel is the whole step as ONE launch: a
5-phase sequential grid over row tiles (the multi-phase scratch pattern of
`fused_orthog` composed with the clamped neighbor-halo blocks of
`stencil_matvec`), with the intermediate vector held in the output block
and every reduction (Cᴴw, the two CGS2 coefficient passes) accumulated in
VMEM scratch:

  phase 0: u = D⁻¹·vin (self + halo tiles); w0[tile] = stencil(c, u);
           cacc += C[:, tile] · w0[tile]
  phase 1: w1[tile] = w0[tile] − Cᵀ[tile] · cacc;
           h1 += mask · (V[:, tile] · w1[tile])
  phase 2: w2[tile] = w1[tile] − Vᵀ[tile] · h1
  phase 3: h2 += mask · (V[:, tile] · w2[tile])
  phase 4: w3[tile] = w2[tile] − Vᵀ[tile] · h2; emit h = h1 + h2, b = cacc

(`fused_orthog` overlaps its phases 2/3 into one; here they are split
because the h2 accumulation must see the FULLY updated w2 of its own tile
only — same dependency structure, one more pass over the tile in VMEM,
still zero extra HBM traffic.)

The deflation block C may be empty (k = 0, plain GMRES): the wrapper pads
it to one ZERO row, whose projection is an exact no-op.

The norm/breakdown/Givens tail of the Arnoldi step stays outside — it is
O(m) scalar work on the small Hessenberg column, not worth a launch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import ZERO, row_block


def _dot2(a, b):
    """Reduce the trailing (bx, ny) tile axes: (r, bx, ny)·(bx, ny) →
    (r, 1, 1). Broadcast-multiply + reduce keeps every value 3-D with
    tiled trailing axes, the form Mosaic lowers."""
    p = jnp.sum(a * b[None], axis=2, keepdims=True)
    return jnp.sum(p, axis=1, keepdims=True)


def _combine(h, a):
    """Σ_r h[r]·a[r]: (r, 1, 1) coefficients × (r, bx, ny) rows → (bx, ny)."""
    return jnp.sum(h.astype(a.dtype) * a, axis=0)


def _kernel(c5_ref, idg_ref, idg_up_ref, idg_dn_ref, vin_ref, vin_up_ref,
            vin_dn_ref, crows_ref, v_ref, mask_ref, wout_ref, h_ref, b_ref,
            cacc_s, h1_s, h2_s, *, nx_tiles: int):
    phase = pl.program_id(0)
    t = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(jnp.logical_and(phase == 0, t == 0))
    def _init():
        cacc_s[...] = jnp.zeros_like(cacc_s)
        h1_s[...] = jnp.zeros_like(h1_s)
        h2_s[...] = jnp.zeros_like(h2_s)

    @pl.when(phase == 0)
    def _p0():
        c = c5_ref[...]                      # (5, bx, ny)
        u = idg_ref[...] * vin_ref[...]      # Jacobi apply, this tile
        bx, ny = u.shape
        # halo rows are preconditioned on the fly from the neighbor tiles
        # (clamped index_map; first/last tiles mask the out-of-range halo)
        prev = jnp.where(t > 0, idg_up_ref[bx - 1, :] * vin_up_ref[bx - 1, :],
                         jnp.zeros_like(u[0]))
        nxt = jnp.where(t < nx_tiles - 1, idg_dn_ref[0, :] * vin_dn_ref[0, :],
                        jnp.zeros_like(u[0]))
        up = jnp.concatenate([prev[None, :], u[:-1, :]], axis=0)
        down = jnp.concatenate([u[1:, :], nxt[None, :]], axis=0)
        zcol = jnp.zeros((bx, 1), u.dtype)
        left = jnp.concatenate([zcol, u[:, :-1]], axis=1)
        right = jnp.concatenate([u[:, 1:], zcol], axis=1)
        w0 = (c[0] * u + c[1] * up + c[2] * down + c[3] * left + c[4] * right)
        wout_ref[...] = w0
        cacc_s[...] += _dot2(crows_ref[...], w0).astype(cacc_s.dtype)

    @pl.when(phase == 1)
    def _p1():
        w1 = wout_ref[...] - _combine(cacc_s[...], crows_ref[...])
        wout_ref[...] = w1
        h1_s[...] += (mask_ref[...] * _dot2(v_ref[...], w1)).astype(h1_s.dtype)

    @pl.when(phase == 2)
    def _p2():
        wout_ref[...] = wout_ref[...] - _combine(h1_s[...], v_ref[...])

    @pl.when(phase == 3)
    def _p3():
        h2_s[...] += (mask_ref[...]
                      * _dot2(v_ref[...], wout_ref[...])).astype(h2_s.dtype)

    @pl.when(phase == 4)
    def _p4():
        wout_ref[...] = wout_ref[...] - _combine(h2_s[...], v_ref[...])

        @pl.when(t == nt - 1)
        def _emit():
            h_ref[...] = (h1_s[...] + h2_s[...]).astype(h_ref.dtype)
            b_ref[...] = cacc_s[...].astype(b_ref.dtype)


# The kernel's scoped VMEM. XLA keeps a kernel operand in VMEM across a
# loop when it fits beside that scope: the lockstep sweep's basis, written
# in place (104 MiB at 64 chains and 128²), or its recycle rows C (60 MiB)
# would then be read over VMEM, not HBM where bench/cost.py counts them.
# 80 MiB of v5e's 128 leaves 48 MiB beside the kernel: room for the stencil
# fields, the Jacobi diagonal and v_j, which it counts as VMEM, and for
# neither of those two.
VMEM_LIMIT_BYTES = 80 * 2**20


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows",
                                             "acc_dtype"))
def arnoldi_step_pallas(coeffs: jax.Array, inv_diag: jax.Array,
                        c_rows: jax.Array, v_basis: jax.Array,
                        vin: jax.Array, mask: jax.Array, *,
                        interpret: bool, block_rows: int = 64,
                        acc_dtype=None):
    """One fused Arnoldi inner iteration.

    coeffs  : (5, nx, ny) stencil fields
    inv_diag: (n,) or (nx, ny) Jacobi inverse diagonal (pass ones for
              precond=None)
    c_rows  : (k, n) or (k, nx, ny) deflation rows Cᴴ (k = 0 → padded to
              one zero row)
    v_basis : (m+1, n) or (m+1, nx, ny) Krylov basis rows (inactive rows
              masked); in the (nx, ny) layout the kernel reads them as they
              are, flat rows are relaid out per call
    vin     : (n,) or (nx, ny) current basis vector v_j
    mask    : (m+1,) float {0,1} — rows 0..j active
    acc_dtype: widen ONLY the CGS2 coefficient scratch (fp32 storage / fp64
    accumulate — KrylovConfig.cgs2_acc); w, b stay in storage dtype.

    Returns (w_orth (vin's shape), hcol (m+1,), bj (k,)) — exactly the
    unfused `precond → matvec → C-projection → fused_orthog` composition.
    """
    nx, ny = coeffs.shape[-2:]
    m1 = v_basis.shape[0]
    k = c_rows.shape[0]
    k1 = max(k, 1)
    dt = vin.dtype
    if k == 0:
        c_rows = jnp.zeros((1, nx * ny), dt)
    bx = row_block(nx, block_rows)
    nt = nx // bx
    acc = jnp.dtype(acc_dtype) if acc_dtype is not None else dt

    wout, h, bj = pl.pallas_call(
        functools.partial(_kernel, nx_tiles=nt),
        grid=(5, nt),
        in_specs=[
            pl.BlockSpec((5, bx, ny), lambda p, t: (ZERO, t, ZERO)),
            pl.BlockSpec((bx, ny), lambda p, t: (t, ZERO)),
            # clamped neighbor tiles supply the halo rows (phase 0 only)
            pl.BlockSpec((bx, ny), lambda p, t: (jnp.maximum(t - 1, 0), ZERO)),
            pl.BlockSpec((bx, ny),
                         lambda p, t: (jnp.minimum(t + 1, nt - 1), ZERO)),
            pl.BlockSpec((bx, ny), lambda p, t: (t, ZERO)),
            pl.BlockSpec((bx, ny), lambda p, t: (jnp.maximum(t - 1, 0), ZERO)),
            pl.BlockSpec((bx, ny),
                         lambda p, t: (jnp.minimum(t + 1, nt - 1), ZERO)),
            pl.BlockSpec((k1, bx, ny), lambda p, t: (ZERO, t, ZERO)),
            pl.BlockSpec((m1, bx, ny), lambda p, t: (ZERO, t, ZERO)),
            pl.BlockSpec((m1, 1, 1), lambda p, t: (ZERO, ZERO, ZERO)),
        ],
        out_specs=[
            pl.BlockSpec((bx, ny), lambda p, t: (t, ZERO)),
            pl.BlockSpec((m1, 1, 1), lambda p, t: (ZERO, ZERO, ZERO)),
            pl.BlockSpec((k1, 1, 1), lambda p, t: (ZERO, ZERO, ZERO)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nx, ny), dt),
            jax.ShapeDtypeStruct((m1, 1, 1), dt),
            jax.ShapeDtypeStruct((k1, 1, 1), dt),
        ],
        scratch_shapes=[
            pltpu.VMEM((k1, 1, 1), dt),
            pltpu.VMEM((m1, 1, 1), acc),
            pltpu.VMEM((m1, 1, 1), acc),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name="arnoldi_step",
    )(coeffs,
      inv_diag.reshape(nx, ny), inv_diag.reshape(nx, ny),
      inv_diag.reshape(nx, ny),
      vin.reshape(nx, ny), vin.reshape(nx, ny), vin.reshape(nx, ny),
      c_rows.reshape(k1, nx, ny), v_basis.reshape(m1, nx, ny),
      mask.reshape(m1, 1, 1))
    return wout.reshape(vin.shape), h.reshape(m1), bj.reshape(k1)[:k]
