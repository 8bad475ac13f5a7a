"""Tile-size rules shared by the Pallas kernels.

Mosaic (the TPU kernel compiler) takes a block whose last two dimensions
are multiples of (8, 128) or span the whole array axis, and index maps
that return int32: under `jax_enable_x64` a bare python `0` in an index
map traces as int64 and the kernel fails to legalize, so index maps use
`ZERO` instead.
"""
from __future__ import annotations

import numpy as np

ZERO = np.int32(0)
_MAX_GRID_STEPS = 65536
LANE = 128
_SUBLANE = 8


def round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def padded_tiles(n: int, block_n: int, what: str, steps_factor: int = 1):
    """(bn, n_pad, nt) for a padded 1-D tiling of n — never a degenerate
    divisor fallback; fails loudly past the grid-step sanity cap. Shared by
    every 1-D-tiled kernel (dia_spmv, fused_orthog); `steps_factor` is the
    kernel's grid steps per tile (e.g. 3 phases)."""
    bn = min(block_n, round_up(n, LANE))
    n_pad = round_up(n, bn)
    nt = n_pad // bn
    if nt * steps_factor > _MAX_GRID_STEPS:
        raise ValueError(f"{what} grid of {nt} steps (n={n}, block_n={bn}) "
                         f"exceeds the sanity cap {_MAX_GRID_STEPS}")
    return bn, n_pad, nt


def row_block(nx: int, block_rows: int) -> int:
    """Rows per tile of an (nx, ny) field: the largest divisor of nx that is
    ≤ block_rows and a multiple of 8, else the whole axis."""
    for bx in range(min(block_rows, nx), 0, -1):
        if nx % bx == 0 and bx % _SUBLANE == 0:
            return bx
    return nx
