"""Bytes and operations of the fused `arnoldi_step` kernel
(src/repro/kernels/arnoldi_step.py), and its share of the chip's HBM
roofline in a traced slice.

One call is what the lockstep solver launches per Arnoldi step: the kernel
vmapped over B chains, in fp32. Its grid runs (chain, phase, row tile) in
that order (the vmap puts the chain axis first): 5 phases over nt = nx / bx
row tiles of bx rows. The blocks are counted from the kernel's BlockSpecs
and index maps (`blocks` below) as the TPU pipeline of a Pallas call moves
them: an input block is copied in when its block index differs from the
grid step before, and an output block is copied out when its index is about
to change and at the end. Every block counts in full, the halo tiles
included.

Only the blocks of operands in HBM cross the chip's HBM link: the basis V
and, in a deflated cycle, the recycle rows C. XLA keeps the kernel's other
operands in VMEM at the lockstep solver's shapes (the stencil fields and
Jacobi diagonal, hoisted out of the Arnoldi loop; v_j, w, the mask, h and
b; the fresh cycle's zero row of C): their compiled layouts carry the VMEM
memory space S(1), which tests/test_tpu_compile.py checks at 128² and 64
chains. Their copies are VMEM to VMEM and are left out of the bytes.

The kernel is bound by bandwidth (about a third of an operation per HBM
byte), so its roofline share is the least time its bytes take at the
chip's HBM bandwidth (peaks.json) over its measured device time.
"""
from __future__ import annotations

import json
import os
from typing import Callable, Iterable, List, Tuple

PHASES = 5
HBM, VMEM = True, False           # where XLA keeps a kernel operand
ITEMSIZE = 4                      # fp32: Mosaic has no fp64
BLOCK_ROWS = 64                   # arnoldi_step_pallas's default
PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def row_block(nx: int, block_rows: int = BLOCK_ROWS) -> int:
    """Rows per tile: the largest divisor of nx that is at most block_rows
    and a multiple of 8, else the whole axis (the kernels' own rule)."""
    for bx in range(min(block_rows, nx), 0, -1):
        if nx % bx == 0 and bx % 8 == 0:
            return bx
    return nx


def blocks(m1: int, k: int, nx: int, ny: int, bx: int) \
        -> List[Tuple[str, int, Callable[[int, int], int], bool]]:
    """The kernel's blocks: (name, elements, index map over (phase, tile) to
    the block's row-tile index, whether its operand is in HBM), inputs then
    outputs, as its BlockSpecs declare them."""
    k1 = max(k, 1)
    nt = nx // bx

    def tile(p, t):
        return t

    def up(p, t):
        return max(t - 1, 0)

    def down(p, t):
        return min(t + 1, nt - 1)

    def whole(p, t):
        return 0

    plane = bx * ny
    return [("coeffs", 5 * plane, tile, VMEM),
            ("inv_diag", plane, tile, VMEM),
            ("inv_diag_up", plane, up, VMEM),
            ("inv_diag_down", plane, down, VMEM),
            ("vin", plane, tile, VMEM), ("vin_up", plane, up, VMEM),
            ("vin_down", plane, down, VMEM),
            ("c_rows", k1 * plane, tile, HBM if k > 0 else VMEM),
            ("v_basis", m1 * plane, tile, HBM),
            ("mask", m1, whole, VMEM),
            # outputs
            ("w", plane, tile, VMEM), ("h", m1, whole, VMEM),
            ("b", k1, whole, VMEM)]


def copies(index: Callable[[int, int], int], nt: int, chains: int) -> int:
    """Block copies over the grid (chain, phase, tile): one per run of
    equal consecutive block indices."""
    n, prev = 0, None
    for c in range(chains):
        for p in range(PHASES):
            for t in range(nt):
                key = (c, index(p, t))
                if key != prev:
                    n, prev = n + 1, key
    return n


def arnoldi_step_bytes(B: int, m: int, k: int, nx: int, ny: int,
                       block_rows: int = BLOCK_ROWS) -> int:
    """HBM bytes of one call over B chains, with a basis of m + 1 rows and
    k deflation rows (k = 0, plain GMRES, pads C to one zero row)."""
    bx = row_block(nx, block_rows)
    nt = nx // bx
    return ITEMSIZE * sum(size * copies(index, nt, B)
                          for _, size, index, where
                          in blocks(m + 1, k, nx, ny, bx) if where == HBM)


def arnoldi_step_flops(B: int, m: int, k: int, nx: int, ny: int,
                       block_rows: int = BLOCK_ROWS) -> int:
    """Floating-point operations of one call: per chain and point, the
    Jacobi apply (1), the 5-point stencil (9), C·w and its removal
    (2 + 2 per row of C), the two CGS2 passes (4 + 4 per basis row) and
    the three subtractions (3); per tile, the two halo rows' Jacobi apply
    and the masked accumulation of h (2 per basis row, twice)."""
    m1, k1 = m + 1, max(k, 1)
    bx = row_block(nx, block_rows)
    nt = nx // bx
    per_point = 1 + 9 + 4 * k1 + 8 * m1 + 3
    per_tile = 2 * ny + 4 * m1
    return B * (nx * ny * per_point + nt * per_tile)


def peaks(device_kind: str) -> dict:
    """The chip's peaks (peaks.json); a device not in the table is an
    error."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}")
    return table[device_kind]


def shape_of(op_name: str, m: int, k: int):
    """(basis steps, deflation rows) of a kernel call from the program it
    ran in: the fresh cycle's m steps with no C, the deflated cycle's m - k
    steps with k rows of C; None for another program."""
    if "_deflated_cycle" in op_name:
        return m - k, k
    if "_fresh_cycle" in op_name:
        return m, 0
    return None


KERNEL = "arnoldi_step"


def roofline(events: Iterable[Tuple[str, float, float, str]], lo: float,
             hi: float, config: dict, traffic: dict, device_kind: str):
    """The kernel's share of its HBM roofline in the slice [lo, hi) ns:
    Σ bytes / bandwidth over Σ device time of its calls that start in the
    slice. `events`: device operations (name, start ns, end ns, op name).
    None when no call is there."""
    from bench.trace import op_family

    fp = config["family_params"]
    kr = config["krylov"]
    nbytes = flops = 0
    secs = 0.0
    calls = other = 0
    for name, s, e, op in events:
        if op_family(name) != KERNEL or not lo <= s < hi:
            continue
        shape = shape_of(op, kr["m"], kr["k"])
        if shape is None:
            other += 1
            continue
        dims = (traffic["chains"],) + shape + (fp["nx"], fp["ny"])
        nbytes += arnoldi_step_bytes(*dims)
        flops += arnoldi_step_flops(*dims)
        secs += (e - s) / 1e9
        calls += 1
    if not calls or secs <= 0:
        return None
    bw = peaks(device_kind)["hbm_bytes_per_s"]
    return {"value": 100.0 * nbytes / bw / secs, "calls": calls,
            "kernel_s": secs, "gb_per_s": nbytes / secs / 1e9,
            "gflop_per_s": flops / secs / 1e9, "other_calls": other}
