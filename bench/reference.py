"""Plain reference for the benchmark's output check: numpy, fp64, no import
of the program under test.

It assembles each sampled system from what the job emitted (the Darcy
permeability field K) with its own 5-point finite-volume code, and measures
every label by its true residual ||b - A u|| / ||b||. `solve` is
the same system solved by a sparse direct factorization in a chosen dtype:
the benchmark's control runs it in float32.
"""
from __future__ import annotations

import numpy as np

# stencil legs, stacked [c, n, s, w, e]: n couples u[i-1, j], s u[i+1, j],
# w u[i, j-1], e u[i, j+1]
C, N, S, W, E = range(5)


def stencil_residual(coeffs, u, b):
    """||b - A u|| / ||b|| per system, numpy fp64. coeffs (N, 5, nx, ny),
    u and b (N, nx, ny)."""
    c, u, b = (np.asarray(a, np.float64) for a in (coeffs, u, b))
    return _norms(b - apply(c, u)) / np.maximum(_norms(b), 1e-300)


def apply(c, u):
    """A u for stacked 5-point stencils c (N, 5, nx, ny), u (N, nx, ny)."""
    up = np.zeros_like(u)
    up[:, 1:] = u[:, :-1]
    down = np.zeros_like(u)
    down[:, :-1] = u[:, 1:]
    left = np.zeros_like(u)
    left[:, :, 1:] = u[:, :, :-1]
    right = np.zeros_like(u)
    right[:, :, :-1] = u[:, :, 1:]
    return (c[:, C] * u + c[:, N] * up + c[:, S] * down + c[:, W] * left
            + c[:, E] * right)


def _norms(x):
    return np.linalg.norm(x.reshape(len(x), -1), axis=1)


def _harmonic(a, b):
    return 2.0 * a * b / (a + b)


def _fv_stencil(k, hx, hy, wall):
    """-div(K grad .) with Dirichlet-0 walls on stacked fields k (N, nx, ny):
    interior faces take the harmonic mean of the two cells, a wall face
    `wall * K` of its cell; legs that leave the grid are dropped."""
    kx = _harmonic(k[:, :-1, :], k[:, 1:, :])
    ky = _harmonic(k[:, :, :-1], k[:, :, 1:])
    tn = np.concatenate([wall * k[:, :1, :], kx], axis=1)
    ts = np.concatenate([kx, wall * k[:, -1:, :]], axis=1)
    tw = np.concatenate([wall * k[:, :, :1], ky], axis=2)
    te = np.concatenate([ky, wall * k[:, :, -1:]], axis=2)
    out = np.empty((len(k), 5) + k.shape[1:])
    out[:, N] = -tn / hx**2
    out[:, S] = -ts / hx**2
    out[:, W] = -tw / hy**2
    out[:, E] = -te / hy**2
    out[:, C] = -(out[:, N] + out[:, S] + out[:, W] + out[:, E])
    out[:, N, 0, :] = 0.0
    out[:, S, -1, :] = 0.0
    out[:, W, :, 0] = 0.0
    out[:, E, :, -1] = 0.0
    return out


def darcy_system(k_field, source: float):
    """Darcy -div(K grad h) = f on the unit square, cell-centred finite
    volumes on an nx x ny grid of spacing 1/(n+1), a wall face at twice the
    cell's K (half a cell to the wall). Returns (coeffs, b)."""
    k = np.asarray(k_field, np.float64)
    nx, ny = k.shape[1:]
    coeffs = _fv_stencil(k, 1.0 / (nx + 1), 1.0 / (ny + 1), wall=2.0)
    return coeffs, np.full(k.shape, float(source))


def solve(coeffs, b, dtype):
    """Each system of the stack solved by a sparse LU in `dtype`; returns
    the solutions as float64 arrays."""
    import scipy.sparse as sp
    from scipy.sparse.linalg import splu

    coeffs, b = np.asarray(coeffs), np.asarray(b)
    nb, _, nx, ny = coeffs.shape
    n = nx * ny
    idx = np.arange(n).reshape(nx, ny)
    out = np.empty((nb, nx, ny))
    every, head, tail = slice(None), slice(1, None), slice(None, -1)
    # (leg, rows that have it, its column offset as a slice of idx)
    legs = ((C, (every, every), (every, every)),
            (N, (head, every), (tail, every)),
            (S, (tail, every), (head, every)),
            (W, (every, head), (every, tail)),
            (E, (every, tail), (every, head)))
    for s in range(nb):
        rows, cols, vals = [], [], []
        for leg, at, to in legs:
            rows.append(idx[at].ravel())
            cols.append(idx[to].ravel())
            vals.append(coeffs[s, leg][at].ravel())
        a = sp.csc_matrix((np.concatenate(vals).astype(dtype),
                           (np.concatenate(rows), np.concatenate(cols))),
                          shape=(n, n))
        x = splu(a).solve(b[s].ravel().astype(dtype))
        out[s] = x.reshape(nx, ny).astype(np.float64)
    return out
