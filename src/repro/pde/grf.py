"""Gaussian random fields (GRF) — the parameter sampler behind the Darcy and
Helmholtz families (paper §6.1, App. D.2), and the solution-space
perturbation source of the label-expansion stage (core/expand.py).

Spectral (Matérn-like) sampling: white noise shaped by the power spectrum
    sqrt_spec(k) ∝ scale * (4π²|k|² + τ²)^(−α/2)
via the 2-D DFT, written as real matmuls. The white-noise tensor is the
*latent*; its low-frequency block is the sorting feature ("parameter
matrix" P^(i) of Algorithm 1).

Key handling: batched draws derive per-draw keys with `jax.random.fold_in`
on the draw index, NOT `jax.random.split` on the batch size — so draw i of
`sample_grf_batch(spec, key, n)` depends only on (key, i), never on n.
That makes batched draws prefix-stable (the first m draws of a size-n
batch equal a size-m batch), identical whether the per-draw sampling runs
under `jax.vmap` or in a python loop, and lets consumers that fan keys out
themselves (the seeded expansion waves) reproduce any single draw from its
index alone.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class GRFSpec:
    nx: int
    ny: int
    alpha: float = 2.5
    tau: float = 7.0
    scale: float = 1.0
    feature_modes: int = 8  # low-frequency latent block kept for sorting


def _sqrt_spectrum(spec: GRFSpec, dtype=jnp.float64) -> jax.Array:
    kx = jnp.fft.fftfreq(spec.nx, d=1.0 / spec.nx).astype(dtype)
    ky = jnp.fft.fftfreq(spec.ny, d=1.0 / spec.ny).astype(dtype)
    k2 = (2 * jnp.pi) ** 2 * (kx[:, None] ** 2 + ky[None, :] ** 2)
    s = spec.scale * (k2 + spec.tau**2) ** (-spec.alpha / 2.0)
    return s.at[0, 0].set(0.0)  # zero-mean field


@partial(jax.jit, static_argnums=(0, 2))
def sample_grf(spec: GRFSpec, key: jax.Array,
               dtype=jnp.float64) -> tuple[jax.Array, jax.Array]:
    """Returns (field (nx, ny) real, latent_features (2·m·m,)), both `dtype`.

    The latent is the low-frequency complex spectrum (real/imag stacked):
    nearby latents ⇒ nearby fields, which is exactly the property the sorting
    pass exploits. `dtype` selects the noise/spectrum precision, and the
    transforms run in it (the label-expansion waves perturb fp64 anchors
    but may sample perturbation fields in fp32).
    """
    noise = jax.random.normal(key, (spec.nx, spec.ny), dtype=dtype)
    re, im, field = _spectral_shape_dft(noise, _sqrt_spectrum(spec, dtype))
    m = spec.feature_modes
    feats = jnp.concatenate([re[:m, :m].ravel(), im[:m, :m].ravel()])
    return field, feats


def _dft(n: int, dtype):
    """(C, S) with the n-point DFT matrix F = C − iS (symmetric)."""
    k = jnp.arange(n)
    ang = (2 * jnp.pi / n) * ((k[:, None] * k[None, :]) % n).astype(dtype)
    return jnp.cos(ang), jnp.sin(ang)


def _spectral_shape_dft(noise, sqrt_spec):
    """fft2 → spectrum → real(ifft2) as real DFT matmuls: the TPU has no
    complex128 FFT. Dense O(n³) for an n×n grid (an FFT is O(n² log n)),
    which is small beside a solve at the families' grids. Returns
    (Re coef, Im coef, field)."""
    nx, ny = noise.shape
    hi = jax.lax.Precision.HIGHEST
    cx, sx = _dft(nx, noise.dtype)
    cy, sy = _dft(ny, noise.dtype)
    mm = partial(jnp.matmul, precision=hi)
    a_re, a_im = mm(cx, noise), -mm(sx, noise)          # F_x · noise
    re = (mm(a_re, cy) + mm(a_im, sy)) * sqrt_spec       # · F_y, shaped
    im = (mm(a_im, cy) - mm(a_re, sy)) * sqrt_spec
    b_re = mm(cx, re) - mm(sx, im)                       # conj(F_x) · coef
    b_im = mm(cx, im) + mm(sx, re)
    field = (mm(b_re, cy) - mm(b_im, sy)) / (nx * ny)   # real(· conj(F_y))
    return re, im, field


def batch_keys(key: jax.Array, n) -> jax.Array:
    """Per-draw keys for a batch: key i = fold_in(key, i). `n` may be an
    int or an index array (reproducing an arbitrary subset of draws)."""
    idx = jnp.arange(n) if isinstance(n, int) else jnp.asarray(n)
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(idx)


def sample_grf_batch(spec: GRFSpec, key: jax.Array, n: int,
                     dtype=jnp.float64):
    """n independent draws, vmapped. Draw i equals
    `sample_grf(spec, fold_in(key, i), dtype)` exactly — see the module
    docstring for the reproducibility contract."""
    return jax.vmap(lambda k: sample_grf(spec, k, dtype))(batch_keys(key, n))
