"""The precision of the jnp paths' fp32 matrix products.

A TPU runs an fp32 `dot_general` at the default precision as one bf16
pass, about three significant digits. The mixed-precision solvers' fp32
corrections need fp32 products: a basis update, a projection or a small
least-squares solve at three digits leaves a correction pass short of
halving the fp64 residual, and the refinement driver then gives the fp32
passes up. So every fp32 product of the solvers and of the kernels' jnp
oracles asks for HIGHEST (fp32 accuracy). An fp64 product keeps the
default (None), so the fp64 path traces the programs it always had.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def of(*operands):
    """The precision of a product of `operands` (arrays or dtypes):
    HIGHEST when it runs in fp32, else None (the backend's default)."""
    dt = jnp.result_type(*operands)
    return jax.lax.Precision.HIGHEST if dt == jnp.float32 else None


def matmul(a, b):
    """`a @ b` at `of(a, b)`."""
    return jnp.matmul(a, b, precision=of(a, b))


def einsum(spec: str, *operands):
    """`jnp.einsum` at `of(*operands)`."""
    return jnp.einsum(spec, *operands, precision=of(*operands))
