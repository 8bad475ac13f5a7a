"""Linear-operator pytrees for the Krylov layer.

Why pytrees instead of closures: the inner Arnoldi cycle is jitted once and
reused across the THOUSANDS of systems in a dataset sequence. A fresh Python
closure per system would force a retrace per system; a pytree operator with
static structure (offsets, kind tags in the treedef) retraces once per
(family, grid, m, k) and streams the rest.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.pde.dia import DIA, Stencil5, dia_matvec
from repro.kernels import ops as kops


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class StencilOp:
    """5-point stencil operator on flat (n,) vectors."""

    coeffs: jax.Array  # (5, nx, ny)
    use_kernel: bool = False  # route matvec through the Pallas kernel

    def tree_flatten(self):
        return (self.coeffs,), self.use_kernel

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(coeffs=children[0], use_kernel=aux)

    @property
    def n(self) -> int:
        return self.coeffs.shape[-2] * self.coeffs.shape[-1]

    @property
    def grid(self) -> Tuple[int, int]:
        return self.coeffs.shape[-2], self.coeffs.shape[-1]

    def apply(self, v: jax.Array) -> jax.Array:
        nx, ny = self.grid
        field = v.reshape(*v.shape[:-1], nx, ny)
        out = kops.stencil5_matvec(
            self.coeffs, field, use_kernel=self.use_kernel
            and kops.kernels_take(self.coeffs.dtype, v.dtype))
        return out.reshape(*v.shape[:-1], nx * ny)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class DIAOp:
    """Diagonal-format operator on flat (n,) vectors."""

    dia: DIA
    use_kernel: bool = False

    def tree_flatten(self):
        return (self.dia,), self.use_kernel

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(dia=children[0], use_kernel=aux)

    @property
    def n(self) -> int:
        return self.dia.n

    def apply(self, v: jax.Array) -> jax.Array:
        return kops.dia_spmv(self.dia, v, use_kernel=self.use_kernel
                             and kops.kernels_take(self.dia.data.dtype,
                                                   v.dtype))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class PreconditionedOp:
    """Right-preconditioned operator v ↦ A(M⁻¹ v).

    Solvers run in z-space (A M⁻¹ z = b) and recover x = M⁻¹ z at the end, so
    the tracked residual is the TRUE residual of A x = b.
    """

    base: object   # StencilOp | DIAOp
    precond: object  # a Preconditioner pytree from precond.py (or None)

    def tree_flatten(self):
        return (self.base, self.precond), None

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(*children)

    @property
    def n(self) -> int:
        return self.base.n

    def apply(self, v: jax.Array) -> jax.Array:
        if self.precond is None:
            return self.base.apply(v)
        return self.base.apply(self.precond.apply(v))

    def from_z(self, z: jax.Array) -> jax.Array:
        """Map z-space solution to x-space."""
        if self.precond is None:
            return z
        return self.precond.apply(z)


def apply_op(op, v: jax.Array) -> jax.Array:
    """Module-level dispatch (stable jit identity)."""
    return op.apply(v)


def cast_operator(op, dtype):
    """Cast every floating leaf of an operator/preconditioner pytree.

    The precision-policy layer builds the fp32 twin of a PreconditionedOp
    with this: static structure (offsets, kind tags, degrees) rides in the
    treedef and is untouched, so the casted twin shares jit caches keyed on
    treedef + (shape, dtype) and retraces exactly once per precision."""
    dtype = jnp.dtype(dtype)

    def _cast(leaf):
        a = jnp.asarray(leaf)
        if jnp.issubdtype(a.dtype, jnp.floating) and a.dtype != dtype:
            return a.astype(dtype)
        return leaf

    return jax.tree_util.tree_map(_cast, op)


def as_operator(problem_op, use_kernel: bool = False):
    """Stencil5 | DIA → solver operator."""
    if isinstance(problem_op, Stencil5):
        return StencilOp(problem_op.coeffs, use_kernel=use_kernel)
    if isinstance(problem_op, DIA):
        return DIAOp(problem_op, use_kernel=use_kernel)
    raise TypeError(f"unsupported operator {type(problem_op)}")
