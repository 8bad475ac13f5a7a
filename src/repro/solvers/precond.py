"""Preconditioners (paper §6.1 "Matrix preconditioning techniques").

TPU adaptation (DESIGN §4.2): ILU/ICC/SOR triangular solves are sequential
and hostile to TPU; the device-native set here is
  none | jacobi | bjacobi (line/tridiagonal blocks — batched dense inverses)
  | rbsor (red-black SSOR: parallel colored sweeps, stencil-only)
  | neumann | cheby (polynomial preconditioners — pure matvec chains)
plus `ilu_host` (scipy spilu behind a pure_callback) retained ONLY for paper-
parity CPU benchmarks. All device preconditioners are pytrees so the jitted
Arnoldi cycle retraces once per family, not per system.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import precision
from repro.pde.dia import DIA, Stencil5

# ---------------------------------------------------------------- pytrees


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class JacobiPrecond:
    inv_diag: jax.Array  # (n,)

    def tree_flatten(self):
        return (self.inv_diag,), None

    @classmethod
    def tree_unflatten(cls, _, ch):
        return cls(*ch)

    def apply(self, v):
        return self.inv_diag * v


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class BlockJacobiPrecond:
    """Line relaxation: one tridiagonal block per grid row, stored as batched
    dense inverses → the apply is ONE batched matmul (MXU-shaped)."""

    inv_blocks: jax.Array  # (nb, bs, bs)

    def tree_flatten(self):
        return (self.inv_blocks,), None

    @classmethod
    def tree_unflatten(cls, _, ch):
        return cls(*ch)

    def apply(self, v):
        nb, bs, _ = self.inv_blocks.shape
        return precision.einsum("bij,bj->bi", self.inv_blocks,
                                v.reshape(nb, bs)).reshape(-1)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class NeumannPrecond:
    """Truncated damped Neumann series on the Jacobi-scaled operator:
    M⁻¹v = ω Σ_{i<d} (I − ω D⁻¹A)^i D⁻¹ v."""

    op: object         # StencilOp | DIAOp (unpreconditioned base)
    inv_diag: jax.Array
    omega: jax.Array   # scalar damping
    degree: int = 4    # static

    def tree_flatten(self):
        return (self.op, self.inv_diag, self.omega), self.degree

    @classmethod
    def tree_unflatten(cls, degree, ch):
        return cls(op=ch[0], inv_diag=ch[1], omega=ch[2], degree=degree)

    def apply(self, v):
        z = self.omega * (self.inv_diag * v)
        acc = z
        for _ in range(self.degree - 1):
            z = z - self.omega * (self.inv_diag * self.op.apply(z))
            acc = acc + z
        # acc = Σ (I-ωD⁻¹A)^i ωD⁻¹ v via the recurrence z_{i+1} = (I-ωD⁻¹A) z_i
        return acc


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class ChebyshevPrecond:
    """Chebyshev polynomial preconditioner on [lmin, lmax] of D⁻¹A (SPD-ish
    families; the classic TPU-friendly SOR/ILU substitute)."""

    op: object
    inv_diag: jax.Array
    lmin: jax.Array
    lmax: jax.Array
    degree: int = 4

    def tree_flatten(self):
        return (self.op, self.inv_diag, self.lmin, self.lmax), self.degree

    @classmethod
    def tree_unflatten(cls, degree, ch):
        return cls(ch[0], ch[1], ch[2], ch[3], degree)

    def apply(self, v):
        # Chebyshev iteration (Saad, Alg. 12.1) solving D⁻¹A z = D⁻¹ v, z₀=0.
        theta = 0.5 * (self.lmax + self.lmin)
        delta = 0.5 * (self.lmax - self.lmin)
        sigma1 = theta / delta
        s = lambda z: self.inv_diag * self.op.apply(z)
        rhs = self.inv_diag * v
        rho = 1.0 / sigma1
        d = rhs / theta
        z = d
        for _ in range(self.degree - 1):
            r = rhs - s(z)
            rho_new = 1.0 / (2.0 * sigma1 - rho)
            d = (rho_new * rho) * d + (2.0 * rho_new / delta) * r
            z = z + d
            rho = rho_new
        return z


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class RBSORPrecond:
    """Red-black SSOR on the 5-point stencil: colored Gauss-Seidel sweeps are
    fully data-parallel (each color updates simultaneously) — the TPU-native
    formulation of the paper's SOR column."""

    coeffs: jax.Array   # (5, nx, ny) stencil
    red: jax.Array      # (nx, ny) float {0,1} checkerboard
    omega: jax.Array
    sweeps: int = 1

    def tree_flatten(self):
        return (self.coeffs, self.red, self.omega), self.sweeps

    @classmethod
    def tree_unflatten(cls, sweeps, ch):
        return cls(ch[0], ch[1], ch[2], sweeps)

    def apply(self, v):
        from repro.kernels import ref

        nx, ny = self.coeffs.shape[-2:]
        f = v.reshape(nx, ny)
        diag = self.coeffs[0]
        z = jnp.zeros_like(f)
        colors_fwd = (self.red, 1.0 - self.red)
        for _ in range(self.sweeps):
            for color in colors_fwd + colors_fwd[::-1]:  # symmetric sweep
                resid = f - ref.stencil5_matvec(self.coeffs, z)
                z = z + self.omega * color * resid / diag
        return z.reshape(-1)


# Host-side preconditioners (CPU paper-parity only). The callback reads a
# module-level slot so the jitted cycle traces ONCE; benchmarks swap the slot
# between systems (documented impurity — never used in the device paths).
_HOST_PRECOND_SLOT: dict = {"fn": None}


def set_host_precond(fn: Optional[Callable[[np.ndarray], np.ndarray]]):
    _HOST_PRECOND_SLOT["fn"] = fn


def _host_apply(v: np.ndarray) -> np.ndarray:
    fn = _HOST_PRECOND_SLOT["fn"]
    return np.asarray(fn(np.asarray(v)), dtype=v.dtype)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class HostPrecond:
    n: int  # static

    def tree_flatten(self):
        return (), self.n

    @classmethod
    def tree_unflatten(cls, n, _):
        return cls(n)

    def apply(self, v):
        return jax.pure_callback(
            _host_apply, jax.ShapeDtypeStruct((self.n,), v.dtype), v,
            vmap_method="sequential")


# ---------------------------------------------------------------- factory


def _power_lmax(op, inv_diag, n, iters=20, seed=0):
    rng = np.random.default_rng(seed)
    v = rng.standard_normal(n)
    v /= np.linalg.norm(v)
    lam = 1.0
    for _ in range(iters):
        w = np.asarray(op.apply(jnp.asarray(inv_diag) * jnp.asarray(v)))
        lam = float(np.linalg.norm(w))
        v = w / max(lam, 1e-30)
    return lam


def make_preconditioner(name: str, problem_op, *, omega: float = 1.0,
                        degree: int = 4, sweeps: int = 1, use_kernel: bool = False):
    """Build a preconditioner pytree for a Stencil5 | DIA operator."""
    from repro.solvers.operator import as_operator

    name = name.lower()
    if name in ("none", "identity"):
        return None

    base = as_operator(problem_op, use_kernel=use_kernel)
    if isinstance(problem_op, Stencil5):
        diag = problem_op.coeffs[Stencil5.C].reshape(-1)
    else:
        diag = problem_op.diagonal()
    inv_diag = 1.0 / diag

    if name == "jacobi":
        return JacobiPrecond(inv_diag)

    if name == "bjacobi":
        if isinstance(problem_op, Stencil5):
            c = np.asarray(problem_op.coeffs)
            nx, ny = c.shape[-2:]
            blocks = np.zeros((nx, ny, ny))
            idx = np.arange(ny)
            blocks[:, idx, idx] = c[0]
            blocks[:, idx[1:], idx[:-1]] = c[3][:, 1:]   # W couples j-1
            blocks[:, idx[:-1], idx[1:]] = c[4][:, :-1]  # E couples j+1
            inv_blocks = np.linalg.inv(blocks)
            return BlockJacobiPrecond(jnp.asarray(inv_blocks))
        dia = problem_op
        n = dia.n
        bs = max(8, int(np.sqrt(n)) // 4)
        nb = n // bs
        dense_blocks = np.zeros((nb, bs, bs))
        data = np.asarray(dia.data)
        for d, off in enumerate(dia.offsets):
            if abs(off) >= bs:
                continue
            for bi in range(nb):
                i0 = bi * bs
                for i in range(max(0, -off), bs - max(0, off)):
                    dense_blocks[bi, i, i + off] = data[d, i0 + i] if off >= 0 else data[d, i0 + i]
        inv_blocks = np.linalg.inv(dense_blocks)
        return BlockJacobiPrecond(jnp.asarray(inv_blocks))

    if name == "rbsor":
        assert isinstance(problem_op, Stencil5), "rbsor is stencil-only"
        nx, ny = problem_op.grid
        ii, jj = jnp.meshgrid(jnp.arange(nx), jnp.arange(ny), indexing="ij")
        red = ((ii + jj) % 2 == 0).astype(jnp.float64)
        return RBSORPrecond(problem_op.coeffs, red, jnp.asarray(omega), sweeps)

    if name == "neumann":
        lmax = _power_lmax(base, np.asarray(inv_diag), base.n)
        w = min(omega, 1.0 / max(lmax, 1e-30))
        return NeumannPrecond(base, inv_diag, jnp.asarray(w), degree)

    if name == "cheby":
        lmax = _power_lmax(base, np.asarray(inv_diag), base.n)
        return ChebyshevPrecond(base, inv_diag, jnp.asarray(lmax / 50.0),
                                jnp.asarray(1.05 * lmax), degree)

    if name == "ilu_host":
        import scipy.sparse as sp
        import scipy.sparse.linalg as spla

        dia = problem_op.to_dia() if isinstance(problem_op, Stencil5) else problem_op
        a = sp.csc_matrix(dia.to_scipy())
        ilu = spla.spilu(a, drop_tol=1e-4, fill_factor=10)
        set_host_precond(ilu.solve)
        return HostPrecond(dia.n)

    raise KeyError(f"unknown preconditioner {name!r}")


def make_preconditioner_batched(name: str, problem_op, *, omega: float = 1.0,
                                degree: int = 4, sweeps: int = 1,
                                use_kernel: bool = False):
    """Stacked preconditioner for the lockstep batched solver.

    `problem_op` is a batched Stencil5 ((B, 5, nx, ny) coeffs) or DIA
    ((B, ndiag, n) data). Builds the per-chain pytrees and stacks every leaf
    on a new leading axis, so the result rides through `jax.vmap(..., 0)`
    next to the batched operator. `ilu_host` cannot batch (module-slot host
    callback) — use the sequential engine for paper-parity ILU runs.
    """
    name = name.lower()
    if name in ("none", "identity"):
        return None
    if name == "ilu_host":
        raise NotImplementedError(
            "ilu_host is a host-callback preconditioner with a single module "
            "slot; it cannot be batched — use engine='sequential'")
    if isinstance(problem_op, Stencil5):
        parts = [make_preconditioner(name, problem_op.take(i), omega=omega,
                                     degree=degree, sweeps=sweeps,
                                     use_kernel=use_kernel)
                 for i in range(problem_op.coeffs.shape[0])]
    elif isinstance(problem_op, DIA):
        parts = [make_preconditioner(name, problem_op.take(i), omega=omega,
                                     degree=degree, sweeps=sweeps,
                                     use_kernel=use_kernel)
                 for i in range(problem_op.data.shape[0])]
    else:
        raise TypeError(f"unsupported batched operator {type(problem_op)}")
    # identical (name, degree, sweeps) → identical treedefs → stackable
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack([jnp.asarray(x) for x in leaves]), *parts)


PRECONDITIONERS = ("none", "jacobi", "bjacobi", "rbsor", "neumann", "cheby", "ilu_host")
