"""Sharding for the datagen system and its neural-operator consumer.

Two users live here:

  * the DATAGEN pipeline (core/pipeline.py) — solver-array specs for the
    lockstep batched GCRO-DR engine, resolved against an EXPLICIT 1-D
    `data` mesh (`datagen_mesh`, `ChainSharding`): arrays with a leading
    chain axis (right-hand sides, residuals, per-chain recycle carries
    U_k/C_k, batched operator/preconditioner leaves) shard on "dp";
  * the FNO consumer (operators/fno.py) — `shard_act(x, ("dp", ...))`
    shards the batch axis over the active mesh's "pod"/"data" axes at trace
    time, and is a no-op without a mesh (single-device CPU runs).

Logical axes: "dp" (batch → ("pod", "data") where those axes exist) and
None (replicated).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


def _active_mesh_axes() -> Tuple[str, ...]:
    try:
        mesh = jax.sharding.get_abstract_mesh()
    except Exception:
        return ()
    if mesh is None or getattr(mesh, "empty", False):
        return ()
    return tuple(mesh.axis_names)


def batch_axes() -> Optional[Tuple[str, ...]]:
    names = _active_mesh_axes()
    axes = tuple(a for a in ("pod", "data") if a in names)
    return axes or None


def logical_to_spec(logical: Sequence) -> Optional[P]:
    """Map a tuple of logical axis names to a PartitionSpec under the active
    mesh; returns None when no mesh is active (smoke tests)."""
    names = _active_mesh_axes()
    if not names:
        return None
    out = []
    for ax in logical:
        if ax is None:
            out.append(None)
        elif ax == "dp":
            out.append(batch_axes())
        else:
            raise ValueError(f"unknown logical axis {ax!r}")
    return P(*out)


def shard_act(x: jax.Array, logical: Sequence):
    """with_sharding_constraint under logical names; no-op without a mesh."""
    spec = logical_to_spec(logical)
    if spec is None:
        return x
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        return x  # shapes not compatible with mesh (tiny smoke configs)


# --------------------------------------------------------------------------
# Datagen solver-array sharding: the lockstep batched GCRO-DR engine
# (solvers/batched.py) advances B independent recycle chains; the chains
# never exchange Krylov information, so the leading chain axis is a pure
# data-parallel ("dp") axis. `ChainSharding` is the spec table the solver
# consults: shard the chain axis of every large device array over a 1-D
# `data` mesh, keep everything else (scalars, small host factors) replicated.
# --------------------------------------------------------------------------


def datagen_mesh(max_shards: Optional[int] = None) -> Optional[Mesh]:
    """1-D (data,) mesh over the available devices for chunk-chain sharding.

    Returns None on a single device (the sharded engine then degenerates to
    the plain batched engine — no mesh, no resharding cost). Test sharding
    on CPU with XLA_FLAGS=--xla_force_host_platform_device_count=8."""
    devs = jax.devices()
    n = len(devs) if max_shards is None else min(len(devs), int(max_shards))
    if n <= 1:
        return None
    return Mesh(np.asarray(devs[:n]), ("data",))


class ChainSharding:
    """Solver-array specs for lockstep chunk-chain sharding.

    Logical rule (the datagen analogue of the "dp" activation axis): any
    solver array whose LEADING axis is the chain axis — right-hand sides
    (B, n), running solutions/residuals (B, n), Krylov bases (B, m+1, n),
    per-chain recycle carries U_k/C_k (B, n, k), batched operator and
    preconditioner leaves (B, ...) — shards that axis over the `data` mesh
    axis. The stacked least-squares work also carries the chain axis and
    runs inside the same sharded dispatch; only the per-cycle flags and the
    small harmonic-Ritz pencils cross to the host (solvers/hostlinalg.py).

    Arrays whose leading dim does not divide the shard count fall back to
    replicated (the pipeline pads the chain count so the hot arrays always
    divide)."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh

    @property
    def num_shards(self) -> int:
        return int(dict(self.mesh.shape)["data"])

    def spec(self, ndim: int) -> P:
        """PartitionSpec sharding only the leading (chain) axis on "dp"."""
        return P("data", *((None,) * (ndim - 1)))

    def put(self, x):
        """device_put one solver array with the chain axis sharded; arrays
        that cannot shard (scalars, non-divisible leading dim) replicate."""
        x = jnp.asarray(x)
        if x.ndim == 0 or x.shape[0] % self.num_shards != 0:
            spec = P()
        else:
            spec = self.spec(x.ndim)
        return jax.device_put(x, NamedSharding(self.mesh, spec))

    def put_tree(self, tree):
        """Shard every array leaf of an operator/preconditioner pytree
        (batched leaves all carry the leading chain axis)."""
        return jax.tree_util.tree_map(self.put, tree)

    @staticmethod
    def partitioner():
        """Context for the solver's sharded dispatches: the GSPMD
        partitioner. In JAX 0.9 the default one (Shardy) cannot compile an
        fp64 QR or SVD whose batch axis is sharded on a TPU ("A tuple
        parameter that is being flattened shouldn't have frontend
        attributes"), and the stacked eigen/LS cleanup is built on both.
        The setting is part of the jit cache key, so other programs keep
        Shardy."""
        from jax._src import config

        return config.use_shardy_partitioner(False)
