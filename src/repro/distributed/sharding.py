"""Sharding rules: logical activation/parameter axes → mesh PartitionSpecs.

Two consumers live here:

  * the TRAINING/SERVING stack (launch/mesh.py meshes) — logical
    activation/parameter axes resolved against the ACTIVE abstract mesh
    (`logical_to_spec`, `shard_act`, `param_specs`);
  * the DATAGEN pipeline (core/pipeline.py) — solver-array specs for the
    lockstep batched GCRO-DR engine, resolved against an EXPLICIT 1-D
    `data` mesh (`datagen_mesh`, `ChainSharding`): arrays with a leading
    chain axis (right-hand sides, residuals, per-chain recycle carries
    U_k/C_k, batched operator/preconditioner leaves) shard on "dp"; the
    small stacked eigen/LS factors are computed ON-DEVICE per cycle
    (solvers/devlinalg.py) — they are (B, m, m)-small, chain-leading like
    everything else, and never gathered to host between cycles.

Mesh layout (launch/mesh.py):
    single-pod : (data=16, model=16)
    multi-pod  : (pod=2, data=16, model=16)

Logical axes used by the model code:
    "dp"    batch                 → ("pod","data") when a pod axis exists
    "tp"    heads / ffn / experts / vocab → "model"
    "fsdp"  weight-shard axis     → "data" (ZeRO-style parameter sharding)
    "sp"    sequence (long-context KV) → "model" where chosen per-arch
    None    replicated

The model code never names raw mesh axes — it calls shard_act(x, spec) with
logical names, resolved against the active (abstract) mesh at trace time, so
the same model lowers on any mesh (including single-device CPU smoke tests,
where the constraint is a no-op).
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
        elif ax == "tp":
            out.append("model" if "model" in names else None)
        elif ax in ("fsdp", "sp"):
            out.append("data" if "data" in names else None)
        elif ax == "sq":   # sequence-parallel attention (heads don't divide
            out.append("model" if "model" in names else None)  # the TP axis)
        else:
            raise ValueError(f"unknown logical axis {ax!r}")
    return P(*out)


def axis_size(name: str) -> int:
    """Size of a mesh axis under the active (abstract) mesh; 1 if absent."""
    try:
        mesh = jax.sharding.get_abstract_mesh()
    except Exception:
        return 1
    if mesh is None or getattr(mesh, "empty", False):
        return 1
    return dict(mesh.shape).get(name, 1)


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
# Parameter sharding rules: match on parameter-path suffixes.
# Conventions (models/*): weights are dicts; names below are leaf key names.
# Megatron TP + ZeRO/FSDP hybrid:
#   column-parallel (output dim sharded over model):  wq wk wv w_up w_gate
#   row-parallel    (input dim sharded over model):   wo w_down
#   experts:        leading expert dim over model (EP), ffn dim over fsdp
#   embeddings/head: vocab over model
# --------------------------------------------------------------------------

_RULES = [
    # (suffix, spec builder: takes ndim -> logical tuple)
    ("embed", lambda nd: ("tp", None)),
    ("lm_head", lambda nd: (None, "tp")),
    ("w_experts_up", lambda nd: ("tp", None, "fsdp")),
    ("w_experts_gate", lambda nd: ("tp", None, "fsdp")),
    ("w_experts_down", lambda nd: ("tp", "fsdp", None)),
    ("w_router", lambda nd: (None, None)),
    ("wq", lambda nd: ("fsdp", "tp")),
    ("wk", lambda nd: ("fsdp", "tp")),
    ("wv", lambda nd: ("fsdp", "tp")),
    ("wo", lambda nd: ("tp", "fsdp")),
    ("w_gate", lambda nd: ("fsdp", "tp")),
    ("w_up", lambda nd: ("fsdp", "tp")),
    ("w_down", lambda nd: ("tp", "fsdp")),
    # MLA low-rank factors
    ("wq_a", lambda nd: ("fsdp", None)),
    ("wq_b", lambda nd: (None, "tp")),
    ("wkv_a", lambda nd: ("fsdp", None)),
    ("wkv_b", lambda nd: (None, "tp")),
    # recurrent / conv blocks: shard the channel dim over model
    ("w_rec_in", lambda nd: ("fsdp", "tp")),
    ("w_rec_out", lambda nd: ("tp", "fsdp")),
]


def _spec_for_path(path: str, ndim: int) -> Tuple:
    for suffix, fn in _RULES:
        if path.endswith(suffix):
            logical = fn(ndim)
            if len(logical) > ndim:  # stacked-per-layer leading dim
                logical = logical[:ndim]
            if len(logical) < ndim:  # leading scan/stack dims replicate
                logical = (None,) * (ndim - len(logical)) + tuple(logical)
            return tuple(logical)
    return (None,) * ndim  # biases, norms, small tables: replicated


def _axis_sizes() -> dict:
    try:
        mesh = jax.sharding.get_abstract_mesh()
    except Exception:
        return {}
    if mesh is None or getattr(mesh, "empty", False):
        return {}
    return dict(mesh.shape)


def _validate_divisibility(spec: P, shape) -> P:
    """Drop mesh-axis assignments that don't divide the dim size (e.g.
    Whisper's 51865 vocab cannot shard over a 16-wide model axis — such
    tables replicate; Megatron would pad, we keep configs exact)."""
    sizes = _axis_sizes()
    out = []
    for i, entry in enumerate(spec):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        total = 1
        for a in axes:
            total *= sizes.get(a, 1)
        out.append(entry if (i < len(shape) and shape[i] % total == 0)
                   else None)
    return P(*out)


def param_specs(params_shape_tree) -> "jax.tree_util.PyTreeDef":
    """PartitionSpec pytree for a parameter (shape) tree, by path suffix.

    Works on trees of ShapeDtypeStruct (jax.eval_shape output) or arrays.
    Dims whose size doesn't divide the assigned mesh axes fall back to
    replicated (validated against the active abstract mesh).
    """

    def visit(path, leaf):
        keys = [getattr(p, "key", getattr(p, "name", None)) for p in path]
        name = "/".join(str(k) for k in keys if k is not None)
        logical = _spec_for_path(name, len(leaf.shape))
        spec = logical_to_spec(logical)
        if spec is None:
            return P()
        return _validate_divisibility(spec, tuple(leaf.shape))

    return jax.tree_util.tree_map_with_path(visit, params_shape_tree)


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
    axis. The stacked O(m³) eigen/LS cleanup also carries the chain axis
    (solvers/devlinalg.py) and runs inside the same sharded dispatch; only
    the per-cycle continuation flags cross to host.

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
