"""Step builders for one (architecture × shape × mesh) cell: the jitted
callable, its abstract inputs (ShapeDtypeStructs — no allocation), and the
in/out sharding trees. Consumed by dryrun.py, train.py and serve.py."""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.distributed.sharding import param_specs
from repro.launch.partition import (batch_specs, cache_specs, logits_spec,
                                    opt_specs_like, to_named, tree_bytes)
from repro.models import api
from repro.train import optim


@dataclasses.dataclass
class CellPlan:
    """Everything needed to lower one cell."""
    step_fn: Callable
    abstract_args: tuple          # ShapeDtypeStruct pytrees
    in_shardings: tuple           # NamedSharding pytrees (same structure)
    out_shardings: Any
    donate_argnums: tuple
    state_bytes: int              # params (+opt +cache) logical bytes
    kind: str


def _named(tree, mesh):
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), tree,
                                  is_leaf=lambda x: isinstance(x, P))


def make_cell_plan(cfg: ModelConfig, shape: ShapeConfig, mesh,
                   optimizer: str = "adamw") -> CellPlan:
    """Build the step + shardings for a cell. Must run under
    ``jax.set_mesh(mesh)`` so logical-axis resolution sees the mesh."""
    b, t = shape.global_batch, shape.seq_len
    params_abs = api.abstract_params(cfg)
    p_specs = param_specs(params_abs)
    batch_abs = api.input_specs(cfg, shape)
    b_specs = batch_specs(batch_abs, b, mesh)

    if shape.kind == "train":
        opt = optim.adamw(3e-4) if optimizer == "adamw" else optim.sgd_fallback()
        opt_abs = jax.eval_shape(opt.init, params_abs)
        o_specs = opt_specs_like(opt_abs, p_specs)
        step_abs = jax.ShapeDtypeStruct((), jnp.int32)
        state_abs = (params_abs, opt_abs, step_abs)
        state_specs = (p_specs, o_specs, P())
        train_step = api.make_train_step(cfg, opt)
        out_specs = (state_specs, {"loss": P()})
        return CellPlan(
            step_fn=train_step,
            abstract_args=(state_abs, batch_abs),
            in_shardings=(_named(state_specs, mesh), _named(b_specs, mesh)),
            out_shardings=_named(out_specs, mesh),
            donate_argnums=(0,),
            state_bytes=tree_bytes(params_abs) + tree_bytes(opt_abs),
            kind="train",
        )

    if shape.kind == "prefill":
        prefill_step = api.make_prefill_step(cfg)
        out_abs = jax.eval_shape(prefill_step, params_abs, batch_abs)
        # out = (logits, cache[, enc_out]) — cache-heuristic specs for the
        # non-logit outputs
        rest_specs = tuple(cache_specs(o, b, mesh) for o in out_abs[1:])
        out_specs = (logits_spec(mesh, b, cfg.vocab),) + rest_specs
        return CellPlan(
            step_fn=prefill_step,
            abstract_args=(params_abs, batch_abs),
            in_shardings=(_named(p_specs, mesh), _named(b_specs, mesh)),
            out_shardings=_named(out_specs, mesh),
            donate_argnums=(),
            state_bytes=tree_bytes(params_abs) + tree_bytes(out_abs[1]),
            kind="prefill",
        )

    # decode: one new token against a seq_len KV cache (serve_step)
    cache_abs = api.abstract_cache(cfg, b, t)
    c_specs = cache_specs(cache_abs, b, mesh)
    decode_step = api.make_decode_step(cfg)

    def serve_step(params, batch, cache):
        logits, new_cache = decode_step(params, batch, cache)
        return logits, new_cache

    # output cache structure can differ from the input one (enc-dec decode
    # unstacks the layer dim) — derive output specs from the actual out tree
    out_abs = jax.eval_shape(serve_step, params_abs, batch_abs, cache_abs)
    out_c_specs = cache_specs(out_abs[1], b, mesh)
    out_specs = (logits_spec(mesh, b, cfg.vocab), out_c_specs)
    return CellPlan(
        step_fn=serve_step,
        abstract_args=(params_abs, batch_abs, cache_abs),
        in_shardings=(_named(p_specs, mesh), _named(b_specs, mesh),
                      _named(c_specs, mesh)),
        out_shardings=_named(out_specs, mesh),
        donate_argnums=(2,),
        state_bytes=tree_bytes(params_abs) + tree_bytes(cache_abs),
        kind="decode",
    )


def lower_cell(cfg: ModelConfig, shape: ShapeConfig, mesh,
               optimizer: str = "adamw"):
    """Lower (no compile) one cell under the mesh. Returns (lowered, plan)."""
    with jax.set_mesh(mesh):
        plan = make_cell_plan(cfg, shape, mesh, optimizer=optimizer)
        jitted = jax.jit(plan.step_fn,
                         in_shardings=plan.in_shardings,
                         out_shardings=plan.out_shardings,
                         donate_argnums=plan.donate_argnums)
        lowered = jitted.lower(*plan.abstract_args)
    return lowered, plan
