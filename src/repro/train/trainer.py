"""Fault-tolerant training loop for the neural operators that consume the
datagen system's labels (operators/: FNO, DeepONet).

The caller supplies `loss_fn(params, batch)`. Behaviors:

  * periodic atomic checkpoints (CheckpointManager) + warm resume — a
    preempted job restarts at the last step with optimizer state intact;
  * fault injection (`fail_at`) for the restart tests;
  * microbatch gradient accumulation: the batch's leading dim splits into
    `micro_batches` equal slices whose gradients are averaged;
  * mesh-aware: pass a mesh and the jitted step traces under it (the FNO
    shards its batch axis with `shard_act`); pass mesh=None for
    single-device CPU runs.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.train.checkpoint import CheckpointManager
from repro.train.optim import Optimizer, adamw


@dataclasses.dataclass
class TrainerConfig:
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    keep: int = 3
    log_every: int = 10
    micro_batches: int = 1


class Trainer:
    def __init__(self, loss_fn: Callable, params, optimizer: Optimizer = None,
                 cfg: TrainerConfig = TrainerConfig(), mesh=None):
        self.loss_fn = loss_fn
        self.optimizer = optimizer or adamw(3e-4)
        self.cfg = cfg
        self.mesh = mesh
        self.ckpt = (CheckpointManager(cfg.ckpt_dir, keep=cfg.keep)
                     if cfg.ckpt_dir else None)

        opt_state = self.optimizer.init(params)
        self.state = {"params": params, "opt": opt_state,
                      "step": jnp.zeros((), jnp.int32)}
        self.history: list = []
        self._step_fn = self._build_step()

    # ----------------------------------------------------------- step fn
    def _build_step(self):
        cfg = self.cfg
        opt = self.optimizer
        loss_fn = self.loss_fn
        nmicro = max(cfg.micro_batches, 1)

        def step(state, batch):
            params = state["params"]

            if nmicro == 1:
                loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            else:
                # microbatch accumulation: batch leading dim splits evenly
                def micro(i, carry):
                    tot_loss, tot_grads = carry
                    mb = jax.tree_util.tree_map(
                        lambda a: jax.lax.dynamic_slice_in_dim(
                            a, i * (a.shape[0] // nmicro),
                            a.shape[0] // nmicro), batch)
                    l, g = jax.value_and_grad(loss_fn)(params, mb)
                    return (tot_loss + l,
                            jax.tree_util.tree_map(jnp.add, tot_grads, g))

                zeros = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, p.dtype), params)
                loss, grads = jax.lax.fori_loop(
                    0, nmicro, micro, (jnp.zeros(()), zeros))
                loss = loss / nmicro
                grads = jax.tree_util.tree_map(lambda g: g / nmicro, grads)

            new_state = dict(state)
            updates, new_opt = opt.update(grads, state["opt"], params)
            new_state["params"] = jax.tree_util.tree_map(
                lambda p, u: p + u, params, updates)
            new_state["opt"] = new_opt
            new_state["step"] = state["step"] + 1
            return new_state, {"loss": loss}

        if self.mesh is not None:
            with jax.set_mesh(self.mesh):
                return jax.jit(step, donate_argnums=0)
        return jax.jit(step, donate_argnums=0)

    # ------------------------------------------------------------ resume
    def maybe_resume(self) -> Optional[int]:
        if self.ckpt is None or self.ckpt.latest_step() is None:
            return None
        self.state, step = self.ckpt.restore(self.state)
        return step

    # -------------------------------------------------------------- run
    def run(self, batches, num_steps: int, fail_at: Optional[int] = None,
            log: Callable = print):
        """batches: iterable/callable yielding batch pytrees."""
        cfg = self.cfg
        get = batches if callable(batches) else (lambda i, it=iter(batches):
                                                 next(it))
        start = int(self.state["step"])
        t0 = time.perf_counter()
        for i in range(start, num_steps):
            if fail_at is not None and i >= fail_at:
                raise RuntimeError(f"injected fault at step {i}")
            batch = get(i)
            if self.mesh is not None:
                with jax.set_mesh(self.mesh):
                    self.state, metrics = self._step_fn(self.state, batch)
            else:
                self.state, metrics = self._step_fn(self.state, batch)
            loss = float(metrics["loss"])
            self.history.append(loss)
            if cfg.log_every and (i + 1) % cfg.log_every == 0:
                dt = time.perf_counter() - t0
                log(f"step {i + 1:5d}  loss {loss:.4f}  "
                    f"{(i + 1 - start) / dt:.2f} steps/s")
            if self.ckpt and cfg.ckpt_every and (i + 1) % cfg.ckpt_every == 0:
                self.ckpt.save(i + 1, self.state)
        if self.ckpt:
            self.ckpt.save(num_steps, self.state)
        return self.state, self.history
