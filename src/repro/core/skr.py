"""SKR — the paper's contribution as a production data-generation pipeline.

Figure-1 pipeline, end to end:
  1. sample NO parameters (problem family, batched)       pde/
  2-3. export PDE → linear systems                         pde/
  c.  SORT the systems (Algorithm 1)                       core/sorting.py
  d.  solve sequentially with GCRO-DR recycling            solvers/gcrodr.py
  d'. EXPAND retired anchors into K derived labels each   core/expand.py
      (operator action in solution space, DiffOAS —
      optional `SKRConfig.expand` axis; f' = A u' by one
      batched SpMV, no solver in the loop)
  e.  assemble the (input, solution) dataset              here
      (+ the expanded `DataGenResult.labels` LabelSet
      with per-label provenance when d' is on)

Time-dependent axis (beyond the paper's steady-state scope):
  t1. sample trajectory latents (IC + coefficient drift)  pde/timedep.py
  t2. export each θ-scheme implicit step as a system      pde/timedep.py
  t3. recycle ACROSS TIME STEPS within a trajectory,      core/trajectory.py
      sort trajectories by t=0 features, advance chunks
      of trajectories in lockstep (engine shared below)
  t4. assemble (u_0..u_nt) trajectory datasets for        core/trajectory.py
      autoregressive NO training (operators/fno.py
      rollout path, examples/train_fno_rollout.py)

Production posture:
  * resumable: the generation state (solver recycle space + completed
    solutions) checkpoints atomically every `ckpt_every` systems — a
    preempted datagen job restarts WARM (the recycle space survives).
  * chunk-parallel (App. E.2.2): the sorted sequence splits into contiguous
    chunks with independent recycle carries, one per worker / `data`-axis
    shard; sorting makes chunk-locality free.

Scheduling lives in `core/pipeline.py` (sort → chain partition → lockstep
packing → engine dispatch); this module supplies the steady-state WORK
ADAPTER (`SteadyWork`) and keeps the historical entry points as thin
frontends. Engines (`generate_dataset_chunked(engine=...)`):
  * "sequential" — chunks back-to-back through the per-system solver
    (paper-parity simulation; `workers=1` is bitwise-identical to
    `SKRGenerator.generate`).
  * "batched" — the W chunks advance in LOCKSTEP through a
    `BatchedGCRODRSolver`: at step t one batched device program solves the
    t-th system of EVERY chunk, each chunk keeping its own recycle carry.
    Shorter chunks are padded with zero right-hand sides (0 iterations,
    x = 0, carry untouched; padded slots are never written back and are
    excluded from the per-chunk stats). Host-side row assembly (operator
    gather + stacked preconditioner) is prefetched one row ahead of the
    device solves.
  * "sharded" — the lockstep batch with its chain axis sharded over the
    `data` mesh axis: one SPMD program per row across every device (test
    on CPU with XLA_FLAGS=--xla_force_host_platform_device_count=8; on a
    single device it degenerates to "batched").

Device-resident cycle (the lockstep engines' dispatch shape): each GCRO-DR
cycle of the batched engine is one fused device program — Arnoldi sweep,
stacked Hessenberg LS (solvers/devlinalg.py) and the masked per-chain
control flow — that ends at the small (k+m)² harmonic-Ritz pencils. The
cycle's one blocking host sync fetches the continuation flags and, in the
same `device_get`, those pencils; the host takes their invariant subspaces
with LAPACK (hostlinalg.ritz_*_padded, on chain blocks) and a refresh
program rebuilds the recycle space on the device. No array of length n
crosses the host link between cycles. With one sync at entry and one bulk
fetch at finalize the sync budget is 2 + cycles (`SolveStats.host_syncs`,
asserted by tests/test_transfer_guard.py). The sequential engine runs the
same eigensolve through hostlinalg.py per system.

Observability (repro.obs, opt-in via `obs.enable()`): the pipeline stages
above are telemetry tap points — `core/pipeline.py` records spans for
steps c/d (sample, sort, chain_partition, prepare_row on the prefetch
thread, execute_row, checkpoint), the solvers attach per-cycle
convergence histories to every `SolveStats` (device-buffered rings in the
lockstep engine, drained inside its finalize fetch so the sync budget
above is unchanged — tests/test_transfer_guard.py runs telemetry-on), and
every `solve_batch` dispatch records live/padded row occupancy
(lockstep utilization). Export with `obs.export_chrome_trace()` /
`obs.export_jsonl()`; disabled, all of it compiles out (bitwise-identical
numerics — tests/test_obs.py). See README "Observability".

Precision policy: set `SKRConfig.krylov.inner_dtype="float32"` to run the
inner Krylov machinery of ALL engines in fp32 (the solvers wrap it in an
fp64 iterative-refinement outer loop — see solvers/gcrodr.py). The
operators/RHS of record and the emitted dataset labels stay fp64 at
`cfg.tol`; the recycle carry is stored fp32, halving the datagen
checkpoint footprint (`ckpt_every` snapshots include the carry).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import pipeline
from repro.core.ckpt import NpzCheckpointer
from repro.core.expand import ExpandConfig, Expander, LabelSet
from repro.core.robust import FaultPlan, RetryPolicy, is_healthy
from repro.core.sorting import chain_length
from repro.pde.problems import LinearProblem, ProblemFamily
from repro.solvers.gcrodr import GCRODRSolver
from repro.solvers.operator import (PreconditionedOp, StencilOp, as_operator)
from repro.solvers.precond import (make_preconditioner,
                                   make_preconditioner_batched)
from repro.solvers.types import KrylovConfig, SequenceStats


@dataclasses.dataclass(frozen=True)
class SKRConfig:
    krylov: KrylovConfig = KrylovConfig()
    sort_method: str = "greedy"     # greedy | grouped | hilbert | random | none
    precond: str = "none"
    use_kernel: bool = False
    ckpt_every: int = 0             # 0 = no datagen checkpoints
    record_recycle: bool = False    # keep per-system U snapshots (Table 2 δ)
    # failure containment (core/robust.py): the retry/escalation ladder is a
    # config axis like precision — None disables containment entirely
    # (pre-containment jaxprs; no retries, no lockstep quarantine).
    retry: Optional[RetryPolicy] = RetryPolicy()
    # "flag": ship every label, non-trustworthy ones flagged in
    # DataGenResult.label_ok; "exclude": drop them from the emitted dataset.
    strict_labels: str = "flag"
    # label expansion (core/expand.py): fan each healthy anchor solution
    # into k derived (f' = A u', u') labels. None (the default) is OFF —
    # the pipeline runs bitwise-identical to pre-expansion builds.
    expand: Optional[ExpandConfig] = None

    def __post_init__(self):
        assert self.strict_labels in ("flag", "exclude"), self.strict_labels


@dataclasses.dataclass
class DataGenResult:
    inputs: np.ndarray        # (N, nx, ny) NO input channel
    solutions: np.ndarray     # (N, nx, ny) labels, in ORIGINAL sample order
    order: np.ndarray         # solve order used
    stats: SequenceStats
    sort_seconds: float
    chain_len: float
    recycle_snapshots: list   # optional [(sys_idx, U(n,k)), ...]
    # per-row label trustworthiness (converged at tol, finite, not
    # quarantined) — aligned with `solutions`' first axis; all-True after
    # strict_labels="exclude" filtering. None only from legacy callers.
    label_ok: Optional[np.ndarray] = None
    # expanded labels (core/expand.py) when cfg.expand is set: every
    # healthy anchor's k+1 (f' = A u', u') pairs with per-label provenance
    # (anchor_idx / kind / t). None when expansion is off.
    labels: Optional[LabelSet] = None


def _index_problem(batch: LinearProblem, i: int) -> LinearProblem:
    return jax.tree_util.tree_map(lambda a: a[i], batch)


def _problem_op_of(batch: LinearProblem, i: int):
    from repro.pde.dia import Stencil5

    return Stencil5(batch.op.coeffs[i])


class SteadyWork(pipeline.WorkAdapter):
    """Pipeline work adapter for steady-state linear systems (Figure 1).

    Owns the sampled `LinearProblem` batch and the per-engine solve
    plumbing; `core/pipeline.py` owns sorting, chain partitioning, lockstep
    padding/prefetch, sharding and checkpoint cadence."""

    item_noun = "system"
    ckpt_key = "solutions"   # historical checkpoint field name

    def __init__(self, family: ProblemFamily, cfg: SKRConfig):
        self.family = family
        self.cfg = cfg
        self.batch: Optional[LinearProblem] = None
        self.feats: Optional[np.ndarray] = None
        self.outputs: Optional[np.ndarray] = None
        self.snapshots: list = []
        self.expander: Optional[Expander] = None

    def _make_expander(self) -> Optional[Expander]:
        ecfg = getattr(self.cfg, "expand", None)
        if ecfg is None:
            return None
        return Expander(ecfg, self.family.nx, self.family.ny,
                        use_kernel=self.cfg.use_kernel)

    # ------------------------------------------------------- sampling
    def sample(self, key: jax.Array, num: int) -> np.ndarray:
        self.batch = self.family.sample_batch(key, num)
        self.feats = np.asarray(self.batch.features)
        return self.feats

    # ------------------------------------- sequential (single-chain)
    def alloc_full(self, num: int):
        self.outputs = np.zeros((num, self.family.nx, self.family.ny))
        self.label_ok = np.ones(num, dtype=bool)
        self.expander = self._make_expander()

    def restore_outputs(self, arr: np.ndarray):
        # caveat: label_ok is not checkpointed — items completed BEFORE a
        # resume default to trustworthy (pre-containment checkpoints never
        # shipped unconverged labels, so the default is honest)
        self.outputs = arr

    def _assemble(self, i: int):
        """(op, b) for system `i`, applying any pending one-shot faults.
        Called FRESH per retry attempt (solve_one_guarded's make_problem
        contract) so an injected transient poisons only one assembly."""
        cfg = self.cfg
        prob_op = _problem_op_of(self.batch, i)
        b = np.asarray(self.batch.b[i]).reshape(-1)
        if self.fault is not None:
            b = self.fault.apply_rhs(i, b)
            coeffs = np.asarray(prob_op.coeffs)
            poisoned = self.fault.apply_operator(i, coeffs)
            if poisoned is not coeffs:
                from repro.pde.dia import Stencil5

                prob_op = Stencil5(jnp.asarray(poisoned))
        precond = make_preconditioner(cfg.precond, prob_op,
                                      use_kernel=cfg.use_kernel)
        op = PreconditionedOp(as_operator(prob_op, cfg.use_kernel), precond)
        return op, b

    def _solve_one(self, i: int, solver: GCRODRSolver):
        if self.fault is not None:
            self.fault.apply_carry(i, solver)
        policy = getattr(self.cfg, "retry", None)
        if policy is None:
            return solver.solve(*self._assemble(i))
        from repro.core.robust import solve_one_guarded

        return solve_one_guarded(solver, lambda: self._assemble(i), policy,
                                 label=f"{self.item_noun} {i}")

    def solve_item(self, i: int, solver: GCRODRSolver,
                   stats: SequenceStats) -> list:
        x, st = self._solve_one(i, solver)
        self.outputs[i] = x.reshape(self.family.nx, self.family.ny)
        self.label_ok[i] = is_healthy(st)
        stats.append(st)
        if self.cfg.record_recycle and solver.u_carry is not None:
            self.snapshots.append((i, solver.u_carry.copy()))
        return [st]

    # -------------------------------- label expansion (pipeline hooks)
    def expand_item(self, i: int, solver):
        """Post-solve phase, sequential engine: fan system `i`'s retired
        anchor into k derived labels (only healthy anchors expand)."""
        if self.expander is None or not self.label_ok[i]:
            return
        self.expander.expand_one(self.batch.op.coeffs[i], self.outputs[i],
                                 i, chain=0)

    def expand_row(self, solver, t: int, idx: np.ndarray):
        """Post-solve phase, lockstep engines: ONE expansion wave over the
        retired row — operator stack and solutions are still device-resident
        (`prepare_row`'s upload / the solver's `x_device` stash), so the
        wave adds no H2D traffic and no host syncs."""
        if self.expander is None or self._row_ctx is None:
            return
        coeffs, healthy = self._row_ctx
        self._row_ctx = None
        if solver.x_device is None:
            return
        self.expander.wave(coeffs, solver.x_device,
                           np.where(idx >= 0, idx, 0), healthy)

    # ---- checkpoint extras: expanded labels + provenance ------------
    def ckpt_extra(self) -> dict:
        return self.expander.ckpt_arrays() if self.expander else {}

    def ckpt_required(self) -> tuple:
        return ("exp_f", "exp_u", "exp_anchor", "exp_kind", "exp_t") \
            if self.expander else ()

    def restore_extra(self, state: dict):
        if self.expander is not None and "exp_f" in state:
            self.expander.restore(state)

    def full_result(self, order, stats, sort_s, clen) -> DataGenResult:
        order = np.asarray(order)
        inputs = np.asarray(self.batch.no_input)
        sols, label_ok = self.outputs, self.label_ok
        if getattr(self.cfg, "strict_labels", "flag") == "exclude" \
                and not label_ok.all():
            # arrays are in ORIGINAL sample order here: filter them by the
            # mask; `order` keeps the surviving solves' original indices
            order = order[label_ok[order]]
            inputs, sols = inputs[label_ok], sols[label_ok]
            label_ok = np.ones(len(sols), dtype=bool)
        return DataGenResult(
            inputs=inputs,
            solutions=sols,
            order=order,
            stats=stats,
            sort_seconds=sort_s,
            chain_len=clen,
            recycle_snapshots=self.snapshots,
            label_ok=label_ok,
            labels=self.expander.result() if self.expander else None,
        )

    # ---------------------------------------------- chunked engines
    def solve_chunk_sequential(self, sub) -> DataGenResult:
        """One chunk through the per-system sequential solver (paper-parity
        baseline; bitwise-matches the single-chain generator per chunk)."""
        solver = self.make_solver()
        stats = SequenceStats()
        nx, ny = self.family.nx, self.family.ny
        sols = np.zeros((len(sub), nx, ny))
        expander = self._make_expander()   # chunk-local expansion chain
        for pos, i in enumerate(sub):
            x, st = self._solve_one(int(i), solver)
            sols[pos] = x.reshape(nx, ny)
            stats.append(st)
            if expander is not None and is_healthy(st):
                expander.expand_one(self.batch.op.coeffs[int(i)], sols[pos],
                                    int(i), chain=0)
        return self._chunk_result(sub, sols, stats, expander=expander)

    def begin_lockstep(self, subs):
        from repro.pde.dia import Stencil5

        nx, ny = self.family.nx, self.family.ny
        num = int(np.asarray(self.batch.b).shape[0])
        self._subs = subs
        self._sols = [np.zeros((len(s), nx, ny)) for s in subs]
        self._stats = [SequenceStats() for _ in subs]
        self._all_st5 = Stencil5(jnp.asarray(self.batch.op.coeffs))
        self._b_all = np.asarray(self.batch.b).reshape(num, -1)
        self._requeue = []   # (chain, row, original index) to re-solve
        self.expander = self._make_expander()
        self._row_ctx = None   # (row coeffs device, healthy mask) for waves

    def prepare_row(self, t: int, idx: np.ndarray):
        """HOST-side row assembly (runs on the prefetch thread): gather the
        row's operators, factor the stacked preconditioner, pack the RHS."""
        cfg = self.cfg
        clamped = np.where(idx >= 0, idx, 0)
        obs.hostlink("h2d", clamped)
        st5 = self._all_st5.take(jnp.asarray(clamped))   # (W, 5, nx, ny)
        if self.fault is not None and self.fault.nan_operator:
            from repro.pde.dia import Stencil5

            coeffs, dirty = np.array(st5.coeffs, copy=True), False
            obs.hostlink("d2h", coeffs)
            for w, i in enumerate(idx):
                if i < 0:
                    continue
                poisoned = self.fault.apply_operator(int(i), coeffs[w])
                if poisoned is not coeffs[w]:
                    coeffs[w], dirty = poisoned, True
            if dirty:   # the preconditioner factors the poisoned operator
                obs.hostlink("h2d", coeffs)
                st5 = Stencil5(jnp.asarray(coeffs))
        precond = make_preconditioner_batched(cfg.precond, st5,
                                              use_kernel=cfg.use_kernel)
        ops = PreconditionedOp(StencilOp(st5.coeffs, cfg.use_kernel), precond)
        bvec = self._b_all[clamped].copy()
        bvec[idx < 0] = 0.0                              # padded slots
        if self.fault is not None:
            for w, i in enumerate(idx):
                if i >= 0:
                    bvec[w] = self.fault.apply_rhs(int(i), bvec[w])
        obs.hostlink("h2d", bvec)
        return ops, jnp.asarray(bvec)

    def execute_row(self, solver, t: int, idx: np.ndarray, prepared):
        ops, bvec = prepared
        nx, ny = self.family.nx, self.family.ny
        if self.fault is not None:
            for w, i in enumerate(idx):
                if i >= 0:
                    self.fault.apply_carry(int(i), solver, chain=w)
        xs, st_list = solver.solve_batch(ops, bvec, padded_rows=idx < 0)
        healthy = np.zeros(len(idx), dtype=bool)
        for w, i in enumerate(idx):
            if i < 0:
                continue                                 # padding row
            self._sols[w][t] = xs[w].reshape(nx, ny)
            self._stats[w].append(st_list[w])
            healthy[w] = is_healthy(st_list[w])
            # any unhealthy solve (quarantined OR plain non-convergence)
            # goes to the requeue — the sequential engine would have walked
            # the ladder for it, so the lockstep engine must too
            if getattr(self.cfg, "retry", None) is not None \
                    and not is_healthy(st_list[w]):
                self._requeue.append((w, t, int(i)))
        if self.expander is not None:
            # stash for the pipeline's expand_row phase: the row's operator
            # stack (already device-resident from prepare_row) + health mask
            self._row_ctx = (ops.base.coeffs, healthy)

    def requeue_quarantined(self):
        """Containment requeue: systems the lockstep engine quarantined
        mid-dispatch are re-solved on a FRESH sequential chain through the
        escalation ladder, the in-dispatch attempt counting as attempt 0 —
        so the ladder walk (and `escalation_path`) matches what the
        sequential engine would have taken under the same fault."""
        if not self._requeue:
            return
        from repro.core.robust import solve_one_guarded

        policy = getattr(self.cfg, "retry", None) or RetryPolicy()
        nx, ny = self.family.nx, self.family.ny
        solver = self.make_solver()
        for w, t, i in self._requeue:
            solver.u_carry = None    # cold per system: no cross-requeue state
            # chain w's stats hold exactly one (non-padded) record per row,
            # so per_system[t] IS row t's in-dispatch attempt
            x, st = solve_one_guarded(
                solver, lambda i=i: self._assemble(i), policy,
                failed_stats=self._stats[w].per_system[t],
                label=f"{self.item_noun} {i}")
            self._sols[w][t] = np.asarray(x).reshape(nx, ny)
            self._stats[w].per_system[t] = st
            if self.expander is not None and is_healthy(st):
                # the in-dispatch attempt was unhealthy, so the wave masked
                # this anchor out; the recovered solve expands here instead
                self.expander.drop_anchor(i)
                self.expander.expand_one(self.batch.op.coeffs[i],
                                         self._sols[w][t], i, chain=w)
        obs.counter_add("health.requeued", len(self._requeue))
        self._requeue = []

    def chunk_result(self, w: int) -> DataGenResult:
        return self._chunk_result(self._subs[w], self._sols[w],
                                  self._stats[w], expander=self.expander,
                                  chain=w)

    def _chunk_result(self, sub, sols, stats, expander=None,
                      chain=None) -> DataGenResult:
        sub = np.asarray(sub, dtype=np.int64)
        label_ok = np.array([is_healthy(s) for s in stats.solved],
                            dtype=bool) if len(stats.solved) == len(sub) \
            else np.ones(len(sub), dtype=bool)
        if getattr(self.cfg, "strict_labels", "flag") == "exclude" \
                and not label_ok.all():
            sub, sols = sub[label_ok], sols[label_ok]
            label_ok = np.ones(len(sub), dtype=bool)
        return DataGenResult(
            inputs=np.asarray(self.batch.no_input)[sub],
            solutions=sols,
            order=sub,
            stats=stats,
            sort_seconds=0.0,
            chain_len=chain_length(self.feats, sub),
            recycle_snapshots=[],
            label_ok=label_ok,
            labels=expander.result(chain=chain) if expander else None,
        )


class SteadyStream(SteadyWork):
    """Streaming work adapter for steady systems (core/serve.py): the
    scheduler dispatches WAVES — one system per occupied slot — instead of
    pre-packed lockstep rows. Row assembly is `prepare_row` verbatim (the
    wave's slot→item map plays the row index), so the streamed solve per
    item is the same device program as the offline lockstep path. Every
    live slot's item finishes in one dispatch (`done` all-live).

    Streaming v1 posture: solver-level containment stays armed (quarantine,
    divergence guards via `cfg.retry`), but the offline requeue ladder does
    not run — an unhealthy solve flags `label_ok[i]` False and the stream
    moves on. Results land per ITEM (`outputs[i]`), not per chain."""

    stream_prefetchable = True   # assembly is item-pure: safe to run ahead

    def begin_stream(self, slots: int):
        from repro.pde.dia import Stencil5

        nx, ny = self.family.nx, self.family.ny
        num = int(np.asarray(self.batch.b).shape[0])
        self._all_st5 = Stencil5(jnp.asarray(self.batch.op.coeffs))
        self._b_all = np.asarray(self.batch.b).reshape(num, -1)
        self.outputs = np.zeros((num, nx, ny))
        self.label_ok = np.zeros(num, dtype=bool)
        self.item_iters = np.zeros(num, dtype=np.int64)
        self.stats = SequenceStats()

    def start_item(self, w: int, i: int):
        """Steady items carry no per-slot state — the wave assembly reads
        everything from the sampled batch."""

    def assemble(self, slot_items: np.ndarray):
        return self.prepare_row(0, np.asarray(slot_items, dtype=np.int64))

    def apply(self, solver, slot_items: np.ndarray, prepared) -> np.ndarray:
        ops, bvec = prepared
        nx, ny = self.family.nx, self.family.ny
        xs, st_list = solver.solve_batch(ops, bvec,
                                         padded_rows=slot_items < 0)
        done = np.zeros(len(slot_items), dtype=bool)
        for w, i in enumerate(slot_items):
            if i < 0:
                continue
            i = int(i)
            self.outputs[i] = xs[w].reshape(nx, ny)
            self.label_ok[i] = is_healthy(st_list[w])
            self.item_iters[i] = st_list[w].iterations
            self.stats.append(st_list[w])
            done[w] = True
        return done


class SKRGenerator:
    """Resumable SKR data generator over one problem family (a thin
    frontend over `core/pipeline.run_resumable`)."""

    def __init__(self, family: ProblemFamily, cfg: SKRConfig,
                 ckpt_dir: Optional[str] = None):
        self.family = family
        self.cfg = cfg
        self.ckpt_dir = ckpt_dir
        self._ckpt = NpzCheckpointer(ckpt_dir, "datagen_state.npz")

    def generate(self, key: jax.Array, num: int,
                 progress_cb: Optional[Callable[[int, int], None]] = None,
                 fail_at: Optional[int] = None,
                 fault: Optional[FaultPlan] = None,
                 mismatch: str = "rotate") -> DataGenResult:
        """Generate `num` (input, solution) pairs.

        fail_at: injection hook for the fault-tolerance tests — raises after
        that many systems (simulating preemption); a rerun resumes from the
        checkpoint, recycle space intact.
        fault: full seeded `FaultPlan` (chaos tests) — NaN poisoning of
        chosen systems' RHS/operator/carry, preemption with optional
        checkpoint corruption; see core/robust.py.
        mismatch: policy when a loaded checkpoint belongs to a run of a
        different size — see `pipeline.run_resumable`.
        """
        work = SteadyWork(self.family, self.cfg)
        return pipeline.run_resumable(work, key, num, ckpt=self._ckpt,
                                      ckpt_every=self.cfg.ckpt_every,
                                      progress_cb=progress_cb,
                                      fail_at=fail_at, fault=fault,
                                      mismatch=mismatch)


def generate_dataset(family: ProblemFamily, key: jax.Array, num: int,
                     cfg: SKRConfig, ckpt_dir: Optional[str] = None,
                     **kw) -> DataGenResult:
    return SKRGenerator(family, cfg, ckpt_dir).generate(key, num, **kw)


def generate_dataset_baseline(family: ProblemFamily, key: jax.Array, num: int,
                              krylov: KrylovConfig, precond: str = "none") -> DataGenResult:
    """GMRES baseline (paper's comparison): identical pipeline, k=0, no sort."""
    cfg = SKRConfig(
        krylov=dataclasses.replace(krylov, k=0),
        sort_method="none",
        precond=precond,
    )
    return SKRGenerator(family, cfg).generate(key, num)


def generate_dataset_chunked(family: ProblemFamily, key: jax.Array, num: int,
                             cfg: SKRConfig, workers: int = 8,
                             engine: str = "batched",
                             fault: Optional[FaultPlan] = None,
                             ) -> list[DataGenResult]:
    """App. E.2.2 task decomposition: sort once, split the sorted order into
    `workers` contiguous chunks, each chunk gets its OWN recycle carry.

    engine="batched" (default) advances all chunks concurrently through the
    lockstep `BatchedGCRODRSolver`; engine="sharded" additionally shards the
    chunk-chain axis over the `data` mesh (all available devices);
    engine="sequential" is the per-system loop (chunks back-to-back — the
    paper-parity simulation). `workers=1` always uses the sequential path:
    it is bitwise-identical to `SKRGenerator.generate`. Configs the lockstep
    engine cannot batch (`ilu_host`, `ritz_refresh="final"`) auto-route to
    the sequential path.
    """
    work = SteadyWork(family, cfg)
    work.fault = fault
    return pipeline.run_chunked(work, key, num, workers, engine)
