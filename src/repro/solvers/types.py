"""Shared solver types and stats (the paper's two metrics: wall time and
iteration count, tracked per system and per sequence)."""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class SolveStats:
    iterations: int = 0       # Krylov (Arnoldi) steps — the paper's "iter"
    matvecs: int = 0          # total operator applications (incl. recycle QR)
    cycles: int = 0           # restart cycles
    converged: bool = False
    rel_residual: float = np.inf
    wall_time_s: float = 0.0
    breakdown: bool = False
    # mixed-precision accounting (inner_dtype="float32" runs only):
    outer_refinements: int = 0  # fp64 iterative-refinement passes taken
    fp64_fallback: bool = False  # fp32 cycles stagnated → finished in fp64
    # lockstep-engine padding accounting: True marks a zero-RHS padding row
    # (shorter chunk / sharding fill / phase-masked finished chain) — it
    # costs nothing (0 iterations, wall_time_s = 0.0) and is EXCLUDED from
    # SequenceStats aggregates so iteration/time totals compare cleanly
    # across engines
    padded: bool = False
    # adaptive-Δt accounting: True marks a solve whose step the error
    # controller REJECTED — real work (kept in every aggregate; the cycles
    # also updated the recycle carry, which is what makes the retry cheap),
    # flagged so accepted-step efficiency can be derived
    rejected: bool = False
    # dispatch-overhead accounting: every host↔device boundary the solver
    # crossed for THIS system. `host_syncs` counts blocking device→host
    # fetches (`device_get` / `np.asarray` on a device array / `float(...)`
    # of a device scalar); `dispatches` counts jitted device programs
    # launched. Lockstep engines report the SHARED batch totals on every
    # non-padded chain (like wall_time_s) — the per-cycle sync budget is
    # the claim the trajectory_recycle benchmark tracks.
    host_syncs: int = 0
    dispatches: int = 0
    # failure-containment accounting (core/robust.py): `retries` counts
    # escalation-ladder attempts taken before this record's solve settled;
    # `escalation_path` names the rungs, in order (e.g. ("drop_carry",
    # "grow_m")); `quarantined=True` marks a solve whose ladder was
    # exhausted without a converged finite solution — the label is NOT
    # trustworthy (strict_labels decides whether it ships flagged or is
    # excluded). The lockstep engine also sets `quarantined` on chains its
    # in-dispatch divergence guard masked out mid-solve; the pipeline then
    # requeues those systems and REPLACES the record.
    retries: int = 0
    quarantined: bool = False
    escalation_path: tuple = ()
    # convergence telemetry (observability runs only): a
    # `repro.obs.KrylovTelemetry` with this system's per-cycle residual /
    # stall / deflation-dimension history. None whenever `repro.obs` is
    # disabled — typed as object so the stats layer stays import-free of
    # the obs package.
    telemetry: Optional[object] = None

    def merge_inner(self, other: "SolveStats"):
        """Fold an inner (correction-solve) pass into this outer record."""
        self.iterations += other.iterations
        self.matvecs += other.matvecs
        self.cycles += other.cycles
        self.host_syncs += other.host_syncs
        self.dispatches += other.dispatches


@dataclasses.dataclass
class SequenceStats:
    """Aggregates over a sorted sequence of systems (one dataset).

    Zero-RHS padding rows emitted by the lockstep engines (`padded=True`)
    are kept in `per_system` for auditability but excluded from every
    aggregate — a padded slot solved nothing, so counting it would skew
    per-system means when comparing engines with different padding."""

    per_system: List[SolveStats] = dataclasses.field(default_factory=list)

    def append(self, s: SolveStats):
        self.per_system.append(s)

    @property
    def solved(self) -> List[SolveStats]:
        """Real (non-padding) solves — the aggregation population."""
        return [s for s in self.per_system if not s.padded]

    @property
    def num(self) -> int:
        return len(self.solved)

    @property
    def num_padded(self) -> int:
        return len(self.per_system) - self.num

    @property
    def total_iterations(self) -> int:
        return int(sum(s.iterations for s in self.solved))

    @property
    def mean_iterations(self) -> float:
        return self.total_iterations / max(1, self.num)

    @property
    def total_time_s(self) -> float:
        return float(sum(s.wall_time_s for s in self.solved))

    @property
    def mean_time_s(self) -> float:
        return self.total_time_s / max(1, self.num)

    @property
    def num_converged(self) -> int:
        return int(sum(s.converged for s in self.solved))

    @property
    def num_hit_maxiter(self) -> int:
        return self.num - self.num_converged

    @property
    def num_rejected(self) -> int:
        """Adaptive-Δt solves the error controller rejected (real work,
        included in iteration/time totals)."""
        return int(sum(s.rejected for s in self.solved))

    @property
    def total_outer_refinements(self) -> int:
        """Mixed-precision fp64 refinement passes, REAL solves only — a
        padded row never runs an outer pass, and the engines guarantee it
        (padding is excluded from the refinement loop), so excluding
        padded rows here cannot double-count."""
        return int(sum(s.outer_refinements for s in self.solved))

    @property
    def num_fp64_fallback(self) -> int:
        """Real solves that fell back to fp64 correction cycles."""
        return int(sum(s.fp64_fallback for s in self.solved))

    @property
    def total_host_syncs(self) -> int:
        """Blocking device→host fetches across the sequence (lockstep
        chains share each batch's count, so this over-counts shared syncs
        by the chain multiplicity — divide by chains-per-batch for the
        per-dispatch-stream number, or read `mean_host_syncs`)."""
        return int(sum(s.host_syncs for s in self.solved))

    @property
    def mean_host_syncs(self) -> float:
        return self.total_host_syncs / max(1, self.num)

    @property
    def total_dispatches(self) -> int:
        return int(sum(s.dispatches for s in self.solved))

    # ------------------------------------------------ health aggregates
    @property
    def num_quarantined(self) -> int:
        return int(sum(s.quarantined for s in self.solved))

    @property
    def num_retried(self) -> int:
        """Solves that walked at least one escalation-ladder rung."""
        return int(sum(s.retries > 0 for s in self.solved))

    @property
    def total_retries(self) -> int:
        return int(sum(s.retries for s in self.solved))

    @property
    def num_recovered(self) -> int:
        """Retried solves that still converged — the ladder paid off."""
        return int(sum(s.retries > 0 and s.converged for s in self.solved))

    @property
    def label_quality(self) -> float:
        """Fraction of real solves whose label is trustworthy (converged,
        finite residual, not quarantined) — the signal `strict_labels`
        acts on and the obs layer exports as a gauge."""
        good = sum(s.converged and not s.quarantined
                   and np.isfinite(s.rel_residual) for s in self.solved)
        return good / max(1, self.num)

    def escalation_counts(self) -> dict:
        """How often each ladder rung was taken across the sequence."""
        out: dict = {}
        for s in self.solved:
            for rung in s.escalation_path:
                out[rung] = out.get(rung, 0) + 1
        return out

    @property
    def utilization(self) -> float:
        """Live fraction of all lockstep rows this sequence dispatched
        (1.0 for engines that never pad). The per-sequence twin of
        `obs.Registry.utilization()` — derivable from stats alone, so the
        regression gate can enforce a floor without observability on."""
        total = len(self.per_system)
        return self.num / total if total > 0 else 1.0

    def summary(self) -> dict:
        out = {
            "num": self.num,
            "mean_iterations": self.mean_iterations,
            "mean_time_s": self.mean_time_s,
            "total_time_s": self.total_time_s,
            "converged": self.num_converged,
            "hit_maxiter": self.num_hit_maxiter,
            "padded": self.num_padded,
            "rejected": self.num_rejected,
            "outer_refinements": self.total_outer_refinements,
            "fp64_fallback": self.num_fp64_fallback,
            "host_syncs": self.total_host_syncs,
            "mean_host_syncs": self.mean_host_syncs,
            "dispatches": self.total_dispatches,
            "utilization": self.utilization,
            # containment surfacing (core/robust.py): retry/quarantine
            # counts and the per-rung escalation tally, always present so
            # consumers need not special-case fault-free runs
            "health": {
                "healthy": int(sum(not s.quarantined and s.retries == 0
                                   for s in self.solved)),
                "recovered": self.num_recovered,
                "quarantined": self.num_quarantined,
                "failed": int(sum(
                    s.quarantined and not np.isfinite(s.rel_residual)
                    for s in self.solved)),
                "retries": self.total_retries,
                "escalations": self.escalation_counts(),
                "label_quality": self.label_quality,
            },
        }
        # merge the live telemetry registry (occupancy, lockstep and
        # host-link counters) when observability is on; a late import keeps
        # the stats layer usable without the obs package on the path
        from repro import obs
        if obs.enabled():
            out["obs"] = obs.summary()
        return out


@dataclasses.dataclass(frozen=True)
class KrylovConfig:
    """Shared GMRES / GCRO-DR configuration.

    m        : max Krylov subspace per cycle (GMRES restart length; GCRO-DR
               uses k recycled + (m-k) new directions — same peak memory)
    m_max    : restart-growth cap for plain GMRES (k=0): when a cycle's
               residual reduction stalls (restarted GMRES on indefinite
               operators, e.g. Helmholtz, can stagnate at any fixed m), the
               restart length doubles up to min(m_max, n). 0 = auto
               (8·m); set m_max = m to pin the classic fixed-restart method.
    k        : recycled-subspace dimension (GCRO-DR only; k=0 ≡ GMRES)
    tol      : relative residual tolerance (PETSc rtol semantics)
    maxiter  : cap on total Krylov iterations per system
    orthog   : "cgs2" (TPU-native fused two-pass classical GS, DESIGN §4.4)
               | "mgs" (paper-faithful modified GS)
    ritz_refresh : "cycle" — recompute the harmonic-Ritz recycle space every
               deflated cycle (paper-faithful GCRO-DR, Alg. 2 l.29-33);
               "final" — only once per system, from its last cycle (beyond-
               paper: drops the per-cycle O(m³) host eig + 2 device round
               trips; EXPERIMENTS.md §Perf iter 4)

    Precision policy (the mixed-precision axis; see README "Precision
    policy"):

    inner_dtype : "float64" (paper-parity default — every Arnoldi cycle,
               preconditioner apply and recycle-space update runs in fp64,
               the exact historical path) | "float32" — the inner Krylov
               machinery runs in fp32 while the operator/RHS of record stay
               fp64: an fp64 outer iterative-refinement loop downcasts the
               current TRUE residual, solves the correction system A·d = r
               in fp32 to `inner_tol`, accumulates x += d in fp64 and
               recomputes the true fp64 residual until `tol` (classic
               inexact-Krylov/IR; the recycled U_k only seeds the search
               space, so accuracy is owned by the outer loop and dataset
               labels stay at fp64 tolerance).
    inner_tol : relative residual reduction target of ONE fp32 correction
               solve (per outer pass). The outer residual contracts by
               ~max(inner_tol, κ·eps_f32) per pass.
    ir_max_outer : cap on fp32 refinement passes per system; exceeded (or a
               pass reduces the residual by < 2×) → the solver falls back to
               fp64 correction cycles, guarding against fp32 stagnation.
    cgs2_acc : "native" — CGS2 accumulates h in the basis dtype (fp32 inner
               cycles accumulate in fp32) | "float64" — fp32 storage with
               fp64 accumulation in the fused orthogonalization (robustness
               knob for ill-scaled bases).
    """

    m: int = 40
    k: int = 15
    tol: float = 1e-8
    maxiter: int = 10_000
    orthog: str = "cgs2"
    ritz_refresh: str = "cycle"
    m_max: int = 0
    inner_dtype: str = "float64"
    inner_tol: float = 1e-4
    ir_max_outer: int = 10
    cgs2_acc: str = "native"

    def __post_init__(self):
        assert 0 <= self.k < self.m, "need 0 <= k < m"
        assert self.orthog in ("cgs2", "mgs")
        assert self.ritz_refresh in ("cycle", "final")
        assert self.m_max == 0 or self.m_max >= self.m, "need m_max >= m"
        assert self.inner_dtype in ("float64", "float32")
        assert 0.0 < self.inner_tol < 1.0, "inner_tol is a relative reduction"
        assert self.ir_max_outer >= 1
        assert self.cgs2_acc in ("native", "float64")
