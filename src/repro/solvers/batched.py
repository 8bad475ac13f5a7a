"""Batched multi-chain GCRO-DR — the lockstep engine behind chunk-parallel
SKR datagen (paper App. E.2.2).

The sequential `GCRODRSolver` advances ONE recycling chain and pays the full
host↔device round-trip + dispatch latency per tiny cycle. This engine
advances B independent chains (one per sorted chunk) SIMULTANEOUSLY, and —
unlike the sequential solver — keeps every length-n array on device: the
Arnoldi sweep, the Hessenberg least-squares and the harmonic-Ritz pencil
are one fused jitted program per cycle (the stacked drivers in
`solvers/devlinalg.py`). Per cycle the host fetches four boolean flags —
`device_get` of (any chain still active, every active chain owns a
recycle space, any chain advanced, restart growth requested) — to pick the
next cycle's static shape, and in the same fetch the small (k+m)² pencils:
it takes their invariant subspaces with LAPACK (`hostlinalg.ritz_*_padded`;
a TPU emulates fp64 with loops of f32 pairs, so a device eigensolve there
is serial latency) and a refresh program rebuilds (C, U) from them on the
device. That is ONE host sync per cycle, and no array of length n crosses
the host link; a full `solve_batch` costs 2 + #cycles syncs (entry flags +
per-cycle flags + finalize fetch), tracked in `SolveStats.host_syncs`.

Each chain keeps its OWN recycle carry U_k — the chains never exchange
Krylov information, exactly the App. E.2.2 task decomposition.

Lockstep semantics (who iterates when):

* Per cycle, every chain runs ≤ m Arnoldi steps in ONE lockstep
  `lax.while_loop` with one step counter (`arnoldi_cycle_batched`, which
  writes each step's new basis rows in place); a chain that hits its own
  tolerance mid-cycle stops there, so per-chain iterates match the
  sequential solver.
* Whole cycles are phase-uniform: a "fresh" (establishing) cycle or a
  "deflated" cycle runs for ALL chains at once. Converged / stalled /
  maxiter chains are masked by passing tol_abs = +inf (their cycle takes 0
  steps, their least-squares solution is forced to y = 0 by the dead-column
  padding and the step mask, and the padded y = 0 update is a no-op on z
  and r).
* Mixed phases resolve conservatively: while ANY active chain still lacks a
  recycle space, the whole batch runs fresh GMRES(m) cycles (chains that
  already own a space simply re-establish it from their newest cycle). With
  healthy warm starts — the steady state of a sorted sequence — every chain
  goes straight to deflated cycles and the per-chain math is identical to
  `GCRODRSolver.solve`, modulo vmapped-matmul float reassociation and the
  eigenvector basis (one stacked LAPACK eig of the padded pencils instead
  of a per-chain one — same invariant subspace, tested in
  test_devlinalg.py).
* Rare rank trouble in the batched warm-start QR drops the carry for the
  affected chains only (the masked `devlinalg.tri_inv_stacked` gate); a
  failed harmonic-Ritz refresh keeps the chain's previous space, as in the
  sequential solver.

Wall-time accounting: the batch advances as one device program, so each
returned `SolveStats.wall_time_s` is the LOCKSTEP latency of the whole
batched solve (identical across chains) — the honest parallel-latency
number App. E.2.2 reports (max over workers == the shared wall clock).
`host_syncs` / `dispatches` follow the same convention: every non-padded
chain reports the shared batch totals.

Sharding (the multi-device axis): the chains are data-parallel — they share
no Krylov information — so the leading chain axis of every large device
array shards cleanly over a 1-D `data` mesh. Construct the solver with a
`distributed.sharding.ChainSharding` and every lockstep dispatch runs as
ONE SPMD program across the mesh: right-hand sides, residuals, bases,
per-chain recycle carries and the small per-chain LS factors live
chain-sharded on device; only the flags and the harmonic-Ritz pencils are
fetched to the host between cycles, as on one device. The caller owns
making the chain count divide the shard count (core/pipeline.py pads with
zero-RHS chains).

Precision policy: `cfg.inner_dtype="float32"` routes `solve_batch` through
`_solve_batch_mixed` — the fp64 outer iterative-refinement loop of the
sequential solver lifted to lockstep granularity. All B chains share each
outer pass (converged chains ride along as zero-RHS padding rows); the
bandwidth-bound inner machinery — lockstep Arnoldi cycles, preconditioner
applies, recycle-space updates, and now also the stacked eigen/LS work —
runs in fp32 at half the HBM traffic, while b, the accumulated x and every
residual of record stay fp64. The per-chain recycle carries are stored
fp32.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels import ops as kops
from repro.kernels import precision
from repro.obs.telemetry import KrylovTelemetry, drain_chain
from repro.solvers import devlinalg as dl
from repro.solvers import hostlinalg as hl
from repro.solvers import gcrodr as _seq
from repro.solvers.arnoldi import arnoldi_cycle_batched
from repro.solvers.gmres import _ir_accum
from repro.solvers.operator import apply_op, cast_operator
from repro.solvers.types import KrylovConfig, SolveStats

_TINY = 1e-300

# --- the sequential solver's fused device steps, vmapped over chains -------
# (called INSIDE the fused cycle programs below — they inline at trace time)
_warm_start_b = jax.vmap(_seq._warm_start)
_fresh_update_b = jax.vmap(_seq._fresh_update)
_fresh_cu_b = jax.vmap(_seq._fresh_cu)
_rhs_and_dnorm_b = jax.vmap(_seq._rhs_and_dnorm)
_deflated_update_b = jax.vmap(_seq._deflated_update)
_whv_blocks_b = jax.vmap(_seq._whv_blocks)
_next_cu_b = jax.vmap(_seq._next_cu)
_apply_cols_b = jax.vmap(jax.vmap(apply_op, in_axes=(None, 1), out_axes=1))


@jax.jit
def _from_z_b(ops, z):
    """Per-chain solution x from the preconditioned iterate z."""
    with jax.named_scope("skr/finalize"):
        return jax.vmap(lambda op, z: op.from_z(z))(ops, z)


@jax.jit
def _ir_accum_b(base, b, x, d):
    """Outer iterative-refinement step, per chain: x += d (upcast) + true
    fp64 residual of the UNpreconditioned base — one dispatch per outer
    pass."""
    with jax.named_scope("skr/update"):
        return jax.vmap(_ir_accum)(base, b, x, d)


@jax.jit
def _downcast_masked(r, need):
    """fp32 correction right-hand sides: live rows downcast, the rest zero
    (a zero row is the lockstep engine's own padding no-op)."""
    with jax.named_scope("skr/update"):
        return jnp.where(jnp.asarray(need)[:, None], r,
                         0.0).astype(jnp.float32)


@jax.jit
def _scaled_cols_b(u, dnorm):
    """Ũ = U / ‖U cols‖ per chain; the dtype-aware clamp keeps masked chains
    (U = 0) NaN-free in BOTH precisions (1e-300 underflows to 0 in fp32) —
    sequential chains never hit it."""
    tiny = jnp.finfo(dnorm.dtype).tiny
    return u / jnp.maximum(dnorm[:, None, :], tiny)


def _mat_post_b(y, inv_r):
    """Per-chain Y R⁻¹ (stacked right-multiply by the small R factor)."""
    return precision.einsum("bnk,bkl->bnl", y, inv_r)


@jax.jit
def _keep_carry(est, u, prev, quar):
    """The device-resident recycle carry after a solve: a chain that owns a
    space this solve takes it, the others keep `prev` bitwise; quarantined
    chains (containment on, else `quar` is None) restart cold."""
    with jax.named_scope("skr/update"):
        if quar is not None:
            est = est & ~quar
        u = _mask(est, u, prev.astype(u.dtype))
        return u if quar is None else _mask(quar, jnp.zeros_like(u), u)


def _sel(mask_np, new, old):
    """Per-chain select: rows of `new` where mask, else `old`."""
    m = jnp.asarray(mask_np).reshape((-1,) + (1,) * (new.ndim - 1))
    return jnp.where(m, new, old)


def _mask(mask, new, old):
    """Traced per-chain select (same as _sel, without the host cast)."""
    return jnp.where(mask.reshape((-1,) + (1,) * (new.ndim - 1)), new, old)


# ---------------------------------------------------------------------------
# the device-resident cycle programs
#
# State lives in a dict of device arrays threaded through three jitted
# programs: _entry (norms + warm start), _fresh_cycle / _deflated_cycle
# (one whole GCRO-DR cycle each), and a finalize fetch. Between cycle
# dispatches the host reads ONLY the 4-flag vector each cycle returns.
#
# Telemetry (repro.obs): the programs take two extra STATIC args —
# `tele_cap` (device ring slots per chain; 0 = off, the default) and
# `tele_delta` (record the δ(Q,C) refresh angle). With tele_cap = 0 no
# buffers enter the state dict and the traced jaxpr is IDENTICAL to the
# pre-telemetry programs — bitwise-identical numerics, zero extra
# dispatches (tests/test_obs.py). With tele_cap > 0 each cycle writes its
# per-chain residual norm / stall flag / recycle dimension (and optionally
# δ) into NaN-initialized (B, tele_cap) rings at slot cycle % tele_cap; the
# host drains them inside the finalize fetch it already pays, so the
# host_syncs = 2 + cycles invariant holds with telemetry ON
# (tests/test_transfer_guard.py runs both ways).
#
# Phase scopes: the programs' work sits under `jax.named_scope` names with
# the root `skr` — `skr/entry`, `skr/arnoldi` (with `skr/arnoldi/matvec`
# and `skr/arnoldi/orthog`, solvers/arnoldi.py), `skr/lstsq`, `skr/update`,
# `skr/ritz`, `skr/finalize` — so that device time in a profile is named
# by solver phase (README "Observability"). They change no equation.
# ---------------------------------------------------------------------------


def _tele_init(s, bsz, dt, *, tele_cap: int, tele_delta: bool):
    """Preallocate the per-chain telemetry rings (traced inside _entry)."""
    s["tcnt"] = jnp.zeros((), jnp.int32)
    s["tlm_res"] = jnp.full((bsz, tele_cap), jnp.nan, dt)
    s["tlm_stall"] = jnp.zeros((bsz, tele_cap), bool)
    s["tlm_dim"] = jnp.zeros((bsz, tele_cap), jnp.int32)
    if tele_delta:
        s["tlm_delta"] = jnp.full((bsz, tele_cap), jnp.nan, dt)


def _tele_record(s, k: int, *, tele_cap: int, tele_delta: bool, delta=None):
    """Write one cycle's telemetry column (traced inside the cycle
    programs — rides in the fused dispatch, no extra launch)."""
    idx = s["tcnt"] % tele_cap
    s["tlm_res"] = s["tlm_res"].at[:, idx].set(s["rnorm"])
    s["tlm_stall"] = s["tlm_stall"].at[:, idx].set(s["stalled"])
    dim = jnp.where(s["est"], k, 0).astype(jnp.int32)
    s["tlm_dim"] = s["tlm_dim"].at[:, idx].set(dim)
    if tele_delta:
        col = (delta if delta is not None
               else jnp.full(s["rnorm"].shape, jnp.nan,
                             s["tlm_delta"].dtype))
        s["tlm_delta"] = s["tlm_delta"].at[:, idx].set(col)
    s["tcnt"] = s["tcnt"] + 1
    return s


def _delta_qc_b(c_old, c_new, ok):
    """Per-chain δ(Q,C) between orthonormal recycle bases before/after the
    harmonic-Ritz refresh: sin θ_max = sqrt(1 − σ_min(C_oldᵀC_new)²) — one
    stacked (k × k) SVD, NaN where the refresh was rejected (the device
    twin of core/metrics.delta_subspace, tested against it)."""
    ov = precision.einsum("bnk,bnl->bkl", c_old, c_new)
    sv = jnp.linalg.svd(ov, compute_uv=False)
    delta = jnp.sqrt(jnp.clip(1.0 - sv[:, -1] ** 2, 0.0, 1.0))
    return jnp.where(ok, delta, jnp.nan)


@partial(jax.jit, static_argnames=("k",))
def _zeros_state(b, *, k: int):
    bsz, n = b.shape
    with jax.named_scope("skr/entry"):
        return (jnp.zeros_like(b), jnp.zeros((bsz, n, k), b.dtype),
                jnp.zeros((bsz, n, k), b.dtype))


def _active_mask(s, aux):
    act = (~aux["zerob"] & ~aux["pad"] & ~s["stalled"]
           & (s["rnorm"] > aux["tol_abs"]) & (s["iters"] < aux["lim"]))
    if "quar" in s:   # containment on: quarantined chains are frozen
        act = act & ~s["quar"]
    return act


def _flags(s, aux, active_prev, step, any_grew):
    """The ONLY per-cycle device→host payload: 4 booleans — 5 with the
    containment layer on, the health flag riding the SAME fetch (the
    host_syncs = 2 + cycles budget is untouched)."""
    nxt = _active_mask(s, aux)
    out = [nxt.any(),                    # keep cycling?
           (s["est"] | ~nxt).all(),      # deflated-ready?
           (step & active_prev).any(),   # anyone advanced?
           any_grew]                     # restart growth (k=0)
    if "quar" in s:
        out.append(s["quar"].any())      # per-batch health flag
    return jnp.stack(out)


def _no_progress(s, step):
    """Count, per chain, the cycles in a row that left its best residual so
    far within 1 % (3 of them mark it stalled). Against the best and not
    the previous cycle: at the fp32 round-off floor the residual wanders,
    and a cycle that only wins back a rise must not restart the count."""
    s["no_prog"] = jnp.where(step & (s["rnorm"] > 0.99 * s["best"]),
                             s["no_prog"] + 1, 0)
    s["best"] = jnp.minimum(s["best"], s["rnorm"])
    return s


def _contain_guard(s, aux, active, z_prev, r_prev, rn_prev, z, r, rn):
    """In-dispatch divergence quarantine (containment on): a chain whose
    updated residual went non-finite or beyond the divergence threshold is
    rolled back to its cycle-start iterate and quarantined — masked to a
    frozen row from the next cycle on (reusing the padding machinery via
    `_active_mask`) instead of poisoning the shared dispatch. Already-
    quarantined chains stay frozen at their last good iterate (a y = 0
    update on a NaN basis would otherwise NaN the held z)."""
    bad = active & (~jnp.isfinite(rn) | (rn > aux["div_abs"]))
    hold = bad | s["quar"]
    z = _mask(hold, z_prev, z)
    r = _mask(hold, r_prev, r)
    rn = jnp.where(hold, rn_prev, rn)
    return z, r, rn, s["quar"] | bad


@partial(jax.jit, static_argnames=("k", "use_carry", "pad_given",
                                   "contain", "tele_cap", "tele_delta",
                                   "stall_break"))
def _entry(ops, b, z0, c0, u0, uc, cok, pad_in, tol, lim, div,
           *, k: int, use_carry: bool, pad_given: bool,
           contain: bool = False, tele_cap: int = 0,
           tele_delta: bool = False, stall_break: bool = False):
    """Norms, padding mask and the warm start (Alg. 2 l.2-7) as one fused
    dispatch. The warm-start rank gate is the batched masked triangular
    inverse (devlinalg.tri_inv_stacked) — no per-chain host loop.

    contain=False (no RetryPolicy) traces the exact pre-containment
    program — no quarantine state enters the dict, no extra flag is
    fetched, bitwise-identical numerics (the tele_cap=0 pattern). With
    contain=True the state gains a per-chain `quar` bool and aux gains the
    absolute divergence threshold `div * ||b||`; a chain whose RHS is
    already non-finite is quarantined at entry (its row never solves).
    stall_break=True adds each chain's best residual so far (`best`), which
    the cycles' stall test compares against."""
    with jax.named_scope("skr/entry"):
        bsz = b.shape[0]
        dt = b.dtype
        bnorm = jnp.linalg.norm(b, axis=1)
        tol_abs = tol * bnorm
        zerob = bnorm == 0.0
        pad = pad_in if pad_given else zerob
        aux = dict(b=b, bnorm=bnorm, tol_abs=tol_abs, zerob=zerob, pad=pad,
                   lim=lim)
        s = dict(z=z0, r=b, rnorm=bnorm, c=c0, u=u0,
                 est=jnp.zeros(bsz, bool), stalled=jnp.zeros(bsz, bool),
                 no_prog=jnp.zeros(bsz, jnp.int32),
                 iters=jnp.zeros(bsz, jnp.int32),
                 matvecs=jnp.zeros(bsz, jnp.int32),
                 cycles=jnp.zeros(bsz, jnp.int32))
        if contain:
            aux["div_abs"] = div * bnorm
            s["quar"] = ~jnp.isfinite(bnorm) & ~pad
        if use_carry and k > 0:
            want = cok & ~zerob & ~pad & (bnorm > tol_abs)
            au = _apply_cols_b(ops, uc)
            q, rr = jnp.linalg.qr(au)
            inv_rr, ok = dl.tri_inv_stacked(rr, want)
            u_new = _mat_post_b(uc, inv_rr)
            z2, r2, rn2 = _warm_start_b(u_new, q, s["z"], s["r"])
            s["z"] = _mask(ok, z2, s["z"])
            s["r"] = _mask(ok, r2, s["r"])
            s["rnorm"] = jnp.where(ok, rn2, s["rnorm"])
            s["c"] = _mask(ok, q, s["c"])
            s["u"] = _mask(ok, u_new, s["u"])
            s["est"] = ok
            s["matvecs"] = jnp.where(want, k, 0).astype(jnp.int32)
        if stall_break:
            s["best"] = s["rnorm"]
        if tele_cap > 0:
            _tele_init(s, bsz, dt, tele_cap=tele_cap, tele_delta=tele_delta)
        f = _flags(s, aux, jnp.zeros(bsz, bool), jnp.zeros(bsz, bool),
                   jnp.zeros((), bool))
        return s, aux, f


@partial(jax.jit, static_argnames=("m", "k", "orthog", "use_kernel",
                                   "h_acc", "stall_break", "can_grow",
                                   "contain", "tele_cap", "tele_delta"))
def _fresh_cycle(ops, s, aux, *, m: int, k: int, orthog: str,
                 use_kernel: bool, h_acc: str, stall_break: bool,
                 can_grow: bool, contain: bool = False,
                 tele_cap: int = 0, tele_delta: bool = False):
    """One lockstep fresh GMRES(m) cycle (Alg. 2 l.9-18) as ONE device
    program: Arnoldi sweep → stacked Hessenberg LS → solution update →
    (k > 0) the harmonic-Ritz pencil of the space establishment.

    Returns (s, flags, pend): for k > 0 `pend` holds what the host
    eigensolve and `_fresh_refresh` need, and the flags hold the
    deflated-ready flag from before the refresh, which the host recomputes
    from `pend["ready"]` (BatchedGCRODRSolver._host_ritz); None for k = 0."""
    bsz, n = s["r"].shape
    dt = s["r"].dtype
    with jax.named_scope("skr/update"):
        active = _active_mask(s, aux)
        eff_tol = jnp.where(active, aux["tol_abs"], jnp.inf)
    with jax.named_scope("skr/arnoldi"):
        empty_c = jnp.zeros((bsz, 0, n), dt)
    cyc = arnoldi_cycle_batched(ops, empty_c, s["r"], eff_tol, m=m,
                                orthog=orthog, use_kernel=use_kernel,
                                h_acc=h_acc)
    with jax.named_scope("skr/lstsq"):
        j = cyc.j_used.astype(jnp.int32)
        step = j > 0
        y = dl.hessenberg_lstsq_stacked(cyc.h, j, s["rnorm"])
    with jax.named_scope("skr/update"):
        rprev = s["rnorm"]
        z, r, rn = _fresh_update_b(ops, aux["b"], s["z"], cyc.v,
                                   y.astype(dt))
        if contain:
            z, r, rn, quar = _contain_guard(s, aux, active, s["z"], s["r"],
                                            rprev, z, r, rn)
            s = dict(s, quar=quar)
        s = dict(s, z=z, r=r, rnorm=rn,
                 iters=s["iters"] + jnp.where(step, j, 0),
                 matvecs=s["matvecs"] + jnp.where(step, j + 1, 0),
                 cycles=s["cycles"] + step.astype(jnp.int32))
        if stall_break:
            s = _no_progress(s, step)
        any_grew = jnp.zeros((), bool)
    if k > 0:
        # establish / re-establish recycle spaces per chain: the pencil
        with jax.named_scope("skr/ritz"):
            pend = dict(a=dl.first_cycle_pencil_stacked(cyc.h, j), h=cyc.h,
                        v=cyc.v, j=j,
                        can=step if not contain else step & ~s["quar"])
    else:
        pend = None
        # adaptive restart growth (see gmres_solve): grow when any chain
        # ran a full cycle and stalled; the host doubles m on the flag
        with jax.named_scope("skr/update"):
            grew = (step & (j == m) & (s["rnorm"] > aux["tol_abs"])
                    & (s["rnorm"] > 0.5 * rprev))
            any_grew = grew.any()
            if can_grow:
                # a longer cycle deserves a fresh shot at making progress
                s["no_prog"] = jnp.where(any_grew, 0, s["no_prog"])
            s["stalled"] = s["stalled"] | (cyc.breakdown & step
                                          & (s["rnorm"] > aux["tol_abs"]))
    with jax.named_scope("skr/update"):
        if stall_break:
            s["stalled"] = s["stalled"] | (s["no_prog"] >= 3)
        if k > 0:
            pend["ready"] = s["est"] | ~_active_mask(s, aux)
        elif tele_cap > 0:
            s = _tele_record(s, k, tele_cap=tele_cap,
                             tele_delta=tele_delta)
        return s, _flags(s, aux, active, step, any_grew), pend


@partial(jax.jit, static_argnames=("k", "tele_cap", "tele_delta"))
def _fresh_refresh(s, v, h, p, q, inv_rr, est_new, *, k: int,
                   tele_cap: int = 0, tele_delta: bool = False):
    """The fresh cycle's last step: the first recycle spaces C = V_{m+1} Q,
    U = V_m P R⁻¹ from the host's P, Q, R⁻¹ for the chains `est_new`; the
    others keep theirs."""
    s = dict(s)
    with jax.named_scope("skr/ritz"):
        c_new, yk = _fresh_cu_b(v, h, p, q)
        u_new = _mat_post_b(yk, inv_rr)
        s["c"] = _mask(est_new, c_new, s["c"])
        s["u"] = _mask(est_new, u_new, s["u"])
        s["est"] = s["est"] | est_new
    if tele_cap > 0:
        # a fresh cycle (re)establishes the space: no before/after pair to
        # compare, so δ is recorded NaN
        with jax.named_scope("skr/update"):
            s = _tele_record(s, k, tele_cap=tele_cap, tele_delta=tele_delta)
    return s


@partial(jax.jit, static_argnames=("mi", "k", "orthog", "use_kernel",
                                   "h_acc", "stall_break", "contain",
                                   "tele_cap", "tele_delta"))
def _deflated_cycle(ops, s, aux, *, mi: int, k: int, orthog: str,
                    use_kernel: bool, h_acc: str, stall_break: bool,
                    contain: bool = False, tele_cap: int = 0,
                    tele_delta: bool = False):
    """One lockstep deflated cycle (Alg. 2 l.19-33) as ONE device program:
    deflated Arnoldi sweep → stacked Ĝ least-squares → solution update →
    the stacked generalized harmonic-Ritz pencil M.

    Returns (s, flags, pend): `pend` holds what the host eigensolve and
    `_deflated_refresh` need. The flags do not depend on the refresh.
    `tele_cap`, `tele_delta`: recorded by `_deflated_refresh`."""
    with jax.named_scope("skr/update"):
        active = _active_mask(s, aux)
        eff_tol = jnp.where(active, aux["tol_abs"], jnp.inf)
    with jax.named_scope("skr/arnoldi"):
        c_rows = jnp.swapaxes(s["c"], 1, 2)
    cyc = arnoldi_cycle_batched(ops, c_rows, s["r"], eff_tol, m=mi,
                                orthog=orthog, use_kernel=use_kernel,
                                h_acc=h_acc)
    with jax.named_scope("skr/lstsq"):
        j = cyc.j_used.astype(jnp.int32)
        step = j > 0
        dt = s["r"].dtype

        ctr, vr, dnorm = _rhs_and_dnorm_b(s["c"], s["u"], cyc.v, s["r"])
        g = dl.assemble_g_stacked(dnorm, cyc.b, cyc.h, j)
        rhs = jnp.concatenate([ctr, vr], axis=1)
        ys = dl.lstsq_stacked(g, rhs)
        # frozen chains (j = 0) still have Cᵀr ≠ 0 — force their update to
        # the padded no-op the host engine produced by skipping them
        ys = jnp.where(step[:, None], ys, 0.0)
        y_k, y_m = ys[:, :k], ys[:, k:]
    with jax.named_scope("skr/update"):
        ut = _scaled_cols_b(s["u"], dnorm)
        rprev = s["rnorm"]
        z, r, rn = _deflated_update_b(ops, aux["b"], s["z"], ut, cyc.v,
                                      y_k.astype(dt), y_m.astype(dt))
        if contain:
            z, r, rn, quar = _contain_guard(s, aux, active, s["z"], s["r"],
                                            rprev, z, r, rn)
            s = dict(s, quar=quar)
        s = dict(s, z=z, r=r, rnorm=rn,
                 iters=s["iters"] + jnp.where(step, j, 0),
                 matvecs=s["matvecs"] + jnp.where(step, j + 1, 0),
                 cycles=s["cycles"] + step.astype(jnp.int32))
        if stall_break:
            s = _no_progress(s, step)
            s["stalled"] = s["stalled"] | (s["no_prog"] >= 3)

    # the stacked generalized harmonic-Ritz pencil of the next spaces
    with jax.named_scope("skr/ritz"):
        cu, cv, vu, vv = _whv_blocks_b(s["c"], ut, cyc.v)
        whv = dl.assemble_whv_stacked(cu, cv, vu, vv, j)
        pend = dict(mm=dl.deflated_pencil_stacked(g, whv), j=j, g=g, ut=ut,
                    v=cyc.v, step=step)
    with jax.named_scope("skr/update"):
        s["stalled"] = s["stalled"] | (cyc.breakdown & step
                                      & (s["rnorm"] > aux["tol_abs"]))
        return s, _flags(s, aux, active, step, jnp.zeros((), bool)), pend


@partial(jax.jit, static_argnames=("k", "contain", "tele_cap",
                                   "tele_delta"))
def _deflated_refresh(s, g, ut, v, step, p, ritz_ok, *, k: int,
                      contain: bool = False, tele_cap: int = 0,
                      tele_delta: bool = False):
    """The deflated cycle's last step: the next recycle spaces C' = Ŵ Q,
    U' = V̂ P R⁻¹ (Alg. 2 l.31-33) from the host's harmonic-Ritz basis P,
    for chains that stepped with ritz_ok; the others keep theirs."""
    s = dict(s)
    with jax.named_scope("skr/ritz"):
        if contain:   # a quarantined chain must not refresh from garbage
            ritz_ok = ritz_ok & ~s["quar"]
        q, inv_rr, ref_ok = dl.refresh_factors(precision.matmul(g, p),
                                                ritz_ok & step)
        c_new, yk = _next_cu_b(ut, v, s["c"], p[:, :k], p[:, k:],
                               q[:, :k], q[:, k:])
        u_new = _mat_post_b(yk, inv_rr)
        c_old = s["c"]
        s["c"] = _mask(ref_ok, c_new, s["c"])
        s["u"] = _mask(ref_ok, u_new, s["u"])
    if tele_cap > 0:
        with jax.named_scope("skr/ritz"):
            delta = (_delta_qc_b(c_old, s["c"], ref_ok) if tele_delta
                     else None)
        with jax.named_scope("skr/update"):
            s = _tele_record(s, k, tele_cap=tele_cap,
                             tele_delta=tele_delta, delta=delta)
    return s


class BatchedGCRODRSolver:
    """B sequence-stateful GCRO-DR chains in lockstep. One instance per
    chunk-decomposed sorted sequence; call `solve_batch` once per lockstep
    "row" of systems (the t-th system of every chunk).

    GMRES is still the k = 0 special case — the batch then runs lockstep
    restarted-GMRES cycles with the same adaptive restart growth as
    `gmres_solve` (triggered when any active chain stalls).

    Per-chain Δt / phase-masked rows (adaptive trajectory datagen): the
    solver is agnostic to WHERE each chain's system came from — the
    trajectory engine assembles per-chain operators A_w = β₀M + γΔt_w L(t_w)
    with every chain at its own time point and step size (one vmapped
    builder), so one `solve_batch` dispatch advances chains at different
    phases. Chains that finished their trajectory arrive as `padded_rows`
    (zero RHS, excluded from solving outright, carry untouched) until the
    whole lockstep row completes.
    """

    def __init__(self, cfg: KrylovConfig, use_kernel: bool = False,
                 stall_break: bool = False, sharding=None, policy=None,
                 device_carry: bool = False):
        if cfg.k > 0 and cfg.ritz_refresh != "cycle":
            raise NotImplementedError(
                "BatchedGCRODRSolver implements the paper-faithful "
                "ritz_refresh='cycle' schedule; 'final' needs per-chain "
                "last-cycle snapshots (use the sequential engine)")
        kops.check_solver_request(cfg, use_kernel)
        self.cfg = cfg
        self.use_kernel = use_kernel
        # policy: optional core.robust.RetryPolicy — arms the in-dispatch
        # containment layer: per-chain quarantine state, the divergence
        # guard in every cycle program, a 5th health flag riding the
        # per-cycle fetch, and carry-write blocking for quarantined chains.
        # None (the default) traces the EXACT pre-containment programs —
        # bitwise-identical numerics, same sync budget. Escalation/retry
        # itself is the pipeline's job (core/robust.solve_one_guarded on
        # the requeued systems); the solver only contains and reports.
        self.policy = policy
        # sharding: optional distributed.sharding.ChainSharding — shards the
        # leading chain axis of every large device array over the `data`
        # mesh axis, turning each lockstep dispatch into one SPMD program
        self.sharding = sharding
        # stall_break: mask out (as stalled) chains whose cycles stop
        # reducing the residual instead of spinning the lockstep to maxiter
        # — set by the mixed-precision outer loop on its inner fp32 solver,
        # where the fp32 round-off floor is an expected exit
        self.stall_break = stall_break
        # device_carry: keep `u_carry` on the device between solves (the
        # finalize fetch leaves it out) — set by the mixed-precision outer
        # loop on its inner fp32 solver, so the carry does not cross the
        # host link at every refinement pass; the outer loop fetches it
        # once per call, with its iterate
        self.device_carry = device_carry
        self.u_carry: np.ndarray | jax.Array | None = None   # (B, n, k)
        self.carry_ok: np.ndarray | None = None  # (B,) bool
        self.systems_solved = 0
        # x_device: the DEVICE-resident (B, n) solution of the most recent
        # solve_batch — the finalize fetch returns numpy, but post-solve
        # device consumers (the label-expansion waves, core/expand.py) read
        # this stash instead of re-uploading x. Same buffer the numpy copy
        # came from, so consuming it is bitwise-equivalent.
        self.x_device = None
        self._inner: BatchedGCRODRSolver | None = None    # fp32 correction
        self._inner64: BatchedGCRODRSolver | None = None  # fp64 fallback
        # the public carry object the fp32 inner solver holds a device copy
        # of (the mixed path skips the upload while `u_carry` is still it)
        self._carry_mirror: np.ndarray | None = None

    def reset(self):
        self.u_carry = None
        self._carry_mirror = None
        self.carry_ok = None
        self.systems_solved = 0
        self.x_device = None
        self._inner = None
        self._inner64 = None

    def swap_slot(self, w: int, carry: np.ndarray | None = None,
                  carry_ok: bool = False):
        """Mid-flight slot swap — the streaming scheduler's refill hook
        (core/serve.py). When chain slot `w` retires and a NEW chain takes
        the slot between dispatches, only the recycle carry is solver
        state: operators and RHS arrive fresh each `solve_batch`, and jit
        caches on shapes, so same-shape new buffer contents never
        recompile. `carry=None` (the fresh-chain default) zeroes the
        slot's carry and clears `carry_ok`; passing an (n, k) `carry`
        adopts it (the scheduler's assignment decided the retiring chain's
        subspace is still relevant). Applies to this solver AND the
        mixed-precision inner/fallback mirrors so a later downcast cannot
        resurrect the retired chain's subspace. Pure host numpy — zero
        device syncs, so the `host_syncs <= 2 + cycles` budget is
        untouched (pinned by tests/test_serve.py under transfer_guard);
        the fp32 inner solver's device carry takes the public carry it
        mirrors, and is fetched only if the public one was replaced."""
        for s in (self, self._inner, self._inner64):
            if s is None or s.u_carry is None:
                continue
            if isinstance(s.u_carry, jax.Array):
                # the mixed path's device-resident inner carry: continue
                # from the public carry it mirrors (swapped just above)
                s.u_carry = (self.u_carry
                             if self.u_carry is self._carry_mirror
                             else np.array(jax.device_get(s.u_carry)))
            if carry is None:
                s.u_carry[w] = 0.0
                ok = False
            else:
                s.u_carry[w] = np.asarray(carry, dtype=s.u_carry.dtype)
                ok = bool(carry_ok)
            if s.carry_ok is not None:
                s.carry_ok[w] = ok

    def _dev(self, x):
        """Place one solver array: chain-sharded over the mesh when a
        ChainSharding is configured, default single-device otherwise."""
        return x if self.sharding is None else self.sharding.put(x)

    # ------------------------------------------------------------------
    def solve_batch(self, ops, b, padded_rows=None):
        """Solve B independent systems, one per chain.

        ops : PreconditionedOp pytree whose EVERY leaf carries a leading
              B axis (batched StencilOp/DIAOp + stacked preconditioner).
        b   : (B, n) right-hand sides. A zero row marks a padded chain
              (shorter chunk): it converges at 0 iterations with x = 0 and
              leaves the chain's recycle carry untouched.
        padded_rows : optional (B,) bool — which rows are PADDING (drive
              `SolveStats.padded` + the zeroed wall time). Defaults to the
              zero-RHS rows; the pipeline passes its own mask so a
              legitimate b = 0 system is not miscounted as padding. A row
              MARKED padded is excluded from solving outright (x = 0,
              carry untouched, zero counts) even if its RHS is nonzero —
              a padding row must never contribute phantom iterations or
              refinement passes to the sequence aggregates.

        Returns (x (B, n) np.ndarray, [SolveStats] * B).
        """
        with obs.span("solve_batch", cat="solver"):
            if self.sharding is None:
                return self._solve_batch(ops, b, padded_rows)
            with self.sharding.partitioner():
                return self._solve_batch(ops, b, padded_rows)

    def _solve_batch(self, ops, b, padded_rows):
        cfg = self.cfg
        if cfg.inner_dtype == "float32":
            return self._solve_batch_mixed(ops, b, padded_rows)
        k = cfg.k
        t0 = time.perf_counter()
        if not isinstance(b, jax.Array):
            obs.hostlink("h2d", b)
        b = self._dev(jnp.asarray(b))
        if self.sharding is not None:
            ops = self.sharding.put_tree(ops)
        bsz, n = b.shape
        dt = b.dtype

        # ---- entry: one fused dispatch (norms + warm start), one sync ----
        # (zeros come from a jitted constant — jnp.zeros OUTSIDE jit moves a
        # scalar host→device, which transfer_guard("disallow") rejects)
        z0, c0, u0 = (self._dev(a) for a in _zeros_state(b, k=k))
        use_carry = k > 0 and self.u_carry is not None
        pad_given = padded_rows is not None
        # containment is STATIC: no policy → the exact pre-containment
        # programs, bitwise-identical
        contain = self.policy is not None
        div = (self.policy.divergence_ratio if contain else 0.0)
        with obs.span("carry_upload", cat="solver"):
            carry = self.u_carry if use_carry else None
            cok_np = (self.carry_ok if use_carry
                      else np.zeros(bsz, bool))
            pad_np = (np.asarray(padded_rows) if pad_given
                      else np.zeros(bsz, bool))
            # 0-d numpy scalars: a bare python scalar counts as an
            # IMPLICIT host→device transfer under
            # jax.transfer_guard("disallow")
            scalars = (np.asarray(cfg.tol, dt),
                       np.asarray(cfg.maxiter, np.int32),
                       np.asarray(div, dt))
            obs.hostlink("h2d",
                         None if isinstance(carry, jax.Array) else carry,
                         cok_np, pad_np, scalars)
            uc = self._dev(jnp.asarray(carry)) if use_carry else u0
            cok = jnp.asarray(cok_np)
            pad_in = jnp.asarray(pad_np)
            tol_d, lim_d, div_d = (jnp.asarray(a) for a in scalars)
        # telemetry config is STATIC: capacity 0 (obs disabled) traces the
        # exact pre-telemetry programs — bitwise-identical, no extra work
        tele_cap = obs.krylov_capacity()
        tele_delta = obs.delta_enabled() and k > 0
        s, aux, f = _entry(ops, b, z0, c0, u0, uc, cok, pad_in,
                           tol_d, lim_d, div_d,
                           k=k, use_carry=use_carry, pad_given=pad_given,
                           contain=contain, tele_cap=tele_cap,
                           tele_delta=tele_delta, stall_break=self.stall_break)
        with obs.span("host_sync", cat="solver", what="entry_flags"):
            fl = jax.device_get(f)
        obs.hostlink("d2h", fl)
        any_active, all_est = bool(fl[0]), bool(fl[1])
        host_syncs, dispatches = 1, 1

        m_fresh = cfg.m  # k=0: grows adaptively, mirroring gmres_solve
        m_cap = min(n, cfg.m_max if cfg.m_max else 8 * cfg.m)

        # ---- the cycle loop: a cycle program, one flag sync, (k > 0) the
        # host eigensolve and a refresh program, each cycle ---------------
        while any_active:
            fresh = k == 0 or not all_est
            with obs.span("cycle_dispatch", cat="solver"):
                if fresh:
                    s, f, pend = _fresh_cycle(
                        ops, s, aux, m=m_fresh, k=k, orthog=cfg.orthog,
                        use_kernel=self.use_kernel, h_acc=cfg.cgs2_acc,
                        stall_break=self.stall_break,
                        can_grow=m_fresh < m_cap, contain=contain,
                        tele_cap=tele_cap, tele_delta=tele_delta)
                else:
                    s, f, pend = _deflated_cycle(
                        ops, s, aux, mi=cfg.m - k, k=k, orthog=cfg.orthog,
                        use_kernel=self.use_kernel, h_acc=cfg.cgs2_acc,
                        stall_break=self.stall_break, contain=contain,
                        tele_cap=tele_cap, tele_delta=tele_delta)
            # the small pencils ride the flag fetch (None for k = 0)
            small = None if pend is None else {
                key: pend[key] for key in
                (("a", "h", "j", "can", "ready") if fresh else ("mm", "j"))}
            with obs.span("host_sync", cat="solver", what="cycle_flags"):
                fl, small = jax.device_get((f, small))
            obs.hostlink("d2h", fl, small)
            any_active, all_est, any_step, any_grew = map(bool, fl[:4])
            if pend is not None:
                with obs.span("cycle_dispatch", cat="solver"):
                    s, ready = self._host_ritz(
                        s, pend, small, fresh, contain=contain,
                        tele_cap=tele_cap, tele_delta=tele_delta)
                if fresh:
                    all_est = ready
                dispatches += 1
            if contain and bool(fl[4]):
                # the health flag rides the SAME fetch: zero extra syncs
                obs.counter_add("health.lockstep_quarantine_flag")
            host_syncs += 1
            dispatches += 1
            if any_grew and m_fresh < m_cap:
                m_fresh = min(2 * m_fresh, m_cap)
            if not any_step:
                break  # every active chain stagnated at 0 steps

        # ---- finalize: one dispatch + one bulk fetch ---------------------
        # the telemetry rings ride IN the same fetch — draining them costs
        # zero additional syncs, preserving host_syncs = 2 + cycles
        x_dev = _from_z_b(ops, s["z"])
        self.x_device = x_dev
        fetch = (x_dev, s["rnorm"], s["iters"], s["matvecs"], s["cycles"],
                 s["stalled"], s["est"],
                 None if self.device_carry else s["u"], aux["bnorm"],
                 aux["zerob"], aux["pad"])
        if contain:
            # the quarantine verdicts ride the EXISTING finalize fetch
            fetch = fetch + (s["quar"],)
        nbase = len(fetch)
        tkeys = ()
        if tele_cap > 0:
            tkeys = (("tlm_res", "tlm_stall", "tlm_dim")
                     + (("tlm_delta",) if tele_delta else ()))
            fetch = fetch + tuple(s[t] for t in tkeys) + (s["tcnt"],)
        with obs.span("host_sync", cat="solver", what="finalize"):
            got = jax.device_get(fetch)
        obs.hostlink("d2h", got)
        (x, rnorm, iters, matvecs, cycles, stalled, established, u_np,
         bnorm, zerob, pad) = got[:11]
        quar = got[11] if contain else np.zeros(bsz, bool)
        tbufs, tcnt = None, 0
        if tele_cap > 0:
            tbufs = dict(zip(tkeys, got[nbase:-1]))
            tcnt = int(got[-1])
        host_syncs += 1
        dispatches += 1
        wall = time.perf_counter() - t0
        converged = zerob | (rnorm <= cfg.tol * bnorm)
        stats = []
        for i in range(bsz):
            stats.append(SolveStats(
                iterations=int(iters[i]),
                matvecs=int(matvecs[i]),
                cycles=int(cycles[i]),
                converged=bool(converged[i]) and not bool(quar[i]),
                # quarantined: the in-dispatch guard froze this chain —
                # the pipeline requeues the system through the escalation
                # ladder (core/robust.py) and replaces this record
                quarantined=bool(quar[i]),
                rel_residual=0.0 if zerob[i]
                else float(rnorm[i] / bnorm[i]),
                # lockstep latency, shared by the batch; a padding row
                # solved nothing and reports 0 so engine comparisons of
                # per-chunk totals stay honest
                wall_time_s=0.0 if pad[i] else wall,
                breakdown=bool(stalled[i]),
                padded=bool(pad[i]),
                # shared batch totals (see module docstring): 2 + #cycles
                # syncs — entry flags, one 4-flag fetch per cycle, finalize
                host_syncs=0 if pad[i] else host_syncs,
                dispatches=0 if pad[i] else dispatches,
                telemetry=(drain_chain(tbufs, i, tcnt, tele_cap)
                           if tbufs is not None and not pad[i] else None),
            ))
        # lockstep occupancy: this solve was one dispatch of bsz rows, of
        # which the non-padded ones did real work
        if obs.enabled():
            live = np.nonzero(~pad)[0]
            obs.record_dispatch(len(live), bsz,
                                iters=[int(iters[i]) for i in live],
                                cycles=[int(cycles[i]) for i in live])

        if k > 0:
            with obs.span("carry_store", cat="solver"):
                # carry Ỹ_k per chain (Alg. 2 line 34); chains that never
                # owned a space this solve keep their previous carry —
                # BITWISE (the old numpy rows are reused, not
                # round-tripped). The carry is stored in the SOLVE dtype
                # (fp32 under the mixed inner solver).
                if contain:
                    # carry quarantine: a quarantined chain's space was
                    # built from (or alongside) a diverging iterate — never
                    # let it seed the chain's NEXT system; the chain
                    # restarts cold
                    established = established & ~quar
                if self.u_carry is None:
                    self.carry_ok = np.zeros(bsz, dtype=bool)
                if self.device_carry:
                    # uc: the carry this solve started from (zeros if none)
                    self.u_carry = _keep_carry(s["est"], s["u"], uc,
                                               s["quar"] if contain else None)
                else:
                    if self.u_carry is None:
                        self.u_carry = np.zeros((bsz, n, k),
                                                dtype=u_np.dtype)
                    keep = established[:, None, None]
                    self.u_carry = np.where(keep, u_np,
                                            self.u_carry.astype(u_np.dtype))
                self.carry_ok = self.carry_ok | established
                if contain and quar.any():
                    if not self.device_carry:
                        self.u_carry[quar] = 0.0
                    self.carry_ok = self.carry_ok & ~quar
                    obs.counter_add("health.quarantined_chains",
                                    int(quar.sum()))
        self.systems_solved += int((~zerob & ~pad).sum())
        return x, stats

    def _host_ritz(self, s, pend, small, fresh, *, contain, tele_cap,
                   tele_delta):
        """One cycle's harmonic-Ritz eigensolve on the host (LAPACK, from
        the fetched pencils `small`), then the refresh program on the
        device. Returns (s, all_est): a fresh cycle's deflated-ready flag
        after the refresh, None after a deflated cycle."""
        k = self.cfg.k
        dt = small["a" if fresh else "mm"].dtype
        t0 = time.perf_counter()
        if fresh:
            p, ok = hl.ritz_first_cycle_padded(small["a"], small["j"], k)
            blocks = hl.last_blocks()
            q, inv_rr, ok = hl.refresh_factors_stacked(small["h"] @ p, ok)
            est_new = ok & small["can"]
            ups = (p.astype(dt), q.astype(dt), inv_rr.astype(dt), est_new)
        else:
            p, ok = hl.ritz_deflated_padded(small["mm"], small["j"], k)
            blocks = hl.last_blocks()
            ups = (p.astype(dt), ok)
        live = small["j"] > 0
        obs.counter_add("ritz.host_s", time.perf_counter() - t0)
        # blocks: the chain blocks the eigensolve ran in (1 inline)
        obs.counter_add("ritz.host_calls")
        obs.counter_add("ritz.host_blocks", blocks)
        obs.counter_add("ritz.host_parallel_calls", float(blocks > 1))
        obs.counter_add("ritz.host_chains", int(live.sum()))
        obs.counter_add("ritz.host_gated", int((live & ~ok).sum()))
        obs.hostlink("h2d", ups)
        ups = [self._dev(jnp.asarray(a)) for a in ups]
        if fresh:
            s = _fresh_refresh(s, pend["v"], pend["h"], *ups, k=k,
                               tele_cap=tele_cap, tele_delta=tele_delta)
            return s, bool((small["ready"] | est_new).all())
        s = _deflated_refresh(s, pend["g"], pend["ut"], pend["v"],
                              pend["step"], *ups, k=k, contain=contain,
                              tele_cap=tele_cap, tele_delta=tele_delta)
        return s, None

    # ------------------------------------------------------------------
    def _solve_batch_mixed(self, ops, b, padded_rows=None):
        """fp64 iterative refinement over fp32 LOCKSTEP correction solves.

        The whole batch advances through the same outer passes: per pass,
        every still-unconverged chain's fp64 residual is downcast into the
        correction right-hand side (converged chains get zero rows — the
        engine's own padding no-op, so their recycle carries are untouched)
        and ONE inner lockstep solve reduces each by `cfg.inner_tol`; the
        fp64 accumulate + true-residual recompute is one batched dispatch.
        A chain whose fp32 pass did not halve its residual while it started
        from a recycled space gets one more fp32 pass without it (its carry
        dropped: a space recycled from the previous system can stall the
        deflated cycles far above the fp32 floor, where fresh cycles do
        not). When a chain stagnates in fp32 otherwise, the WHOLE batch
        falls back to fp64 correction passes (lockstep latency is the max
        over chains anyway).
        Counters (repro.obs): `mixed.dispatches` (this call), and per outer
        pass `mixed.passes_fp32` or `mixed.passes_fp64`.
        """
        cfg = self.cfg
        t0 = time.perf_counter()
        obs.counter_add("mixed.dispatches")
        if not isinstance(b, jax.Array):
            b = np.asarray(b, np.float64)
            obs.hostlink("h2d", b)
        b = self._dev(jnp.asarray(b, jnp.float64))
        if self.sharding is not None:
            ops = self.sharding.put_tree(ops)
        bsz, n = b.shape
        x = self._dev(jnp.zeros((bsz, n), b.dtype))
        r = b
        bnorm = np.asarray(jnp.linalg.norm(b, axis=1))
        obs.hostlink("d2h", bnorm)
        host_syncs, dispatches = 1, 1
        rnorm = bnorm.copy()
        tol_abs = cfg.tol * bnorm
        zerob = bnorm == 0.0
        # marked-padded rows never enter an outer pass: a padding row must
        # not accrue outer_refinements / fp64_fallback (or iterations) that
        # SequenceStats would then mis-attribute to real solves
        pad = zerob if padded_rows is None else np.asarray(padded_rows)

        iters = np.zeros(bsz, dtype=int)
        matvecs = np.zeros(bsz, dtype=int)
        cycles = np.zeros(bsz, dtype=int)
        outer = np.zeros(bsz, dtype=int)
        fb64 = np.zeros(bsz, dtype=bool)
        stuck = np.zeros(bsz, dtype=bool)  # no-progress even in fp64
        dropped = np.zeros(bsz, dtype=bool)  # carry dropped for a retry
        warm = np.zeros(bsz, dtype=bool)     # pass started from a carry
        ops32 = cast_operator(ops, jnp.float32)
        # outer-loop telemetry is host-side and free: the fp64 residual
        # norms are already fetched every pass (kind="outer"; the inner
        # fp32 lockstep solves record their own per-cycle device rings)
        outer_hist = [] if obs.enabled() else None

        if self._inner is None:
            self._inner = BatchedGCRODRSolver(cfg, use_kernel=self.use_kernel,
                                              stall_break=True,
                                              device_carry=True,
                                              sharding=self.sharding)
        inner = self._inner
        # push the public carry (possibly from a checkpoint or an earlier
        # precision) down into the inner solver, stored fp32 — unless it is
        # the carry this solver stored last, which the inner solver still
        # holds on the device
        if self.u_carry is not None:
            with obs.span("carry_upload", cat="solver"):
                if (self.u_carry is not self._carry_mirror
                        or inner.u_carry is None):
                    inner.u_carry = np.asarray(self.u_carry, np.float32)
                inner.carry_ok = (self.carry_ok.copy()
                                  if self.carry_ok is not None else None)
        fallback = False
        passes = 0
        while True:
            need = ~zerob & ~pad & (rnorm > tol_abs) & (iters < cfg.maxiter)
            if not need.any():
                break
            # per-pass budget honors the MOST-advanced needy chain's cap
            # (inner maxiter is batch-wide; a laggard just resumes next
            # pass), so no chain overshoots cfg.maxiter the way a
            # least-advanced budget would allow
            budget = int(max(1, cfg.maxiter - int(iters[need].max())))
            if not fallback and passes < cfg.ir_max_outer:
                # ---- fp32 lockstep correction pass ---------------------
                # per-pass tol follows the MOST demanding chain (lockstep
                # latency is the max over chains — oversolving easy chains
                # inside the same dispatch is free)
                tol_i = min(0.5, max(cfg.inner_tol,
                                     0.25 * float((tol_abs[need]
                                                   / rnorm[need]).min())))
                inner.cfg = dataclasses.replace(cfg, inner_dtype="float64",
                                                tol=tol_i, maxiter=budget)
                obs.hostlink("h2d", need)
                warm = (inner.carry_ok.copy() if inner.u_carry is not None
                        and inner.carry_ok is not None
                        else np.zeros(bsz, dtype=bool))
                d, st_in = inner.solve_batch(ops32, _downcast_masked(r, need))
                outer += need
                obs.counter_add("mixed.passes_fp32")
            else:
                # ---- fp64 fallback lockstep pass -----------------------
                if self._inner64 is None:
                    # no stall_break: the fp64 backstop may legitimately
                    # plateau for stretches (indefinite operators) — it gets
                    # the same patience as the plain fp64 engine
                    self._inner64 = BatchedGCRODRSolver(
                        cfg, use_kernel=self.use_kernel,
                        sharding=self.sharding)
                tol_i = min(0.5, max(0.5 * float((tol_abs[need]
                                                  / rnorm[need]).min()),
                                     1e-14))
                self._inner64.cfg = dataclasses.replace(
                    cfg, inner_dtype="float64", tol=tol_i, maxiter=budget)
                self._inner64.u_carry = (
                    np.asarray(jax.device_get(inner.u_carry), np.float64)
                    if inner.u_carry is not None else None)
                self._inner64.carry_ok = (inner.carry_ok.copy()
                                          if inner.carry_ok is not None
                                          else None)
                obs.hostlink("h2d", need)
                rhs = jnp.where(jnp.asarray(need)[:, None], r, 0.0)
                d, st_in = self._inner64.solve_batch(ops, rhs)
                if self._inner64.u_carry is not None:
                    inner.u_carry = np.asarray(self._inner64.u_carry,
                                               np.float32)
                    inner.carry_ok = self._inner64.carry_ok.copy()
                fb64 |= need
                obs.counter_add("mixed.passes_fp64")
            passes += 1
            host_syncs += max(st.host_syncs for st in st_in)
            dispatches += max(st.dispatches for st in st_in) + 1
            for i in np.nonzero(need)[0]:
                iters[i] += st_in[i].iterations
                matvecs[i] += st_in[i].matvecs
                cycles[i] += st_in[i].cycles
            rprev, x_prev, r_prev = rnorm, x, r
            obs.hostlink("h2d", d)
            with obs.span("cycle_dispatch", cat="solver"):
                x, r, rn = _ir_accum_b(ops.base, b, x, jnp.asarray(d))
            matvecs += need
            rnorm = np.asarray(rn)
            obs.hostlink("d2h", rnorm)
            host_syncs += 1
            bad = need & (~np.isfinite(rnorm) | (rnorm > rprev))
            if bad.any():   # overflow OR diverging correction — roll back
                obs.hostlink("h2d", bad, bad)   # the masks of two selects
                x = _sel(~bad, x, x_prev)
                r = _sel(~bad, r, r_prev)
                rnorm = np.where(bad, rprev, rnorm)
            if outer_hist is not None:
                outer_hist.append(rnorm.copy())
            no_prog = need & ~(rnorm <= 0.5 * rprev) & (rnorm > tol_abs)
            if no_prog.any():
                if fallback:
                    stuck |= no_prog  # a true stall, not budget exhaustion
                    break             # fp64 lockstep is stuck too — stop
                if (no_prog & ~(warm & ~dropped)).any():
                    fallback = True  # fp32 stagnated somewhere → fp64 batch
                else:                # retry those chains cold, in fp32
                    dropped |= no_prog
                    inner.carry_ok = inner.carry_ok & ~no_prog

        # ---- finalize ----------------------------------------------------
        self.x_device = x   # fp64 accumulated iterate, device-resident
        # the inner solver's device carry rides the iterate's fetch
        u_dev = (inner.u_carry if cfg.k > 0
                 and isinstance(inner.u_carry, jax.Array) else None)
        x_np, u_np = jax.device_get((x, u_dev))
        obs.hostlink("d2h", x_np, u_np)
        host_syncs += 1
        wall = time.perf_counter() - t0
        converged = zerob | (rnorm <= tol_abs)
        # containment (policy armed): the outer IR loop is host-mediated,
        # so quarantine here is a pure host-side classification — a chain
        # whose norms went non-finite (poisoned RHS/operator) or whose
        # residual diverged past the policy threshold is flagged for the
        # pipeline's requeue; NaN comparison semantics already kept it out
        # of every outer pass (a NaN `need` entry is False)
        quar = np.zeros(bsz, dtype=bool)
        if self.policy is not None:
            quar = (~pad & ~zerob
                    & (~np.isfinite(bnorm) | ~np.isfinite(rnorm)
                       | (rnorm > self.policy.divergence_ratio * bnorm)))
        stats = []
        for i in range(bsz):
            stats.append(SolveStats(
                iterations=int(iters[i]),
                matvecs=int(matvecs[i]),
                cycles=int(cycles[i]),
                converged=bool(converged[i]) and not bool(quar[i]),
                quarantined=bool(quar[i]),
                rel_residual=0.0 if zerob[i]
                else float(rnorm[i] / bnorm[i]),
                # shared lockstep latency; 0 for padding rows
                wall_time_s=0.0 if pad[i] else wall,
                # breakdown marks a genuine stall (no progress even in the
                # fp64 fallback) — maxiter exhaustion stays False, matching
                # the plain engines' semantics
                breakdown=bool(stuck[i]),
                outer_refinements=int(outer[i]),
                fp64_fallback=bool(fb64[i]),
                padded=bool(pad[i]),
                host_syncs=0 if pad[i] else host_syncs,
                dispatches=0 if pad[i] else dispatches,
                telemetry=(KrylovTelemetry(
                    res_hist=np.array([row[i] for row in outer_hist]),
                    kind="outer")
                    if outer_hist is not None and not pad[i] else None),
            ))
        if cfg.k > 0 and inner.u_carry is not None:
            with obs.span("carry_store", cat="solver"):
                self.u_carry = np.require(
                    inner.u_carry if u_np is None else u_np, np.float32,
                    ["W"])
                self.carry_ok = (inner.carry_ok.copy()
                                 if inner.carry_ok is not None else None)
                if quar.any():   # carry quarantine, as in the fp64 path
                    self.u_carry[quar] = 0.0
                    if self.carry_ok is not None:
                        self.carry_ok = self.carry_ok & ~quar
                    inner.u_carry = self.u_carry
                    if inner.carry_ok is not None:
                        inner.carry_ok = inner.carry_ok & ~quar
                self._carry_mirror = self.u_carry
        self.systems_solved += int((~zerob & ~pad).sum())
        return x_np, stats
