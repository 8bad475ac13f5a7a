"""The device-resident lockstep engine must not move data implicitly.

`jax.transfer_guard("disallow")` errors on every IMPLICIT host↔device
transfer while still permitting explicit ones (`jnp.asarray`, `device_put`,
`device_get` / `np.asarray` on a device array). The refactored
`BatchedGCRODRSolver.solve_batch` is designed to cross the boundary only at
explicit, counted points — entry upload, one fetch per cycle (4 flags and
the small harmonic-Ritz pencils; the host's bases go back by explicit
puts), one finalize fetch — so an entire lockstep solve (including
warm-started follow-up solves and the k = 0 GMRES special case) must run
clean under the guard. A regression here means some per-cycle host round-trip crept back
into the hot loop."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.pde.dia import Stencil5
from repro.pde.registry import get_family
from repro.solvers.batched import BatchedGCRODRSolver
from repro.solvers.operator import PreconditionedOp, StencilOp
from repro.solvers.precond import make_preconditioner_batched
from repro.solvers.types import KrylovConfig


def _batched_ops(nx=10, chains=3, seed=11):
    fam = get_family("poisson", nx=nx, ny=nx)
    batch = fam.sample_batch(jax.random.PRNGKey(seed), chains)
    st5 = Stencil5(jnp.asarray(batch.op.coeffs))
    pre = make_preconditioner_batched("jacobi", st5)
    ops = PreconditionedOp(StencilOp(st5.coeffs), pre)
    b = np.asarray(batch.b).reshape(chains, -1)
    return ops, b


@pytest.mark.parametrize("telemetry", [False, True],
                         ids=["obs_off", "obs_on"])
@pytest.mark.parametrize("k", [0, 6])
def test_lockstep_solve_has_no_implicit_transfers(k, telemetry):
    """Both with observability off (the default) and ON — the device
    telemetry rings are accumulated inside the jitted cycle programs and
    drained by the EXISTING finalize fetch, so turning them on must not
    add a single transfer or blocking sync to the hot loop."""
    ops, b = _batched_ops()
    cfg = KrylovConfig(m=18, k=k, tol=1e-8, maxiter=2000)
    solver = BatchedGCRODRSolver(cfg)
    if telemetry:
        obs.enable(delta_qc=True)
    try:
        with jax.transfer_guard("disallow"):
            x, stats = solver.solve_batch(ops, b)
            if k > 0:
                # the warm-started follow-up exercises the carry upload +
                # batched re-biorthogonalization path under the guard too
                x, stats = solver.solve_batch(ops, b)
    finally:
        obs.disable()
    assert all(s.converged for s in stats)
    # the sync budget claim: entry + one per cycle + finalize — exactly
    # one blocking fetch per cycle, telemetry on or off
    cycles = max(s.cycles for s in stats)
    assert all(s.host_syncs <= 2 + cycles for s in stats if not s.padded)
    if telemetry:
        # the rings drained: every chain carries its per-cycle history
        # (batch-shared ring → at least the chain's own cycle count)
        for s in stats:
            assert s.telemetry is not None
            assert len(s.telemetry.res_hist) >= s.cycles
            assert np.isfinite(s.telemetry.res_hist).all()
    else:
        assert all(s.telemetry is None for s in stats)


@pytest.mark.parametrize("k", [0, 6])
def test_lockstep_containment_keeps_sync_budget(k):
    """Containment ON (a RetryPolicy attached) adds a per-batch health flag
    to the EXISTING per-cycle flag fetch and a quarantine mask to the
    EXISTING finalize fetch — the sync budget must stay 2 + cycles and the
    solve must run clean under the transfer guard."""
    from repro.core.robust import RetryPolicy

    ops, b = _batched_ops()
    cfg = KrylovConfig(m=18, k=k, tol=1e-8, maxiter=2000)
    solver = BatchedGCRODRSolver(cfg, policy=RetryPolicy())
    with jax.transfer_guard("disallow"):
        x, stats = solver.solve_batch(ops, b)
        if k > 0:
            x, stats = solver.solve_batch(ops, b)
    assert all(s.converged for s in stats)
    assert not any(s.quarantined for s in stats)
    cycles = max(s.cycles for s in stats)
    assert all(s.host_syncs <= 2 + cycles for s in stats if not s.padded)


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["ref", "pallas"])
def test_expansion_wave_adds_no_transfers_or_syncs(use_kernel):
    """Label expansion (core/expand.py) rides the retired lockstep row:
    the wave consumes the solver's device-resident `x_device` stash and the
    row's already-uploaded operator stack, accumulates its results as
    device arrays, and drains them only at `result()` — so a solve + wave
    runs clean under the transfer guard and the solver's sync budget stays
    exactly 2 + cycles with expansion ON."""
    from repro.core.expand import ExpandConfig, Expander

    chains = 3
    ops, b = _batched_ops(chains=chains)
    cfg = KrylovConfig(m=18, k=6, tol=1e-8, maxiter=2000)
    solver = BatchedGCRODRSolver(cfg)
    exp = Expander(ExpandConfig(k=4), 10, 10, use_kernel=use_kernel)
    idx = np.arange(chains)
    live = np.ones(chains, dtype=bool)
    with jax.transfer_guard("disallow"):
        x, stats = solver.solve_batch(ops, b)
        exp.wave(ops.base.coeffs, solver.x_device, idx, live)
    cycles = max(s.cycles for s in stats)
    assert all(s.host_syncs <= 2 + cycles for s in stats if not s.padded)
    labels = exp.result()    # the one bulk drain, outside the guard
    assert len(labels) == chains * 5
    assert np.isfinite(labels.f).all() and np.isfinite(labels.u).all()


def test_lockstep_syncs_scale_with_cycles_not_chains():
    """host_syncs is a batch-shared count: growing B must not grow it."""
    cfg = KrylovConfig(m=18, k=6, tol=1e-8, maxiter=2000)
    counts = {}
    for chains in (2, 4):
        ops, b = _batched_ops(chains=chains)
        _, stats = BatchedGCRODRSolver(cfg).solve_batch(ops, b)
        counts[chains] = max(s.host_syncs for s in stats)
    assert counts[4] <= counts[2] + 2  # same cycle count up to ±2 cycles
