"""Observability layer (`repro.obs`) contract tests.

The load-bearing guarantees:

* DISABLED (default) the instrumentation compiles out — the lockstep
  solver's outputs are bitwise-identical and it launches zero extra device
  programs or blocking syncs;
* ENABLED, the device telemetry rings ride inside the existing jitted
  cycle programs and drain through the existing finalize fetch, so the
  sync/dispatch budget is unchanged (see also test_transfer_guard.py);
* ring buffers bound memory (trace ring and device Krylov rings both);
* the Chrome trace export is loadable and shows row prefetch overlapping
  solve dispatch on distinct thread tracks;
* the fused device δ(Q,C) proxy agrees with the host oracle
  `core.metrics.delta_subspace`;
* the solver's phase scopes name its device work and change no equation;
  spans mirror into the profiler as `skr:` annotations; the lockstep and
  host-link counters equal their reckoning from SolveStats and shapes.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.metrics import delta_subspace
from repro.obs.telemetry import ring_order
from repro.obs.trace import Tracer
from repro.pde.dia import Stencil5
from repro.pde.registry import get_family
from repro.solvers.batched import BatchedGCRODRSolver, _delta_qc_b
from repro.solvers.operator import PreconditionedOp, StencilOp
from repro.solvers.precond import make_preconditioner_batched
from repro.solvers.types import KrylovConfig, SequenceStats


@pytest.fixture(autouse=True)
def _obs_off():
    """Every test starts AND ends disabled — the module default."""
    obs.disable()
    yield
    obs.disable()


def _batched_ops(nx=10, chains=3, seed=11, family="poisson"):
    fam = get_family(family, nx=nx, ny=nx)
    batch = fam.sample_batch(jax.random.PRNGKey(seed), chains)
    st5 = Stencil5(jnp.asarray(batch.op.coeffs))
    pre = make_preconditioner_batched("jacobi", st5)
    ops = PreconditionedOp(StencilOp(st5.coeffs), pre)
    b = np.asarray(batch.b).reshape(chains, -1)
    return ops, b


def _solve(k=6, **kw):
    ops, b = _batched_ops(**kw)
    cfg = KrylovConfig(m=18, k=k, tol=1e-8, maxiter=2000)
    x, stats = BatchedGCRODRSolver(cfg).solve_batch(ops, b)
    return np.asarray(x), stats


# ------------------------------------------------------------- off = free
def test_disabled_is_the_default_and_a_noop():
    assert not obs.enabled()
    # the span fast path returns ONE shared null object — no allocation
    assert obs.span("a") is obs.span("b")
    assert obs.krylov_capacity() == 0
    assert not obs.delta_enabled()
    assert obs.summary() == {}
    assert obs.tracer() is None and obs.registry() is None
    assert obs.export_chrome_trace("/dev/null") is False
    assert obs.export_jsonl("/dev/null") is False
    obs.record_dispatch(1, 2)  # must not raise with no registry


def test_telemetry_off_is_bitwise_identical_and_adds_nothing():
    """off → on → off: the two disabled runs must agree BITWISE (the
    tele_cap=0 static default yields the pre-telemetry jaxpr), and the
    enabled run must match the disabled dispatch/sync budget exactly."""
    x_off, st_off = _solve()
    obs.enable(delta_qc=True)
    x_on, st_on = _solve()
    obs.disable()
    x_off2, st_off2 = _solve()

    assert np.array_equal(x_off, x_off2)  # bitwise, not tolerance
    # telemetry rides the existing programs: same dispatches, same syncs
    for a, b in zip(st_off, st_on):
        assert a.dispatches == b.dispatches
        assert a.host_syncs == b.host_syncs
        assert a.cycles == b.cycles
    assert all(s.telemetry is None for s in st_off)
    assert all(s.telemetry is not None for s in st_on)
    # enabled output still agrees numerically (different jaxpr, same math)
    np.testing.assert_allclose(x_on, x_off, rtol=1e-12, atol=1e-12)


# --------------------------------------------------------- bounded memory
def test_ring_order_chronology_and_dropped():
    order, dropped = ring_order(3, 8)
    assert dropped == 0 and list(order) == [0, 1, 2]
    order, dropped = ring_order(6, 4)  # slots wrapped once: oldest at 2
    assert dropped == 2 and list(order) == [2, 3, 0, 1]
    order, dropped = ring_order(8, 4)  # exact multiple of capacity
    assert dropped == 4 and list(order) == [0, 1, 2, 3]


def test_device_ring_bounds_memory():
    """More cycles than ring slots: history keeps the NEWEST `capacity`
    entries and reports the overflow instead of growing."""
    obs.enable(krylov_capacity=2)
    _, stats = _solve(k=0)  # plain GMRES restarts → several cycles
    s = stats[0]
    assert s.cycles > 2, "need an overflowing run for this test"
    t = s.telemetry
    assert len(t.res_hist) == 2
    assert t.dropped == s.cycles - 2
    assert np.isfinite(t.res_hist).all()
    # newest-last: the final ring entry is the converged residual
    assert t.res_hist[-1] <= t.res_hist[0]


def test_trace_ring_bounds_memory():
    tr = Tracer(capacity=8)
    for i in range(20):
        with tr.span("s", "t", i=i):
            pass
    events = tr.snapshot()
    assert len(events) == 8
    assert tr.dropped == 12
    # the survivors are the NEWEST spans
    assert [e["args"]["i"] for e in events] == list(range(12, 20))


# -------------------------------------------------- device δ(Q,C) ~ oracle
def test_device_delta_qc_matches_host_oracle():
    """The fused per-chain sin θ_max proxy equals `delta_subspace` for
    orthonormal same-dimension bases (the only way it is ever called)."""
    rng = np.random.default_rng(0)
    n, k, bsz = 40, 6, 3
    olds, news = [], []
    for _ in range(bsz):
        olds.append(np.linalg.qr(rng.standard_normal((n, k)))[0])
        news.append(np.linalg.qr(rng.standard_normal((n, k)))[0])
    # include a near-identical pair (δ → 0) to cover the clip edge
    news[0] = olds[0] @ np.linalg.qr(rng.standard_normal((k, k)))[0]
    dev = np.asarray(_delta_qc_b(jnp.asarray(np.stack(olds)),
                                 jnp.asarray(np.stack(news)),
                                 jnp.ones(bsz, bool)))
    for i in range(bsz):
        host = delta_subspace(olds[i], news[i])
        assert dev[i] == pytest.approx(host, abs=1e-8)
    # rejected-refresh chains report NaN, not a stale angle
    masked = np.asarray(_delta_qc_b(jnp.asarray(np.stack(olds)),
                                    jnp.asarray(np.stack(news)),
                                    jnp.zeros(bsz, bool)))
    assert np.isnan(masked).all()


# ------------------------------------------------------ registry/summary
def test_registry_utilization_and_summary_merge():
    obs.enable()
    obs.record_dispatch(3, 4, iters=[10, 12, 14], cycles=[2, 1, 2])
    snap = obs.summary()
    assert snap["utilization"] == pytest.approx(0.75)
    assert snap["counters"]["lockstep.rows_live"] == 3
    assert snap["counters"]["lockstep.rows_total"] == 4
    assert snap["counters"]["krylov.iterations"] == 36
    assert snap["counters"]["krylov.cycles"] == 2
    # the lockstep efficiency pair replaces the last-value imbalance gauge
    assert snap["counters"]["lockstep.cycles_needed"] == 5
    assert snap["counters"]["lockstep.cycles_paid"] == 6
    assert obs.registry().lockstep_eff() == pytest.approx(5 / 6)
    assert snap["gauges"] == {}
    from repro.obs.report import render_report
    assert "lockstep efficiency        83.3%" in render_report(
        {}, registry=obs.registry())
    # SequenceStats.summary() carries the live registry when enabled
    seq = SequenceStats()
    assert "obs" in seq.summary()
    obs.disable()
    assert "obs" not in seq.summary()


def test_lockstep_solve_populates_registry():
    obs.enable()
    _, stats = _solve()
    snap = obs.summary()
    assert snap["counters"]["lockstep.dispatches"] == 1
    assert snap["counters"]["lockstep.rows_total"] == len(stats)
    assert snap["utilization"] == 1.0  # no padding in this batch


# ------------------------------------------- end-to-end heat trace export
def test_heat_trajectory_trace_and_telemetry(tmp_path):
    """The ISSUE's acceptance run: heat-family chunked trajectory datagen
    with tracing on → loadable Chrome trace whose prefetch thread overlaps
    the solve track, per-cycle residual histories on every non-padded
    chain, and a utilization summary."""
    from repro.core.trajectory import (TrajConfig,
                                       generate_trajectories_chunked)
    from repro.pde.registry import get_timedep_family

    obs.enable(delta_qc=True)
    fam = get_timedep_family("heat", nx=12, ny=12, nt=4, dt=5e-2)
    cfg = TrajConfig(krylov=KrylovConfig(m=24, k=8, tol=1e-8,
                                         maxiter=2000),
                     sort_method="greedy", precond="jacobi")
    chunks = generate_trajectories_chunked(fam, jax.random.PRNGKey(0), 4,
                                           cfg, workers=2,
                                           engine="batched")

    # every non-padded chain carries its full per-cycle residual history
    # (the ring is batch-shared: a chain that converged early keeps
    # recording its settled residual until the batch finishes, so the
    # history covers AT LEAST the chain's own cycles)
    for c in chunks:
        for s in c.stats.solved:
            assert s.telemetry is not None
            assert len(s.telemetry.res_hist) >= s.cycles
            assert np.isfinite(s.telemetry.res_hist).all()
        assert c.stats.summary()["obs"]["utilization"] == 1.0

    path = tmp_path / "trace.json"
    assert obs.export_chrome_trace(str(path))
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    names = {e["tid"]: e["args"]["name"] for e in evs
             if e.get("ph") == "M" and e.get("name") == "thread_name"}
    assert any(n.startswith("prefetch") for n in names.values())
    # prepare_row runs on the prefetch thread, execute_row on the main
    # thread — distinct Perfetto tracks whose intervals overlap in time
    prep = [(e["ts"], e["ts"] + e["dur"], e["tid"]) for e in evs
            if e.get("name") == "prepare_row"]
    exe = [(e["ts"], e["ts"] + e["dur"], e["tid"]) for e in evs
           if e.get("name") == "execute_row"]
    assert prep and exe
    assert {t for *_, t in prep}.isdisjoint({t for *_, t in exe})
    assert any(a < e1 and s1 < b for a, b, _ in prep
               for s1, e1, _ in exe), "prefetch/solve overlap missing"


# ------------------------------------------------ device phase scopes
SCOPES = ("skr/entry", "skr/arnoldi", "skr/arnoldi/matvec",
          "skr/arnoldi/orthog", "skr/lstsq", "skr/update", "skr/ritz",
          "skr/finalize")


def _programs(k=4):
    """The lockstep solver's four device programs at a small shape, each
    as (jitted function, positional args, static kwargs)."""
    from repro.solvers import batched as bt

    ops, b = _batched_ops(nx=8)
    b = jnp.asarray(b)
    bsz = b.shape[0]
    z0, c0, u0 = bt._zeros_state(b, k=k)
    args = (ops, b, z0, c0, u0, u0, jnp.ones(bsz, bool),
            jnp.zeros(bsz, bool), jnp.asarray(1e-8),
            jnp.asarray(np.int32(100)), jnp.asarray(0.0))
    entry_kw = dict(k=k, use_carry=True, pad_given=True, contain=True,
                    stall_break=True)
    s, aux, _ = bt._entry(*args, **entry_kw)
    cyc_kw = dict(k=k, orthog="cgs2", use_kernel=False, h_acc="native",
                  stall_break=True, contain=True)
    return {"entry": (bt._entry, args, entry_kw),
            "fresh": (bt._fresh_cycle, (ops, s, aux),
                      dict(cyc_kw, m=10, can_grow=False)),
            "deflated": (bt._deflated_cycle, (ops, s, aux),
                         dict(cyc_kw, mi=6)),
            "finalize": (bt._from_z_b, (ops, s["z"]), {})}


def test_phase_scopes_name_the_programs_work():
    """Every scope of the table is in the programs' HLO `op_name`
    metadata, and nearly all of the cycle programs' own instructions
    carry an `skr` scope: the rest are the call sites of a few nested jits
    (`jit(norm)`), whose bodies carry it."""
    import re

    seen = set()
    for name, (fn, args, kw) in _programs().items():
        hlo = fn.lower(*args, **kw).as_text(dialect="hlo", debug_info=True)
        own = [n for n in re.findall(r'op_name="([^"]*)"', hlo)
               if n.startswith("jit(")]
        seen |= {sc for sc in SCOPES for n in own if sc in n}
        bare = [n for n in own if "skr" not in n]
        assert all("/" not in n for n in bare), (name, bare)
        if name in ("fresh", "deflated"):
            assert len(bare) <= 0.1 * len(own), (name, bare, len(own))
    assert seen == set(SCOPES)


def test_phase_scopes_change_no_equation(monkeypatch):
    """The scopes are trace-time metadata: each program's jaxpr is the
    same with `jax.named_scope` made a no-op."""
    import contextlib

    def jaxprs():
        out = {}
        for name, (fn, args, kw) in _programs().items():
            out[name] = str(jax.make_jaxpr(
                lambda *a, fn=fn, kw=kw: fn.__wrapped__(*a, **kw))(*args))
        return out

    scoped = jaxprs()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert jaxprs() == scoped


# --------------------------------------- spans, counters: off and on
def _two_solves(solver, ops, b):
    """A cold then a warm-started solve (carry upload and store)."""
    x1, st1 = solver.solve_batch(ops, b)
    x2, st2 = solver.solve_batch(ops, 1.5 * b)
    return [np.asarray(x1), np.asarray(x2)], st1 + st2


def test_obs_off_creates_no_span_and_computes_no_counter(monkeypatch):
    """Off → on (spans and counters, no device telemetry: the benchmark's
    traced mode) → off, under the transfer guard: the outputs agree
    bitwise, the sync and dispatch counts match, and while off no span
    object is built and no counter computed."""
    from repro.obs import trace as obs_trace
    from repro.obs.metrics import Registry

    ops, b = _batched_ops()
    cfg = KrylovConfig(m=18, k=6, tol=1e-8, maxiter=2000)

    def run():
        with jax.transfer_guard("disallow"):
            return _two_solves(BatchedGCRODRSolver(cfg), ops, b)

    def boom(*a, **k):
        raise AssertionError("observability work while disabled")

    with monkeypatch.context() as m:
        m.setattr(obs_trace._Span, "__init__", boom)
        m.setattr(Registry, "counter_add", boom)
        m.setattr(Registry, "record_dispatch", boom)
        xs_off, st_off = run()
    obs.enable(krylov_capacity=0)
    xs_on, st_on = run()
    assert obs.summary()["counters"]["hostlink.d2h_bytes"] > 0
    obs.disable()
    xs_off2, st_off2 = run()
    for a, b2, c in zip(xs_off, xs_on, xs_off2):
        assert np.array_equal(a, b2) and np.array_equal(a, c)
    for a, b2, c in zip(st_off, st_on, st_off2):
        assert a.host_syncs == b2.host_syncs == c.host_syncs
        assert a.dispatches == b2.dispatches == c.dispatches


def test_lockstep_cycle_counters_match_the_harness_arithmetic():
    """cycles_needed / cycles_paid is the benchmark's `lockstep.eff`
    arithmetic on the solve's own SolveStats: per dispatch, Σ live chains'
    cycles over live chains × the largest, summed over dispatches."""
    ops, b = _batched_ops(chains=4, seed=5)
    cfg = KrylovConfig(m=6, k=0, tol=1e-8, maxiter=2000)
    solver = BatchedGCRODRSolver(cfg)
    obs.enable(krylov_capacity=0)
    dispatches = []
    for pad in ([False] * 4, [False, True, False, False]):
        _, stats = solver.solve_batch(ops, b, padded_rows=np.array(pad))
        dispatches.append([s.cycles for s in stats if not s.padded])
    c = obs.summary()["counters"]
    need = sum(sum(d) for d in dispatches)
    paid = sum(len(d) * max(d) for d in dispatches)
    assert len({n for d in dispatches for n in d}) > 1, \
        "chains of equal cycle counts do not test the ratio"
    assert c["lockstep.cycles_needed"] == need
    assert c["lockstep.cycles_paid"] == paid
    assert obs.registry().lockstep_eff() == pytest.approx(need / paid)


@pytest.mark.parametrize("contain", [False, True],
                         ids=["plain", "containment"])
def test_hostlink_bytes_match_the_shapes(contain, monkeypatch):
    """The byte counters equal the reckoning from the arrays' shapes: a
    cold and a warm solve of numpy right-hand sides."""
    from repro.core.robust import RetryPolicy
    from repro.solvers import hostlinalg as hl

    calls = {"ritz_first_cycle_padded": 0, "ritz_deflated_padded": 0}
    for name in calls:
        def spy(*a, real=getattr(hl, name), name=name):
            calls[name] += 1
            return real(*a)
        monkeypatch.setattr(hl, name, spy)

    ops, b = _batched_ops()
    bsz, n = b.shape
    m, k = 18, 6
    solver = BatchedGCRODRSolver(
        KrylovConfig(m=m, k=k, tol=1e-8, maxiter=2000),
        policy=RetryPolicy() if contain else None)
    obs.enable(krylov_capacity=0)
    _, stats = _two_solves(solver, ops, b)
    f8, i4 = 8, 4
    flags = 5 if contain else 4                  # booleans per flag fetch
    h2d = d2h = 0
    for warm, st in ((False, stats[0]), (True, stats[bsz])):
        h2d += bsz * n * f8                      # the right-hand sides
        h2d += bsz * n * k * f8 if warm else 0   # the recycle carry
        h2d += 2 * bsz + f8 + i4 + f8            # carry_ok, pad; tol, lim, div
        d2h += flags * (st.host_syncs - 1)       # entry + per-cycle flags
        d2h += (bsz * n * f8                     # x
                + bsz * n * k * f8               # U
                + 2 * bsz * f8                   # rnorm, bnorm
                + 3 * bsz * i4                   # iters, matvecs, cycles
                + 4 * bsz                        # stalled, est, zerob, pad
                + (bsz if contain else 0))       # quar
    # each cycle's harmonic-Ritz pencils down, the host's bases up
    fresh, deflated = calls.values()
    assert fresh > 0 and deflated > 0
    d2h += fresh * (bsz * m * m * f8             # A
                    + bsz * (m + 1) * m * f8     # H̄
                    + bsz * i4 + 2 * bsz)        # j; can, ready
    h2d += fresh * (bsz * m * k * f8             # P
                    + bsz * (m + 1) * k * f8     # Q
                    + bsz * k * k * f8 + bsz)    # R⁻¹; est
    d2h += deflated * (bsz * m * m * f8 + bsz * i4)   # M (k + mi = m); j
    h2d += deflated * (bsz * m * k * f8 + bsz)        # P; ok
    c = obs.summary()["counters"]
    assert c["hostlink.h2d_bytes"] == h2d
    assert c["hostlink.d2h_bytes"] == d2h


def test_spans_mirror_into_the_profiler(tmp_path):
    """Each span opens a `skr:<name>` profiler annotation (`.<what>` for a
    host sync) on the thread that runs it, while the tracer's records
    keep their names and arguments; the session stays readable through
    `obs.last()` after `disable()` until the next `enable()`."""
    import glob

    from jax.profiler import ProfileData

    ops, b = _batched_ops()
    solver = BatchedGCRODRSolver(KrylovConfig(m=18, k=6, tol=1e-8,
                                              maxiter=2000))
    _two_solves(solver, ops, b)                 # compile outside the trace
    obs.enable(krylov_capacity=0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _two_solves(solver, ops, b)
    finally:
        jax.profiler.stop_trace()
    obs.disable()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {ev.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:") for line in plane.lines
             for ev in line.events if ev.name.startswith("skr:")}
    assert names == {"skr:solve_batch", "skr:carry_upload",
                     "skr:host_sync.entry_flags", "skr:cycle_dispatch",
                     "skr:host_sync.cycle_flags", "skr:host_sync.finalize",
                     "skr:carry_store"}
    tracer, registry = obs.last()
    spans = [e for e in tracer.snapshot() if e["ph"] == "X"]
    assert {e["args"]["what"] for e in spans if e["name"] == "host_sync"} \
        == {"entry_flags", "cycle_flags", "finalize"}
    assert sum(e["name"] == "solve_batch" for e in spans) == 2
    assert registry.snapshot()["counters"]["lockstep.dispatches"] == 2
    obs.enable()
    assert obs.last() == (None, None)


def test_host_eig_counters(monkeypatch):
    """`ritz.host_chains` counts the chain refreshes the host eigensolve
    ran, which is Σ the chains' `SolveStats.cycles`; `ritz.host_gated` the
    chains its gate kept on their old space; `ritz.host_s` its seconds."""
    from repro.solvers import hostlinalg as hl

    ops, b = _batched_ops()
    cfg = KrylovConfig(m=18, k=6, tol=1e-8, maxiter=2000)
    real, gated = hl.ritz_deflated_padded, []

    def gate_first_chain(mm, j, k):
        p, ok = real(mm, j, k)
        ok = ok.copy()
        ok[0] = False               # chain 0 keeps its space every cycle
        gated.append(int(((j > 0) & ~ok).sum()))
        return p, ok

    monkeypatch.setattr(hl, "ritz_deflated_padded", gate_first_chain)
    obs.enable(krylov_capacity=0)
    _, stats = _two_solves(BatchedGCRODRSolver(cfg), ops, b)
    c = obs.summary()["counters"]
    assert all(s.converged for s in stats)
    assert c["ritz.host_chains"] == sum(s.cycles for s in stats)
    assert c["ritz.host_gated"] == sum(gated) > 0
    assert c["ritz.host_s"] > 0


def test_blocked_host_ritz_is_the_inline_solve(monkeypatch):
    """A Darcy row whose host eigensolve runs in chain blocks on the pool
    gives the iterates, carries, stats and syncs of the inline stacked
    call; `ritz.host_calls` counts the `_host_ritz` calls and
    `ritz.host_blocks` the blocks they used (one each inline)."""
    import dataclasses

    from repro.solvers import hostlinalg as hl

    real, calls = BatchedGCRODRSolver._host_ritz, []

    def spy(self, *a, **kw):
        calls.append(1)
        return real(self, *a, **kw)

    monkeypatch.setattr(BatchedGCRODRSolver, "_host_ritz", spy)
    monkeypatch.setattr(hl, "_cpus", lambda: 4)
    ops, b = _batched_ops(nx=8, chains=8, family="darcy")
    cfg = KrylovConfig(m=18, k=6, tol=1e-8, maxiter=2000)
    runs = {}
    for name, min_rows in (("inline", 10**9), ("blocked", 2)):
        monkeypatch.setattr(hl, "MIN_BLOCK_ROWS", min_rows)
        calls.clear()
        obs.enable(krylov_capacity=0)
        solver = BatchedGCRODRSolver(cfg)
        xs, stats = _two_solves(solver, ops, b)
        runs[name] = dict(
            xs=xs, carry=np.asarray(solver.u_carry), ok=solver.carry_ok,
            stats=[dataclasses.replace(st, wall_time_s=0.0) for st in stats],
            c=obs.summary()["counters"], calls=len(calls))
        obs.disable()
    inline, blocked = runs["inline"], runs["blocked"]
    for x_in, x_bl in zip(inline["xs"], blocked["xs"]):
        np.testing.assert_array_equal(x_bl, x_in)
    np.testing.assert_array_equal(blocked["carry"], inline["carry"])
    np.testing.assert_array_equal(blocked["ok"], inline["ok"])
    assert blocked["stats"] == inline["stats"]
    assert all(st.converged for st in inline["stats"])
    for run in (inline, blocked):
        c = run["c"]
        assert c["ritz.host_calls"] == run["calls"] > 0
    ci, cb = inline["c"], blocked["c"]
    assert ci["ritz.host_blocks"] == ci["ritz.host_calls"]
    assert ci["ritz.host_parallel_calls"] == 0
    assert cb["ritz.host_blocks"] > cb["ritz.host_calls"]
    assert 0 < cb["ritz.host_parallel_calls"] <= cb["ritz.host_calls"]
