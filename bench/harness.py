"""The benchmark's harness: builds one cell from its files, warms it up, runs
the measured window, and checks every label the window emitted against the
plain reference (`reference.py`).

A cell of `BENCHMARK.json` names a configuration (`configs/<name>.json`: the
deployment, its sizes and guarantees) and a traffic mix
(`traffic/<name>.json`: job size and chain count, read by the one job
generator below). A job of steady systems (`core/skr.SteadyWork`) runs
`core/pipeline.run_chunked` with `engine="batched"`, the path of
`generate_dataset_chunked`; the harness subclasses the work adapter only to
stamp each lockstep row and record each solver dispatch.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Optional

import numpy as np

from bench import reference
from bench import trace as trace_mod

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
# where a traced run leaves its profile (overwritten by the next one)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
# the traced slice: from the first row end past this share of the window,
# this many seconds, closed at the next span or row end (the chip runs
# millions of small fp64 operations a second here: a one-second slice of
# 8-chain rows overflowed the profiler's device buffer)
TRACE_FROM, TRACE_SECONDS = 0.5, 0.1


# ----------------------------------------------------------------- files
def manifest() -> dict:
    with open(MANIFEST) as f:
        return json.load(f)


def _load(kind: str, name: str) -> dict:
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def cell(name: str, man: Optional[dict] = None):
    """(workload entry, configuration, traffic) of the cell `name`."""
    man = man or manifest()
    for entry in man["workloads"]:
        if entry["name"] == name:
            return entry, _load("configs", entry["config"]), \
                _load("traffic", entry["traffic"])
    raise KeyError(f"no workload {name!r} in {MANIFEST}")


def metric_names(man: dict, entry: dict, group: str) -> list:
    """The metrics of `group` (`end_to_end` or `per_layer`) that the cell
    reports: those without a `workloads` list, and those whose list names
    it."""
    return [m for m in man[group]
            if entry["name"] in m.get("workloads", [entry["name"]])]


def read_metric(name: str, record: dict, tr=None):
    """Run the reader `metrics/<name>.py` on the run's record (and the
    traced slice, when there is one). None means nothing to read."""
    import importlib.util

    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("bench_metric", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(record, tr)


# -------------------------------------------------------------- counters
class CompileCounter:
    """XLA compilations (cache hits included) and persistent-cache hits,
    read from jax.monitoring."""

    def __init__(self):
        import jax

        self.n = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1


class WindowClosed(Exception):
    """Raised at the end of the row in flight when the window has closed."""


class Window:
    """Row clock of one run. `rows` stamps each lockstep row's end and
    `dispatches` each solver dispatch (per-chain cycles and iterations, the
    dispatch's host syncs). Closes at `deadline`, or after `max_rows`."""

    def __init__(self, max_rows: Optional[int] = None):
        self.max_rows = max_rows
        self.deadline = None
        self.t0 = None
        self.rows = []
        self.dispatches = []
        self.on_row = None

    def open(self, seconds: float):
        self.rows, self.dispatches = [], []
        self.t0 = time.perf_counter()
        self.deadline = self.t0 + seconds

    def dispatch(self, stats):
        live = [s for s in stats if not s.padded]
        if live:
            self.dispatches.append(dict(
                cycles=[s.cycles for s in live],
                iterations=[s.iterations for s in live],
                host_syncs=max(s.host_syncs for s in live)))

    def row_done(self):
        t = time.perf_counter()
        self.rows.append(t)
        if self.on_row is not None:
            self.on_row(t)
        if (self.deadline is not None and t >= self.deadline) or \
                (self.max_rows is not None and len(self.rows) >= self.max_rows):
            raise WindowClosed


# ------------------------------------------------------------ job kinds
def _record_solver(solver, window: Window):
    inner = solver.solve_batch

    def solve_batch(ops, b, padded_rows=None):
        xs, stats = inner(ops, b, padded_rows=padded_rows)
        window.dispatch(stats)
        return xs, stats

    solver.solve_batch = solve_batch
    return solver


def _observed(work_cls, window: Window):
    """The work adapter `work_cls` with each lockstep row's end stamped and
    each solver dispatch recorded in `window`; nothing else changes."""

    class Work(work_cls):
        def make_lockstep_solver(self, sharding=None):
            return _record_solver(super().make_lockstep_solver(sharding),
                                  window)

        def execute_row(self, solver, t, idx, prepared):
            super().execute_row(solver, t, idx, prepared)
            window.row_done()

    return Work


def _krylov(config):
    from repro.solvers.types import KrylovConfig

    return KrylovConfig(**config["krylov"])


def _check_config(config):
    if config.get("engine") != "batched" or config.get("retry") != "default":
        raise ValueError("the harness runs engine 'batched' with the default "
                         "RetryPolicy; got engine "
                         f"{config.get('engine')!r}, retry "
                         f"{config.get('retry')!r}")


class SteadyJob:
    """Jobs of steady systems: each label is one (K, u) pair."""

    def __init__(self, config: dict, traffic: dict):
        from repro.core.skr import SKRConfig
        from repro.pde.registry import get_family

        _check_config(config)
        self.config, self.traffic = config, traffic
        self.family = get_family(config["family"], **config["family_params"])
        self.cfg = SKRConfig(krylov=_krylov(config),
                             sort_method=config["sort_method"],
                             precond=config["precond"],
                             use_kernel=config["use_kernel"],
                             strict_labels="flag")
        # the label guarantee as the configuration states it: tol, and the
        # rounding by which two fp64 evaluations of a residual differ
        self.limit = config["label_residual_limit"]

    def work(self, window: Window):
        from repro.core.skr import SteadyWork

        return _observed(SteadyWork, window)(self.family, self.cfg)

    def emitted(self, work, results, rows):
        """The labels of a job's first `rows` lockstep rows, in the form
        the check reads."""
        inputs, labels, ok, own = [], [], [], []
        from repro.core.robust import is_healthy

        for res in results:
            r = len(res.order) if rows is None else min(rows, len(res.order))
            st = res.stats.solved[:r]
            inputs.append(res.inputs[:r])
            labels.append(res.solutions[:r])
            ok.append(np.array([is_healthy(s) for s in st], bool)
                      if rows is not None else res.label_ok[:r])
            own.append([s.rel_residual for s in st])
        return dict(input=np.concatenate(inputs), label=np.concatenate(labels),
                    ok=np.concatenate(ok), own=np.concatenate(own))

    def check(self, ems, block: int = 256) -> dict:
        """Every label flagged ok, by its true residual against the system
        assembled from its emitted K. `gap`, not compared: the largest
        relative gap between the program's own residual of a label and the
        reference's (the rounding of two fp64 evaluations)."""
        worst, over, gap = 0.0, 0, 0.0
        for em in ems:
            sel = np.nonzero(em["ok"])[0]
            for i in range(0, len(sel), block):
                part = sel[i:i + block]
                a, b = reference.darcy_system(
                    em["input"][part], self.config["family_params"]["source"])
                res = reference.stencil_residual(a, em["label"][part], b)
                worst = max(worst, _worst(res))
                over += int((~(res <= self.limit)).sum())
                gap = max(gap, _worst(np.abs(em["own"][part] - res) / res))
        return {"max_res": dict(value=worst, limit=self.limit), "over": over,
                "gap": gap}


def _worst(res) -> float:
    """Largest residual; a non-finite one reads as infinity."""
    res = np.asarray(res, np.float64)
    return float("inf") if not np.isfinite(res).all() else \
        float(res.max(initial=0.0))


def due(n: int, chains: int, rows=None) -> int:
    """Labels a job of n items on `chains` chains owes after `rows` lockstep
    rows (all of them when rows is None): the pipeline splits the sorted
    order into chains whose lengths differ by at most one."""
    sizes = [len(c) for c in np.array_split(np.arange(n), chains)]
    return sum(sizes) if rows is None else sum(min(rows, c) for c in sizes)


def run_job(jobs, key, window: Window):
    """One job through the pipeline. Returns (its emitted labels, the
    labels it owes, whether the window closed inside it)."""
    from repro.core import pipeline

    work = jobs.work(window)
    rows0 = len(window.rows)
    n, chains = jobs.traffic["items_per_job"], jobs.traffic["chains"]
    try:
        results = pipeline.run_chunked(work, key, n, chains, "batched")
        closed = False
    except WindowClosed:
        results = [work.chunk_result(w) for w in range(chains)]
        closed = True
    rows = len(window.rows) - rows0 if closed else None
    return jobs.emitted(work, results, rows), due(n, chains, rows), closed


# ------------------------------------------------------------- tracing
class _Both:
    __slots__ = ("a", "b")

    def __init__(self, a, b):
        self.a, self.b = a, b

    def __enter__(self):
        self.a.__enter__()
        self.b.__enter__()
        return self

    def __exit__(self, *exc):
        self.b.__exit__(*exc)
        return self.a.__exit__(*exc)


def enable_spans(on_span=None, capacity: int = 1 << 20):
    """Turn on the program's spans with its device Krylov telemetry off
    (a telemetry capacity is a static argument of the cycle programs, so
    anything else would run other programs than the untraced run), and
    mirror every span into the profiler as a host annotation. `on_span` is
    called on the main thread as each span opens."""
    import jax
    from repro import obs

    main = threading.main_thread().ident

    class AnnotatingTracer(obs.Tracer):
        def span(self, name, cat="datagen", **args):
            if on_span is not None and threading.get_ident() == main:
                on_span(time.perf_counter())
            return _Both(super().span(name, cat, **args),
                         jax.profiler.TraceAnnotation(name))

    obs.enable(trace_capacity=capacity, krylov_capacity=0)
    # obs.enable() builds a plain Tracer; the annotating one takes its place
    obs._TRACER = AnnotatingTracer(capacity=capacity)


class Slice:
    """The traced slice: TRACE_SECONDS from the first lockstep row end past
    TRACE_FROM of the window, so that it holds the start of a row (its
    entry and first cycles) as well as the cycles of a row in flight. The
    main thread closes it at the first span or row end past its length (a
    cycle's flag fetch comes every few hundred ms at most). `paused_s` is
    the time that starting and stopping the profiler took in the window;
    a slice still open when the window closes is stopped after it."""

    def __init__(self, window: Window, seconds: float, out_dir: str):
        self.window, self.out_dir = window, out_dir
        self.start_at = window.t0 + TRACE_FROM * seconds
        self.ann = None
        self.done = False
        self.paused_s = 0.0

    def row_end(self, t):
        import jax

        if self.ann is None and not self.done and t >= self.start_at:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # annotations only, no frames
            jax.profiler.start_trace(self.out_dir, profiler_options=opts)
            self.ann = jax.profiler.TraceAnnotation(trace_mod.SLICE)
            self.ann.__enter__()
            now = time.perf_counter()
            self.paused_s += now - t
            self.end_at = now + TRACE_SECONDS
        else:
            self.tick(t)

    def tick(self, t):
        if self.ann is not None and not self.done and t >= self.end_at:
            self.close(in_window=True)

    def close(self, in_window: bool = False):
        import jax

        if self.ann is not None and not self.done:
            t = time.perf_counter()
            self.ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            if in_window:
                self.paused_s += time.perf_counter() - t
        self.done = True


# ------------------------------------------------------------------ run
def base_key(seed: int):
    import jax

    s = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)


def run(config: dict, traffic: dict, seed: int, seconds: float,
        traced: bool, t_start: float) -> dict:
    """One run of a cell: set-up (warm-up through two rows of a job of the
    cell's own shapes), the window, then the check. Returns the record the
    metric readers take."""
    import jax

    counter = CompileCounter()
    if traced:
        enable_spans()
    jobs = SteadyJob(config, traffic)
    base = base_key(seed)
    run_job(jobs, jax.random.fold_in(base, 1), Window(max_rows=2))
    warm_compiles, warm_hits = counter.n, counter.hits

    window = Window()
    window.open(seconds)
    if traced:
        import shutil

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        slice_ = Slice(window, seconds, TRACE_DIR)
        window.on_row = slice_.row_end
        enable_spans(slice_.tick)   # fresh buffers: the window's spans only
    setup_s = window.t0 - t_start
    ems, owed, n_jobs = [], 0, 0
    win_key = jax.random.fold_in(base, 0)
    try:
        while True:
            em, n_due, closed = run_job(
                jobs, jax.random.fold_in(win_key, n_jobs), window)
            ems.append(em)
            owed += n_due
            n_jobs += 1
            if closed or time.perf_counter() >= window.deadline:
                break
    finally:
        if traced:
            slice_.close()
    t_end = window.rows[-1] if closed else time.perf_counter()
    window_compiles = counter.n - warm_compiles
    record = dict(setup_s=setup_s, window_s=t_end - window.t0,
                  t0_ns=int(window.t0 * 1e9), t1_ns=int(t_end * 1e9),
                  jobs=n_jobs, rows=len(window.rows),
                  labels_ok=sum(int(e["ok"].sum()) for e in ems),
                  labels=sum(len(e["ok"]) for e in ems), labels_due=owed,
                  dispatches=window.dispatches,
                  compiles=dict(setup=warm_compiles, setup_cache_hits=warm_hits,
                                window=window_compiles),
                  config=config, traffic=traffic)
    if traced:
        from repro import obs

        record["profiler_s"] = slice_.paused_s
        main = threading.main_thread().ident
        record["spans"] = [(e["name"], e["ts"], e["dur"])
                           for e in obs.tracer().snapshot()
                           if e.get("ph") == "X" and e.get("tid") == main]
        obs.disable()
    record["memory_peak_bytes"] = _peak_bytes()
    record["emitted"] = ems
    record["job_kind"] = jobs
    return record


def _peak_bytes() -> int:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def check(record: dict) -> dict:
    """The numbers compared, each with its limit; frees the labels.
    `failed`: labels the program flagged failed, and labels it flagged ok
    that miss tol; `missing`: labels the window's rows owed and did not
    emit. A sound run reads 0 for both."""
    jobs, ems = record.pop("job_kind"), record.pop("emitted")
    checks = jobs.check(ems)
    record["residual_gap"] = checks.pop("gap")
    checks["failed"] = dict(value=record["labels"] - record["labels_ok"]
                            + checks.pop("over"), limit=0)
    checks["missing"] = dict(value=record["labels_due"] - record["labels"],
                             limit=0)
    return checks


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
