"""repro.obs — opt-in observability for the datagen pipeline.

One process-global switch gates three signal families:

* **spans** (`obs.span(...)`) — nested wall-time tracing over pipeline
  phases, ring-buffered, exportable as JSONL or a Chrome/Perfetto
  `trace.json`, and mirrored into the JAX profiler as `skr:<name>` host
  annotations (`obs/trace.py`);
* **device Krylov telemetry** — per-cycle per-chain convergence rings the
  lockstep solver accumulates ON DEVICE and drains in its one finalize
  fetch (`obs/telemetry.py`; threaded through `solvers/batched.py`);
* **counters/gauges** (`obs.record_dispatch(...)`, `obs.hostlink(...)`) —
  lockstep utilization and efficiency, and the bytes the row path moves
  between host and device, merged into `SequenceStats.summary()`
  (`obs/metrics.py`).

Disabled (the default, and the state every import starts in) the
instrumentation compiles out: `span()` is a `None`-check returning a shared
no-op, `krylov_capacity()` returns 0 so the jitted cycle programs trace
WITHOUT telemetry buffers (identical jaxprs → bitwise-identical numerics,
zero extra dispatches — regression-tested in tests/test_obs.py), and
`record_dispatch` and `hostlink` return immediately.

Usage:

    from repro import obs
    obs.enable(delta_qc=True)
    ... run datagen ...
    obs.export_chrome_trace("results/TRACE_heat.json")
    print(obs.summary()["utilization"])
    obs.disable()
    tracer, registry = obs.last()   # the closed session, still readable
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.obs.metrics import Registry
from repro.obs.telemetry import (KrylovTelemetry, TelemetryConfig,
                                 drain_chain, ring_order)
from repro.obs.trace import NULL_SPAN, Tracer

__all__ = [
    "enable", "disable", "enabled", "span", "instant", "counter",
    "counter_add", "gauge_set", "tracer", "registry", "record_dispatch",
    "record_stream", "hostlink", "last", "krylov_capacity",
    "delta_enabled", "summary", "export_chrome_trace", "export_jsonl",
    "KrylovTelemetry", "TelemetryConfig", "drain_chain", "ring_order",
    "Tracer", "Registry",
]

_TRACER: Optional[Tracer] = None
_REGISTRY: Optional[Registry] = None
_KRYLOV: Optional[TelemetryConfig] = None
# the (tracer, registry) of the session the last disable() closed
_LAST: tuple = (None, None)


def enable(trace_capacity: int = 65536, krylov_capacity: int = 128,
           delta_qc: bool = False):
    """Turn observability ON (idempotent: re-enabling starts fresh buffers).

    krylov_capacity: device ring slots per chain for per-cycle convergence
    telemetry; it is a STATIC argument of the lockstep cycle programs, so
    the first telemetry-on solve per shape pays a retrace. 0 disables the
    device rings while keeping spans/counters live.
    delta_qc: also record the per-cycle δ(Q,C) recycle-refresh angle (adds
    one (k×k) SVD to the fused deflated-cycle program).
    """
    global _TRACER, _REGISTRY, _KRYLOV, _LAST
    _LAST = (None, None)
    _TRACER = Tracer(capacity=trace_capacity)
    _REGISTRY = Registry()
    _KRYLOV = TelemetryConfig(capacity=max(int(krylov_capacity), 1),
                              delta_qc=bool(delta_qc)) \
        if krylov_capacity > 0 else None


def disable():
    """Turn observability OFF. The closed session's tracer and registry stay
    readable through `last()` until the next `enable()`; nothing records
    into them any more."""
    global _TRACER, _REGISTRY, _KRYLOV, _LAST
    if _TRACER is not None:
        _LAST = (_TRACER, _REGISTRY)
    _TRACER = None
    _REGISTRY = None
    _KRYLOV = None


def enabled() -> bool:
    return _TRACER is not None


def last() -> tuple:
    """(tracer, registry) of the session the last `disable()` closed;
    (None, None) before any, and from the next `enable()` on."""
    return _LAST


# ---------------------------------------------------------------- tracing
def span(name: str, cat: str = "datagen", **args):
    """Context manager timing one phase; free no-op when disabled."""
    t = _TRACER
    if t is None:
        return NULL_SPAN
    return t.span(name, cat, **args)


def instant(name: str, cat: str = "datagen", **args):
    t = _TRACER
    if t is not None:
        t.instant(name, cat, **args)


def counter(name: str, values: dict, cat: str = "datagen"):
    t = _TRACER
    if t is not None:
        t.counter(name, values, cat=cat)


def tracer() -> Optional[Tracer]:
    return _TRACER


# --------------------------------------------------------------- registry
def registry() -> Optional[Registry]:
    return _REGISTRY


def counter_add(name: str, value: float = 1.0):
    """Bump a registry counter; free no-op when disabled. The containment
    layer (core/robust.py, solvers/batched.py) reports retry / quarantine /
    fault events through this — e.g. `health.retries`,
    `health.quarantined`, `faults.nan_rhs`."""
    r = _REGISTRY
    if r is not None:
        r.counter_add(name, value)


def gauge_set(name: str, value: float):
    """Set a last-value registry gauge; free no-op when disabled. The
    label-expansion stage reports its headline rate through this
    (`expand.labels_per_second`)."""
    r = _REGISTRY
    if r is not None:
        r.gauge_set(name, value)


def record_dispatch(live: int, total: int, iters=None, cycles=None):
    """Lockstep occupancy hook (see Registry.record_dispatch); also samples
    a Chrome counter track so utilization renders on the trace timeline."""
    r = _REGISTRY
    if r is None:
        return
    r.record_dispatch(live, total, iters=iters, cycles=cycles)
    t = _TRACER
    if t is not None:
        t.counter("lockstep_rows", {"live": live, "padded": total - live})


def record_stream(queue_depth: int, occupied: int, slots: int):
    """Streaming-scheduler occupancy hook (see Registry.record_stream);
    also samples a Chrome counter track so queue depth and slot occupancy
    render on the trace timeline next to `lockstep_rows`."""
    r = _REGISTRY
    if r is None:
        return
    r.record_stream(queue_depth, occupied, slots)
    t = _TRACER
    if t is not None:
        t.counter("stream", {"queue": queue_depth, "occupied": occupied,
                             "free": slots - occupied}, cat="serve")


def hostlink(direction: str, *arrays):
    """Count the bytes of `arrays` (pytrees of numpy or device arrays, by
    `nbytes`) moved host→device (`direction` "h2d") or device→host ("d2h")
    into the counter `hostlink.<direction>_bytes`; free no-op when
    disabled. The row path calls it at each explicit put and fetch."""
    r = _REGISTRY
    if r is None:
        return
    r.counter_add(f"hostlink.{direction}_bytes",
                  float(sum(a.nbytes for a in jax.tree.leaves(arrays))))


# --------------------------------------------------- device Krylov config
def krylov_capacity() -> int:
    """Static ring capacity for the lockstep cycle programs (0 = compiled
    out: no buffers in the state dict, jaxpr identical to pre-telemetry)."""
    k = _KRYLOV
    return k.capacity if k is not None else 0


def delta_enabled() -> bool:
    k = _KRYLOV
    return k.delta_qc if k is not None else False


# ---------------------------------------------------------------- exports
def summary() -> dict:
    """Counters/gauges/utilization snapshot ({} when disabled)."""
    r = _REGISTRY
    return r.snapshot() if r is not None else {}


def export_chrome_trace(path: str) -> bool:
    t = _TRACER
    if t is None:
        return False
    t.to_chrome_trace(path)
    return True


def export_jsonl(path: str) -> bool:
    t = _TRACER
    if t is None:
        return False
    t.to_jsonl(path)
    return True
