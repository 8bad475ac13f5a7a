"""Structured tracing: nested spans over the datagen pipeline, recorded to
an in-memory ring buffer.

The tracer is a process-global singleton toggled by `obs.enable()` /
`obs.disable()` (see `obs/__init__.py`). Disabled — the default — every
entry point degenerates to a `None` check returning a shared no-op object,
so instrumented hot loops (the per-cycle flag fetch of the lockstep solver)
pay one attribute load when tracing is off and NOTHING is allocated.

Spans carry (name, category, start, duration, thread id, attrs). Each
live span is also mirrored into the JAX profiler as a host annotation
(`jax.profiler.TraceAnnotation`) named `skr:<name>`, with `.<what>` added
for a span that carries a `what` argument (`skr:host_sync.cycle_flags`),
so a profile taken while tracing is on places the program's own phases on
the profiler's clock, next to the device operations; the `skr:` prefix
tells them from JAX's own annotations. Outside a profile an annotation
costs one native call on entry and exit. The ring
buffer (`collections.deque(maxlen=...)`) bounds memory on long trajectory
runs: old events fall off the front, and `dropped` counts them so exports
are honest about truncation.

Two export formats:

* `to_jsonl(path)` — one JSON object per line, trivially greppable and
  stream-parsable (the "telemetry JSONL" CI artifact).
* `to_chrome_trace(path)` — the Chrome trace-event format: open the file in
  `chrome://tracing` or https://ui.perfetto.dev and the prefetch thread's
  `prepare_row` spans render on their OWN track, visually overlapped (or
  not!) with the main thread's `solve_dispatch` spans. Occupancy counter
  events render as a counter track, so lockstep utilization is inspectable
  on the same timeline.
"""
from __future__ import annotations

import json
import threading
import time
from collections import deque
from typing import Optional

from jax.profiler import TraceAnnotation


class _NullSpan:
    """Shared no-op context manager returned while tracing is disabled."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


class _Span:
    """One live span: records itself into the tracer's ring on exit, and
    holds its profiler annotation open while it runs."""

    __slots__ = ("tracer", "name", "cat", "args", "t0", "ann")

    def __init__(self, tracer: "Tracer", name: str, cat: str, args: dict):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self.t0 = 0
        self.ann = None

    def __enter__(self):
        what = self.args.get("what")
        self.ann = TraceAnnotation(f"skr:{self.name}"
                                   + (f".{what}" if what else ""))
        self.ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self.ann.__exit__(*exc)
        self.tracer._record({
            "ph": "X", "name": self.name, "cat": self.cat,
            "ts": self.t0, "dur": t1 - self.t0,
            "tid": threading.get_ident(),
        } | ({"args": self.args} if self.args else {}))
        return False


class Tracer:
    """Ring-buffered span/counter recorder (thread-safe appends)."""

    def __init__(self, capacity: int = 65536):
        self.capacity = int(capacity)
        self.events: deque = deque(maxlen=self.capacity)
        self.dropped = 0
        self._lock = threading.Lock()
        self._tid_names: dict[int, str] = {}
        self.epoch_ns = time.perf_counter_ns()

    # ------------------------------------------------------------- record
    def _record(self, ev: dict):
        tid = ev.get("tid")
        with self._lock:
            if tid is not None and tid not in self._tid_names:
                self._tid_names[tid] = threading.current_thread().name
            if len(self.events) == self.capacity:
                self.dropped += 1
            self.events.append(ev)

    def span(self, name: str, cat: str = "datagen", **args) -> _Span:
        return _Span(self, name, cat, args)

    def instant(self, name: str, cat: str = "datagen", **args):
        self._record({"ph": "i", "name": name, "cat": cat,
                      "ts": time.perf_counter_ns(),
                      "tid": threading.get_ident()}
                     | ({"args": args} if args else {}))

    def counter(self, name: str, values: dict, cat: str = "datagen"):
        """A Chrome counter sample ("C" event) — e.g. the per-dispatch
        live/padded lockstep occupancy timeline."""
        self._record({"ph": "C", "name": name, "cat": cat,
                      "ts": time.perf_counter_ns(),
                      "tid": threading.get_ident(), "args": values})

    # ------------------------------------------------------------ analyze
    def snapshot(self) -> list[dict]:
        with self._lock:
            return list(self.events)

    def phase_seconds(self) -> dict[str, float]:
        """Total seconds per span name (complete spans only) — the
        time-per-phase breakdown of the run report."""
        acc: dict[str, float] = {}
        for ev in self.snapshot():
            if ev.get("ph") == "X":
                acc[ev["name"]] = acc.get(ev["name"], 0.0) \
                    + ev["dur"] / 1e9
        return acc

    # ------------------------------------------------------------- export
    def _export_events(self) -> list[dict]:
        evs = self.snapshot()
        out = []
        for ev in evs:
            e = dict(ev)
            e["pid"] = 0
            e["ts"] = (e["ts"] - self.epoch_ns) / 1e3      # µs since enable
            if "dur" in e:
                e["dur"] = e["dur"] / 1e3
            out.append(e)
        return out

    def to_jsonl(self, path: str):
        """One event per line; a leading meta line records drop counts so a
        truncated ring is visible to consumers."""
        with open(path, "w") as f:
            f.write(json.dumps({"meta": {"events": len(self.events),
                                         "dropped": self.dropped,
                                         "capacity": self.capacity}}) + "\n")
            for ev in self._export_events():
                f.write(json.dumps(ev) + "\n")

    def to_chrome_trace(self, path: str):
        """Chrome/Perfetto trace.json (load in chrome://tracing)."""
        events = self._export_events()
        with self._lock:
            tid_names = dict(self._tid_names)
        # thread-name metadata rows: the prefetch executor thread shows up
        # named, so the prefetch/solve overlap is readable at a glance
        for tid, tname in tid_names.items():
            events.append({"ph": "M", "name": "thread_name", "pid": 0,
                           "tid": tid, "args": {"name": tname}})
        with open(path, "w") as f:
            json.dump({"traceEvents": events,
                       "displayTimeUnit": "ms",
                       "otherData": {"dropped_events": self.dropped}}, f)
