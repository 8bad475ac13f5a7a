"""Counters/gauges registry + lockstep-utilization accounting.

The registry is the scalar side of the telemetry layer: monotonically
increasing counters (dispatches, live/padded lockstep rows, Krylov
iterations and cycles, bytes over the host link) and last-value gauges
(the streaming scheduler's queue and slots). It is
what `SequenceStats.summary()` merges in when observability is enabled, and
what the future streaming scheduler will read live — the ">80% non-padded
rows" target of the ROADMAP's online-scheduler item is exactly
`utilization()` here.

Occupancy convention: every lockstep `solve_batch` dispatch records how many
chain rows were LIVE vs PADDED (zero-RHS fill: shorter chunks, sharding
fill, phase-masked finished chains). `utilization()` is the live fraction
over all dispatched rows — device work actually spent on real systems.
Lockstep efficiency is `lockstep.cycles_needed / lockstep.cycles_paid`:
the cycles the live chains needed over live chains × the dispatch's
largest cycle count, summed over dispatches (the slowest chain sets how
long every chain of the SPMD program runs). 1.0 means perfect lockstep.
"""
from __future__ import annotations

import threading


class Registry:
    """Thread-safe counters + gauges (plain floats, no label sets — the
    datagen pipeline is one process; shard axes live in the values)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.counters: dict[str, float] = {}
        self.gauges: dict[str, float] = {}

    def counter_add(self, name: str, value: float = 1.0):
        with self._lock:
            self.counters[name] = self.counters.get(name, 0.0) + value

    def gauge_set(self, name: str, value: float):
        with self._lock:
            self.gauges[name] = float(value)

    # --------------------------------------------- lockstep occupancy
    def record_dispatch(self, live: int, total: int, iters=None,
                        cycles=None):
        """One lockstep solve_batch dispatch: `live` non-padded rows out of
        `total`; `iters` and `cycles` = per-LIVE-chain iteration and cycle
        counts. `krylov.cycles` gains the dispatch's largest cycle count,
        `lockstep.cycles_needed` their sum and `lockstep.cycles_paid` live
        chains × the largest."""
        with self._lock:
            c = self.counters
            c["lockstep.dispatches"] = c.get("lockstep.dispatches", 0.0) + 1
            c["lockstep.rows_live"] = c.get("lockstep.rows_live", 0.0) + live
            c["lockstep.rows_total"] = (c.get("lockstep.rows_total", 0.0)
                                        + total)
        if iters is not None and len(iters) > 0:
            self.counter_add("krylov.iterations", float(sum(iters)))
        if cycles is not None and len(cycles) > 0:
            most = float(max(cycles))
            self.counter_add("krylov.cycles", most)
            self.counter_add("lockstep.cycles_needed", float(sum(cycles)))
            self.counter_add("lockstep.cycles_paid", len(cycles) * most)

    # ------------------------------------------- streaming occupancy
    def record_stream(self, queue_depth: int, occupied: int, slots: int):
        """One streaming-scheduler tick (core/serve.py): current request
        queue depth and slot occupancy. Gauges carry the live values; the
        tick counter gives the sample count."""
        self.gauge_set("stream.queue_depth", queue_depth)
        self.gauge_set("stream.slots_occupied", occupied)
        self.gauge_set("stream.slots_total", slots)
        self.counter_add("stream.ticks")

    def lockstep_eff(self):
        """cycles_needed / cycles_paid over all dispatches (None before a
        dispatch with cycles)."""
        with self._lock:
            paid = self.counters.get("lockstep.cycles_paid", 0.0)
            need = self.counters.get("lockstep.cycles_needed", 0.0)
        return need / paid if paid > 0 else None

    def utilization(self) -> float:
        """Live fraction of all dispatched lockstep rows (1.0 = no padding;
        the streaming-scheduler target reads >0.8 here)."""
        with self._lock:
            total = self.counters.get("lockstep.rows_total", 0.0)
            live = self.counters.get("lockstep.rows_live", 0.0)
        return live / total if total > 0 else 1.0

    def snapshot(self) -> dict:
        with self._lock:
            out = {"counters": dict(self.counters),
                   "gauges": dict(self.gauges)}
        out["utilization"] = self.utilization()
        return out

    def reset(self):
        with self._lock:
            self.counters.clear()
            self.gauges.clear()
