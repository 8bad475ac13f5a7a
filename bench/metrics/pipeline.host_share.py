"""Share of the window the main thread spent in the pipeline's host stages
between rows (the program's `sample`, `sort`, `chain_partition` and
`prefetch_wait` spans), in %. The time the profiler took to start and stop
inside the window is left out of it."""

STAGES = ("sample", "sort", "chain_partition", "prefetch_wait")


def read(record, trace=None):
    spans = record.get("spans")
    if spans is None:
        return None
    lo, hi = record["t0_ns"], record["t1_ns"]
    held = sum(max(0, min(ts + dur, hi) - max(ts, lo))
               for name, ts, dur in spans if name in STAGES)
    return 100.0 * held / (hi - lo - 1e9 * record.get("profiler_s", 0.0))
