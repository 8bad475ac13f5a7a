"""Share of the window the main thread spent at row boundaries, in %: the
own time of the program's row-boundary spans (the entry and finalize
fetches, the carry's upload and store, `solve_batch`'s and `execute_row`'s
own time, `prefetch_wait`), over the window. The time the profiler took to
start and stop inside the window is left out of both: it starts at a row
end, inside `execute_row`, and stops at the first span that opens past the
slice's end, inside `solve_batch` while a row runs (a row lasts longer
than the slice). Nothing is read when the pause outlasts those spans
(bench/phases.py)."""
from bench import phases


def read(record, trace=None):
    tracer, _ = phases.last_session()
    if tracer is None:
        return None
    lo, hi = record["t0_ns"], record["t1_ns"]
    own = phases.self_times(phases.main_thread_spans(tracer), lo, hi)
    if not any(k in own for k in phases.ROW_SPANS if k != "execute_row"):
        return None
    paused = record.get("profiler_s", 0.0)
    held = sum(own.get(k, 0.0) for k in phases.ROW_SPANS) - paused
    return 100.0 * held / ((hi - lo) / 1e9 - paused) if held >= 0 else None
