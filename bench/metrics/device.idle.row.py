"""Share of the traced slice in which the chip is idle while the main
thread's innermost program span (its `skr:` annotation, or the program's
own record of a span open when the profiler started or stopped) is one of
the row boundary's: the entry and finalize fetches, the carry's upload and
store, the own time of `solve_batch` and `execute_row`, or
`prefetch_wait`, in %. The rest of `device.idle` (under other spans, or
none) goes as an extra (bench/phases.py)."""
from bench import phases


def read(record, trace=None):
    p = phases.of(record, trace)
    if p is None or set(p.gaps_s) <= {phases.NONE}:
        return None
    row = p.idle_share(*phases.ROW_SPANS)
    cycle = p.idle_share(*phases.CYCLE_SPANS)
    return {"value": row,
            "rest": p.idle_share(*p.gaps_s) - row - cycle}
