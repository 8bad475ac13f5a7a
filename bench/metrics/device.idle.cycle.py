"""Share of the traced slice in which the chip is idle while the main
thread's innermost program span (its `skr:` annotation, or the program's
own record of a span open when the profiler started or stopped) is a
cycle's flag fetch (`host_sync.cycle_flags`) or a cycle program's launch
(`cycle_dispatch`), in % (bench/phases.py)."""
from bench import phases


def read(record, trace=None):
    p = phases.of(record, trace)
    if p is None or set(p.gaps_s) <= {phases.NONE}:
        return None
    return p.idle_share(*phases.CYCLE_SPANS)
