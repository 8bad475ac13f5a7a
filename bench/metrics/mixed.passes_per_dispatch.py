"""Refinement passes per lockstep solver dispatch in the window (the
program's counters `mixed.passes_fp32` + `mixed.passes_fp64` over
`mixed.dispatches`)."""
from bench import phases


def read(record, trace=None):
    _, registry = phases.last_session()
    if registry is None:
        return None
    c = registry.snapshot()["counters"]
    n = c.get("mixed.dispatches", 0.0)
    if n <= 0:
        return None
    passes = c.get("mixed.passes_fp32", 0.0) + c.get("mixed.passes_fp64", 0.0)
    return {"value": passes / n, "dispatches": n}
