"""Lockstep efficiency, in %: over the window's solver dispatches, the
cycles the live chains needed, over live chains times the dispatch's
largest cycle count (the slowest chain sets the dispatch's length)."""


def read(record, trace=None):
    need = sum(sum(d["cycles"]) for d in record["dispatches"])
    paid = sum(len(d["cycles"]) * max(d["cycles"])
               for d in record["dispatches"])
    return 100.0 * need / paid if paid else None
