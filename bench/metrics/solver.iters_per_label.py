"""Krylov iterations of the window's dispatches (refinement passes
included), per label emitted."""


def read(record, trace=None):
    if not record["labels"]:
        return None
    return sum(sum(d["iterations"]) for d in record["dispatches"]) \
        / record["labels"]
