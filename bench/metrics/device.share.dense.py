"""Device self time under `skr/lstsq` and `skr/ritz` (the stacked dense
work of `solvers/devlinalg.py` and its call sites: the least squares, the
harmonic Ritz pencils, the refresh of C and U), over the device's busy
time in the traced slice, in %; the two parts go as extras
(bench/phases.py)."""
from bench import phases


def read(record, trace=None):
    p = phases.of(record, trace)
    if p is None or not p.scoped:
        return None
    return {"value": p.share("lstsq", "ritz"), "lstsq": p.share("lstsq"),
            "ritz": p.share("ritz")}
