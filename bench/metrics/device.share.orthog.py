"""Device self time under `skr/arnoldi/orthog` (the C-projection and CGS2
or MGS), over the device's busy time in the traced slice, in %
(bench/phases.py)."""
from bench import phases


def read(record, trace=None):
    p = phases.of(record, trace)
    if p is None or not p.scoped:
        return None
    return p.share("orthog")
