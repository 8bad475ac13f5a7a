"""Device self time under `skr/arnoldi` outside its matvec and orthog
children (basis writes, Givens, norms and the selects `vmap` puts on the
loop's carry), over the device's busy time in the traced slice, in %
(bench/phases.py)."""
from bench import phases


def read(record, trace=None):
    p = phases.of(record, trace)
    if p is None or not p.scoped:
        return None
    return p.share("basis")
