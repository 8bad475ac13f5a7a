"""Device self time under `skr/arnoldi/matvec` (the stencil matvec and
Jacobi apply, or the fused Arnoldi step on the kernel path), over the
device's busy time in the traced slice, in % (bench/phases.py)."""
from bench import phases


def read(record, trace=None):
    p = phases.of(record, trace)
    if p is None or not p.scoped:
        return None
    return p.share("matvec")
