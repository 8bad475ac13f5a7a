"""Bytes the row path moved between host and device in the window (the
program's counters `hostlink.h2d_bytes` and `hostlink.d2h_bytes`), in MiB
per label emitted; each direction goes as an extra."""
from bench import phases

MIB = float(1 << 20)


def read(record, trace=None):
    _, registry = phases.last_session()
    if registry is None or not record["labels"]:
        return None
    c = registry.snapshot()["counters"]
    if "hostlink.h2d_bytes" not in c:
        return None
    per = MIB * record["labels"]
    h2d, d2h = c["hostlink.h2d_bytes"] / per, \
        c.get("hostlink.d2h_bytes", 0.0) / per
    return {"value": h2d + d2h, "h2d": h2d, "d2h": d2h}
