"""Device self time of operations whose op name has no `skr` scope (the
pipeline's own device work: the row's operator gather and
preconditioner), over the device's busy time in the traced slice, in %.
The rest of the busy time goes as extras, so that the five
`device.share.*` metrics and these sum to 100: `entry`, `update` and
`finalize` (under those scopes), and `inserted` (operations XLA inserted
at a program's top level, which carry no op name) (bench/phases.py)."""
from bench import phases


def read(record, trace=None):
    p = phases.of(record, trace)
    if p is None or not p.scoped:
        return None
    return {"value": p.share("unscoped"), "entry": p.share("entry"),
            "update": p.share("update"), "finalize": p.share("finalize"),
            "inserted": p.share(phases.INSERTED), "busy_s": p.busy_s}
