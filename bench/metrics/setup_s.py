"""Process start to window start: imports, compile-cache load or compile,
the warm-up job's sampling and two rows (host clock)."""


def read(record, trace=None):
    return record["setup_s"]
