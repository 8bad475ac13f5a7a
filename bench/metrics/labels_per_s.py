"""Labels emitted in the window that met the configuration's tol, over the
time from the window's start to the end of its last row (host clock)."""


def read(record, trace=None):
    return record["labels_ok"] / record["window_s"]
