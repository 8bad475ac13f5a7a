"""Blocking device-to-host fetches of the window's dispatches (one count
per dispatch, shared by its chains), per label emitted."""


def read(record, trace=None):
    if not record["labels"]:
        return None
    return sum(d["host_syncs"] for d in record["dispatches"]) \
        / record["labels"]
