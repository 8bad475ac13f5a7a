"""Share of the traced slice in which no operation ran on the chip, in %:
1 - (union of device operation intervals) / (slice length)."""


def read(record, trace=None):
    summ = record.get("trace_summary")
    if summ is None or summ.chips == 0:
        return None
    return 100.0 * summ.idle_share
