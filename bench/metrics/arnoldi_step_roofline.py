"""The fused `arnoldi_step` kernel's share of the chip's HBM roofline in
the traced slice, in %: the bytes its calls move (bench/cost.py, from its
BlockSpecs) at the chip's HBM bandwidth (bench/peaks.json), over their
device time. Extras: calls, kernel_s, gb_per_s, gflop_per_s."""
from bench import cost, harness, phases
from bench import trace as xtrace


def read(record, trace=None):
    if trace is None or not trace.ops:
        return None
    lo, hi = trace.slice
    ops = phases.read(xtrace.find(harness.TRACE_DIR))
    return cost.roofline([ev for chip in ops for ev in chip], lo, hi,
                         record["config"], record["traffic"],
                         record["device_kind"])
