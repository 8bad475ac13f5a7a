"""Reduction of a profiler trace (`.xplane.pb`) to the benchmark's device
numbers: busy time (the union of the intervals in which an operation ran on
a chip), idle share, device time per operation, and the idle
gaps named by the host annotation the main thread was in.

Everything is clipped to the traced slice: the interval of the host
annotation named `SLICE` that the harness opens around the rows it traces.
`read` turns the file into plain tuples; the reductions below work on those
tuples, so a test can hand them a trace written by hand.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Tuple

SLICE = "bench_slice"
DEVICE_PREFIX = "/device:TPU:"
# the device line that holds one event per executed HLO operation
OPS_LINE = "XLA Ops"
# where the profiler says it lost device events
TRACEME, DROPPED = "XLA TraceMe", "Trace Buffers Dropped"

Span = Tuple[str, float, float]          # (name, start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    """slice: (start_ns, end_ns) of the traced slice; ops: per chip, the
    device operations (name, start, end); host: the main thread's
    annotations (name, start, end)."""
    slice: Tuple[float, float]
    ops: List[List[Span]]
    host: List[Span]


def find(profile_dir: str) -> str:
    """The newest `.xplane.pb` under a `jax.profiler` output directory."""
    paths = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    return max(paths, key=os.path.getmtime)


def read(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host_lines, chips = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            chips.append(plane)
        elif plane.name.startswith("/host:"):
            host_lines.extend(plane.lines)
    main, window = None, None
    for line in host_lines:
        for ev in line.events:
            if ev.name == SLICE:
                main, window = line, (ev.start_ns, ev.end_ns)
                break
        if main is not None:
            break
    if main is None:
        raise ValueError(f"no {SLICE!r} annotation in {path}")
    host = [(ev.name, ev.start_ns, ev.end_ns) for ev in main.events
            if ev.name != SLICE]
    ops = []
    for plane in sorted(chips, key=lambda p: p.name):
        lines = {ln.name: ln for ln in plane.lines}
        if any(ev.name == DROPPED for ln in plane.lines if ln.name == TRACEME
               for ev in ln.events):
            raise ValueError(f"{plane.name} dropped trace buffers in {path}: "
                             "the slice holds more device events than the "
                             "profiler keeps, so its busy time is unknown")
        line = lines.get(OPS_LINE)
        if line is None:
            continue
        chip = [(op_name(ev.name), ev.start_ns, ev.end_ns)
                for ev in line.events]
        ops.append(sorted(chip, key=lambda e: (e[1], -e[2])))
    return Trace(slice=window, ops=ops, host=host)


def op_name(text: str) -> str:
    """The HLO instruction name of a device op event, whose name may be the
    whole instruction (`%fusion.3 = f32[8]{0} fusion(...)` -> `fusion.3`)."""
    head = text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def _clip(start, end, lo, hi):
    return max(start, lo), min(end, hi)


def busy_intervals(events, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Union of the events' [start, end) intervals, clipped to [lo, hi)."""
    out: List[List[float]] = []
    for ev in sorted(events, key=lambda e: e[1]):
        s, e = _clip(ev[1], ev[2], lo, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def idle_intervals(busy, lo: float, hi: float) -> List[Tuple[float, float]]:
    """The complement of sorted disjoint `busy` intervals in [lo, hi)."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


class Annotations:
    """The main thread's host annotations (properly nested, as one thread's
    are), indexed to find the innermost one open at a time."""

    def __init__(self, host: List[Span]):
        self.spans = sorted(host, key=lambda h: (h[1], -h[2]))
        self.starts = [h[1] for h in self.spans]
        self.parent, stack = [], []
        for i, (_, s, e) in enumerate(self.spans):
            while stack and self.spans[stack[-1]][2] <= s:
                stack.pop()
            self.parent.append(stack[-1] if stack else -1)
            stack.append(i)

    def innermost(self, t: float) -> str:
        """Name of the innermost annotation open at time t; the slice
        itself when none is."""
        import bisect

        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.spans[i][2] <= t:
            i = self.parent[i]
        return self.spans[i][0] if i >= 0 else SLICE


@dataclasses.dataclass
class Summary:
    window_s: float
    chips: int                    # chips with a device operations line
    busy_s: float                 # mean over chips
    op_s: Dict[str, float]        # device seconds per operation name, summed
    gaps_s: Dict[str, float]      # idle seconds per host annotation, summed

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top(self, table: Dict[str, float], n: int = 10):
        return [[k, v] for k, v in sorted(table.items(),
                                          key=lambda kv: -kv[1])[:n]]


def op_family(name: str) -> str:
    """An HLO instruction name without its numeric suffix
    (`arnoldi_step.3` -> `arnoldi_step`)."""
    head, dot, tail = name.rpartition(".")
    return head if dot and tail.isdigit() else name


def self_times(chip, lo: float, hi: float) -> Dict[str, float]:
    """Device seconds per operation family inside [lo, hi), each event
    counted without the events nested in it (a loop's body ops run inside
    the loop's own event). `chip` is sorted by (start, -end)."""
    out: Dict[str, float] = {}
    stack: List[list] = []            # [family, start, end, child time]

    def pop():
        fam, s, e, kids = stack.pop()
        a, b = _clip(s, e, lo, hi)
        if b > a:
            out[fam] = out.get(fam, 0.0) + (b - a - kids) / 1e9
        if stack:
            stack[-1][3] += max(0.0, b - a)

    for name, s, e in chip:
        while stack and stack[-1][2] <= s:
            pop()
        stack.append([op_family(name), s, e, 0.0])
    while stack:
        pop()
    return out


def summarize(tr: Trace) -> Summary:
    lo, hi = tr.slice
    busy_total, op_s, gaps_s = 0.0, {}, {}
    host = Annotations(tr.host)
    for chip in tr.ops:
        busy = busy_intervals(chip, lo, hi)
        busy_total += sum(e - s for s, e in busy)
        for fam, sec in self_times(chip, lo, hi).items():
            op_s[fam] = op_s.get(fam, 0.0) + sec
        for s, e in idle_intervals(busy, lo, hi):
            name = host.innermost(0.5 * (s + e))
            gaps_s[name] = gaps_s.get(name, 0.0) + (e - s) / 1e9
    nchips = max(len(tr.ops), 1)
    return Summary(window_s=(hi - lo) / 1e9, chips=len(tr.ops),
                   busy_s=busy_total / nchips / 1e9,
                   op_s=op_s, gaps_s={k: v / nchips for k, v in gaps_s.items()})
