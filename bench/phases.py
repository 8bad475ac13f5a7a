"""Additions to the trace reduction (`trace.py`): the traced slice's device
time by the program's phase scopes, and its idle gaps by the program's own
spans.

The solver names the work of its jitted programs with `jax.named_scope`
under one root, `skr` (`skr/entry`, `skr/arnoldi`, `skr/arnoldi/matvec`,
`skr/arnoldi/orthog`, `skr/lstsq`, `skr/update`, `skr/ritz`,
`skr/finalize`). XLA keeps the scope path in each instruction's `op_name`
metadata, which a TPU profile carries as the `tf_op` stat of each device
operation's event metadata (`read` below). A phase is the last scope name
of the path; work in `skr/arnoldi` outside both of its children is the
`basis` phase (basis writes, Givens, norms, the selects that `vmap` puts
on the loop's carry), and an operation under no `skr` scope is
`unscoped`. Whatever `jit(...)`, `vmap(...)` or `while/body` JAX adds to
the path does not matter. An operation XLA inserted carries no op name:
inside another operation's event (a loop body) it takes that one's phase;
at a program's top level (layout copies, async copy starts and ends) it is
`inserted`.

`repro.obs` mirrors each span into the profiler as a host annotation named
`skr:<name>` (`skr:host_sync.<what>`); an idle gap goes to the innermost
such span open at its midpoint (`none` when there is none). The profile
writes an annotation when it ends, and only one that began while it ran:
the spans open when the profiler started or stopped (the row's
`execute_row` and `solve_batch`) are taken from the program's own tracer,
put on the profile's clock by the offset at which the spans both hold
line up.

On a program without the scopes or the annotations (an older checkout)
every reduction here finds nothing: the readers then report nothing.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

from bench import trace as xtrace

ROOT = "skr"
# the scope names under ROOT, and the phase each one's own work belongs to
SCOPES = {"entry": "entry", "arnoldi": "basis", "matvec": "matvec",
          "orthog": "orthog", "lstsq": "lstsq", "update": "update",
          "ritz": "ritz", "finalize": "finalize"}
SPAN_PREFIX = "skr:"
# the program's spans whose idle gaps are per-cycle costs, and those at the
# row boundary (`solve_batch`, `execute_row`: their own time, outside the
# spans nested in them)
CYCLE_SPANS = ("host_sync.cycle_flags", "cycle_dispatch")
ROW_SPANS = ("host_sync.entry_flags", "host_sync.finalize", "carry_upload",
             "carry_store", "solve_batch", "execute_row", "prefetch_wait")
NONE = "none"
INSERTED = "inserted"

ScopedOp = Tuple[str, float, float, str]   # (name, start, end, op_name)


def phase(op_name: str) -> str:
    """The phase of a device operation from its framework op name:
    `jit(_deflated_cycle)/vmap(skr/arnoldi)/while/body/skr/arnoldi/orthog/
    dot_general:` -> `orthog` (a TPU profile ends the name with `:`).
    Several fused names (`a;b`) take the first."""
    path = (op_name or "").split(";", 1)[0]
    parts = [p for p in re.split(r"[/():]", path) if p]
    if ROOT not in parts:
        return "unscoped"
    last = None
    for i, p in enumerate(parts):
        if p == ROOT:
            j = i + 1
            while j < len(parts) and parts[j] in SCOPES:
                last = parts[j]
                j += 1
    return SCOPES[last] if last else "unscoped"


# ------------------------------------------------------------- reading
# A device operation's framework op name is a stat of its event METADATA
# (`jax.profiler.ProfileData` gives an event's own stats only), so the
# profile is read with a schema of the parts of `xplane.proto` used here.
OP_NAME_STAT = "tf_op"


def _xspace_class():
    """The message class of an XSpace, reduced to the fields read here
    (tsl/profiler/protobuf/xplane.proto; the parser skips the rest)."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    f = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")
    T = descriptor_pb2.FieldDescriptorProto
    kinds = {"i": T.TYPE_INT64, "u": T.TYPE_UINT64, "s": T.TYPE_STRING}
    schema = {
        "XSpace": [("planes", 1, "XPlane", True)],
        "XPlane": [("name", 2, "s", False), ("lines", 3, "XLine", True),
                   ("event_metadata", 4, "EventMetadataEntry", True),
                   ("stat_metadata", 5, "StatMetadataEntry", True)],
        "EventMetadataEntry": [("key", 1, "i", False),
                               ("value", 2, "XEventMetadata", False)],
        "StatMetadataEntry": [("key", 1, "i", False),
                              ("value", 2, "XStatMetadata", False)],
        "XLine": [("name", 2, "s", False), ("timestamp_ns", 3, "i", False),
                  ("events", 4, "XEvent", True)],
        "XEvent": [("metadata_id", 1, "i", False),
                   ("offset_ps", 2, "i", False),
                   ("duration_ps", 3, "i", False)],
        "XEventMetadata": [("id", 1, "i", False), ("name", 2, "s", False),
                           ("display_name", 4, "s", False),
                           ("stats", 5, "XStat", True)],
        "XStat": [("metadata_id", 1, "i", False),
                  ("str_value", 5, "s", False), ("ref_value", 7, "u", False)],
        "XStatMetadata": [("id", 1, "i", False), ("name", 2, "s", False)],
    }
    for name, fields in schema.items():
        m = f.message_type.add(name=name)
        for fname, num, kind, repeated in fields:
            fd = m.field.add(name=fname, number=num, label=(
                T.LABEL_REPEATED if repeated else T.LABEL_OPTIONAL))
            if kind in kinds:
                fd.type = kinds[kind]
            else:
                fd.type, fd.type_name = T.TYPE_MESSAGE, f".bench_xplane.{kind}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _op_names(plane) -> Dict[int, Tuple[str, str]]:
    """Per event metadata id: (the operation's name, its op name)."""
    stat_names = {e.key: e.value.name for e in plane.stat_metadata}
    out = {}
    for e in plane.event_metadata:
        md, op = e.value, ""
        for st in md.stats:
            if stat_names.get(st.metadata_id) == OP_NAME_STAT:
                op = st.str_value or stat_names.get(st.ref_value, "")
                break
        out[e.key] = (md.display_name or md.name, op)
    return out


def read(path: str) -> List[List[ScopedOp]]:
    """Per chip, the device operations of the `XLA Ops` line (times as
    `trace.read` gives them) with the framework op name each carries,
    sorted by (start, -end)."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = []
    for plane in sorted(space.planes, key=lambda p: p.name):
        if not plane.name.startswith(xtrace.DEVICE_PREFIX):
            continue
        names = _op_names(plane)
        for line in plane.lines:
            if line.name != xtrace.OPS_LINE:
                continue
            chip = []
            for ev in line.events:
                name, op = names.get(ev.metadata_id, ("", ""))
                start = line.timestamp_ns + ev.offset_ps / 1e3
                chip.append((xtrace.op_name(name), start,
                             start + ev.duration_ps / 1e3, op))
            out.append(sorted(chip, key=lambda e: (e[1], -e[2])))
    return out


# ------------------------------------------------------------ reducing
@dataclasses.dataclass
class Phases:
    busy_s: float                 # mean over chips, as trace.Summary's
    phase_s: Dict[str, float]     # device self seconds per phase, mean
    gaps_s: Dict[str, float]      # idle seconds per innermost skr: span
    window_s: float

    @property
    def scoped(self) -> bool:
        """Whether any device operation carried an skr scope."""
        return any(v > 0 for k, v in self.phase_s.items()
                   if k != "unscoped")

    def share(self, *names) -> float:
        """Device self time of the phases `names` over all device self time
        in the slice (its busy time: events nest), in %."""
        total = sum(self.phase_s.values())
        return 100.0 * sum(self.phase_s.get(n, 0.0) for n in names) \
            / total if total > 0 else 0.0

    def idle_share(self, *spans) -> float:
        """Idle time under the spans `spans` over the slice, in %."""
        return 100.0 * sum(self.gaps_s.get(n, 0.0) for n in spans) \
            / self.window_s


def phase_times(chip: List[ScopedOp], lo: float, hi: float) \
        -> Dict[str, float]:
    """Device self seconds per phase inside [lo, hi) (trace.self_times,
    keyed by phase instead of operation family). `chip` is sorted by
    (start, -end)."""
    keyed, open_ = [], []             # open_: [(end, phase)] of enclosing
    for _, s, e, op in chip:
        while open_ and open_[-1][0] <= s:
            open_.pop()
        ph = phase(op) if op else (open_[-1][1] if open_ else INSERTED)
        keyed.append((ph, s, e))
        open_.append((e, ph))
    return xtrace.self_times(keyed, lo, hi)


def _clock_offset(recorded, events, tol: float = 50e3) -> Optional[float]:
    """Profile clock minus the tracer's clock, in ns: the offset at which
    most of the spans the profile recorded start within `tol` of a span of
    the same label in the tracer's `events` (label, start, end); None when
    fewer than two line up."""
    import bisect

    starts: Dict[str, list] = {}
    for label, s, _ in events:
        starts.setdefault(label, []).append(s)
    for v in starts.values():
        v.sort()

    def near(label, t):
        v = starts.get(label, [])
        i = bisect.bisect_left(v, t - tol)
        return i < len(v) and v[i] <= t + tol

    known = [r for r in recorded if r[0] in starts]
    if not known:
        return None
    anchor = min(known, key=lambda r: len(starts[r[0]]))
    best, hits = None, 1
    for s in starts[anchor[0]]:
        off = anchor[1] - s
        n = sum(near(label, t - off) for label, t, _ in known)
        if n > hits:
            best, hits = off, n
    return best


def program_spans(tr: xtrace.Trace, events=None) -> List[xtrace.Span]:
    """The program's spans in the slice, on the profile's clock, by label:
    the `skr:` annotations the profile recorded, and from the tracer's
    `events` (label, start, end) the spans open when the profiler started
    or stopped, which the profile cannot hold."""
    lo, hi = tr.slice
    spans = [(h[0][len(SPAN_PREFIX):], h[1], h[2]) for h in tr.host
             if h[0].startswith(SPAN_PREFIX)]
    off = _clock_offset(spans, events) if events else None
    if off is not None:
        spans += [(label, s + off, e + off) for label, s, e in events
                  if s + off < lo < e + off or s + off < hi < e + off]
    return spans


def gaps_by_span(tr: xtrace.Trace, events=None) -> Dict[str, float]:
    """Idle seconds of the slice per innermost program span open at the
    gap's midpoint (`program_spans`); `none` where none is. Mean over
    chips."""
    lo, hi = tr.slice
    host = xtrace.Annotations(program_spans(tr, events))
    out: Dict[str, float] = {}
    for chip in tr.ops:
        busy = xtrace.busy_intervals(chip, lo, hi)
        for s, e in xtrace.idle_intervals(busy, lo, hi):
            name = host.innermost(0.5 * (s + e))
            key = NONE if name == xtrace.SLICE else name
            out[key] = out.get(key, 0.0) + (e - s) / 1e9
    n = max(len(tr.ops), 1)
    return {k: v / n for k, v in out.items()}


def reduce(tr: xtrace.Trace, ops: List[List[ScopedOp]],
           events=None) -> Phases:
    lo, hi = tr.slice
    phase_s: Dict[str, float] = {}
    busy = 0.0
    for chip in ops:
        busy += sum(e - s for s, e in xtrace.busy_intervals(chip, lo, hi))
        for k, v in phase_times(chip, lo, hi).items():
            phase_s[k] = phase_s.get(k, 0.0) + v
    n = max(len(ops), 1)
    return Phases(busy_s=busy / n / 1e9,
                  phase_s={k: v / n for k, v in phase_s.items()},
                  gaps_s=gaps_by_span(tr, events), window_s=(hi - lo) / 1e9)


def of(record: dict, tr) -> Optional[Phases]:
    """The traced slice's phases, read once per run and kept in the
    record; None without a device trace."""
    if tr is None or not tr.ops:
        return None
    if "phases" not in record:
        from bench import harness

        tracer, _ = last_session()
        events = None if tracer is None else [
            (span_label(e), e["ts"], e["ts"] + e["dur"])
            for e in main_thread_spans(tracer)]
        record["phases"] = reduce(tr, read(xtrace.find(harness.TRACE_DIR)),
                                  events)
    return record["phases"]


# ------------------------------------------------- the program's spans
def last_session():
    """(tracer, registry) of the program's last observability session,
    when the program keeps it (`repro.obs.last`); (None, None) otherwise."""
    from repro import obs

    last = getattr(obs, "last", None)
    return last() if last is not None else (None, None)


def span_label(ev: dict) -> str:
    what = (ev.get("args") or {}).get("what")
    return ev["name"] + (f".{what}" if what else "")


def self_times(events, lo: float, hi: float) -> Dict[str, float]:
    """Seconds per span label inside [lo, hi) ns, each span without the
    spans nested in it (trace.self_times). `events`: one thread's complete
    ("X") spans, which nest properly."""
    spans = sorted(((span_label(e), e["ts"], e["ts"] + e["dur"])
                    for e in events), key=lambda e: (e[1], -e[2]))
    return xtrace.self_times(spans, lo, hi)


def main_thread_spans(tracer) -> list:
    import threading

    main = threading.main_thread().ident
    return [e for e in tracer.snapshot()
            if e.get("ph") == "X" and e.get("tid") == main]
