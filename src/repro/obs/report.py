"""Human-readable run reports from the telemetry layer.

`render_report` turns one datagen run's signals — per-family
`SequenceStats`, the tracer's phase timings, and the registry's occupancy
counters — into the terminal summary `examples/datagen_report.py` prints:
time per pipeline phase, iterations cold vs recycled (the paper's headline
contrast), syncs per cycle, and lockstep utilization.

Everything here is duck-typed against `solvers.types.SequenceStats` (only
properties are read) so reports can also be rebuilt from deserialized
benchmark artifacts.
"""
from __future__ import annotations


def _fmt_s(sec: float) -> str:
    return f"{sec * 1e3:8.1f} ms" if sec < 1.0 else f"{sec:8.2f} s "


def phase_table(phase_seconds: dict) -> list[str]:
    """Time-per-phase lines, longest first, with share of traced time."""
    if not phase_seconds:
        return ["  (no spans recorded)"]
    total = sum(phase_seconds.values())
    lines = []
    for name, sec in sorted(phase_seconds.items(), key=lambda kv: -kv[1]):
        share = 100.0 * sec / total if total > 0 else 0.0
        lines.append(f"  {name:<24s} {_fmt_s(sec)}  {share:5.1f}%")
    return lines


def cold_vs_recycled(seq) -> tuple[float, float]:
    """(cold, recycled) mean iterations: the FIRST real solve of a sequence
    starts with an empty recycle space; later ones inherit the carry. The
    ratio is the per-sequence view of the paper's headline speedup."""
    solved = seq.solved
    if not solved:
        return 0.0, 0.0
    cold = float(solved[0].iterations)
    rest = solved[1:]
    warm = (sum(s.iterations for s in rest) / len(rest)) if rest else cold
    return cold, warm


def family_lines(name: str, seq) -> list[str]:
    """Per-family breakdown block (one PDE family / dataset sequence)."""
    s = seq.summary()
    cold, warm = cold_vs_recycled(seq)
    cyc = sum(st.cycles for st in seq.solved)
    sync_per_cycle = ((s["host_syncs"] - 2 * s["num"]) / cyc
                      if cyc > 0 else 0.0)
    total_rows = s["num"] + s["padded"]
    util = s["num"] / total_rows if total_rows > 0 else 1.0
    lines = [
        f"[{name}]",
        f"  systems solved          {s['num']:8d}"
        f"   (padded rows: {s['padded']})",
        f"  mean iterations         {s['mean_iterations']:8.1f}",
        f"  iters cold vs recycled  {cold:8.1f} -> {warm:.1f}"
        + (f"   ({cold / warm:.2f}x)" if warm > 0 else ""),
        f"  total wall time         {_fmt_s(s['total_time_s'])}",
        f"  host syncs / cycle      {sync_per_cycle:8.2f}",
        f"  lockstep utilization    {100.0 * util:7.1f}%",
    ]
    if s.get("outer_refinements", 0):
        lines.append(f"  fp32 refinement passes  "
                     f"{s['outer_refinements']:8d}"
                     f"   (fp64 fallbacks: {s['fp64_fallback']})")
    return lines


def render_report(families: dict, tracer=None, registry=None) -> str:
    """The full run report: per-family blocks + phase times + occupancy."""
    out = ["=== datagen telemetry report ==="]
    for name, seq in families.items():
        out.extend(family_lines(name, seq))
    if tracer is not None:
        out.append("[time per phase]")
        out.extend(phase_table(tracer.phase_seconds()))
        if tracer.dropped:
            out.append(f"  (ring dropped {tracer.dropped} events)")
    if registry is not None:
        snap = registry.snapshot()
        out.append("[lockstep occupancy]")
        c = snap["counters"]
        out.append(f"  dispatches              "
                   f"{int(c.get('lockstep.dispatches', 0)):8d}")
        out.append(f"  rows live / total       "
                   f"{int(c.get('lockstep.rows_live', 0)):8d} / "
                   f"{int(c.get('lockstep.rows_total', 0))}")
        out.append(f"  utilization             "
                   f"{100.0 * snap['utilization']:7.1f}%")
        eff = registry.lockstep_eff()
        if eff is not None:
            out.append(f"  lockstep efficiency     {100.0 * eff:7.1f}%"
                       "   (cycles needed / paid)")
    return "\n".join(out)
