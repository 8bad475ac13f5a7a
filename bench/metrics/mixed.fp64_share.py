"""Share of the mixed-precision refinement passes in the window that ran in
fp64 (the program's counters `mixed.passes_fp64` over `mixed.passes_fp32`
+ `mixed.passes_fp64`), in %: 0 when every fp32 pass does its job."""
from bench import phases


def read(record, trace=None):
    _, registry = phases.last_session()
    if registry is None:
        return None
    c = registry.snapshot()["counters"]
    f32, f64 = c.get("mixed.passes_fp32", 0.0), c.get("mixed.passes_fp64", 0.0)
    if "mixed.dispatches" not in c or f32 + f64 <= 0:
        return None
    return {"value": 100.0 * f64 / (f32 + f64), "passes_fp32": f32,
            "passes_fp64": f64}
