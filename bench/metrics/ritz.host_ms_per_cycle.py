"""Host milliseconds of the lockstep engine's harmonic-Ritz eigensolve per
k > 0 cycle in the window (the program's counters `ritz.host_s` over
`ritz.host_calls`). Extras: `blocks_per_call`, the chain blocks a call ran
in (`ritz.host_blocks` over `ritz.host_calls`; 1 inline), and
`parallel_share`, the calls that ran in 2 or more blocks, in %."""
from bench import phases


def read(record, trace=None):
    _, registry = phases.last_session()
    if registry is None:
        return None
    c = registry.snapshot()["counters"]
    calls = c.get("ritz.host_calls", 0.0)
    if calls <= 0:
        return None
    return {"value": 1000.0 * c.get("ritz.host_s", 0.0) / calls,
            "calls": calls,
            "blocks_per_call": c.get("ritz.host_blocks", 0.0) / calls,
            "parallel_share":
                100.0 * c.get("ritz.host_parallel_calls", 0.0) / calls}
