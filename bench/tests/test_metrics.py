"""Readers of the program's counters: nothing to read without them, and the
value with its extras from a registry that holds them."""
import pytest

from bench import harness, phases
from repro.obs.metrics import Registry

# counters → (value, extras) of `ritz.host_ms_per_cycle`
HOST_RITZ = [
    ({"ritz.host_s": 0.05}, None),
    ({"ritz.host_s": 0.05, "ritz.host_calls": 4.0, "ritz.host_blocks": 4.0,
      "ritz.host_parallel_calls": 0.0},
     (12.5, {"calls": 4.0, "blocks_per_call": 1.0, "parallel_share": 0.0})),
    ({"ritz.host_s": 0.03, "ritz.host_calls": 3.0, "ritz.host_blocks": 20.0,
      "ritz.host_parallel_calls": 2.0},
     (10.0, {"calls": 3.0, "blocks_per_call": 20 / 3,
             "parallel_share": 200 / 3})),
]


def _session(monkeypatch, counters):
    if counters is None:
        monkeypatch.setattr(phases, "last_session", lambda: (None, None))
        return
    reg = Registry()
    for name, value in counters.items():
        reg.counter_add(name, value)
    monkeypatch.setattr(phases, "last_session", lambda: (None, reg))


@pytest.mark.parametrize("counters,want", [(None, None)] + HOST_RITZ)
def test_host_ritz_ms_per_cycle(monkeypatch, counters, want):
    _session(monkeypatch, counters)
    got = harness.read_metric("ritz.host_ms_per_cycle", {})
    if want is None:
        assert got is None
        return
    value, extras = want
    assert got["value"] == pytest.approx(value)
    for key, x in extras.items():
        assert got[key] == pytest.approx(x)
