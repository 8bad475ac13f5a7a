"""Shared fixtures. NOTE: do NOT set XLA device-count flags here — smoke
tests and benches must see 1 CPU device; a test that needs a multi-device
mesh forces virtual CPU devices in a subprocess of its own
(test_pipeline.py::test_sharded_equivalence_on_8_virtual_devices).

Also installs a fallback `hypothesis` stub when the real package is absent
(see requirements-dev.txt): `@given` degrades to a fixed deterministic set of
example cases (bounds + seeded draws) so the property tests still collect and
exercise the code, just without shrinking/fuzzing."""
import functools
import inspect
import os
import sys
import types

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def _install_hypothesis_stub():
    try:
        import hypothesis  # noqa: F401
        return
    except ImportError:
        pass

    import numpy as _np

    class _Strategy:
        """Bounded scalar strategy: deterministic example draws only."""

        def __init__(self, kind, lo, hi):
            self.kind, self.lo, self.hi = kind, lo, hi

        def example(self, case: int, rng) -> object:
            if case == 0:
                return self.lo
            if case == 1:
                return self.hi
            if self.kind == "int":
                return int(rng.integers(self.lo, self.hi + 1))
            return float(rng.uniform(self.lo, self.hi))

    def integers(lo, hi):
        return _Strategy("int", int(lo), int(hi))

    def floats(lo, hi, **_kw):
        return _Strategy("float", float(lo), float(hi))

    def given(*strategies, **kw_strategies):
        assert not kw_strategies, "stub supports positional strategies only"

        def deco(fn):
            @functools.wraps(fn)
            def run():
                for case in range(5):  # lo-corner, hi-corner, 3 seeded draws
                    rng = _np.random.default_rng(1234 + case)
                    fn(*(s.example(case, rng) for s in strategies))

            # hide the original signature or pytest treats the strategy
            # params as fixtures
            del run.__wrapped__
            run.__signature__ = inspect.Signature()
            return run
        return deco

    def settings(*_a, **_kw):
        return lambda fn: fn

    mod = types.ModuleType("hypothesis")
    strat = types.ModuleType("hypothesis.strategies")
    strat.integers = integers
    strat.floats = floats
    mod.given = given
    mod.settings = settings
    mod.strategies = strat
    mod.__stub__ = True
    sys.modules["hypothesis"] = mod
    sys.modules["hypothesis.strategies"] = strat


_install_hypothesis_stub()

import jax
import numpy as np
import pytest

jax.config.update("jax_enable_x64", True)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)
