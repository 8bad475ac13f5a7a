"""The trace reduction on a slice written by hand."""
import pytest

from bench import trace as xt


def _trace():
    # slice [100, 200) ns. Chip 0: a loop [90,140) with a kernel call
    # [110,130) inside it, a kernel call [150,160), a copy [190,250);
    # chip 1: one fusion over the whole slice
    chip0 = [("while.1", 90, 140), ("arnoldi_step.2", 110, 130),
             ("arnoldi_step.7", 150, 160), ("copy.3", 190, 250)]
    chip1 = [("fusion.4", 100, 200)]
    host = [("execute_row", 100, 175), ("host_sync", 130, 150),
            ("prepare_row", 175, 200)]
    return xt.Trace(slice=(100, 200), ops=[chip0, chip1], host=host)


def test_busy_idle_and_gaps_by_annotation():
    s = xt.summarize(_trace())
    assert s.window_s == pytest.approx(100e-9)
    # chip 0 busy [100,140) + [150,160) + [190,200) = 60 ns, chip 1
    # 100 ns: mean 80 ns
    assert s.busy_s == pytest.approx(80e-9)
    assert s.idle_share == pytest.approx(0.2)
    # chip 0 gaps: [140,150) in host_sync, [160,190) (midpoint 175) in
    # prepare_row; halved over two chips
    assert s.gaps_s == pytest.approx({"host_sync": 5e-9,
                                      "prepare_row": 15e-9})


def test_self_time_by_family():
    s = xt.summarize(_trace())
    # the loop's own time leaves out the kernel call nested in it
    assert s.op_s == pytest.approx({"while": 20e-9, "arnoldi_step": 30e-9,
                                    "copy": 10e-9, "fusion": 100e-9})
    assert s.top(s.op_s, 1) == [["fusion", pytest.approx(100e-9)]]


def test_innermost_annotation():
    ann = xt.Annotations([(xt.SLICE, 0, 100), ("a", 10, 50), ("b", 20, 30),
                          ("c", 60, 70)])
    assert [ann.innermost(t) for t in (5, 15, 25, 35, 55, 65, 99, 150)] == \
        [xt.SLICE, "a", "b", "a", xt.SLICE, "c", xt.SLICE, xt.SLICE]


def test_interval_helpers():
    busy = xt.busy_intervals([("x", 0, 10), ("y", 5, 15), ("z", 20, 30)],
                             2, 25)
    assert busy == [(2, 15), (20, 25)]
    assert xt.idle_intervals(busy, 0, 40) == [(0, 2), (15, 20), (25, 40)]
    assert xt.op_family("arnoldi_step.12") == "arnoldi_step"
    assert xt.op_family("while.body") == "while.body"
    assert xt.op_name("%fusion.10327 = (f32[8,15]{0,1}) fusion(%a.1), "
                      "kind=kLoop") == "fusion.10327"
    assert xt.op_name("copy-start.4") == "copy-start.4"
