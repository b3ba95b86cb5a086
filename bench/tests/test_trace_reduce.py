"""The trace reduction, on hand-built events and on a small trace recorded
on a TPU v5e (``data/small.xplane.pb``, made by ``make_trace.py``)."""
import os

import pytest

from bench.trace import reduce

DATA = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")

# two device ops overlapping, one apart; a window of 0..100 ns
OPS = [("fusion", 10, 30), ("matmul", 20, 40), ("fusion", 60, 70)]
ANN = [("bench:a", 0, 50), ("bench:b", 50, 100)]


def test_union_busy_and_gaps():
    assert reduce.union(OPS, (0, 100)) == [(10, 40), (60, 70)]
    assert reduce.busy_ns(OPS, (0, 100)) == 40
    assert reduce.busy_ns(OPS, (25, 65)) == 20          # clipped to the window
    assert reduce.gaps(OPS, (0, 100)) == [(0, 10), (40, 60), (70, 100)]


def test_idle_share():
    t = reduce.Trace({"/device:TPU:0": OPS}, {}, ANN, (0, 100))
    assert t.busy_s() == pytest.approx(40e-9)
    assert t.idle_share() == pytest.approx(0.6)
    empty = reduce.Trace({"/device:TPU:0": []}, {}, ANN, (0, 100))
    assert empty.idle_share() == 1.0


def test_top_ops_sums_by_name():
    top = reduce.top_ops(OPS, (0, 100))
    assert top == [["fusion", pytest.approx(30e-9)],
                   ["matmul", pytest.approx(20e-9)]]


def test_ops_are_named_by_their_program():
    mods = [("jit_a(12)", 5, 45), ("jit_b(3)", 55, 80)]
    ops = [("%fusion.3 = f32[8] fusion(...)", 10, 30), ("%dot = f32[8] dot()", 60, 70),
           ("%copy", 90, 95)]
    assert [n for n, _, _ in reduce.named_ops(ops, mods)] == [
        "jit_a/%fusion.3", "jit_b/%dot", "?/%copy"]


def test_gaps_are_named_by_the_open_annotation():
    top = reduce.top_gaps(OPS, (0, 100), ANN)
    assert top == [["bench:b", pytest.approx(30e-9)],
                   ["bench:a", pytest.approx(20e-9)],
                   ["bench:a", pytest.approx(10e-9)]]
    assert reduce.open_at(ANN, 150) == "between requests"


def test_module_seconds_by_program_name():
    mods = {"/device:TPU:0": [("jit_matmul(1)", 0, 40), ("jit_other", 40, 90),
                              ("jit_matmul(1)", 90, 120)]}
    t = reduce.Trace({}, mods, ANN, (0, 100))
    assert t.window_s == pytest.approx(100e-9)
    assert reduce.module_seconds(t, ("jit_matmul",)) == pytest.approx(50e-9)


def test_recorded_chip_trace():
    t = reduce.load(DATA, "bench:")
    assert [a[0] for a in t.annotations] == ["bench:req0", "bench:req1",
                                            "bench:req2"]
    assert list(t.ops) == ["/device:TPU:0"]
    ops = t.ops["/device:TPU:0"]
    # each request runs the program once, then the host sleeps 20 ms
    assert 0 < t.busy_s() < 0.03
    assert 0.5 < t.idle_share() < 1.0
    assert sum(d for _, d in reduce.top_ops(ops, t.window)) \
        == pytest.approx(reduce.busy_ns(ops, t.window) * 1e-9, rel=0.2)
    gaps = reduce.top_gaps(ops, t.window, t.annotations, k=3)
    assert len(gaps) == 3
    assert all(g[0].startswith("bench:req") and g[1] > 0.015 for g in gaps)
    assert reduce.module_seconds(t, ("jit_step",)) > 0
    named = reduce.named_ops(ops, t.modules["/device:TPU:0"])
    assert reduce.top_ops(named, t.window)[0][0] == "jit_step/%fusion"
