"""End-to-end metric arithmetic, the GCDA work functions and the peaks
table."""
import importlib.util
import os

import numpy as np
import pytest

from bench.roofline import work
from bench.run import Run

METRICS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "metrics")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), os.path.join(METRICS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def closed_loop(ms, kind, stall_ms=0.0):
    """Records of back-to-back requests; the window ends when the last one
    does. ``stall_ms`` is added to the middle request."""
    recs, t = [], 0.0
    for i, m in enumerate(ms):
        m = m + (stall_ms if i == len(ms) // 2 else 0.0)
        recs.append({"template": "t", "kind": kind, "t0": t, "t1": t + m / 1e3,
                     "ms": m})
        t += m / 1e3
    return Run(kind, 1.0, t, recs)


def test_p95_is_over_every_request():
    ms = list(range(1, 201))                  # 200 requests, 1..200 ms
    run = closed_loop(ms, "query")
    assert reader("gcdi_p95_ms")(run) == pytest.approx(np.percentile(ms, 95))
    assert reader("gcdi_p95_ms")(run) == pytest.approx(190.05)


def test_rate_is_completed_over_window():
    run = closed_loop([100.0] * 50, "query")
    assert reader("gcdi_qps")(run) == pytest.approx(10.0)


def test_task_ms_is_window_over_tasks():
    run = closed_loop([150.0] * 40, "analyze")
    assert reader("gcdia_task_ms")(run) == pytest.approx(150.0)
    assert reader("gcdi_qps")(run) is None


@pytest.mark.parametrize("name,worse", [("gcdi_p95_ms", "up"),
                                        ("gcdi_qps", "down"),
                                        ("gcdia_task_ms", "up")])
def test_a_stall_moves_every_metric(name, worse):
    kind = "analyze" if name == "gcdia_task_ms" else "query"
    ms = [20.0] * 200
    base = reader(name)(closed_loop(ms, kind))
    # 11 stalled requests: more than the 5% above the 95th percentile
    stalled = closed_loop(ms, kind)
    for r in stalled.records[:11]:
        r["ms"] += 500.0
    stalled.window_s += 11 * 0.5
    got = reader(name)(stalled)
    assert (got > base) if worse == "up" else (got < base)
    one = reader(name)(closed_loop(ms, kind, stall_ms=5000.0))
    if name != "gcdi_p95_ms":           # one request is not a 95th percentile
        assert (one > base) if worse == "up" else (one < base)


def test_work_at_known_shapes():
    assert work.multiply(4, 3) == (2 * 4 * 4 * 3, 4 * (4 * 3 + 4 * 4))
    assert work.similarity(4, 3) == (2 * 16 * 3 + 2 * 4 * 3 + 2 * 16,
                                     4 * (4 * 3 + 16))
    f, b = work.regression(10, 4, 100)
    assert f == 100 * (2 * 10 * 4 + 4 * 10 + 2 * 10 * 4 + 3 * 4)
    assert b == 4 * (10 * 4 + 10 + 8)
    assert work.work("REGRESSION", 10, 4, 100) == (f, b)
    assert work.work("MULTIPLY", 4, 3) == work.multiply(4, 3)
    assert work.work("SIMILARITY", 4, 3) == work.similarity(4, 3)
    # the A2/A3 size on the chip: 2.6 GB of output, memory-bound on a v5e
    f, b = work.multiply(25_524, 200)
    t = work.roofline_s(f, b, "TPU v5 lite")
    assert t == pytest.approx(b / 819e9)
    assert t > f / 197e12


def test_peaks_refuse_an_unknown_kind():
    with pytest.raises(KeyError, match="no peaks for device kind"):
        work.roofline_s(1.0, 1.0, "TPU v99")


def test_interbuffer_miss_share_is_over_the_gcdia_tasks_lookups():
    run = closed_loop([50.0] * 6, "analyze")
    counts = [(0, 3), (1, 1), (1, 1), (0, 3), (1, 1), (1, 1)]  # A3, A2, A1 x 2
    for r, (h, m) in zip(run.records, counts):
        r["interbuffer"] = {"hits": h, "misses": m, "bypasses": 0,
                            "evictions": 3 * (h == 0)}
    assert reader("interbuffer_miss.gcdia")(run) == pytest.approx(10 / 14)
    for r in run.records:
        r["interbuffer"].update(hits=0, misses=3)
    assert reader("interbuffer_miss.gcdia")(run) == 1.0
    untraced = closed_loop([50.0] * 6, "analyze")
    assert reader("interbuffer_miss.gcdia")(untraced) is None
    queries = closed_loop([50.0] * 6, "query")
    for r in queries.records:
        r["interbuffer"] = {"hits": 0, "misses": 1, "bypasses": 0,
                            "evictions": 0}
    assert reader("interbuffer_miss.gcdia")(queries) is None
