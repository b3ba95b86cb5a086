"""The engine's spans in a traced run: the helpers on hand-built spans and
tuples, a profiler trace of G3 recorded on the CPU, and two G3 requests
recorded on a TPU v5e (``data/spans.xplane.pb``, made by
``make_span_trace.py``), read back with ``bench.trace.reduce``."""
import glob
import os
from types import SimpleNamespace

import jax
import pytest

from bench.trace import reduce, spans

SPAN_DATA = os.path.join(os.path.dirname(__file__), "data", "spans.xplane.pb")
PHASES = ["gredo:lower", "gredo:stage", "gredo:launch", "gredo:readback"]

# device ops in 0..100 ns; a request annotation around the engine's spans:
# the query, an operator in it and a phase of the operator
OPS = [("fusion", 10, 30), ("fusion", 60, 70)]
ANN = [("bench:G3", 0, 90)]
SPANS = [("gredo:query", 5, 85), ("gredo:DeviceMatchPattern", 25, 80),
         ("gredo:launch", 55, 75)]


def test_span_seconds_sums_by_name_without_the_root():
    sp = [SimpleNamespace(name="query", dur=1.0),
          SimpleNamespace(name="plan", dur=0.1),
          SimpleNamespace(name="EquiJoin", dur=0.3),
          SimpleNamespace(name="EquiJoin", dur=0.2),
          SimpleNamespace(name="finish", dur=0.05)]
    got = spans.span_seconds(SimpleNamespace(spans=sp))
    assert got == {"plan": 0.1, "EquiJoin": pytest.approx(0.5),
                   "finish": 0.05}


def test_idle_is_named_by_the_innermost_span():
    # idle 0..10 (the request to 5, then the query), 30..60 (the
    # DeviceMatchPattern, its launch from 55), 70..100 (the launch to 75,
    # the DeviceMatchPattern to 80, the query to 85, the request to 90,
    # then between requests)
    got = dict(spans.idle_by_span(OPS, (0, 100), SPANS, ANN))
    assert got == {"bench:G3": pytest.approx(10e-9),
                   "gredo:DeviceMatchPattern": pytest.approx(30e-9),
                   "gredo:launch": pytest.approx(10e-9),
                   "gredo:query": pytest.approx(10e-9),
                   "between requests": pytest.approx(10e-9)}
    assert sum(got.values()) == pytest.approx(70e-9)   # all the idle time
    top = spans.idle_by_span(OPS, (0, 100), SPANS, ANN, k=2)
    assert [n for n, _ in top] == ["gredo:DeviceMatchPattern", "bench:G3"]


def test_timeline_of_touching_and_nested_intervals():
    ivs = [("a", 0, 10), ("b", 10, 20), ("c", 12, 15), ("d", 30, 40)]
    assert spans.timeline(ivs) == [(0, 10, "a"), (10, 12, "b"),
                                   (12, 15, "c"), (15, 20, "b"),
                                   (30, 40, "d")]


def test_busy_within_windows():
    ops = [("f", 10, 30), ("g", 20, 40), ("h", 60, 70)]
    got = spans.busy_within(ops, [(0, 100), (25, 65), (41, 59)])
    assert got == [pytest.approx(40e-9), pytest.approx(20e-9), 0.0]
    assert spans.busy_within(ops, []) == []


def _nests(evs) -> bool:
    """Host events of one thread nest: each lies inside the innermost one
    still open at its start."""
    stack = []
    for _, s, e in sorted(evs, key=lambda a: (a[1], -a[2])):
        while stack and stack[-1][2] <= s:
            stack.pop()
        if stack and e > stack[-1][2]:
            return False
        stack.append((None, s, e))
    return True


def _record(tmp_path, eng, q):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench:G3"):
            eng.query(q)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    return path


def test_profiler_annotations_nest_like_the_spans(tmp_path):
    from repro.core import GredoEngine, telemetry
    from repro.data import m2bench
    assert telemetry.ANNOTATION_PREFIX == spans.PREFIX
    db = m2bench.generate(sf=1)
    eng = GredoEngine(db, telemetry=True)
    q = m2bench.q_g3()
    eng.query(q)                                    # compile outside
    t = reduce.load(_record(tmp_path / "on", eng, q), spans.PREFIX)
    qt = eng.telemetry.last_trace()
    ann = sorted(t.annotations, key=lambda a: (a[1], -a[2]))
    # one annotation per span, in the span tree's order, nesting alike
    assert [n for n, _, _ in ann] == [spans.PREFIX + s.name
                                      for s in qt.spans]
    assert _nests(ann)
    for s in qt.spans[1:]:
        p = qt.spans[s.parent]
        a, b = ann[s.id], ann[p.id]
        assert b[1] <= a[1] and a[2] <= b[2]
    dmp = next(s for s in qt.spans if s.name == "DeviceMatchPattern")
    assert [c.name for c in qt.children_of(dmp.id)] == [
        "lower", "stage", "launch", "readback"]
    assert [c.name for c in qt.children_of(0) if c.cat == "phase"] == [
        "plan", "finish"]

    off = GredoEngine(db)                           # no session attached
    path = _record(tmp_path / "off", off, q)
    with pytest.raises(RuntimeError, match="no host annotation"):
        reduce.load(path, spans.PREFIX)
    assert reduce.load(path, "bench:").annotations[0][0] == "bench:G3"


def test_recorded_chip_trace_puts_spans_and_device_on_one_clock():
    t = reduce.load(SPAN_DATA, "bench:")
    sp = reduce.load(SPAN_DATA, spans.PREFIX).annotations
    assert [a[0] for a in t.annotations] == ["bench:G3"] * 2
    assert [n for n, _, _ in sp if n in PHASES] == PHASES * 2
    assert _nests(sp + t.annotations)
    ops = t.ops["/device:TPU:0"]
    named = reduce.named_ops(ops, t.modules["/device:TPU:0"])
    chain = [o for o in named if o[0].startswith("jit__chain_device/")]
    launch = [(s, e) for n, s, e in sp if n == "gredo:launch"]
    # the chain program runs inside the launch phase; a sliver of it
    # outlives the overflow flag and falls in readback
    busy = reduce.busy_ns(chain, t.window) * 1e-9
    assert busy > 0
    assert sum(spans.busy_within(chain, launch)) > 0.9 * busy
    idle = spans.idle_by_span(ops, t.window, sp, t.annotations, k=100)
    total = sum(v for _, v in idle)
    assert total == pytest.approx(t.window_s - t.busy_s(), rel=1e-6)
    assert sum(v for n, v in idle if n.startswith(spans.PREFIX)) > 0.9 * total
