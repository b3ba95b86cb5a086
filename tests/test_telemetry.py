"""Telemetry layer: span tracing, Chrome-trace export, metrics registry
snapshot/delta semantics, per-graph write counters, q-error monitoring, and
the disabled-path overhead guard."""
import json
import time

import numpy as np
import pytest

from repro.core import (GredoEngine, Registry, Telemetry,
                        validate_chrome_trace, physical)
from repro.core import deltastore, telemetry
from repro.core.interbuffer import fingerprint, value_nbytes
from repro.core.schema import Query
from repro.data import m2bench

pytestmark = pytest.mark.fast


@pytest.fixture(scope="module")
def db():
    return m2bench.generate(sf=1)


# ---------------------------------------------------------------------------
# Span tree vs DAG shape
# ---------------------------------------------------------------------------


def _expected_shape(node, memo):
    """Mirror of the executor's visit order: a fresh node opens a span
    covering its children; a signature already executed collapses to a
    leaf pseudo-span (memo hit)."""
    sig = node.signature()
    if sig in memo:
        return (node.kind, [])
    memo.add(sig)
    return (node.kind, [_expected_shape(c, memo) for c in node.children])


@pytest.mark.parametrize("mode", ["gredo", "dual", "single"])
def test_span_tree_matches_dag_shape(db, mode):
    eng = GredoEngine(db, mode=mode, telemetry=True)
    eng.query(m2bench.q_g1())
    trace = eng.telemetry.last_trace()
    assert trace is not None
    assert trace.shape() == [_expected_shape(eng.last_dag, set())]


def test_interbuffer_hit_pseudo_span(db):
    eng = GredoEngine(db, telemetry=True)
    eng.analyze(m2bench.a3_multiply())
    eng.analyze(m2bench.a3_multiply())      # root satisfied from inter-buffer
    trace = eng.telemetry.last_trace()
    hits = [s for s in trace.spans if s.args.get("cache") == "interbuffer-hit"]
    assert hits and hits[0].name == eng.last_dag.kind
    assert eng.last_stats.interbuffer_hit


# ---------------------------------------------------------------------------
# Phase spans and profiler annotations
# ---------------------------------------------------------------------------


class _Annotations:
    """Stand-in for ``jax.profiler.TraceAnnotation`` that logs each enter
    and exit, to check that the annotations nest and are all closed."""

    def __init__(self):
        self.log: list = []
        self.open: list = []
        outer = self

        class Ann:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                outer.log.append(("enter", self.name))
                outer.open.append(self.name)
                return self

            def __exit__(self, *exc):
                outer.log.append(("exit", self.name))
                assert outer.open.pop() == self.name, "annotations must nest"

        self.cls = Ann


@pytest.fixture
def annotations(monkeypatch):
    a = _Annotations()
    monkeypatch.setattr(telemetry, "_TraceAnnotation", a.cls)
    return a


def _phases(trace, sid):
    return [c for c in trace.children_of(sid) if c.cat == telemetry.PHASE]


@pytest.mark.parametrize("task", ["q_g3", "a3_multiply", "a_shard_reg"])
def test_phases_lie_inside_their_span(db, task, annotations):
    eng = GredoEngine(db, telemetry=True)
    req = getattr(m2bench, task)()
    (eng.query if task.startswith("q_") else eng.analyze)(req)
    tr = eng.telemetry.last_trace()
    root = tr.spans[0]
    assert root.name == ("query" if task.startswith("q_") else "analyze")
    assert [p.name for p in _phases(tr, 0)] == ["plan", "finish"]
    want = {"DeviceMatchPattern": ["lower", "stage", "launch", "readback"],
            "RandomAccessMatrix": ["build", "transfer"],
            "Rel2Matrix": ["build", "transfer"]}
    seen = set()
    for s in tr.spans:
        ph = _phases(tr, s.id)
        assert sum(p.dur for p in ph) <= s.dur
        assert all(p.ts >= s.ts and p.ts + p.dur <= s.ts + s.dur + 1e-9
                   for p in ph)
        if s.name in want:
            assert [p.name for p in ph] == want[s.name]
            seen.add(s.name)
    assert seen
    # every span's annotation was entered once and exited, nested alike
    assert annotations.open == []
    enters = [n for e, n in annotations.log if e == "enter"]
    assert enters == [telemetry.ANNOTATION_PREFIX + s.name for s in tr.spans]


def test_plan_span_is_within_the_plan_and_walk_time(db):
    eng = GredoEngine(db, telemetry=True)
    eng.query(m2bench.q_g3())
    tr = eng.telemetry.last_trace()
    plan = _phases(tr, 0)[0]
    walk = eng.last_stats.seconds - sum(o["seconds"]
                                        for o in eng.last_stats.operators
                                        if o["executed"])
    assert plan.name == "plan" and 0 < plan.dur <= walk
    # the operators run between the two engine phases
    ops = [s for s in tr.children_of(0) if s.cat != telemetry.PHASE]
    finish = _phases(tr, 0)[1]
    assert plan.ts + plan.dur <= ops[0].ts
    assert ops[-1].ts + ops[-1].dur <= finish.ts


def test_request_that_raises_leaves_no_span_open(db, annotations,
                                                 monkeypatch):
    eng = GredoEngine(db, telemetry=True)
    q = m2bench.q_g3()
    eng.query(q)

    def boom(*a, **k):
        raise RuntimeError("lost the device")

    monkeypatch.setattr(physical.DeviceMatchPattern, "run", boom)
    with pytest.raises(RuntimeError, match="lost the device"):
        eng.query(q)
    tr = eng.telemetry.last_trace()
    assert tr.open_spans() == []
    assert annotations.open == []
    assert tr.spans[0].dur >= max(s.ts + s.dur for s in tr.spans[1:])
    tr.close()                          # closing again changes nothing
    assert annotations.open == []
    # a failed plan (before any operator) closes as well
    with pytest.raises(Exception):
        eng.query(Query(select=("nope.x",), froms=()))
    assert eng.telemetry.last_trace().open_spans() == []
    assert annotations.open == []


def test_render_counts_phases_in_their_operator(db):
    eng = GredoEngine(db, telemetry=True)
    eng.query(m2bench.q_g3())
    tr = eng.telemetry.last_trace()
    out = tr.render(top=20)
    assert "[lower]" in out and "[plan]" in out
    dmp = next(s for s in tr.spans if s.name == "DeviceMatchPattern")
    line = next(l for l in out.splitlines()
                if l.lstrip().startswith("DeviceMatchPattern")
                and "self_ms=" in l)
    assert float(line.split("self_ms=")[1].split()[0]) == pytest.approx(
        dmp.dur * 1e3, abs=1e-3)        # a leaf: its phases are its own


def test_flight_record_holds_phases_and_the_open_root(db):
    eng = GredoEngine(db, telemetry=True)
    eng.query(m2bench.q_g3())
    rec = eng.observer.ring[-1]
    cats = {s["name"]: s["cat"] for s in rec.spans}
    assert cats["lower"] == cats["plan"] == cats["finish"] == "phase"
    root = rec.spans[0]
    assert root["parent"] == -1 and root["dur"] > 0


# ---------------------------------------------------------------------------
# Chrome trace-event export
# ---------------------------------------------------------------------------


def test_chrome_trace_round_trips_and_nests(db):
    eng = GredoEngine(db, telemetry=True)
    eng.analyze(m2bench.a3_multiply())
    eng.query(m2bench.q_g1())
    doc = json.loads(eng.telemetry.collector.to_chrome_json())
    assert validate_chrome_trace(doc) == []
    events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert events
    for tid in {e["tid"] for e in events}:
        evs = [e for e in events if e["tid"] == tid]
        # begin order == span-id order: ts must be monotonically
        # non-decreasing, and each span must end within its enclosing one
        ts = [e["ts"] for e in evs]
        assert ts == sorted(ts)
        root = evs[0]
        for e in evs[1:]:
            assert e["ts"] >= root["ts"] - 1e-6
            assert e["ts"] + e["dur"] <= root["ts"] + root["dur"] + 0.5


def test_validator_rejects_malformed_traces():
    assert validate_chrome_trace({}) == ["missing traceEvents"]
    bad = {"traceEvents": [{"name": "x", "ph": "X", "pid": 1, "tid": 0,
                            "ts": -5, "dur": 2}]}
    assert validate_chrome_trace(bad)
    overlap = {"traceEvents": [
        {"name": "a", "ph": "X", "pid": 1, "tid": 0, "ts": 0, "dur": 10},
        {"name": "b", "ph": "X", "pid": 1, "tid": 0, "ts": 5, "dur": 10}]}
    assert any("nesting" in p for p in validate_chrome_trace(overlap))


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


def test_histogram_percentiles():
    h = telemetry.Histogram("t")
    for v in np.linspace(1e-4, 1e-1, 1000):
        h.observe(float(v))
    assert h.count == 1000
    assert h.p50 == pytest.approx(5e-2, rel=0.5)
    assert h.p50 <= h.p95 <= h.p99 <= h.max
    assert np.isnan(telemetry.Histogram("e").p99)


def test_registry_snapshot_delta_across_write_burst(db):
    eng = GredoEngine(db, telemetry=True)
    reg = eng.telemetry.registry
    g = db.graphs["Interested_in"]
    before = reg.snapshot()
    n0 = g.vertex_tables["Tags"].nrows
    for i in range(3):
        g.insert_vertices("Tags", {"tid": np.array([90000 + i]),
                                   "content": np.array([f"t{i}"]),
                                   "popularity": np.array([0.0])})
    delta = Registry.delta(before, reg.snapshot())
    assert delta["deltastore.Interested_in.write_batches"] == 3
    assert delta["deltastore.Interested_in.write_rows"] == 3
    # the other graph's counters must not move (per-graph isolation)
    assert delta.get("deltastore.Follows.write_batches", 0) == 0
    assert g.vertex_tables["Tags"].nrows == n0 + 3


def test_write_counters_per_graph(db):
    # per-graph counters are the only write-path accounting now (the
    # module-global WRITE_COUNTERS alias is gone); the registry exposes
    # them namespaced per graph
    g1 = db.graphs["Follows"]
    assert not hasattr(deltastore, "WRITE_COUNTERS")
    b0 = g1.write_counters.write_batches
    g1.insert_edges({"svid": np.array([0]), "tvid": np.array([1]),
                     "since": np.array([2020])})
    assert g1.write_counters.write_batches == b0 + 1
    eng = GredoEngine(db, telemetry=True)
    snap = eng.telemetry.registry.snapshot()
    assert snap["deltastore.Follows.write_batches"] == b0 + 1


def test_per_query_interbuffer_delta(db):
    eng = GredoEngine(db, telemetry=True)
    task = m2bench.a3_multiply()
    eng.analyze(task)
    eng.analyze(task)
    # second run: one hit, zero misses *for this query* even though the
    # cumulative counters carry the first run's misses
    assert eng.last_interbuffer_delta["hits"] == 1
    assert eng.last_interbuffer_delta["misses"] == 0
    assert eng.interbuffer.misses > 0
    out = eng.explain_last()
    assert "interbuffer (this query)" in out
    assert "(cumulative)" in out


# ---------------------------------------------------------------------------
# Q-error monitor
# ---------------------------------------------------------------------------


def test_qerror_monitor_flags_misestimate():
    mon = telemetry.QErrorMonitor(threshold=4.0, max_log=8)
    mon.start_plan()
    assert mon.record("q", "Scan", "Scan[ok]", 100, 110) < 4.0
    assert mon.record("q", "Join", "Join[bad]", 1000, 10) == 100.0
    assert len(mon.last_plan) == 1
    assert mon.last_plan[0].op == "Join"
    assert mon.worst(1)[0].q_error == 100.0
    # zero-row operators clamp instead of dividing by zero
    assert mon.record("q", "Sel", "Sel[empty]", 0, 0) == 1.0
    for i in range(20):     # bounded log keeps the worst offenders
        mon.record("q", "Op", f"Op[{i}]", 10 ** (i % 5 + 1), 1)
    assert len(mon.log) <= 8
    assert mon.worst(1)[0].q_error == 100000.0


def test_engine_records_qerrors_per_plan(db):
    tel = Telemetry(qerror_threshold=1.000001)   # flag any est != actual
    eng = GredoEngine(db, telemetry=tel)
    eng.query(m2bench.q_g4())
    assert tel.qerror.observations > 0
    assert tel.qerror.last_plan, "an exactly-estimated 4-join plan is " \
                                 "vanishingly unlikely"
    assert "q-error flags" in eng.explain_last()
    assert eng.last_registry_delta.get("qerror.observations", 0) > 0


# ---------------------------------------------------------------------------
# explain_last timing annotations (satellite: seconds + % of total, top-k)
# ---------------------------------------------------------------------------


def test_explain_last_shows_seconds_and_pct(db):
    eng = GredoEngine(db)
    eng.query(m2bench.q_g1())
    out = eng.explain_last(top=3)
    assert "ms=" in out and "pct=" in out
    assert "top 3 operators by time" in out


def test_profile_returns_trace_without_permanent_telemetry(db):
    eng = GredoEngine(db)
    assert eng.telemetry is None
    prof = eng.profile(m2bench.q_g1())
    assert eng.telemetry is None            # transient session detached
    assert prof.result.nrows > 0
    assert prof.trace is not None and prof.trace.total_seconds() > 0
    assert "total_ms=" in prof.render(top=2)
    assert prof.registry_delta.get("engine.queries") == 1


# ---------------------------------------------------------------------------
# Disabled-telemetry overhead guard
# ---------------------------------------------------------------------------


def _execute_pre_telemetry(node, ctx):
    """Frozen copy of physical.execute as it was before span tracing — the
    honest baseline for the overhead bound."""
    sig = node.signature()
    if sig in ctx.memo:
        node.stats.memoized = True
        return ctx.memo[sig]
    if ctx.interbuffer is not None and node.cacheable:
        hit = ctx.interbuffer.get(fingerprint(sig))
        if hit is not None:
            node.stats.cached = True
            node.stats.rows = physical._result_rows(hit)
            node.stats.nbytes = value_nbytes(hit)
            ctx.nodes_reused += 1
            ctx.memo[sig] = hit
            return hit
    inputs = [_execute_pre_telemetry(c, ctx) for c in node.children]
    t0 = time.perf_counter()
    out = node.run(ctx, *inputs)
    node.stats.seconds += time.perf_counter() - t0
    node.stats.executed = True
    node.stats.rows = physical._result_rows(out)
    if ctx.interbuffer is not None or physical.TRACK_NBYTES:
        node.stats.nbytes = value_nbytes(out)
    ctx.nodes_run += 1
    if ctx.interbuffer is not None and node.cacheable:
        est = ctx.ests.get(id(node)) if ctx.ests is not None else None
        out = ctx.interbuffer.put(fingerprint(sig), out,
                                  est_cost=None if est is None else est[1])
    ctx.memo[sig] = out
    return out


def test_disabled_telemetry_overhead_bounded(db):
    """trace=None must cost only pointer checks: paired min-of-N on the
    same DAG vs the pre-telemetry executor, generous CI-noise bound (the
    trace benchmark measures the honest <2% figure on quiet hardware)."""
    eng = GredoEngine(db)
    dag = eng.optimized_plan(m2bench.q_g1())
    for _ in range(3):
        _execute_pre_telemetry(dag, physical.ExecContext(db))
        physical.execute(dag, physical.ExecContext(db))
    base, new = [], []
    for _ in range(15):
        t0 = time.perf_counter()
        _execute_pre_telemetry(dag, physical.ExecContext(db))
        base.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        physical.execute(dag, physical.ExecContext(db))
        new.append(time.perf_counter() - t0)
    assert min(new) <= min(base) * 1.25


def test_trace_collector_bounded():
    coll = telemetry.TraceCollector(max_spans=10)
    for i in range(8):
        qt = coll.start_query(f"q{i}")
        for _ in range(3):
            qt.end(qt.begin("Op"))
        qt.close()
        coll.trim()
    total = sum(len(t.spans) for t in coll.traces)
    assert total <= 10 or len(coll.traces) == 1
    assert coll.dropped_spans > 0
    assert coll.last().label == "q7"    # newest trace always survives


def test_empty_histogram_summary_is_finite():
    h = telemetry.Histogram("e")
    s = h.summary()
    assert s == {"count": 0, "sum": 0.0, "p50": 0.0, "p95": 0.0, "p99": 0.0}
    json.dumps(s)                       # strict-JSON safe (no NaN)
    # percentile() itself still says "no data" with NaN (asserted above in
    # test_histogram_percentiles) — only the snapshot view is zero-filled


def test_registry_to_openmetrics_exposition():
    reg = Registry()
    reg.counter("engine.queries").inc(3)
    reg.gauge("pool.bytes").set(1.5)
    h = reg.histogram("engine.query_seconds")
    h.observe(0.002)
    h.observe(5.0)
    reg.register_source("ib", lambda: {"hits": 7, "rate": 0.25})
    text = reg.to_openmetrics()
    lines = text.splitlines()
    assert "# TYPE engine_queries counter" in lines
    assert "engine_queries_total 3" in lines
    assert "# TYPE pool_bytes gauge" in lines
    assert "pool_bytes 1.5" in lines
    # histogram: cumulative buckets, +Inf catch-all, sum/count
    assert "# TYPE engine_query_seconds histogram" in lines
    buckets = [l for l in lines
               if l.startswith("engine_query_seconds_bucket")]
    assert buckets[-1] == 'engine_query_seconds_bucket{le="+Inf"} 2'
    counts = [int(l.rsplit(" ", 1)[1]) for l in buckets]
    assert counts == sorted(counts)     # cumulative, monotone
    assert "engine_query_seconds_count 2" in lines
    assert any(l.startswith("engine_query_seconds_sum 5.002") for l in lines)
    # pull sources export as gauges under a sanitized namespace
    assert "ib_hits 7" in lines and "ib_rate 0.25" in lines
    assert lines[-1] == "# EOF" and text.endswith("\n")
    # names obey the OpenMetrics grammar
    for l in lines:
        if not l.startswith("#"):
            name = l.split(" ")[0].split("{")[0]
            assert telemetry.Registry._om_name(name) == name


def test_engine_openmetrics_end_to_end(db):
    eng = GredoEngine(db, telemetry=True)
    eng.query(m2bench.q_g1())
    eng.health()
    text = eng.telemetry.registry.to_openmetrics()
    assert "engine_queries_total 1" in text
    assert "health_status" in text      # health gauges ride along
    assert "flight_records 1" in text   # flight-recorder source too
