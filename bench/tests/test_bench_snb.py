"""The LDBC SNB configuration, landed as files alone: its generator, its
parameter curation, its reference and control, its cell's readers, and the
e-commerce cells left as they were, at sf 0.1 (1,528 persons) on the CPU."""
import importlib.util
import os

import numpy as np
import pytest

from bench import check, ldbc_reference as ref, run, workload
from bench.gen import ldbc_snb, m2b_ecom

SEED = 2**31 + 2718
CELL = "snb_ic1_ic13"
METRICS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "metrics")


def metric(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), os.path.join(METRICS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name):
    return metric(name).read


def small():
    _, _, cfg, _ = run.cell(CELL)
    return dict(cfg, sf=0.1)


@pytest.fixture(scope="module")
def data():
    return ldbc_snb.generate(0.1, SEED)


def test_the_cell_is_made_of_its_own_files():
    bench, w, cfg, mix = run.cell(CELL)
    assert w["config"] == "ldbc_snb_sf10" and w["chips"] == 1
    assert cfg["generator"] == "ldbc_snb" and cfg["sf"] == 10
    assert cfg["reference"] == "ldbc_reference"
    assert set(next(c for c in bench["configs"]
                    if c["name"] == "ldbc_snb_sf10")["reduced"]) == \
        set(cfg["reduced"])
    assert mix["kind"] == "query" and set(mix["templates"]) == {"IC1", "IC13"}
    for name in ("gcdi_p95_ms", "gcdi_qps"):
        e = next(m for m in bench["end_to_end"] if m["name"] == name)
        assert e["workloads"] == ["ecom_gcdi_mix", CELL]


@pytest.mark.parametrize("seed", [SEED, 7])
def test_every_seed_has_the_stated_counts(seed):
    n, e = ldbc_snb.counts(0.1)
    _, raw = ldbc_snb.generate(0.1, seed)
    g = raw["graphs"]["knows"]
    a, b = g["edges"]["svid"], g["edges"]["tvid"]
    assert len(g["vertices"]["Person"]["id"]) == n and len(a) == e
    assert not (a == b).any()
    keys = np.minimum(a, b) * n + np.maximum(a, b)
    assert len(np.unique(keys)) == e
    assert np.bincount(np.concatenate([a, b]), minlength=n).min() >= 1


def test_each_seed_draws_its_own_topology():
    """The seed draws the graph itself, not only who sits where: the degree
    multisets of two seeds differ, and one seed gives one graph."""
    def degrees(seed):
        g = ldbc_snb.generate(0.1, seed)[1]["graphs"]["knows"]
        e = g["edges"]
        return np.sort(np.bincount(np.concatenate([e["svid"], e["tvid"]])))
    assert not np.array_equal(degrees(SEED), degrees(7))
    assert np.array_equal(degrees(7), degrees(7))


def test_email_and_speaks_are_multi_valued(data):
    p = data[1]["graphs"]["knows"]["vertices"]["Person"]
    emails = [e.split(";") for e in p["email"]]
    assert {len(e) for e in emails} == set(range(1, ldbc_snb.MAX_EMAILS + 1))
    flat = [a for e in emails for a in e]
    assert len(set(flat)) == len(flat) and all("@" in a for a in flat)
    speaks = [s.split(";") for s in p["speaks"]]
    assert all(1 <= len(s) == len(set(s)) <= 3 for s in speaks)
    assert {x for s in speaks for x in s} <= set(ldbc_snb.LANGUAGES)


def test_the_program_stores_knows_both_ways(data):
    db, raw = data
    g = db.graphs["knows"]
    assert g.edges.nrows == 2 * len(raw["graphs"]["knows"]["edges"]["svid"])
    assert g.n_vertices == ldbc_snb.counts(0.1)[0]


def test_the_pool_builds_from_the_new_files_alone(data):
    _, raw = data
    pool = workload.build_pool(workload.load_mix(CELL), SEED, raw, ldbc_snb)
    assert {t: len(v) for t, v in pool.items()} == {"IC1": 64, "IC13": 64}
    row_ptr, col = ref.csr(raw, "knows")
    first = raw["graphs"]["knows"]["vertices"]["Person"]["firstName"]
    for e in pool["IC1"]:
        (_, _, pid), (_, _, name) = e.spec["where"]
        d = ref.bfs(row_ptr, col, pid, 3)
        assert np.diff(row_ptr)[pid] >= 1
        assert name in set(first[d > 0])
        assert e.request.match.reach.quantifier == (1, 3)
    for e in pool["IC13"]:
        (_, _, p1), (_, _, p2) = e.spec["where"]
        assert p1 != p2 and e.request.match.reach.quantifier == (1, None)
    again = workload.build_pool(workload.load_mix(CELL), SEED, raw, ldbc_snb)
    assert [e.spec for e in again["IC1"]] == [e.spec for e in pool["IC1"]]


def test_rows_off_reads_a_dictionary_column():
    from repro.core.storage import DictColumn
    got = [np.array([1, 2]), np.asarray(DictColumn(values=["x", "y"]))]
    assert ref.rows_off(got, [np.array([1, 2]), np.array(["x", "y"],
                                                        dtype=object)]) == 0
    assert ref.rows_off(got, [np.array([1, 2]), np.array(["x", "z"],
                                                        dtype=object)]) == 2


def test_program_is_correct_and_the_control_is_not():
    m = run.measure(CELL, SEED, 0.5, False, config=small())
    kw = dict(ref=m.ref)
    prog = check.numbers("query", m.kept, m.raw, m.mix, m.failed, **kw)
    ctl = check.numbers("query", m.kept, m.raw, m.mix, m.failed,
                        control=True, **kw)
    assert prog["wrong_rows"] == 0 and prog["failed_requests"] == 0
    assert ctl["wrong_rows"] > 0
    assert check.verdict("query", prog)[0]
    assert not check.verdict("query", ctl)[0]
    assert {e.template for e, _, _ in m.kept} == {"IC1", "IC13"}


def test_the_control_is_one_level_short():
    spec = {"select": ["f.id", "e0.hops"],
            "match": {"graph": "knows",
                      "hops": [["a", "Person", "knows", "f", "Person", 1, 3]]},
            "where": [["a.id", "==", 0]]}
    _, raw = ldbc_snb.generate(0.1, SEED)
    want = ref.relation(raw, spec)
    low = ref.distinct(want)
    assert want.hops == 1 and low[1].max() == want[1].max() - 1
    assert ref.rows_off(low, want) == int((want[1] == want[1].max()).sum())


def test_a_traced_run_reads_the_cells_metrics():
    line = run.run_cell(CELL, SEED, 0.5, True, config=small())
    assert line["correct"]
    m = line["metrics"]
    assert m["reach_op_ms.snb"]["value"] > 0
    assert m["host_op_ms.snb"]["value"] > 0
    assert m["plan_walk_ms.snb"]["value"] > 0
    assert 0.0 <= m["device_idle.snb"]["value"] <= 1.0
    # no device plane on the CPU, so no program time to read
    assert "reach_program_ms.snb" not in m
    assert "device_match_ms.gcdi" not in m


def test_reach_program_ms_is_per_request_that_ran_the_device_reach():
    from bench.trace.reduce import Trace
    recs = [{"kind": "query", "op_s": {"DeviceReach": 0.004}},
            {"kind": "query", "op_s": {"DeviceReach": 0.002}},
            {"kind": "query", "op_s": {"Reach": 0.5}}]
    # an IC1 launch (one frontier row) and an IC13 launch (two)
    tr = Trace(ops={"/device:TPU:0": []},
               modules={"/device:TPU:0": [("jit_reach_program", 0, 3_000_000),
                                          ("jit_reach_program", 0, 1_000_000),
                                          ("jit_other", 0, 9_000_000)]},
               annotations=[], window=(0, 10_000_000))
    r = run.Run("query", 1.0, 0.01, recs, trace=tr)
    assert reader("reach_program_ms.snb")(r) == pytest.approx(2.0)
    assert reader("reach_op_ms.snb")(r) == pytest.approx(3.0)
    assert reader("reach_program_ms.snb")(run.Run("query", 1.0, 0.01,
                                                  recs)) is None


@pytest.mark.parametrize("ends", [1, 2])
def test_every_reach_launch_runs_the_module_the_reader_counts(ends):
    """IC1's one-sided and IC13's two-sided launches compile to a module
    whose name ``reach_program_ms.snb`` counts."""
    import re
    import jax.numpy as jnp
    from repro.kernels.traversal import ops
    text = ops.reach_program.lower(
        jnp.zeros((8, 8), jnp.int8), jnp.zeros((ends,), jnp.int32),
        jnp.ones((8,), bool), jnp.int32(1), jnp.int32(3),
        transpose=False).as_text()
    name = re.search(r"module @(\S+)", text).group(1)
    assert name.startswith(metric("reach_program_ms.snb").PROGRAMS)


def test_host_op_and_plan_walk_readers_leave_out_the_device_reach():
    recs = [{"kind": "query", "seconds": 0.010,
             "op_s": {"DeviceReach": 0.006, "Project": 0.002}},
            {"kind": "query", "seconds": 0.005,
             "op_s": {"DeviceReach": 0.004}},
            {"kind": "gcdia", "seconds": 1.0, "op_s": {"MatMul": 0.9}}]
    r = run.Run("query", 1.0, 0.01, recs)
    assert reader("host_op_ms.snb")(r) == pytest.approx(1.0)
    assert reader("plan_walk_ms.snb")(r) == pytest.approx(1.5)
    assert reader("host_op_ms.snb")(run.Run("query", 1.0, 0.01, [])) is None


def test_e_commerce_plans_are_left_as_they_were():
    """G3's plan at sf 16 keeps its device chain and capacity, and no
    e-commerce query builds a dense adjacency."""
    from repro.core import GredoEngine
    db, raw = m2b_ecom.generate(16, SEED)
    m2b_ecom.build_indexes(db)
    eng = GredoEngine(db)
    mix = workload.load_mix("gcdi_mix")
    dag = eng.optimized_plan(workload.build_query(mix["templates"]["G3"]))
    dev = [n for n in _walk(dag) if n.kind == "DeviceMatchPattern"]
    assert [(n.access, n.capacity) for n in dev] == [("device-chain", 524288)]
    small_db, small_raw = m2b_ecom.generate(1, SEED)
    m2b_ecom.build_indexes(small_db)
    eng = GredoEngine(small_db)
    for e in [es[0] for es in workload.build_pool(
            mix, SEED, small_raw, m2b_ecom).values()]:
        eng.query(e.request)
        assert not {"Reach", "DeviceReach"} & {
            o["op"] for o in eng.last_stats.operators}
    assert all(getattr(g, "_dense_adjacency", None) is None
               for g in list(db.graphs.values())
               + list(small_db.graphs.values()))


def _walk(n):
    yield n
    for c in n.children:
        yield from _walk(c)
