"""The reach of a quantified hop: LDBC SNB's IC1 and IC13 through
``GredoEngine.query``, on the device form (the jnp program on the CPU) and
the host BFS, against the benchmark's plain reference, on seeded data at
sf 0.1 (1,528 persons)."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import ldbc_reference as ref  # noqa: E402
from bench import workload  # noqa: E402
from bench.gen import ldbc_snb  # noqa: E402
from repro.core import GredoEngine, optimizer, pattern_jit  # noqa: E402
from repro.core import physical as ph  # noqa: E402
from repro.core.schema import Predicate, Query, chain_pattern  # noqa: E402
from repro.core.storage import Database, DictColumn, Graph, Table  # noqa: E402

SEED = 2**31 + 1818


@pytest.fixture(scope="module")
def snb():
    db, raw = ldbc_snb.generate(0.1, SEED)
    ldbc_snb.build_indexes(db)
    pool = workload.build_pool(workload.load_mix("snb_ic1_ic13"), SEED, raw,
                               ldbc_snb)
    return db, raw, pool


def _ops(eng) -> set:
    return {o["op"] for o in eng.last_stats.operators if o["executed"]}


def _answer(eng, entry) -> list:
    out = eng.query(entry.request)
    return [np.asarray(out.col(a)) if not isinstance(out.col(a), DictColumn)
            else out.col(a) for a in entry.spec["select"]]


@pytest.mark.parametrize("form", ["device", "host"])
@pytest.mark.parametrize("template", ["IC1", "IC13"])
def test_reach_equals_reference(snb, monkeypatch, form, template):
    db, raw, pool = snb
    if form == "host":
        monkeypatch.setattr(optimizer, "REACH_DEVICE_SHARE", 0.0)
    eng = GredoEngine(db)
    for entry in pool[template][:8]:
        got = _answer(eng, entry)
        want = ref.relation(raw, entry.spec)
        assert ref.rows_off(got, want) == 0, entry.spec["where"]
        assert ("DeviceReach" in _ops(eng)) == (form == "device")
        assert ("Reach" in _ops(eng)) == (form == "host")


def _ic1(pid: int, name: str, lo: int = 1, hi=3) -> Query:
    return Query(select=("f.id", "e0.hops"), froms=(),
                 match=chain_pattern("knows", ("a", "Person", "knows", "f",
                                               "Person", lo, hi)),
                 where=(Predicate("a.id", "==", pid),
                        Predicate("f.firstName", "==", name)))


def test_start_person_is_excluded_and_included_at_min_zero(snb):
    db, raw, _ = snb
    person = raw["graphs"]["knows"]["vertices"]["Person"]
    # a person whose first name some friend within 3 hops shares
    row_ptr, col = ref.csr(raw, "knows")
    pid = next(s for s in range(len(person["id"]))
               if (person["firstName"][ref.bfs(row_ptr, col, s, 3) > 0]
                   == person["firstName"][s]).any())
    name = person["firstName"][pid]
    eng = GredoEngine(db)
    ids = np.asarray(eng.query(_ic1(pid, name)).col("f.id"))
    assert len(ids) and pid not in ids
    out = eng.query(_ic1(pid, name, lo=0))
    got = dict(zip(np.asarray(out.col("f.id")), np.asarray(out.col("e0.hops"))))
    assert got[pid] == 0 and len(got) == len(ids) + 1


def _toy(edges, n, directed=False):
    u = np.array([a for a, _ in edges])
    v = np.array([b for _, b in edges])
    if not directed:
        u, v = np.concatenate([u, v]), np.concatenate([v, u])
    person = Table("Person", {"id": np.arange(n),
                              "firstName": DictColumn(values=["x"] * n)})
    db = Database()
    db.add_graph(Graph("knows", {"Person": person},
                       Table("knows_edges", {"svid": u, "tvid": v}),
                       "Person", "Person"))
    return db


def _pairs(out) -> dict:
    return dict(zip(np.asarray(out.col("f.id")).tolist(),
                    np.asarray(out.col("e0.hops")).tolist()))


@pytest.mark.parametrize("form", ["device", "host"])
def test_many_paths_one_row_at_shortest_distance(monkeypatch, form):
    if form == "host":
        monkeypatch.setattr(optimizer, "DEVICE_MATCH", False)
    # 0 reaches 3 by 0-1-3, 0-2-3 and 0-4-5-3; 6 sits apart
    db = _toy([(0, 1), (0, 2), (1, 3), (2, 3), (0, 4), (4, 5), (5, 3)], 7)
    out = GredoEngine(db).query(_ic1(0, "x", 1, None))
    assert _pairs(out) == {1: 1, 2: 1, 4: 1, 3: 2, 5: 2}


@pytest.mark.parametrize("form", ["device", "host"])
def test_unreachable_pair_gives_no_row(monkeypatch, form):
    if form == "host":
        monkeypatch.setattr(optimizer, "DEVICE_MATCH", False)
    db = _toy([(0, 1), (1, 2), (3, 4)], 5)
    q = Query(select=("e0.hops",), froms=(),
              match=chain_pattern("knows", ("a", "Person", "knows", "b",
                                            "Person", 1, None)),
              where=(Predicate("a.id", "==", 0), Predicate("b.id", "==", 4)))
    eng = GredoEngine(db)
    assert eng.query(q).nrows == 0
    q.where = (Predicate("a.id", "==", 0), Predicate("b.id", "==", 2))
    assert np.asarray(eng.query(q).col("e0.hops")).tolist() == [2]


def test_hub_start_equals_reference(snb):
    db, raw, _ = snb
    row_ptr, _ = ref.csr(raw, "knows")
    hub = int(np.argmax(np.diff(row_ptr)))
    spec = {"select": ["f.id", "e0.hops"], "from": [],
            "match": {"graph": "knows",
                      "hops": [["a", "Person", "knows", "f", "Person", 1, 3]]},
            "where": [["a.id", "==", hub]]}
    eng = GredoEngine(db)
    got = eng.query(workload.build_query(spec))
    assert "DeviceReach" in _ops(eng)
    assert ref.rows_off([np.asarray(got.col(a)) for a in spec["select"]],
                        ref.relation(raw, spec)) == 0
    assert got.nrows > 0.5 * len(row_ptr)


def test_directed_reach_walks_edges_backwards_from_a_pinned_end():
    # 0 -> 1 -> 2 -> 3, 4 -> 3: from 0 only 1, 2, 3 are reached; pinning
    # the end (3) alone starts the search there, over reversed edges
    db = _toy([(0, 1), (1, 2), (2, 3), (4, 3)], 5, directed=True)
    eng = GredoEngine(db)
    fwd = eng.query(_ic1(0, "x", 1, None))
    assert _pairs(fwd) == {1: 1, 2: 2, 3: 3}
    q = Query(select=("a.id", "e0.hops"), froms=(),
              match=chain_pattern("knows", ("a", "Person", "knows", "f",
                                            "Person", 1, None)),
              where=(Predicate("f.id", "==", 3),))
    out = eng.query(q)
    assert [n.pplan.reverse for n in _walk(eng.last_dag)
            if n.kind == "DeviceReach"] == [True]
    assert dict(zip(np.asarray(out.col("a.id")).tolist(),
                    np.asarray(out.col("e0.hops")).tolist())) == \
        {2: 1, 4: 1, 1: 2, 0: 3}


def _walk(n):
    yield n
    for c in n.children:
        yield from _walk(c)


def test_pending_deltas_fall_back_to_the_host(snb):
    db, raw, pool = snb
    db2, raw2 = ldbc_snb.generate(0.1, SEED)
    entry = pool["IC1"][0]
    eng = GredoEngine(db2)
    dag = eng.optimized_plan(entry.request)
    assert [n.kind for n in _walk(dag) if "Reach" in n.kind] == ["DeviceReach"]
    g = db2.graphs["knows"]
    pid = entry.spec["where"][0][2]
    far = int(np.argmax(ref.bfs(*ref.csr(raw2, "knows"), pid)))
    g.insert_edges({"svid": np.array([pid, far]),
                    "tvid": np.array([far, pid])})
    assert g.delta.has_pending()
    # the plan made before the write runs on the host at run time
    out = ph.execute(dag, ph.ExecContext(db2))
    dr = next(n for n in _walk(dag) if n.kind == "DeviceReach")
    assert dr.access == "host-fallback"
    raw2["graphs"]["knows"]["edges"] = {
        "svid": np.append(raw2["graphs"]["knows"]["edges"]["svid"], pid),
        "tvid": np.append(raw2["graphs"]["knows"]["edges"]["tvid"], far)}
    raw2.pop("_reference_csr", None)
    want = ref.relation(raw2, entry.spec)
    assert ref.rows_off([out.col(a) for a in entry.spec["select"]], want) == 0
    # a plan made now keeps the host BFS
    eng.query(entry.request)
    assert "Reach" in _ops(eng) and "DeviceReach" not in _ops(eng)
    assert any("pending deltas" in r for r in eng.last_report.rewrites)
    g.compact()
    eng.query(entry.request)
    assert "DeviceReach" in _ops(eng)


def test_five_tuple_chains_are_unchanged():
    p = chain_pattern("Follows", ("a", "Persons", "Follows", "b", "Persons"),
                      ("b", "Persons", "Follows", "c", "Persons"))
    assert p.reach is None
    assert all(e.quantifier is None for e in p.edges)
    assert p.canonical() == (
        "Follows", (("a", "Persons"), ("b", "Persons"), ("c", "Persons")),
        (("e0", "Follows", "a", "b"), ("e1", "Follows", "b", "c")))
    r = chain_pattern("knows", ("a", "Person", "knows", "b", "Person", 1,
                                None))
    assert r.reach is r.edges[0] and r.edges[0].quantifier == (1, None)


@pytest.mark.parametrize("hops", [
    [("a", "P", "k", "b", "P", 1, 3), ("b", "P", "k", "c", "P")],
    [("a", "P", "k", "b", "P", 2, 1)],
    [("a", "P", "k", "b", "P", -1, 2)],
    [("a", "P", "k", "a", "P", 1, 2)],
    [("a", "P", "k", "b", "P", 1)],
])
def test_bad_quantified_hops_are_refused(hops):
    with pytest.raises(ValueError):
        chain_pattern("g", *hops)


def test_a_predicate_on_the_reach_edge_var_is_refused(snb):
    db, _, _ = snb
    q = _ic1(0, "x")
    q.where = q.where + (Predicate("e0.hops", "==", 2),)
    with pytest.raises(ValueError, match="quantifier"):
        GredoEngine(db).query(q)


def test_profile_shows_reach_counters_and_phases(snb):
    db, _, pool = snb
    eng = GredoEngine(db)
    entry = pool["IC13"][0]
    eng.query(entry.request)            # builds the adjacency
    prof = eng.profile(entry.request)
    d = prof.registry_delta
    assert d["reach.launches"] == 1
    assert d["reach.levels"] >= 1 and d["reach.reached"] >= 1
    side = -(-1528 // 128) * 128
    assert eng.metrics_snapshot()["reach.adjacency_bytes"] >= side * side
    dr = next(s for s in prof.trace.spans if s.name == "DeviceReach")
    assert dr.args["launches"] == 1 and dr.args["access"] == "device-reach"
    assert {s.name for s in prof.trace.spans if s.parent == dr.id} == \
        {"lower", "stage", "launch", "readback"}
    assert pattern_jit.REACH.launches >= 2


@pytest.mark.parametrize("mode", ["gredo", "dual", "single"])
def test_every_engine_mode_gives_the_same_reach(mode):
    db = _toy([(0, 1), (0, 2), (1, 3), (2, 3), (0, 4), (4, 5), (5, 3)], 7)
    out = GredoEngine(db, mode=mode).query(_ic1(0, "x", 2, 2))
    assert _pairs(out) == {3: 2, 5: 2}


@pytest.mark.parametrize("lo,hi", [(1, None), (0, None), (1, 2), (3, 3),
                                   (2, 5)])
def test_two_sided_search_equals_the_host_bfs(lo, hi):
    """A reach pinned at both ends of an undirected graph runs the two-sided
    program: the same distance as a one-sided BFS, in ceil(d/2) passes."""
    from repro.core import pattern
    rng = np.random.default_rng(11)
    # a sparse random graph on 0..49 and a path 50-51-...-59 apart from it
    a, b = rng.integers(0, 50, 70), rng.integers(0, 50, 70)
    edges = [(int(x), int(y)) for x, y in zip(a, b) if x != y]
    edges += [(i, i + 1) for i in range(50, 59)]
    db = _toy(edges, 60)
    g = db.graphs["knows"]
    eng = GredoEngine(db)
    pairs = [(0, 0), (3, 55), (50, 59), (51, 57)] + [
        (int(s), int(t)) for s, t in rng.integers(0, 50, (12, 2))]
    for s, t in pairs:
        q = Query(select=("e0.hops",), froms=(),
                  match=chain_pattern("knows", ("a", "Person", "knows", "b",
                                                "Person", lo, hi)),
                  where=(Predicate("a.id", "==", s), Predicate("b.id", "==", t)))
        got = np.asarray(eng.query(q).col("e0.hops")).tolist()
        d = int(pattern.bfs_distances(g, s)[0][t])
        assert got == ([d] if d >= 0 and lo <= d <= (hi or d) else []), (s, t)
        dr = next(n for n in _walk(eng.last_dag) if n.kind == "DeviceReach")
        if got:
            assert dr.last_kernel_args["levels"] == -(-d // 2)
