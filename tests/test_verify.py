"""Static plan verifier (repro.core.verify): golden broken-DAG fixtures —
one deliberately ill-formed plan per rule family, asserting the verifier
rejects it with the expected rule ID — plus pinning regressions for the
invariant violations the plan sweep surfaced, and a property test that the
optimizer + shard rewrites preserve verifier-inferred schemas under random
delta/tombstone/compaction streams.
"""
from __future__ import annotations

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import cost, optimizer, physical as ph, verify
from repro.core.engine import GredoEngine
from repro.core.schema import (JoinPred, Pattern, PatternVertex, Predicate,
                               Query, chain_pattern)
from repro.core.storage import Database, DictColumn, Graph, RaggedColumn, Table
from repro.data import m2bench

pytestmark = pytest.mark.fast

MODES = ("gredo", "dual", "single")


# ---------------------------------------------------------------------------
# fixtures: a tiny hand-built db for golden broken DAGs, m2bench for
# integration-level checks
# ---------------------------------------------------------------------------

def mini_db() -> Database:
    db = Database()
    db.add_table(Table("T", {
        "a": np.arange(8, dtype=np.int64),
        "f": np.linspace(0.0, 1.0, 8),
        "s": DictColumn(["x", "y"] * 4),
        "r": RaggedColumn([[1, 2], [3]] * 4),
    }))
    db.add_table(Table("U", {
        "k": np.arange(8, dtype=np.int64),
        "s": DictColumn(["x", "z"] * 4),
    }))
    return db


def scan(db: Database, name: str) -> ph.ScanTable:
    return ph.ScanTable(name, db.epoch_of(name))


_M2 = {}


def m2db() -> Database:
    """Shared read-only m2bench database (module-scope cache; hypothesis
    given-wrapped tests cannot take pytest fixtures)."""
    if "db" not in _M2:
        db = m2bench.generate(sf=1)
        m2bench.build_indexes(db)
        _M2["db"] = db
    return _M2["db"]


def rules_of(report: verify.VerifyReport, severity=None) -> set:
    vs = report.violations
    if severity is not None:
        vs = [v for v in vs if v.severity == severity]
    return {v.rule for v in vs}


# ---------------------------------------------------------------------------
# golden broken-DAG fixtures, one per rule
# ---------------------------------------------------------------------------

def test_vcol_unresolved_select_column():
    db = mini_db()
    bad = ph.Select(scan(db, "T"), [Predicate("T.zzz", "==", 1)])
    report = verify.verify_plan(bad, db)
    assert not report.ok
    assert "V-COL" in rules_of(report, verify.ERROR)


def test_vcol_unqualified_predicate():
    db = mini_db()
    bad = ph.Select(scan(db, "T"), [Predicate("a", "==", 1)])
    report = verify.verify_plan(bad, db)
    assert "V-COL" in rules_of(report, verify.ERROR)


def test_vcol_clean_plan_passes():
    db = mini_db()
    good = ph.Select(scan(db, "T"), [Predicate("T.a", "==", 1)])
    report = verify.verify_plan(good, db)
    assert report.ok and not report.violations


def test_vtype_string_vs_int_join_key():
    db = mini_db()
    bad = ph.EquiJoin(JoinPred("T.a", "U.s"), scan(db, "T"), scan(db, "U"))
    report = verify.verify_plan(bad, db)
    assert not report.ok
    assert "V-TYPE" in rules_of(report, verify.ERROR)


def test_vtype_int_vs_float_key_warns_only():
    db = mini_db()
    join = ph.EquiJoin(JoinPred("T.f", "U.k"), scan(db, "T"), scan(db, "U"))
    report = verify.verify_plan(join, db)
    assert report.ok                       # promotion, not a wrong answer
    assert "V-TYPE" in rules_of(report, verify.WARN)


def test_vgcda_ragged_feature_column():
    db = mini_db()
    bad = ph.Rel2Matrix(["r"], scan(db, "T"))
    report = verify.verify_plan(bad, db)
    assert "V-GCDA" in rules_of(report, verify.ERROR)


def test_vgcda_int_feature_promotion_warns():
    db = mini_db()
    m = ph.Rel2Matrix(["a"], scan(db, "T"))
    report = verify.verify_plan(m, db)
    assert report.ok
    assert "V-GCDA" in rules_of(report, verify.WARN)


def test_vgcda_regression_label_width():
    x = ph.Const(np.ones((4, 3), dtype=np.float32))
    y = ph.Const(np.ones((4, 2), dtype=np.float32))
    bad = ph.Regression(3, False, x, y)
    report = verify.verify_plan(bad, Database())
    assert "V-GCDA" in rules_of(report, verify.ERROR)


def test_vgcda_similarity_width_mismatch():
    a = ph.Const(np.ones((4, 3), dtype=np.float32))
    b = ph.Const(np.ones((4, 5), dtype=np.float32))
    report = verify.verify_plan(ph.Similarity(False, a, b), Database())
    assert "V-GCDA" in rules_of(report, verify.ERROR)


def test_vepoch_stale_scan_epoch():
    db = mini_db()
    node = scan(db, "T")
    db.touch_table("T")
    report = verify.verify_plan(node, db)
    assert "V-EPOCH" in rules_of(report, verify.ERROR)


def test_vepoch_project_vector_misses_source():
    db = mini_db()
    join = ph.EquiJoin(JoinPred("T.a", "U.k"), scan(db, "T"), scan(db, "U"))
    # epoch vector covers T only — U's writes would never invalidate a
    # cached result keyed on this vector
    bad = ph.Project(["T.a"], (("T", db.epoch_of("T")),), join)
    report = verify.verify_plan(bad, db)
    assert "V-EPOCH" in rules_of(report, verify.ERROR)
    ok = ph.Project(["T.a"], (("T", db.epoch_of("T")),
                              ("U", db.epoch_of("U"))), join)
    assert verify.verify_plan(ok, db).ok


def test_vepoch_project_vector_unknown_collection():
    db = mini_db()
    bad = ph.Project(["T.a"], (("T", db.epoch_of("T")),
                               ("Ghost", 0)), scan(db, "T"))
    report = verify.verify_plan(bad, db)
    assert "V-EPOCH" in rules_of(report, verify.ERROR)


def _two_label_graph_db() -> Database:
    """Graph whose two vertex labels share a column name at different
    dtypes — the raw material for a signature collision."""
    db = Database()
    ta = Table("A", {"v": np.arange(4, dtype=np.int64)})
    tb = Table("B", {"v": DictColumn(["x", "y", "z", "w"])})
    edges = Table("G_edges", {"svid": np.array([0, 1], dtype=np.int64),
                              "tvid": np.array([0, 1], dtype=np.int64)})
    db.add_table(Table("X", {"x": np.arange(3, dtype=np.int64)}))
    db.add_graph(Graph("G", {"A": ta, "B": tb}, edges, "A", "B"))
    return db


def test_vsig_signature_collision_across_plans():
    # GraphProject's signature params carry (keep, wanted) but not the
    # pattern, so two projections with identical params over the same child
    # can disagree on the backing label — and therefore the dtype of x.v.
    db = _two_label_graph_db()
    child = scan(db, "X")                  # yields the bound var column "x"
    gep = db.epoch_of("G")
    pat_a = Pattern("G", (PatternVertex("x", "A"),), ())
    pat_b = Pattern("G", (PatternVertex("x", "B"),), ())
    gp_a = ph.GraphProject("G", gep, pat_a, ("x",), {"x": ["v"]}, child)
    gp_b = ph.GraphProject("G", gep, pat_b, ("x",), {"x": ["v"]}, child)
    assert gp_a.signature() == gp_b.signature()
    report, sigs = verify.VerifyReport(), {}
    verify.verify_plan(gp_a, db, report, sigs)
    assert report.ok                       # first plan is internally fine
    verify.verify_plan(gp_b, db, report, sigs)
    assert "V-SIG" in rules_of(report, verify.ERROR)


def test_vsig_inplace_column_swap_detected():
    # swapping a column in place without bumping the epoch leaves equal
    # signatures pointing at different schemas — the cache-poisoning hazard
    db = mini_db()
    report, sigs = verify.VerifyReport(), {}
    verify.verify_plan(scan(db, "T"), db, report, sigs)
    t = db.tables["T"]
    t.columns["a"] = np.linspace(0.0, 1.0, 8)    # int64 -> float64, no touch
    verify.verify_plan(scan(db, "T"), db, report, sigs)
    assert "V-SIG" in rules_of(report, verify.ERROR)


def test_vshard_join_without_exchange():
    db = mini_db()
    join = ph.EquiJoin(JoinPred("T.a", "U.k"), scan(db, "T"), scan(db, "U"))
    join.shards = 2
    report = verify.verify_plan(join, db)
    assert "V-SHARD" in rules_of(report, verify.ERROR)


def test_vshard_misaligned_exchange_key():
    db = mini_db()
    ex = ph.Exchange(scan(db, "U"), key="U.s", k=2)   # partitions the wrong key
    join = ph.EquiJoin(JoinPred("T.a", "U.k"), scan(db, "T"), ex)
    join.shards = 2
    report = verify.verify_plan(join, db)
    assert "V-SHARD" in rules_of(report, verify.ERROR)


def test_vshard_aligned_exchange_passes():
    db = mini_db()
    ex = ph.Exchange(scan(db, "U"), key="U.k", k=2)
    join = ph.EquiJoin(JoinPred("T.a", "U.k"), scan(db, "T"), ex)
    join.shards = 2
    assert verify.verify_plan(join, db).ok


def test_vshard_stamp_on_non_shardable_kind():
    db = mini_db()
    node = scan(db, "T")
    node.shards = 2
    report = verify.verify_plan(node, db)
    assert "V-SHARD" in rules_of(report, verify.ERROR)


def test_vshard_exchange_outside_build_side():
    db = mini_db()
    report = verify.verify_plan(ph.Exchange(scan(db, "T"), key="T.a", k=2), db)
    assert "V-SHARD" in rules_of(report, verify.ERROR)


def _device_node(db: Database):
    """A DeviceMatchPattern as the optimizer actually lowers it (q_g3 lowers
    at sf=1), or None when lowering is off in this build."""
    eng = GredoEngine(db)
    naive = eng.physical_plan(m2bench.q_g3())
    dag, _ = optimizer.optimize(naive, db, cache=eng._opt_cache)
    for n in verify._walk(dag):
        if n.kind == "DeviceMatchPattern":
            return n
    return None


def test_vdev_capacity_below_frontier_bound():
    db = m2db()
    flag = optimizer.DEVICE_MATCH
    optimizer.DEVICE_MATCH = True
    try:
        node = _device_node(db)
    finally:
        optimizer.DEVICE_MATCH = flag
    assert node is not None, "q_g3 no longer device-lowers at sf=1"
    assert verify.verify_plan(node, db).ok
    starved = ph.DeviceMatchPattern(node.graph, node.epoch, node.pplan,
                                    access=node.access, capacity=8)
    report = verify.verify_plan(starved, db)
    assert "V-DEV" in rules_of(report, verify.ERROR)


def test_vdev_mask_children_rejected():
    db = m2db()
    flag = optimizer.DEVICE_MATCH
    optimizer.DEVICE_MATCH = True
    try:
        node = _device_node(db)
    finally:
        optimizer.DEVICE_MATCH = flag
    assert node is not None
    masked = ph.DeviceMatchPattern(node.graph, node.epoch, node.pplan,
                                   access=node.access, capacity=node.capacity)
    masked.children = (ph.SemiJoinMask(node.graph, node.epoch, "Persons",
                                       "p", "Persons.id",
                                       scan(db, "Persons")),)
    report = verify.verify_plan(masked, db)
    assert "V-DEV" in rules_of(report, verify.ERROR)


def _reach_nodes(db: Database):
    """The optimized plan of a 1..3-hop reach over Follows, its
    DeviceReach and its GraphProject."""
    q = Query(select=("b.pid", "e0.hops"), froms=(),
              match=chain_pattern("Follows", ("a", "Persons", "Follows", "b",
                                              "Persons", 1, 3)),
              where=(Predicate("a.pid", "==", 3),))
    dag = GredoEngine(db).optimized_plan(q)
    nodes = list(verify._walk(dag))
    return (dag, next(n for n in nodes if n.kind == "DeviceReach"),
            next(n for n in nodes if n.kind == "GraphProject"))


@pytest.mark.parametrize("case", ["ok", "over_budget", "mask_child",
                                  "chain_plan", "edge_predicate",
                                  "projected_attr", "pending_deltas"])
def test_vdev_device_reach(case):
    db = m2db()
    dag, node, proj = _reach_nodes(db)
    if case == "ok":
        assert verify.verify_plan(dag, db).ok
        assert node.budget >= cost.dense_adjacency_bytes(
            db.graphs["Follows"].n_vertices)
        return
    if case == "pending_deltas":
        db = m2bench.generate(sf=1)
        dag, node, _ = _reach_nodes(db)
        db.graphs["Follows"].insert_edges(
            {"svid": np.array([0]), "tvid": np.array([1]),
             **{k: db.graphs["Follows"].edges.col(k)[:1]
                for k in db.graphs["Follows"].edges.columns
                if k not in ("svid", "tvid")}})
        # as planned after the write, at the graph's new epoch
        node = ph.DeviceReach(node.graph, db.epoch_of(node.graph), node.pplan,
                              node.budget)
        report = verify.verify_plan(node, db)
        assert report.ok and "V-DEV" in rules_of(report, verify.WARN)
        return
    bad, rule = node, "V-DEV"
    if case == "over_budget":
        bad = ph.DeviceReach(node.graph, node.epoch, node.pplan, budget=10)
    elif case == "mask_child":
        bad = ph.DeviceReach(node.graph, node.epoch, node.pplan, node.budget)
        bad.children = (ph.SemiJoinMask(node.graph, node.epoch, "Persons",
                                        "p", "Persons.id",
                                        scan(db, "Persons")),)
    elif case == "chain_plan":
        flag = optimizer.DEVICE_MATCH
        optimizer.DEVICE_MATCH = True
        try:
            chain = _device_node(db)
        finally:
            optimizer.DEVICE_MATCH = flag
        bad = ph.DeviceReach(node.graph, node.epoch, chain.pplan, node.budget)
    elif case == "edge_predicate":
        import dataclasses
        pplan = dataclasses.replace(node.pplan, deferred={
            "e0": [Predicate("e0.hops", "==", 2)]})
        bad, rule = ph.Reach(node.graph, node.epoch, pplan), "V-COL"
    else:
        bad = ph.GraphProject(proj.graph, proj.epoch, proj.pattern, proj.keep,
                              {"e0": ["weight"]}, node)
        rule = "V-COL"
    assert rule in rules_of(verify.verify_plan(bad, db), verify.ERROR)


def test_vann_stale_annotation_warns():
    db = mini_db()
    node = scan(db, "T")
    node.out_cols = frozenset({"a", "ghost"})
    report = verify.verify_plan(node, db)
    assert report.ok                       # annotation drift is a WARN
    assert "V-ANN" in rules_of(report, verify.WARN)


def test_veq_retyped_root_rejected():
    db = mini_db()
    naive = scan(db, "T")
    rewritten = ph.PruneCols(scan(db, "T"), ["a", "f"])   # dropped columns
    report = verify.verify_equivalence(naive, rewritten, db)
    assert "V-EQ" in rules_of(report, verify.ERROR)
    assert verify.verify_equivalence(naive, scan(db, "T"), db).ok


# ---------------------------------------------------------------------------
# pinning regressions for real violations the sweep surfaced
# ---------------------------------------------------------------------------

def test_device_lowering_embeds_catalog_epoch_after_graph_replacement():
    # Regression: _select_match_path embedded g.epoch. After db.add_graph
    # replaces a graph, the catalog carries the old lineage forward
    # (epoch_of = lineage + g.epoch), so a raw g.epoch is stale the moment
    # a graph is re-registered — the cached-device-plan poisoning bug.
    db = m2bench.generate(sf=1)
    m2bench.build_indexes(db)
    flag = optimizer.DEVICE_MATCH
    optimizer.DEVICE_MATCH = True
    try:
        node = _device_node(db)
        assert node is not None
        g = db.graphs[node.graph]
        db.add_graph(g)                     # re-register: lineage +1
        assert db.epoch_of(node.graph) != g.epoch
        node = _device_node(db)             # re-lower against the new catalog
    finally:
        optimizer.DEVICE_MATCH = flag
    assert node is not None
    assert node.epoch == db.epoch_of(node.graph)
    assert verify.verify_plan(node, db).ok
    stale = ph.DeviceMatchPattern(node.graph, db.graphs[node.graph].epoch,
                                  node.pplan, access=node.access,
                                  capacity=node.capacity)
    report = verify.verify_plan(stale, db)
    assert "V-EPOCH" in rules_of(report, verify.ERROR)


def test_prune_columns_refreshes_out_cols_annotation():
    # Regression: _prune_columns inserted PruneCols under an Alias but left
    # the alias's pre-prune out_cols annotation in place (with_children
    # carries annotations across the clone) — every pruned plan warned V-ANN.
    db = m2db()
    eng = GredoEngine(db)
    for q in (m2bench.q_g1(), m2bench.q_g3(), m2bench.q_opt_skew()):
        report = eng.verify(q)
        assert report.ok
        assert not report.by_rule("V-ANN"), report.render()


def test_optimizer_capacity_matches_verifier_bound():
    # optimizer and verifier must derive the identical frontier bound, or
    # the verifier would reject the optimizer's own lowered plans
    db = m2db()
    flag = optimizer.DEVICE_MATCH
    optimizer.DEVICE_MATCH = True
    try:
        node = _device_node(db)
    finally:
        optimizer.DEVICE_MATCH = flag
    assert node is not None
    g = db.graphs[node.graph]
    peak = cost.device_frontier_peak(g, node.pplan)
    assert node.capacity == cost.padded_capacity(peak)


# ---------------------------------------------------------------------------
# engine integration: verify(q) across modes/shards, debug mode, explain
# ---------------------------------------------------------------------------

def test_engine_verify_all_modes_and_shards():
    db = m2db()
    floor = cost.SHARD_MIN_ROWS
    try:
        for q in (m2bench.q_g2(), m2bench.q_g3(), m2bench.q_shard_join()):
            for mode in MODES:
                for k in (1, 4):
                    cost.SHARD_MIN_ROWS = 0 if k > 1 else floor
                    report = GredoEngine(db, mode=mode, n_shards=k).verify(q)
                    assert report.ok, report.render()
    finally:
        cost.SHARD_MIN_ROWS = floor


def test_gcda_verify_flags_promotions_only():
    report = GredoEngine(m2db()).verify(m2bench.a_shard_reg())
    assert report.ok
    assert rules_of(report) == {"V-GCDA"}   # int64/float64 -> float32 WARNs


def test_debug_engine_verifies_and_matches_plain_results():
    db = m2db()
    q = m2bench.q_g3()
    plain = GredoEngine(db).query(q)
    eng = GredoEngine(db, debug=True)
    dbg = eng.query(q)
    assert eng.last_verify is not None and eng.last_verify.ok
    assert plain.nrows == dbg.nrows
    assert "== verify ==" in eng.explain_last()


def test_debug_engine_raises_on_broken_catalog():
    db = m2bench.generate(sf=1)
    eng = GredoEngine(db, debug=True)
    q = m2bench.q_shard_join()
    eng.query(q)                            # sane baseline
    t = db.tables["Orders"]
    t.columns["customer_id"] = DictColumn(      # join key: int64 -> dict
        ["c"] * len(np.asarray(t.columns["quantity"])))
    with pytest.raises(verify.PlanVerificationError) as ei:
        eng.query(q)
    assert any(v.rule in ("V-TYPE", "V-SIG") for v in ei.value.report.errors)


def test_explain_carries_verify_lines():
    db = m2db()
    text = GredoEngine(db, debug=True).explain(m2bench.q_g3())
    assert "== verify ==" in text
    assert "verify: plan ok" in text or "verify:" in text


# ---------------------------------------------------------------------------
# property: rewrites preserve schemas under random mutation streams
# ---------------------------------------------------------------------------

_PROP = {}


def _prop_db() -> Database:
    if "db" not in _PROP:
        _PROP["db"] = m2bench.generate(sf=1)
    return _PROP["db"]


@st.composite
def _mutation_ops(draw):
    n = draw(st.integers(min_value=1, max_value=4))
    return [draw(st.sampled_from(["edges", "tombstone", "compact", "touch"]))
            for _ in range(n)]


@settings(max_examples=8, deadline=None)
@given(
    ops=_mutation_ops(),
    seed=st.integers(min_value=0, max_value=2**16),
    qname=st.sampled_from(["q_g2", "q_g3", "q_shard_join", "q_opt_skew"]),
    mode=st.sampled_from(MODES),
    shards=st.sampled_from([1, 4]),
)
def test_rewrites_preserve_schemas_under_mutation(ops, seed, qname, mode,
                                                 shards):
    db = _prop_db()
    rng = np.random.default_rng(seed)
    g = db.graphs["Interested_in"]
    for op in ops:
        if op == "edges":
            m = int(rng.integers(1, 30))
            g.insert_edges({
                "svid": rng.integers(0, 100, m).astype(np.int64),
                "tvid": rng.integers(0, m2bench.N_TAGS, m).astype(np.int64),
                "weight": rng.uniform(0.0, 1.0, m),
            })
        elif op == "tombstone":
            live = g.live_edge_ids()
            m = min(int(rng.integers(1, 20)), len(live))
            if m:
                g.delete_edges(rng.choice(live, m, replace=False))
        elif op == "compact":
            g.compact()
        elif op == "touch":
            db.touch_table("Orders")
    q = getattr(m2bench, qname)()
    floor = cost.SHARD_MIN_ROWS
    cost.SHARD_MIN_ROWS = 0 if shards > 1 else floor
    try:
        report = GredoEngine(db, mode=mode, n_shards=shards).verify(q)
    finally:
        cost.SHARD_MIN_ROWS = floor
    # every stage type-checks against the mutated catalog, the rewrite
    # chain never retypes the root, and signatures stay coherent
    assert report.ok, report.render()
    assert not report.by_rule("V-EQ") and not report.by_rule("V-SIG")
