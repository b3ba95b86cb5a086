"""Fused traversal kernel family + device access path: kernel == jnp oracle
== per-hop jit matcher == host engine (property-tested), overflow retry,
epoch-staleness discipline, optimizer lowering, runtime fallback, batched
point lookups, and the counts the device match reports."""
from types import SimpleNamespace

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import GredoEngine, optimizer, physical, telemetry
from repro.core.pattern import match, plan_pattern
from repro.core.pattern_jit import (COUNTERS, DevicePatternMatcher,
                                    StaleSnapshotError, device_match,
                                    get_matcher)
from repro.core.schema import Predicate, chain_pattern
from repro.core.storage import Graph, Table
from repro.data import m2bench
from repro.kernels.traversal import ops as kops
from repro.kernels.traversal import ref as kref
from repro.kernels.traversal import traversal as kern


def _mk_graph(seed, n_a=20, n_b=10, n_e=80):
    rng = np.random.default_rng(seed)
    A = Table("A", {"attr": rng.integers(0, 3, n_a)})
    B = Table("B", {"attr": rng.integers(0, 3, n_b)})
    E = Table("E", {"svid": rng.integers(0, n_a, n_e),
                    "tvid": rng.integers(0, n_b, n_e),
                    "w": rng.integers(0, 10, n_e)})
    return Graph("G", {"A": A, "B": B}, E, "A", "B")


def _rows(t: Table):
    cols = sorted(t.columns)
    out = []
    for i in range(t.nrows):
        row = []
        for c in cols:
            col = t.col(c)
            v = col.codes[i] if hasattr(col, "codes") else np.asarray(col)[i]
            row.append(v.item() if hasattr(v, "item") else v)
        out.append(tuple(row))
    return sorted(out)


# ---------------------------------------------------------------------------
# Kernel (interpret mode) == jnp oracle, single and batched
# ---------------------------------------------------------------------------


def _random_hop_inputs(seed, n=12, chunk=8):
    rng = np.random.default_rng(seed)
    deg = rng.integers(0, 9, n)
    row_ptr = np.zeros(n + 1, np.int64)
    row_ptr[1:] = np.cumsum(deg)
    m = int(row_ptr[-1])
    col_idx = rng.integers(0, n, m)
    edge_id = rng.permutation(m)
    member = rng.random(n) < 0.7
    edge_pred = rng.random(max(m, 1)) < 0.6
    nch = max(-(-max(m, 1) // chunk), 1)
    chunk_alive = np.ones(nch, bool)
    # kill chunks with no surviving predicate rows (what zone maps compute)
    for c in range(nch):
        if not edge_pred[c * chunk:(c + 1) * chunk].any():
            chunk_alive[c] = False
    return row_ptr, col_idx, edge_id, member, edge_pred, chunk_alive


@pytest.mark.parametrize("seed,capacity", [(0, 128), (1, 128), (2, 256)])
def test_fused_hop_kernel_matches_ref(seed, capacity):
    rp, ci, ei, mem, ep, ca = _random_hop_inputs(seed)
    rng = np.random.default_rng(seed + 100)
    n = len(rp) - 1
    C0 = 6
    frontier = np.zeros(capacity, np.int32)
    frontier[:C0] = rng.integers(0, n, C0)
    fmask = np.zeros(capacity, bool)
    fmask[:C0] = True
    kw = dict(capacity=capacity, chunk=8)
    r = kref.fused_hop_ref(rp, ci, ei, frontier, fmask, mem, ep, ca, **kw)
    k = kern.fused_hop(rp, ci, ei, frontier, fmask, mem, ep, ca,
                       interpret=True, **kw)
    for a, b in zip(r, k):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_batched_hop_kernel_matches_ref():
    rp, ci, ei, mem, ep, ca = _random_hop_inputs(7)
    rng = np.random.default_rng(7)
    n, B, capacity = len(rp) - 1, 5, 128
    frontiers = np.zeros((B, capacity), np.int32)
    fmasks = np.zeros((B, capacity), bool)
    for q in range(B):
        c0 = rng.integers(1, 8)
        frontiers[q, :c0] = rng.integers(0, n, c0)
        fmasks[q, :c0] = True
    kw = dict(capacity=capacity, chunk=8)
    r = kref.batched_hop_ref(rp, ci, ei, frontiers, fmasks, mem, ep, ca, **kw)
    k = kern.batched_hop(rp, ci, ei, frontiers, fmasks, mem, ep, ca,
                         interpret=True, **kw)
    for a, b in zip(r, k):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# jnp oracle == a plain numpy hop, array for array, on the edge cases of the
# expansion and the compaction
# ---------------------------------------------------------------------------


def _np_hop(row_ptr, col_idx, edge_id, frontier, fmask, member, edge_pred,
            chunk_alive, *, capacity, chunk):
    """The hop's contract in numpy: expand by ``np.repeat``, filter with
    clipped lookups, compact by a boolean mask (stable), pad."""
    fr = np.asarray(frontier, np.int64)
    deg = np.where(fmask, row_ptr[fr + 1] - row_ptr[fr], 0)
    src = np.repeat(np.arange(len(fr)), deg)
    first = np.cumsum(deg) - deg
    pos = row_ptr[fr][src] + np.arange(len(src)) - first[src]
    overflowed = len(src) > capacity
    src, pos = src[:capacity], pos[:capacity]
    dst, eid = col_idx[pos], edge_id[pos]
    clip = lambda i, t: t[np.clip(i, 0, len(t) - 1)]
    keep = (clip(dst, member) & clip(eid // chunk, chunk_alive)
            & clip(eid, edge_pred))
    k = int(keep.sum())
    out = [np.zeros(capacity, np.int64), np.full(capacity, -1, np.int64),
           np.full(capacity, -1, np.int64)]
    for o, v in zip(out, (src, dst, eid)):
        o[:k] = v[keep]
    return (*out, k, overflowed)


def _hop_case(name, capacity=32, chunk=4):
    """Hop inputs for one edge case: the frontier's degrees, which of its
    slots are valid, and the zone-map table."""
    rng = np.random.default_rng(sum(map(ord, name)))
    degs = {"degree0_between": [3, 0, 0, 2, 0, 4, 1, 0, 5],
            "empty_frontier": [2, 3, 1],
            "no_edges": [0, 0, 0, 0],
            "total_at_capacity": [9, 7, 0, 8, 8],
            "total_past_capacity": [9, 7, 0, 8, 9],
            "dead_chunks": [5, 6, 0, 7, 3],
            "short_chunk_table": [5, 6, 0, 7, 3]}[name]
    n = len(degs)
    row_ptr = np.zeros(n + 1, np.int64)
    row_ptr[1:] = np.cumsum(degs)
    m = max(int(row_ptr[-1]), 1)        # one dummy entry for an edgeless graph
    col_idx = rng.integers(0, n, m)
    edge_id = rng.permutation(m)
    member = rng.random(n) < 0.8
    edge_pred = rng.random(m) < 0.8
    nch = -(-m // chunk)
    chunk_alive = np.ones(nch, bool)
    if name == "dead_chunks":
        chunk_alive[::2] = False
    if name == "short_chunk_table":
        chunk_alive = chunk_alive[:nch // 2]
        chunk_alive[-1] = False         # tids past the table read this entry
    frontier = np.zeros(capacity, np.int32)
    frontier[:n] = np.arange(n)
    fmask = np.zeros(capacity, bool)
    fmask[:n] = name != "empty_frontier"
    if name == "degree0_between":
        fmask[6] = False                # an invalid slot between live rows
    return (row_ptr, col_idx, edge_id, frontier, fmask, member, edge_pred,
            chunk_alive), dict(capacity=capacity, chunk=chunk)


HOP_CASES = ["degree0_between", "empty_frontier", "no_edges",
             "total_at_capacity", "total_past_capacity", "dead_chunks",
             "short_chunk_table"]


@pytest.mark.parametrize("case", HOP_CASES)
def test_fused_hop_ref_matches_numpy_hop(case):
    args, kw = _hop_case(case)
    want = _np_hop(*args, **kw)
    got = kref.fused_hop_ref(*args, **kw)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), w)
    total = int(np.where(args[4], np.diff(args[0])[args[3]], 0).sum())
    assert bool(got[4]) == (total > kw["capacity"])
    if case == "total_at_capacity":
        assert total == kw["capacity"] and not bool(got[4])
    if case == "total_past_capacity":
        assert total == kw["capacity"] + 1 and int(got[3]) > 0


def test_batched_hop_ref_matches_numpy_hop_per_query():
    args, kw = _hop_case("degree0_between")
    row_ptr, col_idx, edge_id, _, _, member, edge_pred, chunk_alive = args
    rng = np.random.default_rng(15)
    n, B, capacity = len(row_ptr) - 1, 4, kw["capacity"]
    frontiers = np.zeros((B, capacity), np.int32)
    fmasks = np.zeros((B, capacity), bool)
    for q in range(B):                  # query 0 has an empty frontier
        frontiers[q, :2 * q] = rng.integers(0, n, 2 * q)
        fmasks[q, :2 * q] = rng.random(2 * q) < 0.8
    got = kref.batched_hop_ref(row_ptr, col_idx, edge_id, frontiers, fmasks,
                               member, edge_pred, chunk_alive, **kw)
    for q in range(B):
        want = _np_hop(row_ptr, col_idx, edge_id, frontiers[q], fmasks[q],
                       member, edge_pred, chunk_alive, **kw)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g)[q], w)


# ---------------------------------------------------------------------------
# Property test: host == per-hop jit == whole-chain path, including
# tombstone-then-compact write bursts and overflow-forcing capacities
# ---------------------------------------------------------------------------


@given(st.integers(0, 5000), st.sampled_from([None, 0, 1, 2]),
       st.sampled_from([None, 3, 7]), st.booleans())
@settings(max_examples=12, deadline=None)
def test_three_way_equivalence(seed, vpred, wcut, delete_some):
    g = _mk_graph(seed)
    if delete_some:
        rng = np.random.default_rng(seed + 1)
        g.delete_edges(rng.choice(g.edges.nrows, 9, replace=False))
        g.compact()     # device snapshots read base CSRs only
    pattern = chain_pattern("G", ("x", "A", "E", "y", "B"))
    phi = {}
    if vpred is not None:
        phi["y"] = [Predicate("y.attr", "==", vpred)]
    if wcut is not None:
        phi["e0"] = [Predicate("e0.w", "<=", wcut)]
    plan = plan_pattern(g, pattern, {k: list(v) for k, v in phi.items()},
                        projected=set(), force_reverse=False,
                        enable_pushdown=True)
    host = _rows(match(g, plan))
    jit_rel, _ = device_match(g, plan, flavor="jit", initial_capacity=128)
    pal_rel, kargs = device_match(g, plan, flavor="chain",
                                  initial_capacity=128)
    assert _rows(jit_rel) == host
    assert _rows(pal_rel) == host
    assert kargs["flavor"] == "chain" and kargs["hops"] == 1
    assert kargs["capacity"] >= 128 and kargs["launches"] >= 1


def test_pallas_kernel_path_matches_host():
    """Force the actual Pallas kernel (interpret mode on CPU) through
    device_match, not just its jnp oracle."""
    g = _mk_graph(42)
    pattern = chain_pattern("G", ("x", "A", "E", "y", "B"))
    phi = {"y": [Predicate("y.attr", "==", 1)]}
    plan = plan_pattern(g, pattern, phi, projected=set(),
                        force_reverse=False, enable_pushdown=True)
    host = _rows(match(g, plan))
    rel, _ = device_match(g, plan, flavor="chain", initial_capacity=128,
                          use_kernel=True)
    assert _rows(rel) == host


# ---------------------------------------------------------------------------
# Overflow retry: capacity doubling is counted per flavor and per capacity
# ---------------------------------------------------------------------------


def test_jit_overflow_retry_counts_recompiles():
    g = _mk_graph(3)
    m = DevicePatternMatcher(g, initial_capacity=16)   # frontier is 20 wide
    lo, hi = g.label_range("A")
    m.match_chain(np.arange(lo, hi), [None], [None])
    assert m.recompiles >= 1
    assert m.last_capacity > 16


def test_pallas_overflow_retry_counts_capacities():
    g = _mk_graph(4, n_e=500)          # ~500 candidates >> capacity 128
    pattern = chain_pattern("G", ("x", "A", "E", "y", "B"))
    plan = plan_pattern(g, pattern, {}, projected=set(),
                        force_reverse=False, enable_pushdown=True)
    before = COUNTERS.retries
    rel, _ = device_match(g, plan, flavor="chain", initial_capacity=128)
    assert COUNTERS.retries > before
    assert any(cap > 128 for cap in COUNTERS.retry_caps)
    assert _rows(rel) == _rows(match(g, plan))


def test_overflow_relaunch_repeats_stage_and_launch_phases():
    g = _mk_graph(4, n_e=500)          # ~500 candidates >> capacity 128
    pattern = chain_pattern("G", ("x", "A", "E", "y", "B"))
    plan = plan_pattern(g, pattern, {}, projected=set(),
                        force_reverse=False, enable_pushdown=True)
    trace = telemetry.QueryTrace("t")
    op = trace.begin("DeviceMatchPattern", cat="gcda")
    rel, counts = device_match(g, plan, flavor="chain", initial_capacity=128,
                               trace=trace)
    trace.end(op)
    n = counts["launches"]
    assert n >= 2 and counts["capacity"] > 128
    phases = trace.children_of(op)
    assert [p.name for p in phases] == (["lower"] + ["stage", "launch"] * n
                                        + ["readback"])
    assert all(p.cat == telemetry.PHASE for p in phases)
    assert sum(p.dur for p in phases) <= trace.spans[op].dur
    assert _rows(rel) == _rows(match(g, plan))
    # the per-hop jit flavor records no phases
    device_match(g, plan, flavor="jit", trace=trace)
    assert len(trace.spans) == 2 + len(phases)


# ---------------------------------------------------------------------------
# Epoch-staleness discipline: refuse on pending deltas, refresh on compaction
# ---------------------------------------------------------------------------


def test_stale_snapshot_refused_then_refreshed():
    g = _mk_graph(5)
    m = get_matcher(g)
    lo, hi = g.label_range("A")
    epoch0 = m.epoch
    g.insert_edges({"svid": np.array([0, 1]), "tvid": np.array([0, 1]),
                    "w": np.array([1, 2])})
    with pytest.raises(StaleSnapshotError):
        m.match_chain(np.arange(lo, hi), [None], [None])
    # the fused flavor refuses through the same snapshot
    pattern = chain_pattern("G", ("x", "A", "E", "y", "B"))
    plan = plan_pattern(g, pattern, {}, projected=set(),
                        force_reverse=False, enable_pushdown=True)
    with pytest.raises(StaleSnapshotError):
        device_match(g, plan, flavor="chain")
    g.compact()
    cols, _ = m.match_chain(np.arange(lo, hi), [None], [None])
    assert m.epoch == g.epoch > epoch0
    assert m.refreshes >= 1
    assert len(cols[0]) == g.n_live_edges     # unconstrained 1-hop == edges


# ---------------------------------------------------------------------------
# Optimizer lowering + runtime fallback + telemetry plumbing (m2bench)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def db():
    return m2bench.generate(sf=1)


def test_engine_lowers_selective_chain_to_device(db):
    eng = GredoEngine(db)
    q = m2bench.q_g3()
    dag = eng.optimized_plan(q)
    rendered = physical.explain(dag)
    assert "DeviceMatchPattern" in rendered
    assert "via device-chain" in rendered
    assert any("access-path" in n and "device-chain" in n
               for n in eng.last_report.notes())
    opt = eng.query(q)
    optimizer.DEVICE_MATCH = False
    try:
        host = GredoEngine(db).query(q)
    finally:
        optimizer.DEVICE_MATCH = True
    assert _rows(opt) == _rows(host)


def test_runtime_fallback_on_pending_delta():
    g = _mk_graph(6)
    db1 = SimpleNamespace(graphs={"G": g})
    pattern = chain_pattern("G", ("x", "A", "E", "y", "B"))
    plan = plan_pattern(g, pattern, {}, projected=set(),
                        force_reverse=False, enable_pushdown=True)
    node = physical.DeviceMatchPattern("G", g.epoch, plan, capacity=128)
    g.insert_edges({"svid": np.array([2]), "tvid": np.array([2]),
                    "w": np.array([5])})
    out = node.run(SimpleNamespace(db=db1))
    assert node.access == "host-fallback"
    assert _rows(out) == _rows(match(g, plan))


def test_device_query_registry_delta_and_explain(db):
    eng = GredoEngine(db, telemetry=True)
    eng.query(m2bench.q_g3())
    d = eng.last_registry_delta
    assert d.get("traversal_kernels.matches", 0) >= 1
    assert d.get("traversal_kernels.kernel.launches", 0) >= 1
    txt = eng.explain_last()
    assert "traversal kernels (this query):" in txt
    assert "via device-chain" in txt


# ---------------------------------------------------------------------------
# Batched point lookups: one launch == B sequential single-query chains
# ---------------------------------------------------------------------------


def test_batched_traverse_matches_per_query_chains():
    g = _mk_graph(8, n_a=80, n_b=40, n_e=400)
    m = get_matcher(g)
    rp, ci, ei = m.csr(False)
    lo, hi = g.label_range("A")
    starts = np.arange(lo, min(lo + 64, hi), dtype=np.int64)
    assert len(starts) == 64
    members = [None]
    epreds = [np.asarray(g.edges.col("w")) <= 5]
    cals = [None]
    kw = dict(capacity=128, chunk=8)
    bv, be, counts, ok = kops.batched_traverse(
        rp, ci, ei, g.n_vertices, g.edges.nrows, starts, members, epreds,
        cals, **kw)
    assert ok
    for qi, s in enumerate(starts):
        sv, se, sok = kops.traverse_chain(
            rp, ci, ei, g.n_vertices, g.edges.nrows, np.array([s]),
            members, epreds, cals, **kw)
        assert sok
        k = counts[qi]
        assert len(sv[0]) == k
        for col_b, col_s in zip(bv, sv):
            np.testing.assert_array_equal(col_b[qi, :k], col_s)
        for col_b, col_s in zip(be, se):
            np.testing.assert_array_equal(col_b[qi, :k], col_s)
