"""Traversal benchmarks: host vs per-hop jit vs whole-chain device match.

The "chain" rows run the whole chain as one XLA program (the
``device-chain`` access path, the same program on every backend): one
jit'd program for the whole chain with ONE end-of-chain host sync (vs a
dispatch + overflow sync per hop), and predicate tables built through
zone-map skip-scans (vs dense full-column eval per hop). The batched rows
measure launch amortization: B point lookups advanced per launch vs B
sequential dispatch sequences. Times are host wall-clock on whatever
backend runs the benchmark.

Tables: traversal_ladder (single-query latency vs start selectivity),
traversal_batched (point-lookup throughput).
"""
from __future__ import annotations

import time

import numpy as np

from repro.core.pattern import match, plan_pattern
from repro.core.pattern_jit import device_match, get_matcher
from repro.core.schema import Predicate, chain_pattern
from repro.core.storage import Database, Graph, Table
from repro.kernels.traversal import ops as kops

GRAPH = "Chain"
SEL_LADDER = (1e-4, 1e-3, 1e-2, 1e-1)
W_CUT = 0.2                   # edge predicate: clustered, zones prune ~80%


def make_db(sf: int = 1, seed: int = 0) -> Database:
    """Homogeneous 2-hop-able graph: n vertices, avg out-degree 8, a
    uniform vertex attribute for the selectivity ladder and a *clustered*
    edge weight (sorted, so zone maps prune the w-range predicate to a
    contiguous chunk band — the kernel's prefetch-filter showcase)."""
    rng = np.random.default_rng(seed)
    n = 20_000 * sf
    V = Table("V", {"vid": np.arange(n, dtype=np.int64),
                    "grp": (np.arange(n, dtype=np.int64) * 7919) % 10_000})
    deg = rng.poisson(8, n).clip(1, 40)
    src = np.repeat(np.arange(n), deg)
    m = len(src)
    E = Table(GRAPH, {"svid": src,
                      "tvid": rng.integers(0, n, m),
                      "w": np.linspace(0.0, 1.0, m)})
    g = Graph(GRAPH, {"V": V}, E, "V", "V")
    db = Database()
    db.add_graph(g)
    db.indexes.create(GRAPH, "w", kind="zone")          # edge zone maps
    db.indexes.create(GRAPH, "grp", label="V")          # start-vertex seed
    return db


def _pattern():
    return chain_pattern(GRAPH, ("a", "V", GRAPH, "b", "V"),
                         ("b", "V", GRAPH, "c", "V"))


def _plan(g, sel: float):
    cut = max(int(sel * 10_000), 1)
    phi = {"a": [Predicate("a.grp", "<", cut)],
           "e0": [Predicate("e0.w", "<=", W_CUT)],
           "e1": [Predicate("e1.w", "<=", W_CUT)]}
    return plan_pattern(g, _pattern(), phi, projected=set(),
                        force_reverse=False, enable_pushdown=True)


def _best(fn, repeat: int) -> float:
    fn()                                   # warm (jit compile, index build)
    best = float("inf")
    for _ in range(repeat):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def latency_ladder(sf: int = 1, repeat: int = 5) -> list[dict]:
    db = make_db(sf=sf)
    g = db.graphs[GRAPH]
    rows = []
    for sel in SEL_LADDER:
        plan = _plan(g, sel)
        n_rows = match(g, plan).nrows
        host_s = _best(lambda: match(g, plan), repeat)
        jit_s = _best(lambda: device_match(g, plan, flavor="jit"), repeat)
        chain_s = _best(lambda: device_match(g, plan, flavor="chain"), repeat)
        rows.append({
            "table": "traversal_ladder", "sf": sf, "sel": sel,
            "rows": n_rows, "host_s": host_s, "jit_s": jit_s,
            "chain_s": chain_s,
            "chain_vs_jit": jit_s / chain_s,
            "chain_vs_host": host_s / chain_s,
        })
    return rows


def batched_throughput(sf: int = 1, repeat: int = 3,
                       batches=(64, 256)) -> list[dict]:
    db = make_db(sf=sf)
    g = db.graphs[GRAPH]
    matcher = get_matcher(g)
    rp, ci, ei = matcher.csr(False)
    rng = np.random.default_rng(1)
    epred = np.asarray(g.edges.col("w")) <= W_CUT
    members = [None, None]
    epreds = [epred, epred]
    cals = [None, None]
    kw = dict(capacity=1024, chunk=2048)
    n, m = g.n_vertices, g.edges.nrows
    rows = []
    for B in batches:
        starts = rng.integers(0, n, B).astype(np.int64)

        def seq_jit():
            for s in starts:
                matcher.match_chain(np.array([s]), members, epreds,
                                    initial_capacity=1024)

        def seq_fused():
            for s in starts:
                _, _, ok = kops.traverse_chain(rp, ci, ei, n, m,
                                               np.array([s]), members,
                                               epreds, cals, **kw)
                assert ok

        def batched():
            out = kops.batched_traverse(rp, ci, ei, n, m, starts, members,
                                        epreds, cals, **kw)
            assert out[3]

        seq_jit_s = _best(seq_jit, repeat)
        seq_fused_s = _best(seq_fused, repeat)
        batched_s = _best(batched, repeat)
        rows.append({
            "table": "traversal_batched", "sf": sf, "B": B,
            "seq_jit_s": seq_jit_s, "seq_fused_s": seq_fused_s,
            "batched_s": batched_s,
            "batched_qps": B / batched_s,
            "speedup_vs_seq_jit": seq_jit_s / batched_s,
            "speedup_vs_seq_fused": seq_fused_s / batched_s,
        })
    return rows


def run_suite(sf: int = 1, fast: bool = False) -> list[dict]:
    repeat = 2 if fast else 5
    rows = latency_ladder(sf=sf, repeat=repeat)
    rows += batched_throughput(sf=sf, repeat=max(repeat - 1, 1),
                               batches=(64,) if fast else (64, 256))
    return rows


def print_rows(rows: list[dict]) -> None:
    for r in rows:
        if r["table"] == "traversal_ladder":
            print(f"traversal_sel{r['sel']:g}_sf{r['sf']},"
                  f"{r['chain_s']*1e6:.1f},"
                  f"host_us={r['host_s']*1e6:.1f};"
                  f"jit_us={r['jit_s']*1e6:.1f};"
                  f"chain_vs_jit={r['chain_vs_jit']:.2f};rows={r['rows']}")
        elif r["table"] == "traversal_batched":
            print(f"traversal_batched_B{r['B']}_sf{r['sf']},"
                  f"{r['batched_s']*1e6:.1f},"
                  f"qps={r['batched_qps']:.0f};"
                  f"vs_seq_jit={r['speedup_vs_seq_jit']:.2f};"
                  f"vs_seq_fused={r['speedup_vs_seq_fused']:.2f}")
