"""End-to-end smoke of GredoDB on one TPU chip: load the M2Bench-shaped
e-commerce data, run the GCDI queries through ``GredoEngine`` (checked
against the single-engine ablation as the plain reference), check that the
optimizer's device pattern match ran on the chip and equals the host
matcher, including across a write burst and a compaction, and run the GCDA
tasks, checking that each ran its Pallas kernel and agrees with the kernel's
jnp oracle.

    python chip_smoke.py [--sf 16] [--seed 0]

One process, no fallbacks: the first failed check exits non-zero. The last
line of stdout is ``{"ok": true, "device": {...}}`` and is printed only when
every phase passed. JAX's persistent compile cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, or else at ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.run import place_compile_cache  # noqa: E402
from repro.core import GredoEngine, analytics, pattern, pattern_jit  # noqa: E402
from repro.core.storage import DictColumn  # noqa: E402
from repro.data import m2bench  # noqa: E402
from repro.kernels.cosine_sim.ref import cosine_sim_ref  # noqa: E402
from repro.kernels.logreg.ref import logreg_grad_ref  # noqa: E402
from repro.kernels.matmul.ref import matmul_ref  # noqa: E402

ITERS, LR, L2 = 100, 0.5, 1e-4      # engine.analyze / analytics.regression
SAMPLE_ROWS = 256                   # rows per sampled block of an n x n output
# max |kernel - oracle| / max(1, max |oracle|), oracle at HIGHEST matmul
# precision. MULTIPLY sums 0/1 products, which are exact; SIMILARITY adds
# only the rsqrt epilogue's rounding; REGRESSION sums 93k-row f32 gradients
# in another order than the oracle, 100 times.
TOL = {"MatMul": 1e-6, "Similarity": 1e-5, "Regression": 1e-4}


class CompileLog:
    """Counts compile requests (each served by the backend compiler or the
    persistent cache), the seconds they took, and the cache's hits and
    misses, via jax.monitoring."""

    def __init__(self):
        self.requests = 0
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0

    def install(self) -> None:
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def _values(t, name: str) -> list:
    c = t.col(name)
    return (c.decode(c.codes) if isinstance(c, DictColumn)
            else np.asarray(c)).tolist()


def rows(t) -> list[tuple]:
    """The relation as a sorted row multiset (columns in name order)."""
    return sorted(zip(*[_values(t, c) for c in sorted(t.columns)]))


def nodes(root):
    seen, stack = set(), [root]
    while stack:
        n = stack.pop()
        if id(n) not in seen:
            seen.add(id(n))
            yield n
            stack.extend(n.children)


def match_ops(dag) -> list:
    return [n for n in nodes(dag) if n.kind in
            ("MatchPattern", "DeviceMatchPattern", "TableJoinMatch")]


def on_device(dag) -> list:
    return [n for n in match_ops(dag) if n.kind == "DeviceMatchPattern"
            and n.access.startswith("device-") and n.stats.executed]


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def timed(fn, *args):
    t0 = time.perf_counter()
    out = fn(*args)
    return out, (time.perf_counter() - t0) * 1e3


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def phase_device():
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{d.platform!r} ({d.device_kind}, {len(devs)} device(s))",
              file=sys.stderr)
        sys.exit(1)
    return d, f"kind={d.device_kind!r} count={len(devs)}"


def phase_load(sf: int, seed: int):
    db = m2bench.generate(sf=sf, seed=seed)
    m2bench.build_indexes(db)
    sizes = " ".join(f"{g.name}_edges={g.edges.nrows}"
                     for g in db.graphs.values())
    return db, f"sf={sf} seed={seed} orders={db.tables['Orders'].nrows} {sizes}"


def gcdi_queries(db) -> dict:
    return {"q_g1": m2bench.q_g1(), "q_g2": m2bench.q_g2(),
            "q_g3": m2bench.q_g3(), "q_g4": m2bench.q_g4(),
            "q_g5": m2bench.q_g5(),
            "q_point_lookup": m2bench.q_point_lookup(
                *m2bench.point_lookup_keys(db)),
            "q_range_narrow": m2bench.q_range_narrow(),
            "q_shard_join": m2bench.q_shard_join()}


def phase_gcdi(eng, ref, queries: dict):
    """gredo == single for every query; returns the queries whose executed
    plan ran a device pattern match."""
    device = []
    for name, q in queries.items():
        got, ms = timed(eng.query, q)
        access = [f"{n.kind}:{getattr(n, 'access', None)}"
                  for n in match_ops(eng.last_dag)] or ["-"]
        if on_device(eng.last_dag):
            device.append(name)
        want, ref_ms = timed(ref.query, q)
        check(rows(got) == rows(want), f"{name}: gredo != single")
        print(f"  gcdi {name}: rows={got.nrows} gredo_ms={ms} "
              f"single_ms={ref_ms} access={','.join(access)}", flush=True)
    check(bool(device), "no GCDI query ran a DeviceMatchPattern")
    return device, f"queries={len(queries)} gredo==single device={device}"


def _device_query(eng, q, name: str):
    """Run ``q`` and return (result, its device match node, that node's
    explain_last line); fails unless the match ran on the device."""
    got = eng.query(q)
    dm = on_device(eng.last_dag)
    check(bool(dm), f"{name}: no DeviceMatchPattern ran")
    lines = [l.strip() for l in eng.explain_last().splitlines()
             if "DeviceMatchPattern" in l and "rows=" in l]
    check(bool(lines) and f"via {dm[0].access}" in lines[0],
          f"{name}: explain_last shows no device run")
    return got, dm[0], lines[0]


def _stale_refused(g, pplan) -> bool:
    try:
        pattern_jit.device_match(g, pplan)
    except pattern_jit.StaleSnapshotError:
        return True
    return False


def phase_traversal(eng, ref, queries: dict, device: list):
    """Each device match equals the host matcher on the same plan; then a
    write burst sends the query to the host and a compaction brings it back
    to the chip, serving the new edges."""
    for name in device:
        _, dm, line = _device_query(eng, queries[name], name)
        g = eng.db.graphs[dm.graph]
        dev_rel, _ = pattern_jit.device_match(g, dm.pplan,
                                              initial_capacity=dm.capacity)
        check(rows(dev_rel) == rows(pattern.match(g, dm.pplan)),
              f"{name}: device match != host matcher")
        plat = {d.platform for d in pattern_jit.get_matcher(g).row_ptr.devices()}
        check(plat == {jax.devices()[0].platform},
              f"{name}: device CSR lives on {plat}")
        print(f"  device {name}: rows={dev_rel.nrows} == host; {line}",
              flush=True)

    name = device[0]
    q = queries[name]
    _, dm, _ = _device_query(eng, q, name)
    g = eng.db.graphs[dm.graph]
    rng = np.random.default_rng(0)
    pick = rng.choice(g.edges.nrows, 256, replace=False)
    batch = {c: np.asarray(g.edges.col(c))[pick] for c in g.edges.columns}
    batch["tvid"] = rng.permutation(batch["tvid"])
    g.insert_edges(batch)
    check(g.delta.has_pending(), "insert left no pending delta")
    check(_stale_refused(g, dm.pplan), "stale device snapshot served a match")
    stale = eng.query(q)
    check(not on_device(eng.last_dag), f"{name}: pending deltas ran on device")
    check(rows(stale) == rows(ref.query(q)), f"{name}: stale gredo != single")
    g.compact()
    check(not g.delta.has_pending(), "compaction left pending deltas")
    got, _, _ = _device_query(eng, q, name)
    check(pattern_jit.get_matcher(g).epoch == g.epoch, "device snapshot stale")
    check(rows(got) == rows(ref.query(q)), f"{name}: refreshed gredo != single")
    return None, (f"device={device} == host; write burst on {g.name}: "
                  f"pending->host rows={stale.nrows}, compacted->device "
                  f"rows={got.nrows}")


def _multi_hot(rel, group: str, value: str, width: int) -> np.ndarray:
    ids, row = np.unique(np.asarray(rel.col(group)), return_inverse=True)
    x = np.zeros((len(ids), width), np.float32)
    x[row, np.asarray(rel.col(value))] = 1.0
    return x


@jax.jit
def _regression_ref(x, y):
    def step(_, w):
        g, _ = logreg_grad_ref(x, y, w)
        return w - LR * (g + L2 * w)
    return jax.lax.fori_loop(0, ITERS, step,
                             jnp.zeros((x.shape[1],), jnp.float32))


def _err(got, want) -> float:
    return float(jnp.max(jnp.abs(got - want))
                 / jnp.maximum(jnp.max(jnp.abs(want)), 1.0))


def phase_gcda(eng):
    """A2, A3 and the shard-join REGRESSION through engine.analyze. The
    oracle's inputs are rebuilt with numpy from the GCDI relations already
    checked against the single engine."""
    g1 = eng.query(m2bench.q_g1())
    x = jnp.asarray(_multi_hot(g1, "Customer.id", "t.tid", m2bench.N_TAGS))
    sj = eng.query(m2bench.q_shard_join())
    task_reg = m2bench.a_shard_reg()
    feats, label = (spec[1] for spec in task_reg.analytics.inputs)
    xf = jnp.asarray(np.stack([np.asarray(sj.col(c), np.float32)
                               for c in feats], axis=1))
    yl = jnp.asarray(np.asarray(sj.col(label[0]), np.float32))
    n = x.shape[0]
    blocks = sorted({0, max(n // 2 - SAMPLE_ROWS // 2, 0),
                     max(n - SAMPLE_ROWS, 0)})

    cases = [
        ("Similarity", m2bench.a2_similarity(), (n, n),
         lambda a: analytics.similarity(a, a), (x,),
         lambda lo: cosine_sim_ref(x[lo:lo + SAMPLE_ROWS], x)),
        ("MatMul", m2bench.a3_multiply(), (n, n),
         lambda a: analytics.multiply(a, a.T), (x,),
         lambda lo: matmul_ref(x[lo:lo + SAMPLE_ROWS], x.T)),
        ("Regression", task_reg, (xf.shape[1],),
         lambda a, b: analytics.regression(a, b, iters=ITERS), (xf, yl),
         None),
    ]
    summary = []
    for op, task, shape, program, args, oracle in cases:
        t0 = time.perf_counter()
        out = jax.block_until_ready(eng.analyze(task, iters=ITERS))
        ms = (time.perf_counter() - t0) * 1e3
        check(out.shape == shape, f"{op}: shape {out.shape} != {shape}")
        hlo = jax.jit(program).lower(*args).compile().as_text()
        check("tpu_custom_call" in hlo, f"{op}: no Pallas kernel in program")
        with jax.default_matmul_precision("highest"):
            if oracle is None:
                parts = [(out, _regression_ref(xf, yl))]
            else:
                parts = [(out[lo:lo + SAMPLE_ROWS], oracle(lo))
                         for lo in blocks]
            err = max(_err(a, b) for a, b in parts)
        check(all(bool(jnp.all(jnp.isfinite(a))) for a, _ in parts),
              f"{op}: non-finite output")
        check(err <= TOL[op], f"{op}: error {err} > {TOL[op]}")
        print(f"  gcda {op}: shape={tuple(out.shape)} ms={ms} "
              f"tpu_custom_call=yes err={err} tol={TOL[op]}", flush=True)
        summary.append(f"{op}{tuple(out.shape)}")
        del out     # free the n x n output before the next task runs
    return None, " ".join(summary)


def run_phase(name: str, fn, *args):
    t0 = time.perf_counter()
    out, detail = fn(*args)
    print(f"phase {name}: {time.perf_counter() - t0} s {detail}", flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=int, default=16,
                    help="m2bench scale factor (default 16)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache_dir = place_compile_cache()
    dev = run_phase("device", phase_device)
    compiles = CompileLog()
    compiles.install()
    db = run_phase("load", phase_load, args.sf, args.seed)
    eng = GredoEngine(db, mode="gredo")
    ref = GredoEngine(db, mode="single")
    queries = gcdi_queries(db)
    device = run_phase("gcdi", phase_gcdi, eng, ref, queries)
    run_phase("traversal", phase_traversal, eng, ref, queries, device)
    run_phase("gcda", phase_gcda, eng)
    peak = dev.memory_stats()["peak_bytes_in_use"]
    print(f"memory: peak_bytes_in_use={peak}")
    print(f"compile: requests={compiles.requests} seconds={compiles.seconds} "
          f"cache_hits={compiles.hits} cache_misses={compiles.misses} "
          f"cache_dir={cache_dir}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
