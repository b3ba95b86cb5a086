"""Trace suite: telemetry smoke + disabled-path overhead guard.

1. Run a GCDIA reuse ladder (cold A3 multiply, then the warm A2 similarity
   that shares its GCDI sub-plan) with tracing on; export the Chrome
   trace-event JSON to ``experiments/trace_gcdia.json`` and validate it —
   the spans must cover every executed operator of the DAG *including*
   inter-buffer-hit pseudo-spans.
2. Measure the disabled-telemetry executor against a frozen replica of the
   pre-telemetry ``physical.execute`` on the same DAG. The replica is the
   honest baseline: it is byte-for-byte the old executor body, so the
   comparison isolates exactly what tracing added to the hot path (see
   ``measure_overhead`` for why walk time — wall minus internally-timed
   ``node.run`` — is the only estimator that resolves it under jax
   dispatch noise). Must stay < 2% of end-to-end query time
   (``tests/test_telemetry.py`` guards it too).
"""
from __future__ import annotations

import gc
import json
import os
import sys
import time

import numpy as np

from repro.core import GredoEngine, validate_chrome_trace
from repro.core import physical, telemetry
from repro.core.interbuffer import fingerprint, value_nbytes
from repro.data import m2bench


# ---------------------------------------------------------------------------
# Pre-telemetry executor replica (the overhead baseline)
# ---------------------------------------------------------------------------


def execute_baseline(node: physical.PhysicalOp, ctx: physical.ExecContext):
    """``physical.execute`` exactly as it was before span tracing landed —
    kept verbatim so the overhead ratio measures only the telemetry gates."""
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
    inputs = [execute_baseline(c, ctx) for c in node.children]
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


def measure_overhead(sf: int = 1, repeat: int = 30) -> dict:
    """Disabled-telemetry executor vs the pre-PR replica on the same
    gcdia-suite DAG (fresh ExecContext per run, no inter-buffer, so every
    run re-executes the full operator tree).

    End-to-end wall time cannot resolve the question: the jax dispatch in
    this DAG has ms-scale run-to-run variance while the executor walk
    costs ~100µs, so even paired min-of-N bounces ±5%. Both executors
    time ``node.run`` internally, though — wall minus the summed run()
    seconds is exactly the walk's own bookkeeping cost, with the kernel
    noise subtracted out. ``overhead_pct`` is the added walk time as a
    fraction of end-to-end query time, which is what a user pays."""
    db = m2bench.generate(sf=sf)
    eng = GredoEngine(db)
    task = m2bench.a3_multiply()
    p = eng.plan(task.integration)
    naive = physical.build_gcdia(db, p, task, mode="gredo")
    dag, _ = eng._lower(naive)

    def one(fn, inner: int = 5) -> tuple[float, float]:
        # (wall, walk) per execution, batched so µs-scale costs are
        # resolvable above the timer quantum
        run0 = physical.total_seconds(dag)
        t0 = time.perf_counter()
        for _ in range(inner):
            out = fn(dag, physical.ExecContext(db))
        wall = (time.perf_counter() - t0) / inner
        run_s = (physical.total_seconds(dag) - run0) / inner
        # drain the async jax dispatch queue before the next sample — without
        # this the next sample absorbs this one's still-running device work
        telemetry.fence(out)
        return wall, wall - run_s

    for _ in range(3):                  # warm jit/caches for both
        one(execute_baseline)
        one(physical.execute)
    base, disabled = [], []
    gc.collect()
    gc.disable()    # ms-scale GC pauses land randomly on either series
    try:
        for i in range(repeat):
            if i % 2:   # alternate pair order: cancels first-runner bias
                disabled.append(one(physical.execute))
                base.append(one(execute_baseline))
            else:
                base.append(one(execute_baseline))
                disabled.append(one(physical.execute))
    finally:
        gc.enable()
    base_wall = float(min(w for w, _ in base))
    base_walk = float(np.median([k for _, k in base]))
    disabled_walk = float(np.median([k for _, k in disabled]))
    return {"table": "trace_overhead", "sf": sf, "repeat": repeat,
            "baseline_s": base_wall,
            "disabled_s": float(min(w for w, _ in disabled)),
            "baseline_walk_s": base_walk,
            "disabled_walk_s": disabled_walk,
            "overhead_pct": (disabled_walk - base_walk) / base_wall * 100.0}


# ---------------------------------------------------------------------------
# Traced GCDIA run + export
# ---------------------------------------------------------------------------


def traced_gcdia(sf: int = 1,
                 out_path: str = "experiments/trace_gcdia.json") -> list[dict]:
    db = m2bench.generate(sf=sf)
    m2bench.build_indexes(db)
    eng = GredoEngine(db, telemetry=True)
    prof_cold = eng.profile(m2bench.a3_multiply())     # cold: full DAG runs
    prof_warm = eng.profile(m2bench.a2_similarity())   # warm: shares the
                                                       # GCDI relation
    collector = eng.telemetry.collector
    doc = json.loads(collector.to_chrome_json())       # the round-trip check
    problems = validate_chrome_trace(doc)
    if problems:
        raise AssertionError(f"invalid trace export: {problems}")

    # every operator the DAG touched must be covered by a span — executed
    # ones by complete spans, reuse by cache pseudo-spans
    for prof in (prof_cold, prof_warm):
        spans = [s for s in prof.trace.spans if s.cat != "query"]
        assert spans, "trace has no operator spans"
    warm_ops = [o["op"] for o in eng.last_stats.operators   # last = warm run
                if o["executed"] or o["cached"]]
    warm_spans = [s.name for s in prof_warm.trace.spans if s.cat != "query"]
    missing = set(warm_ops) - set(warm_spans)
    if missing:
        raise AssertionError(f"operators without spans: {missing}")

    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"# trace -> {out_path} ({len(doc['traceEvents'])} events, "
          f"valid)", file=sys.stderr)

    rows = []
    for label, prof in (("cold_A3_multiply", prof_cold),
                        ("warm_A2_similarity", prof_warm)):
        cache_hits = sum(1 for s in prof.trace.spans if s.cat == "cache")
        rows.append({
            "table": "trace_gcdia", "sf": sf, "step": label,
            "seconds": prof.seconds,
            "spans": len(prof.trace.spans),
            "cache_pseudo_spans": cache_hits,
            "qerror_flags": len(prof.qerrors),
            "trace_file": out_path,
        })
    return rows


def run_suite(sf: int = 1, fast: bool = False) -> list[dict]:
    rows = traced_gcdia(sf=sf)
    rows.append(measure_overhead(sf=sf, repeat=10 if fast else 30))
    return rows


def print_rows(rows: list[dict]) -> None:
    for r in rows:
        if r["table"] == "trace_gcdia":
            print(f"trace_{r['step']}_sf{r['sf']},{r['seconds']*1e6:.1f},"
                  f"spans={r['spans']};cache_spans={r['cache_pseudo_spans']};"
                  f"qerror_flags={r['qerror_flags']}")
        elif r["table"] == "trace_overhead":
            print(f"trace_disabled_overhead,{r['disabled_s']*1e6:.1f},"
                  f"baseline_us={r['baseline_s']*1e6:.1f};"
                  f"walk_us={r['disabled_walk_s']*1e6:.1f}"
                  f"_vs_{r['baseline_walk_s']*1e6:.1f};"
                  f"overhead_pct={r['overhead_pct']:.2f}")


if __name__ == "__main__":
    print_rows(run_suite())
