"""Benchmark driver: one function per paper table. Prints
``name,us_per_call,derived`` CSV rows plus a readable summary.

Usage: PYTHONPATH=src python -m benchmarks.run [--sf 1] [--fast]
                                               [--suite paper|update|all]
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def _update_suite(fast: bool) -> list[dict]:
    from . import update_bench
    rows = update_bench.run_suite(fast=fast)
    update_bench.print_rows(rows)
    return rows


def _gcdia_suite(sf: int) -> list[dict]:
    """Operator-level inter-buffer reuse: per-step hit rates + per-operator
    timings of the physical DAG (ISSUE 2 acceptance output)."""
    from . import m2bench_suite as m2
    rows = m2.gcdia_operator_reuse(sf=sf)
    for r in rows:
        print(f"gcdia_{r['step']}_sf{r['sf']},{r['seconds']*1e6:.1f},"
              f"hit_rate={r['hit_rate']:.2f};reused_nodes={r['nodes_reused']};"
              f"fetches={r['record_fetches']}")
        for o in r["operators"]:
            tag = ("interbuffer-hit" if o["cached"]
                   else "ran" if o["executed"] else "skipped")
            print(f"#   {o['op']:<20} {tag:<15} rows={o['rows']} "
                  f"ms={o['ms']}", file=sys.stderr)
    return rows


def _optimizer_suite(sf: int, fast: bool) -> list[dict]:
    """Cost-based optimizer: naive query-order DAG vs. rewritten DAG (DP
    join enumeration / semi-join siding / CSE / sink-down) on multi-join
    queries, plus cardinality quality on the Zipfian-skew fixture
    (histogram-overlap vs. NDV-only q-error; bushy DP vs. best left-deep).
    The rewrite overhead is ~1ms/query, so the latency win grows with --sf
    (the Makefile's bench-optimizer target uses --sf 2)."""
    from . import optimizer_bench
    repeat = 2 if fast else 5
    rows = optimizer_bench.optimizer_gain(sf=sf, repeat=repeat)
    rows += optimizer_bench.cardinality_quality(sf=sf, repeat=repeat)
    optimizer_bench.print_rows(rows)
    return rows


def _index_suite(sf: int, fast: bool) -> list[dict]:
    """Secondary-index access paths: indexed vs. full-scan latency on the
    selective fixtures, the selectivity-sweep crossover, and the write-path
    maintenance overhead. The access-path win grows with --sf (the
    Makefile's bench-index target uses --sf 80, where the point lookup's
    full scans dominate the fixed executor overhead)."""
    from . import index_bench
    rows = index_bench.run_suite(sf=sf, fast=fast)
    index_bench.print_rows(rows)
    return rows


def _trace_suite(sf: int, fast: bool) -> list[dict]:
    """Telemetry: traced GCDIA reuse ladder exported as Chrome trace-event
    JSON (schema-validated; experiments/trace_gcdia.json — open it in
    Perfetto) and the disabled-telemetry overhead guard vs the
    pre-telemetry executor."""
    from . import trace_bench
    rows = trace_bench.run_suite(sf=sf, fast=fast)
    trace_bench.print_rows(rows)
    return rows


def _kernels_suite(sf: int, fast: bool) -> list[dict]:
    """Traversal kernel family: single-query latency ladder (host matcher vs
    per-hop jit vs whole-chain program) over start selectivity, batched
    point-lookup throughput (launch amortization across >=64 concurrent
    queries)."""
    from . import traversal_bench
    rows = traversal_bench.run_suite(sf=sf, fast=fast)
    traversal_bench.print_rows(rows)
    return rows


def _shard_suite(sf: int, fast: bool) -> list[dict]:
    """Sharded morsel-parallel execution: single-stream vs 4-shard cold
    end-to-end latency on the scan/join-heavy GCDIA (bit-for-bit checked
    first), the born-sharded Rel2Matrix span assertion, and the small-input
    cost gate (4 shards requested, serial chosen, <=5% overhead)."""
    from . import shard_bench
    rows = shard_bench.run_suite(sf=sf, fast=fast)
    shard_bench.print_rows(rows)
    return rows


def _row_key(r: dict) -> tuple:
    """Stable identity of a bench row (table + whichever discriminator
    fields it carries) — the merged results file is sorted by this, so its
    order no longer depends on which suites ran in which sessions and
    baseline diffs stay reviewable."""
    return tuple(str(r.get(k, "")) for k in
                 ("table", "query", "task", "step", "kernel", "op", "mode",
                  "name", "sf", "n_batches", "selectivity"))


def _save(all_rows: list[dict]) -> None:
    """Merge into experiments/bench_results.json: rows of the tables just
    measured replace their previous records; other suites' rows persist.
    The merged file is written in deterministic (_row_key) order."""
    os.makedirs("experiments", exist_ok=True)
    path = "experiments/bench_results.json"
    fresh_tables = {r.get("table") for r in all_rows}
    kept: list[dict] = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                kept = [r for r in json.load(f)
                        if r.get("table") not in fresh_tables]
        except (ValueError, OSError):
            kept = []
    with open(path, "w") as f:
        json.dump(sorted(kept + all_rows, key=_row_key), f, indent=1,
                  default=str)
    print(f"# full records -> {path}", file=sys.stderr)


def _finish(all_rows: list[dict], args) -> None:
    """Common exit path for every suite: persist, then the machine-readable
    surfaces (--json rows to stdout, --save-baseline into the perf gate's
    committed baseline file)."""
    _save(all_rows)
    if args.json:
        print(json.dumps(sorted(all_rows, key=_row_key), default=str))
    if args.save_baseline:
        from . import regression
        path = regression.update_baseline([all_rows])
        print(f"# baselines -> {path}", file=sys.stderr)


def place_compile_cache() -> str:
    """Leave JAX's persistent compile cache where JAX_COMPILATION_CACHE_DIR
    puts it; else keep it at a fixed path in the checkout (the path is part
    of the cache key, so it must not move between runs). Returns the cache
    directory."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    import jax
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sf", type=int, default=1)
    ap.add_argument("--fast", action="store_true",
                    help="skip the scale-factor sweep / use smoke sizes")
    ap.add_argument("--suite",
                    choices=("paper", "update", "gcdia", "optimizer",
                             "index", "trace", "kernels", "shard", "all"),
                    default="paper",
                    help="paper: GCDI/GCDA tables; update: write-path "
                         "throughput (delta store vs full rebuild); gcdia: "
                         "operator-level inter-buffer reuse (per-operator "
                         "timings + hit rates); optimizer: naive-order vs "
                         "cost-based rewritten DAG latency; index: "
                         "secondary-index access paths vs full scans; "
                         "trace: telemetry smoke — traced GCDIA with "
                         "Chrome-trace export + disabled-overhead guard; "
                         "kernels: traversal kernel family — latency "
                         "ladder, batched point lookups; "
                         "shard: morsel-parallel execution — single-stream "
                         "vs 4-shard latency, born-sharded GCDA handoff, "
                         "small-input serial gate")
    ap.add_argument("--json", action="store_true",
                    help="also print the measured rows as one JSON array on "
                         "stdout (machine-readable; the CSV lines stay)")
    ap.add_argument("--save-baseline", action="store_true",
                    help="write/update experiments/bench_baselines.json "
                         "from this run's rows (the perf-regression gate's "
                         "committed reference; see benchmarks.regression)")
    args = ap.parse_args()
    place_compile_cache()

    from . import m2bench_suite as m2
    from .kernels_bench import kernel_microbench

    print("name,us_per_call,derived")
    all_rows: list[dict] = []

    if args.suite in ("optimizer", "all"):
        all_rows += _optimizer_suite(sf=args.sf, fast=args.fast)
        if args.suite == "optimizer":
            _finish(all_rows, args)
            return

    if args.suite in ("index", "all"):
        all_rows += _index_suite(sf=args.sf, fast=args.fast)
        if args.suite == "index":
            _finish(all_rows, args)
            return

    if args.suite in ("trace", "all"):
        all_rows += _trace_suite(sf=args.sf, fast=args.fast)
        if args.suite == "trace":
            _finish(all_rows, args)
            return

    if args.suite in ("kernels", "all"):
        all_rows += _kernels_suite(sf=args.sf, fast=args.fast)
        if args.suite == "kernels":
            _finish(all_rows, args)
            return

    if args.suite in ("shard", "all"):
        all_rows += _shard_suite(sf=args.sf, fast=args.fast)
        if args.suite == "shard":
            _finish(all_rows, args)
            return

    if args.suite in ("gcdia", "all"):
        all_rows += _gcdia_suite(sf=args.sf)
        if args.suite == "gcdia":
            _finish(all_rows, args)
            return

    if args.suite in ("update", "all"):
        all_rows += _update_suite(fast=args.fast)
        if args.suite == "update":
            _finish(all_rows, args)
            return

    # Figs. 7-8 + Fig. 10: GCDI ablation & graph workloads
    rows = m2.graph_workloads(sf=args.sf)
    all_rows += rows
    for r in rows:
        if "gredo_s" in r and "single_s" in r:
            print(f"gcdi_{r['query']}_sf{r['sf']},{r['gredo_s']*1e6:.1f},"
                  f"speedup_vs_single={r['speedup_vs_single']:.2f};"
                  f"speedup_vs_dual={r['speedup_vs_dual']:.2f};"
                  f"io_gredo={r['gredo_io']};io_single={r['single_io']}")
        elif "gredo_s" in r:
            print(f"gcdi_{r['query']}_sf{r['sf']},{r['gredo_s']*1e6:.1f},"
                  f"reachable={r.get('reachable')}")

    # Figs. 9/12: GCDA ablation
    rows = m2.gcda_ablation(sf=args.sf)
    all_rows += rows
    for r in rows:
        print(f"gcda_{r['task']}_sf{r['sf']},{r['batch_s']*1e6:.1f},"
              f"volcano_speedup={r['speedup']:.1f}")

    # §6.4 inter-buffer reuse
    rows = m2.interbuffer_reuse(sf=args.sf)
    all_rows += rows
    for r in rows:
        print(f"interbuffer_reuse_sf{r['sf']},{r['warm_s']*1e6:.1f},"
              f"reuse_speedup={r['reuse_speedup']:.0f}")

    # Table 5 flavor: scale factors
    if not args.fast:
        rows = m2.scale_factors()
        all_rows += rows
        for r in rows:
            print(f"scale_sf{r['sf']}_{r['mode']},{r['SUM_s']*1e6:.1f},"
                  f"geomean_us={r['GEOMEAN_s']*1e6:.1f}")

    # kernel microbench
    rows = kernel_microbench()
    all_rows += rows
    for r in rows:
        d = f"gflops={r.get('gflops', 0):.1f};" if "gflops" in r else ""
        print(f"kernel_{r['kernel'].split('(')[0]},{r['oracle_s']*1e6:.1f},"
              f"{d}block={r['tpu_block']}")

    _finish(all_rows, args)


if __name__ == "__main__":
    main()
