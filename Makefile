PYTHONPATH := src
export PYTHONPATH

.PHONY: test verify test-fast lint verify-plans bench-smoke bench bench-update bench-gcdia bench-optimizer bench-index bench-trace bench-kernels bench-shard bench-regression

# tier-1 verification (the full suite — unchanged)
test:
	python -m pytest -x -q

# alias used by CI / the verify skill: the fast tier (<60s) gates the inner
# loop; run `make test` for the full tier-1 suite
verify: test-fast

# fast tier: core engine / storage / planner / physical / optimizer /
# cardinality / write-path modules, selected by the `fast` pytest marker
test-fast:
	python -m pytest -x -q -m fast

# repo-wide AST lint (GDL001-GDL005: module-global mutable state, host
# syncs in operator hot paths, nested locks, bare excepts, mutable default
# args). Findings not in lint_baseline.json fail the build; regenerate the
# baseline with `python -m repro.analysis.lint --write-baseline` only for
# findings that are genuinely pre-existing and safe.
lint:
	python -m repro.analysis.lint

# static plan-verification sweep: every m2bench query/task x
# {gredo,dual,single} x shards {1,4} x device lowering on/off, verified
# without executing (see repro.core.verify). Report lands in
# experiments/verify_sweep.json; ERROR-severity violations fail the run.
verify-plans:
	python -m repro.analysis.verify_sweep

# small-size benchmark pass (CI smoke): paper suite fast mode + update +
# optimizer + index suites
bench-smoke:
	python -m benchmarks.run --fast --sf 1
	python -m benchmarks.run --suite update --fast
	python -m benchmarks.run --suite optimizer --fast
	python -m benchmarks.run --suite index --fast --sf 2

bench:
	python -m benchmarks.run --sf 1

bench-update:
	python -m benchmarks.run --suite update

# operator-level inter-buffer reuse (per-operator timings + hit rates)
bench-gcdia:
	python -m benchmarks.run --suite gcdia

# cost-based optimizer: naive query-order DAG vs rewritten DAG latency
bench-optimizer:
	python -m benchmarks.run --suite optimizer --sf 2

# secondary-index access paths: indexed vs full-scan latency + selectivity
# sweep + write-path maintenance overhead (--sf 80: the point lookup's full
# scans dominate the fixed executor overhead there)
bench-index:
	python -m benchmarks.run --suite index --sf 80

# telemetry smoke: one GCDIA reuse ladder traced end-to-end, Chrome-trace
# JSON exported to experiments/trace_gcdia.json (schema-validated; open in
# Perfetto), disabled-telemetry overhead guard
bench-trace:
	python -m benchmarks.run --suite trace --fast

# traversal kernel family: host vs jit vs whole-chain latency ladder,
# batched point-lookup throughput
bench-kernels:
	python -m benchmarks.run --suite kernels

# perf-regression gate: re-measure the paper's headline suites (GCDI/GCDA
# ablations, inter-buffer reuse) and compare against the committed
# noise-aware baselines in experiments/bench_baselines.json; exits non-zero
# on any metric outside its tolerance band. Re-baseline with
# `python -m benchmarks.regression --update-baseline` only for accepted
# perf changes.
bench-regression:
	python -m benchmarks.regression --fast

# sharded morsel-parallel execution: single-stream vs 4-shard cold latency
# on the scan/join-heavy GCDIA (bit-for-bit checked), the born-sharded
# Rel2Matrix handoff assertion, and the small-input serial cost gate
# (--sf 200: the scans dominate the fixed executor overhead there)
bench-shard:
	python -m benchmarks.run --suite shard --sf 200
