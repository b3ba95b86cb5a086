"""Run one benchmark cell once on the chip.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
and a traffic mix (``bench/traffic/<mix>.json``). The configuration's
file names its generator (``bench/gen/<generator>.py``: ``generate``,
``build_indexes`` and its parameter and input kinds), its scale ``sf``,
which reaches the generator as the file writes it, the engine settings,
and optionally its plain reference (``"reference": "<module>"``, the file
``bench/<module>.py``; ``bench/reference.py`` by default). So a new
configuration or mix lands as new files. Set-up places JAX's compile
cache, makes the data from the seed, builds the indexes and the engine,
and runs every request of the pool once (for a session mix, one session
per pool index), which compiles every program the window uses. The window
is a closed loop with one client: each request is sent when the previous
one has returned (a GCDIA task has returned when its output is ready on
the device). It runs whole rounds of the mix (``bench/workload.py``) and
ends with the round in which ``--seconds`` have passed. The outputs kept
for the check are held as the program returned them; once the window has
closed, the answers are read from them, the program's state is freed, and
they are compared with the plain reference (``bench/check.py``).

With ``--trace 0`` the result line carries the cell's end-to-end metrics;
with ``--trace 1`` the engine fences device work inside its operator times,
the profiler records the window, each request's record keeps its operator
times and inter-buffer counters, and the line carries the cell's per-layer
metrics, the device's busy seconds and a breakdown. Each metric is read by
``bench/metrics/<name>.py``.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
ANNOTATION = "bench:"
KEEP_FIRST = 4          # each template's answer kept: one of its first four
KEEP_SHARE = {"query": 0.25, "analyze": 0.0}    # and this share of the rest
SAMPLE_ROWS = 128       # rows of a kept n x n output compared with the reference
IB_COUNTERS = ("hits", "misses", "bypasses", "evictions")


def steady_allocator() -> None:
    """Fix glibc malloc's policy for the process: blocks up to 32 MiB (the
    most glibc allows) come from the heap, and up to 2 GiB of freed heap is
    kept. By default the mmap threshold moves with what the process
    happened to free, so whether each 20 MB matrix of a GCDIA task came
    from held memory or from a fresh, page-faulting mapping depended on the
    run's history."""
    import ctypes
    import ctypes.util
    libc = ctypes.CDLL(ctypes.util.find_library("c"))
    M_TRIM_THRESHOLD, M_MMAP_THRESHOLD = -1, -3
    libc.mallopt.argtypes = (ctypes.c_int, ctypes.c_int)
    libc.mallopt.restype = ctypes.c_int
    if not (libc.mallopt(M_MMAP_THRESHOLD, 32 << 20)
            and libc.mallopt(M_TRIM_THRESHOLD, 2**31 - 1)):
        raise RuntimeError("mallopt refused the allocator settings")


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell(workload: str) -> tuple[dict, dict, dict, dict]:
    """(benchmark, workload entry, configuration, traffic mix) of a cell."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    w = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if w is None:
        raise SystemExit(f"run.py: no workload {workload!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    from bench import workload as wl
    return bench, w, config, wl.load_mix(w["traffic"])


def require_chips(n: int):
    """The devices of a cell that asks for ``n`` chips; exits when JAX finds
    no TPU or fewer than ``n``."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        print(f"run.py: needs {n} TPU chip(s), but JAX found "
              f"{len(devs)} device(s) of platform {devs[0].platform!r} "
              f"({devs[0].device_kind})", file=sys.stderr)
        sys.exit(1)
    return devs[:n]


@dataclasses.dataclass
class Run:
    """What the metric readers see."""

    kind: str                   # "query" or "analyze"
    setup_s: float
    window_s: float
    records: list               # one dict per request completed in the window
    trace: object = None        # bench.trace.reduce.Trace in a traced run
    device_kind: str = ""


def _op_stats(eng) -> dict:
    """Self seconds of the executed operators by kind, and the rows of the
    executed and the inter-buffer-served ones."""
    st = eng.last_stats
    ops: dict = {}
    rows: dict = {}
    for o in st.operators:
        if o["executed"]:
            ops[o["op"]] = ops.get(o["op"], 0.0) + o["seconds"]
        if o["executed"] or o["cached"]:
            rows[o["op"]] = o["rows"]
    return {"seconds": st.seconds, "op_s": ops, "op_rows": rows}


_MATRIX_OP = {"random": "RandomAccessMatrix", "rel2matrix": "Rel2Matrix"}


def _gcda_shape(entry, stats: dict, iters: int) -> dict:
    """The (m, k) features matrix of a GCDA task and its steps, as the
    roofline's work functions take them."""
    a = entry.spec["analytics"]
    inp = a["inputs"][0]
    k = int(inp[3]) if inp[0] == "random" else len(inp[1])
    return {"op": a["op"], "m": int(stats["op_rows"][_MATRIX_OP[inp[0]]]),
            "k": k, "iters": iters if a["op"] == "REGRESSION" else 1}


def _answers(kind: str, kept: list, rng) -> list:
    """What the check compares, taken from the kept outputs once the window
    has closed: ``(entry, answer, rows)`` with a GCDI answer's selected
    columns, a REGRESSION's weights, or ``SAMPLE_ROWS`` rows of an n x n
    output drawn from ``rng`` with their indices."""
    import jax.numpy as jnp
    import numpy as np
    out = []
    for ent, got in kept:
        rows = None
        if kind == "query":
            got = [np.asarray(got.col(a)) for a in ent.spec["select"]]
        elif ent.spec["analytics"]["op"] != "REGRESSION":
            n = got.shape[0]
            rows = np.sort(rng.choice(n, min(SAMPLE_ROWS, n), replace=False))
            got = got[jnp.asarray(rows)]
        out.append((ent, np.asarray(got) if kind == "analyze" else got, rows))
    return out


@dataclasses.dataclass
class Measured:
    """One run's set-up and window, before the check."""

    bench: dict
    workload: dict
    mix: dict
    raw: dict                   # the data, as the reference reads it
    ref: object                 # the configuration's reference module
    kept: list                  # (entry, answer, rows) drawn for the check
    failed: int
    run: Run
    peak_bytes: int
    devices: list
    compiles_window: int


def measure(workload: str, seed: int, seconds: float, trace: bool, *,
            devices=None, config: dict | None = None,
            log=None) -> Measured:
    """Set-up and window of one run; the program's state is freed on return.
    ``config`` replaces the cell's configuration (the tests run it smaller);
    ``log`` is the process's ``CompileLog``."""
    import jax
    import numpy as np

    from bench import compile_cache
    from bench import workload as wl
    from bench.trace import reduce
    from repro.core import GredoEngine
    from repro.core.telemetry import Telemetry

    bench, w, cfg, mix = cell(workload)
    cfg = config or cfg
    devices = devices or jax.devices()[:w["chips"]]
    cache_dir = compile_cache.place()
    if log is None:
        log = compile_cache.CompileLog()
        log.install()
    requests0, seconds0 = log.requests, log.seconds
    t_dev = time.perf_counter()

    gen = _load(os.path.join(BENCH, "gen", f"{cfg['generator']}.py"),
                f"bench_gen_{cfg['generator']}")
    ref_name = cfg.get("reference", "reference")
    ref = _load(os.path.join(BENCH, f"{ref_name}.py"), f"bench_ref_{ref_name}")
    db, raw = gen.generate(cfg["sf"], seed)
    gen.build_indexes(db)
    e = cfg["engine"]
    eng = GredoEngine(db, mode=e["mode"], n_shards=int(e["n_shards"]),
                      interbuffer_bytes=int(e["interbuffer_bytes"]),
                      telemetry=Telemetry(fence_device=True) if trace else None)
    t_load = time.perf_counter()

    kind = mix["kind"]
    iters = int(mix.get("iters", 100))
    pool = wl.build_pool(mix, seed, raw, gen)
    session = mix.get("session")
    warm = wl.warmup(pool, session)
    n_warm = sum(map(len, warm))
    rounds = wl.rounds(pool, seed, session)
    keep_rng = np.random.default_rng([seed, 3])
    keep_at = {t: int(keep_rng.integers(KEEP_FIRST)) for t in sorted(pool)}
    share = KEEP_SHARE[kind]
    seen = dict.fromkeys(pool, 0)

    def serve(ent, first: bool):
        """One request; ``first`` when it opens a round (a session)."""
        if kind == "query":
            return eng.query(ent.request)
        if mix.get("clear_interbuffer") or (session and first):
            eng.interbuffer.clear()
        return jax.block_until_ready(eng.analyze(ent.request, iters=iters))

    for batch in warm:
        for i, ent in enumerate(batch):
            serve(ent, i == 0)
    t_warm = time.perf_counter()
    compiles_setup = log.requests

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    records: list = []
    kept: list = []             # (entry, output) as the program returned it
    failed = 0
    setup_s = time.perf_counter() - T_START
    t0 = time.perf_counter()
    t_end = t0 + seconds
    while time.perf_counter() < t_end:      # whole rounds
        for i, ent in enumerate(next(rounds)):
            ctx = (jax.profiler.TraceAnnotation(ANNOTATION + ent.template)
                   if trace else contextlib.nullcontext())
            with ctx:
                a = time.perf_counter()
                try:
                    out = serve(ent, i == 0)
                except Exception as exc:  # a failed request counts; the run goes on
                    failed += 1
                    print(f"request {len(records) + failed} ({ent.template}) "
                          f"failed: {exc!r}", file=sys.stderr)
                    out = None
                b = time.perf_counter()
            if out is None:
                continue
            rec = {"template": ent.template, "kind": kind, "t0": a, "t1": b,
                   "ms": (b - a) * 1e3}
            if trace:
                rec.update(_op_stats(eng))
                d = eng.last_interbuffer_delta
                rec["interbuffer"] = {k: d.get(k, 0) for k in IB_COUNTERS}
                if kind == "analyze":
                    rec["gcda"] = _gcda_shape(ent, rec, iters)
            records.append(rec)
            n = seen[ent.template]
            seen[ent.template] = n + 1
            if n == keep_at[ent.template] or (share and
                                              keep_rng.random() < share):
                kept.append((ent, out))
            del out
    window_s = time.perf_counter() - t0
    compiles_window = log.requests - compiles_setup
    red = None
    if trace:
        jax.profiler.stop_trace()
        red = reduce.load(reduce.find(TRACE_DIR), ANNOTATION)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    kept = _answers(kind, kept, np.random.default_rng([seed, 4]))
    n_rounds = sum(seen.values()) // len(pool)
    del eng, db, pool, rounds, warm

    print(f"setup: {setup_s} s = start {t_dev - T_START} s + data and "
          f"indexes {t_load - t_dev} s + warm-up {t_warm - t_load} s "
          f"({n_warm} requests); compile requests {compiles_setup - requests0} "
          f"taking {log.seconds - seconds0} s, cache hits {log.hits}, misses "
          f"{log.misses}, cache {cache_dir}", file=sys.stderr)
    print(f"window: {len(records)} requests in {window_s} s "
          f"({n_rounds} rounds), "
          f"{compiles_window} compiles inside the window, {failed} failed, "
          f"{len(kept)} answers kept for the check", file=sys.stderr)
    by: dict = {}
    for r in records:
        by.setdefault(r["template"], []).append(r["ms"])
    print("latency ms by template (count, median, max): " + "; ".join(
        f"{t} {len(v)} {float(np.median(v))} {max(v)}"
        for t, v in sorted(by.items())), file=sys.stderr)
    if trace:
        ib: dict = {}
        for r in records:
            t = ib.setdefault(r["template"], dict.fromkeys(IB_COUNTERS, 0))
            for k in IB_COUNTERS:
                t[k] += r["interbuffer"][k]
        print("inter-buffer by template (hits, misses, bypasses, evictions): "
              + "; ".join(f"{t} " + " ".join(str(v[k]) for k in IB_COUNTERS)
                          for t, v in sorted(ib.items())), file=sys.stderr)
    run = Run(kind, setup_s, window_s, records, trace=red,
              device_kind=devices[0].device_kind)
    return Measured(bench, w, mix, raw, ref, kept, failed, run, peak, devices,
                    compiles_window)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             devices=None, config: dict | None = None) -> dict:
    """One run of a cell; returns the result line as a dict."""
    from bench import check
    from bench.trace import reduce

    m = measure(workload, seed, seconds, trace, devices=devices,
                config=config)
    t_ref = time.perf_counter()
    run = m.run
    ok, table = check.verdict(run.kind, check.numbers(
        run.kind, m.kept, m.raw, m.mix, m.failed, ref=m.ref))
    print(f"reference: {time.perf_counter() - t_ref} s", file=sys.stderr)

    names = m.bench["per_layer"] if trace else m.bench["end_to_end"]
    metrics = {}
    for spec in names:
        if "workloads" in spec and m.workload["name"] not in spec["workloads"]:
            continue
        reader = _load(os.path.join(BENCH, "metrics", f"{spec['name']}.py"),
                       "bench_metric_" + spec["name"].replace(".", "_"))
        v = reader.read(run)
        if v is not None:
            metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
    dev = m.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(m.devices), "memory_peak_bytes": m.peak_bytes}
    line = {"correct": ok, "attempted": len(run.records) + m.failed,
            "failed": m.failed, "metrics": metrics, "device": device}
    if trace:
        red = run.trace
        device["busy_s"] = red.busy_s()
        device["window_s"] = red.window_s
        plane = sorted(red.ops)[0] if red.ops else None
        ops = red.ops.get(plane, [])
        line["breakdown"] = {
            "device_ops": reduce.top_ops(reduce.named_ops(
                ops, red.modules.get(plane, [])), red.window),
            "idle_gaps": reduce.top_gaps(ops, red.window, red.annotations)}
    line["compile_requests_in_window"] = m.compiles_window
    line["checks"] = table
    for n, v in table.items():
        print(f"check {n}: {v['value']} (limit {v['limit']})", file=sys.stderr)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"run.py: no program under {os.path.join(ROOT, 'src')}",
              file=sys.stderr)
        return 2
    sys.path.insert(1, os.path.join(ROOT, "src"))
    _, w, _, _ = cell(args.workload)
    t0 = time.perf_counter()
    import jax  # noqa: F401
    t1 = time.perf_counter()
    devices = require_chips(int(w["chips"]))
    print(f"start: interpreter and harness {t0 - T_START} s, import jax "
          f"{t1 - t0} s, runtime start {time.perf_counter() - t1} s",
          file=sys.stderr)
    line = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                    devices=devices)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    steady_allocator()
    sys.path[0] = ROOT
    sys.exit(main())
