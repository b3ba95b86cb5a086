"""Session mixes, the configuration's own parameter and input kinds and
reference, and the pools pinned to what the harness drew before either
existed, at sf=1 on the CPU."""
import hashlib
import json
import os
import shutil

import numpy as np
import pytest

from bench import run, workload
from bench.gen import m2b_ecom

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SEED = 2**31 + 12345
SESSION_SEED = 2**31 + 4242


def _digest(obj) -> str:
    return hashlib.sha256(json.dumps(obj, sort_keys=True).encode()).hexdigest()[:16]


def _point_and_range_mix():
    q = workload.load_mix("gcdi_mix")["templates"]["G4"]
    return {"kind": "query", "templates": {
        "point": {**q, "where": [["Orders.order_id", "==", "$oid"],
                                 ["Customer.id", "==", "$cid"],
                                 ["p.pid", "==", "$pid"]],
                  "params": {"kind": "order_keys", "count": 4}},
        "range": {**q, "where": [["Product.price", "range", "$lo", "$hi"]],
                  "params": {"kind": "uniform_window", "count": 3,
                             "lo": [1, 499], "width": 0.5}}}}


@pytest.fixture(scope="module")
def raw():
    return m2b_ecom.generate(1, SEED)[1]


# Taken with the harness as it was before parameter and input kinds moved
# into the generator: every bound spec (A1's labels in it) and the template
# and pool index of the first 50 rounds, at sf=1 and seed SEED.
PINNED = {"gcdi_mix": ("7017defa7f2552de", "80fa0f87e0ee9736"),
          "gcdia_cold": ("3110f41251b07ceb", "802e351da77af523"),
          "point_and_range": ("e47b10e7e1a7bed0", "450acbbb0471df1a")}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_pools_and_rounds_are_the_pinned_ones(raw, name):
    mix = (_point_and_range_mix() if name == "point_and_range"
           else workload.load_mix(name))
    pool = workload.build_pool(mix, SEED, raw, m2b_ecom)
    gen = workload.rounds(pool, SEED)
    seq = [[(e.template, e.index) for e in next(gen)] for _ in range(50)]
    specs = {t: [e.spec for e in es] for t, es in pool.items()}
    assert (_digest(specs), _digest(seq)) == PINNED[name]


def test_a1_labels_are_the_pinned_ones(raw):
    pool = workload.build_pool(workload.load_mix("gcdia_cold"), SEED, raw,
                               m2b_ecom)
    y = np.asarray(pool["A1"][0].spec["analytics"]["inputs"][1][1], np.float64)
    assert y.shape == (1617,) and y.sum() == 640.0
    assert hashlib.sha256(y.tobytes()).hexdigest()[:16] == "c131b32e95222ea2"


def _session_mix():
    """The session mix with every template drawn over three price windows,
    so that its pools hold three entries each."""
    mix = workload.load_mix("gcdia_session")
    window = {"kind": "uniform_window", "count": 3, "lo": [1, 400],
              "width": 100}
    templates = {}
    for name, t in mix["templates"].items():
        q = {**t["query"], "from": ["Customer", "Orders", "Product"],
             "joins": t["query"]["joins"] + [
                 ["Orders.customer_id", "Customer.id"],
                 ["Orders.product_id", "Product.id"]],
             "where": t["query"]["where"] + [
                 ["Product.price", "range", "$lo", "$hi"]]}
        templates[name] = {**t, "query": q, "params": window}
    return {**mix, "templates": templates}


def test_session_rounds_keep_their_order_and_share_one_index(raw):
    mix = _session_mix()
    pool = workload.build_pool(mix, SESSION_SEED, raw, m2b_ecom)
    assert {len(v) for v in pool.values()} == {3}

    def first(seed, n):
        gen = workload.rounds(pool, seed, mix["session"])
        return [[(e.template, e.index) for e in next(gen)] for _ in range(n)]
    got = first(SESSION_SEED, 30)
    assert got == first(SESSION_SEED, 30)
    for r in got:
        assert [t for t, _ in r] == ["A3", "A2", "A1"]
        assert len({i for _, i in r}) == 1
    assert {r[0][1] for r in got} == {0, 1, 2}
    assert got != first(SESSION_SEED + 1, 30)


def test_warmup_is_one_session_per_pool_index(raw):
    mix = workload.load_mix("gcdia_session")
    pool = workload.build_pool(mix, SESSION_SEED, raw, m2b_ecom)
    assert [[(e.template, e.index) for e in r]
            for r in workload.warmup(pool, mix["session"])] == \
        [[("A3", 0), ("A2", 0), ("A1", 0)]]
    big = workload.build_pool(_session_mix(), SESSION_SEED, raw, m2b_ecom)
    assert [[(e.template, e.index) for e in r]
            for r in workload.warmup(big, mix["session"])] == \
        [[("A3", i), ("A2", i), ("A1", i)] for i in range(3)]
    cold = workload.build_pool(workload.load_mix("gcdia_cold"), SESSION_SEED,
                               raw, m2b_ecom)
    assert [[e.template for e in r] for r in workload.warmup(cold)] == \
        [["A1"], ["A2"], ["A3"]]


@pytest.mark.parametrize("change", ["unknown_template", "missing_template",
                                    "clear_per_task", "uneven_pools"])
def test_a_malformed_session_is_refused(tmp_path, monkeypatch, raw, change):
    mix = workload.load_mix("gcdia_session")
    if change == "unknown_template":
        mix["session"] = ["A3", "A2", "A9"]
    elif change == "missing_template":
        mix["session"] = ["A3", "A2"]
    elif change == "clear_per_task":
        mix["clear_interbuffer"] = True
    else:
        mix = _session_mix()
        mix["templates"]["A1"]["params"] = {**mix["templates"]["A1"]["params"],
                                            "count": 2}
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "bad.json").write_text(json.dumps(mix))
    monkeypatch.setattr(workload, "HERE", str(tmp_path))
    with pytest.raises(ValueError, match="session"):
        mix = workload.load_mix("bad")
        workload.build_pool(mix, SESSION_SEED, raw, m2b_ecom)


def test_session_clears_the_interbuffer_only_at_each_sessions_start(
        monkeypatch):
    """Warm-up runs one session; every session of the window runs A3, A2,
    A1 with one clear before A3 and none between them."""
    from repro.core import GredoEngine
    from repro.core.interbuffer import InterBuffer
    name = "ecom_gcdia_session"
    _, _, cfg, _ = run.cell(name)
    log = []
    real_clear, real_analyze = InterBuffer.clear, GredoEngine.analyze

    def clear(self):
        log.append("clear")
        real_clear(self)

    def analyze(self, task, **kw):
        log.append(task.analytics.op)
        return real_analyze(self, task, **kw)
    monkeypatch.setattr(InterBuffer, "clear", clear)
    monkeypatch.setattr(GredoEngine, "analyze", analyze)
    line = run.run_cell(name, SESSION_SEED, 1.0, False, config=dict(cfg, sf=1))
    assert line["correct"], line["checks"]
    session = ["clear", "MULTIPLY", "SIMILARITY", "REGRESSION"]
    n = len(log) // len(session)
    assert n >= 2 and log == session * n
    assert line["attempted"] == 3 * (n - 1)


def test_gcda_shape_reads_a_matrix_served_from_the_interbuffer(raw):
    """A2 after A3 takes A3's matrix from the inter-buffer; its shape is
    still read, from the served node's rows."""
    from repro.core import GredoEngine
    db, data = m2b_ecom.generate(1, SEED)
    m2b_ecom.build_indexes(db)
    eng = GredoEngine(db, mode="gredo")
    mix = workload.load_mix("gcdia_session")
    pool = workload.build_pool(mix, SEED, data, m2b_ecom)
    eng.analyze(pool["A3"][0].request)
    eng.analyze(pool["A2"][0].request)
    assert eng.last_interbuffer_delta["hits"] >= 1
    stats = run._op_stats(eng)
    assert "RandomAccessMatrix" not in stats["op_s"]
    assert run._gcda_shape(pool["A2"][0], stats, 100) == {
        "op": "SIMILARITY", "m": 1617, "k": 200, "iters": 1}


# A configuration that lands as files alone: its generator with a parameter
# kind and an input kind of its own, its reference module, its mix, its
# configuration file and a BENCHMARK.json, written beside a copy of bench/.
TOY_GEN = '''"""A toy social graph: persons with an age, four Knows edges each."""
import numpy as np

from repro.core.storage import Database, Graph, Table


def generate(sf, seed):
    n = int(round(128 * sf))
    rng = np.random.default_rng(seed)
    age = rng.integers(18, 80, n).astype(np.int64)
    src = np.repeat(np.arange(n, dtype=np.int64), 4)
    dst = (src + rng.integers(1, n, len(src))) % n
    raw = {"age": age, "src": src, "dst": dst}
    db = Database()
    persons = Table("Persons", {"pid": np.arange(n, dtype=np.int64),
                                "age": age})
    db.add_graph(Graph("Knows", {"Persons": persons},
                       Table("Knows_edges", {"svid": src, "tvid": dst}),
                       "Persons", "Persons"))
    return db, raw


def build_indexes(db):
    return None


def age_floor(params, count, rng, raw):
    return [{"floor": int(v)} for v in rng.integers(18, 40, count)]


def older_than(raw, query, group, age):
    (_, _, floor), = query["where"]
    ids = np.unique(raw["src"][raw["age"][raw["src"]] >= floor])
    return (raw["age"][ids] > age).astype(np.float64).tolist()


PARAMS = {"age_floor": age_floor}
INPUTS = {"older_than": older_than}
'''

TOY_REF = '''"""Plain reference of the toy Knows graph: one hop over the edge list."""
import numpy as np

from bench.reference import (cosine_rows, distinct, gram_rows,  # noqa: F401
                             regression, rel_err, rows_off)


def relation(raw, spec, lower=False):
    (sv, _, _, dv, _), = spec["match"]["hops"]
    keep = np.ones(len(raw["src"]), bool)
    for attr, op, v in spec.get("where", ()):
        assert (attr, op) == (f"{sv}.age", ">=")
        keep &= raw["age"][raw["src"]] >= v
    cols = {f"{sv}.pid": raw["src"][keep], f"{dv}.pid": raw["dst"][keep]}
    return [cols[a] for a in spec["select"]]


def matrix(raw, spec, inp):
    if inp[0] == "const":
        return np.asarray(inp[1], np.float64)
    _, group, value, width = inp
    g, v = relation(raw, {**spec["query"], "select": [group, value]})
    ids, row = np.unique(g, return_inverse=True)
    x = np.zeros((len(ids), int(width)))
    x[row, v] = 1.0
    return x
'''

TOY_MIX = {
    "kind": "analyze", "loop": "closed loop, one client", "iters": 20,
    "regression": {"lr": 0.5, "l2": 0.0001},
    "templates": {"R": {
        "query": {"select": ["a.pid", "b.pid"], "from": [],
                  "match": {"graph": "Knows",
                            "hops": [["a", "Persons", "Knows", "b", "Persons"]]},
                  "where": [["a.age", ">=", "$floor"]]},
        "analytics": {"op": "REGRESSION",
                      "inputs": [["random", "a.pid", "b.pid", 64],
                                 ["older_than", "a.pid", 50]]},
        "params": {"kind": "age_floor", "count": 2}}}}

TOY_CONFIG = {"generator": "toy_knows", "reference": "reference_toy_knows",
              "sf": 0.5, "engine": {"mode": "gredo", "n_shards": 1,
                                    "interbuffer_bytes": 1 << 26}}

TOY_BENCHMARK = {
    "command": ["python3", "bench/run.py"], "paths": ["bench"],
    "run_seconds": 1,
    "configs": [{"name": "toy_knows", "source": "a toy graph",
                 "file": "bench/configs/toy_knows.json", "reduced": [],
                 "why": "a configuration added as files alone"}],
    "workloads": [{"name": "toy_knows.regress", "config": "toy_knows",
                   "traffic": "toy_regress", "chips": 1, "why": "a toy"}],
    "end_to_end": [{"name": "gcdia_task_ms", "unit": "ms", "better": "lower",
                    "bound": 0.1, "source": "host_clock"},
                   {"name": "setup_s", "unit": "s", "better": "lower",
                    "bound": 0.25, "source": "host_clock"}],
    "per_layer": []}


def test_a_configuration_lands_as_new_files(tmp_path, monkeypatch):
    from repro.core import GredoEngine
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    new = {"BENCHMARK.json": json.dumps(TOY_BENCHMARK),
           "bench/gen/toy_knows.py": TOY_GEN,
           "bench/reference_toy_knows.py": TOY_REF,
           "bench/traffic/toy_regress.json": json.dumps(TOY_MIX),
           "bench/configs/toy_knows.json": json.dumps(TOY_CONFIG)}
    for path, text in new.items():
        assert not (tmp_path / path).exists()
        (tmp_path / path).write_text(text)
    monkeypatch.setattr(run, "ROOT", str(tmp_path))
    monkeypatch.setattr(run, "BENCH", str(tmp_path / "bench"))
    monkeypatch.setattr(workload, "HERE", str(tmp_path / "bench"))
    line = run.run_cell("toy_knows.regress", SEED, 1.0, False)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"gcdia_task_ms", "setup_s"}
    real = GredoEngine.analyze
    monkeypatch.setattr(GredoEngine, "analyze",
                        lambda self, t, **kw: real(self, t, **kw) + 0.1)
    line = run.run_cell("toy_knows.regress", SEED, 1.0, False)
    assert not line["correct"], line["checks"]
