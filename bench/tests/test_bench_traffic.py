"""The benchmark's data, traffic and reference at sf=1 on the CPU."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import reference, workload
from bench.gen import m2b_ecom

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SEED = 2**31 + 12345            # larger than 32 signed bits hold


def config(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


def make(name, seed=SEED, sf=1):
    cfg = config(name)
    db, raw = m2b_ecom.generate(sf, seed)
    m2b_ecom.build_indexes(db)
    return cfg, db, raw


@pytest.fixture(scope="module")
def ecom():
    return make("m2b_ecom_sf16")


@pytest.mark.parametrize("name", ["m2b_ecom_sf16"])
def test_row_counts_are_the_configs(name):
    cfg = config(name)
    db, _ = m2b_ecom.generate(cfg["sf"], SEED)
    got = {t: db.tables[t].nrows for t in ("Product", "Customer", "Orders")}
    got["Persons"] = db.graphs["Follows"].vertex_tables["Persons"].nrows
    got["Tags"] = db.graphs["Interested_in"].vertex_tables["Tags"].nrows
    got.update({g: db.graphs[g].edges.nrows for g in db.graphs})
    assert got == cfg["rows"]


def test_same_seed_same_data_and_other_seed_same_sizes():
    _, a = m2b_ecom.generate(1, SEED)
    _, b = m2b_ecom.generate(1, SEED)
    _, c = m2b_ecom.generate(1, SEED + 1)
    for t in a["tables"]:
        for k in a["tables"][t]:
            assert np.array_equal(a["tables"][t][k], b["tables"][t][k])
    for g in a["graphs"]:
        ea, ec = a["graphs"][g]["edges"], c["graphs"][g]["edges"]
        assert np.array_equal(ea["tvid"], b["graphs"][g]["edges"]["tvid"])
        assert len(ea["svid"]) == len(ec["svid"])
        assert not np.array_equal(ea["tvid"], ec["tvid"])
        assert not np.any(ea["svid"] == ea["tvid"]) or g == "Interested_in"


def test_every_seed_has_the_same_gcda_rows():
    """A1-A3's matrices have one row per customer with a food interest;
    that count, and G1's answer, are the same on every seed, so their
    programs compile once."""
    mix = workload.load_mix("gcdia_cold")
    shapes = set()
    for seed in (SEED, SEED + 1, 7):
        _, raw = m2b_ecom.generate(1, seed)
        (e,) = workload.build_pool(mix, seed, raw, m2b_ecom)["A1"]
        x, y = [reference.matrix(raw, e.spec, i)
                for i in e.spec["analytics"]["inputs"]]
        g1 = reference.relation(raw, e.spec["query"])
        shapes.add((x.shape, y.shape, len(g1[0])))
    assert len(shapes) == 1


def test_orders_equal_shredded_documents(ecom):
    from repro.core.storage import DictColumn, shred_documents
    _, db, raw = ecom
    orders = db.tables["Orders"]
    items = orders.col("items")
    r = raw["tables"]["Orders"]
    docs = [{"order_id": int(r["order_id"][i]),
             "customer_id": int(r["customer_id"][i]),
             "product_id": int(r["product_id"][i]),
             "quantity": int(r["quantity"][i]),
             "shipping": {"city": str(r["shipping.city"][i]),
                          "days": int(r["shipping.days"][i])},
             "items": items.values[items.offsets[i]:items.offsets[i + 1]].tolist()}
            for i in range(orders.nrows)]
    shred = shred_documents("Orders", docs)
    assert list(shred.columns) == list(orders.columns)
    for k, col in shred.columns.items():
        mine = orders.col(k)
        if isinstance(col, DictColumn):
            assert list(col.decode(col.codes)) == list(mine.decode(mine.codes))
        elif k == "items":
            assert np.array_equal(col.values, mine.values)
            assert np.array_equal(col.offsets, mine.offsets)
        else:
            assert np.array_equal(col, mine) and col.dtype == mine.dtype


@pytest.mark.parametrize("mix", ["gcdi_mix", "gcdia_cold"])
def test_pool_and_sequence_are_deterministic(ecom, mix):
    _, _, raw = ecom
    m = workload.load_mix(mix)
    a = workload.build_pool(m, SEED, raw, m2b_ecom)
    b = workload.build_pool(m, SEED, raw, m2b_ecom)
    c = workload.build_pool(m, SEED + 1, raw, m2b_ecom)
    assert [e.spec for es in a.values() for e in es] == \
        [e.spec for es in b.values() for e in es]
    assert sorted(a) == sorted(c) == sorted(m["templates"])

    def first(pool, seed, n):
        gen = workload.rounds(pool, seed)
        return [[e.template for e in next(gen)] for _ in range(n)]
    r1, r2 = first(a, SEED, 6), first(b, SEED, 6)
    assert r1 == r2
    assert all(sorted(r) == sorted(a) for r in r1)
    assert len({tuple(r) for r in r1 + first(c, SEED + 1, 6)}) > 1


def test_parameter_kinds_bind_per_seed(ecom):
    _, _, raw = ecom
    q = workload.load_mix("gcdi_mix")["templates"]["G4"]
    mix = {"kind": "query", "templates": {
        "point": {**q, "where": [["Orders.order_id", "==", "$oid"],
                                 ["Customer.id", "==", "$cid"],
                                 ["p.pid", "==", "$pid"]],
                  "params": {"kind": "order_keys", "count": 4}},
        "range": {**q, "where": [["Product.price", "range", "$lo", "$hi"]],
                  "params": {"kind": "uniform_window", "count": 3,
                             "lo": [1, 499], "width": 0.5}}}}
    a = workload.build_pool(mix, SEED, raw, m2b_ecom)
    b = workload.build_pool(mix, SEED + 1, raw, m2b_ecom)
    assert len(a["point"]) == 4 and len(a["range"]) == 3
    assert a["point"][0].spec != b["point"][0].spec
    o = raw["tables"]["Orders"]
    for e in a["point"]:
        (_, _, oid), (_, _, cid), (_, _, pid) = e.spec["where"]
        assert o["customer_id"][oid] == cid
        assert raw["tables"]["Customer"]["person_id"][cid] == pid
        assert len(reference.relation(raw, e.spec)[0]) > 0
    for e in a["range"]:
        (_, _, lo, hi), = e.spec["where"]
        assert 1 <= lo <= 499 and hi == pytest.approx(lo + 0.5)


@pytest.mark.parametrize("data", ["ecom"])
def test_every_template_equals_single_mode_and_the_reference(request, data):
    from repro.core import GredoEngine
    _, db, raw = request.getfixturevalue(data)
    gredo = GredoEngine(db, mode="gredo")
    single = GredoEngine(db, mode="single")
    pool = workload.build_pool(workload.load_mix("gcdi_mix"), SEED, raw,
                                m2b_ecom)
    for name, entries in pool.items():
        for e in entries[:2]:
            got = gredo.query(e.request)
            want = single.query(e.request)
            cols = [np.asarray(got.col(a)) for a in e.spec["select"]]
            assert reference.rows_off(
                cols, [np.asarray(want.col(a)) for a in e.spec["select"]]) == 0, name
            assert reference.rows_off(
                cols, reference.relation(raw, e.spec)) == 0, name


def test_gcdia_templates_run_through_the_engine(ecom):
    from repro.core import GredoEngine
    _, db, raw = ecom
    eng = GredoEngine(db, mode="gredo")
    mix = workload.load_mix("gcdia_cold")
    pool = workload.build_pool(mix, SEED, raw, m2b_ecom)
    reg = mix["regression"]
    for name, (e,) in pool.items():
        out = np.asarray(eng.analyze(e.request, iters=mix["iters"]))
        mats = [reference.matrix(raw, e.spec, i)
                for i in e.spec["analytics"]["inputs"]]
        op = e.spec["analytics"]["op"]
        if op == "REGRESSION":
            want = reference.regression(mats[0], mats[1], mix["iters"],
                                        reg["lr"], reg["l2"])
            assert reference.rel_err(out, want) < 1e-5
        else:
            rows = np.arange(0, mats[0].shape[0], 97)
            fn = reference.gram_rows if op == "MULTIPLY" else reference.cosine_rows
            assert reference.rel_err(out[rows], fn(mats[0], rows)) < 1e-5


def test_a1_labels_are_the_yogurt_buyers_of_the_feature_rows(ecom):
    _, _, raw = ecom
    mix = workload.load_mix("gcdia_cold")
    (e,) = workload.build_pool(mix, SEED, raw, m2b_ecom)["A1"]
    x, y = [reference.matrix(raw, e.spec, i)
            for i in e.spec["analytics"]["inputs"]]
    assert y.shape == (x.shape[0],) and set(np.unique(y)) == {0.0, 1.0}
    assert 0.2 < y.mean() < 0.6
    ids = np.unique(reference.relation(
        raw, {**e.spec["query"], "select": ["Customer.id"]})[0])
    t = raw["tables"]
    yogurt = {i for i, s in enumerate(t["Product"]["title"])
              if s.split(" ")[0] == "Yogurt"}
    bought = {int(c) for c, p in zip(t["Orders"]["customer_id"],
                                      t["Orders"]["product_id"])
              if int(p) in yogurt}
    assert [float(int(i) in bought) for i in ids] == list(y)


def test_a1_sigmoid_stays_unsaturated(ecom):
    """Over A1's 100 steps every logit stays where the sigmoid is far from
    0 and 1, so the regression's answer depends on the sigmoid itself: a
    linear stand-in for it moves the weights by more than the limit."""
    from bench import check
    _, _, raw = ecom
    mix = workload.load_mix("gcdia_cold")
    (e,) = workload.build_pool(mix, SEED, raw, m2b_ecom)["A1"]
    x, y = [reference.matrix(raw, e.spec, i)
            for i in e.spec["analytics"]["inputs"]]
    reg = mix["regression"]
    w = np.zeros(x.shape[1])
    linear = np.zeros(x.shape[1])
    for _ in range(mix["iters"]):
        z = x @ w
        assert np.abs(z).max() < 4.0
        p = 1.0 / (1.0 + np.exp(-z))
        w = w - reg["lr"] * (x.T @ (p - y) / len(y) + reg["l2"] * w)
        q = 0.5 + (x @ linear) / 4.0
        linear = linear - reg["lr"] * (x.T @ (q - y) / len(y)
                                       + reg["l2"] * linear)
    want = reference.regression(x, y, mix["iters"], reg["lr"], reg["l2"])
    assert reference.rel_err(w, want) < 1e-12
    assert reference.rel_err(linear, want) > check.limit("reg_err")


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ecom_gcdi_mix",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "TPU" in proc.stderr and "'cpu'" in proc.stderr
    assert proc.stdout.strip() == ""


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ecom_gcdi_mix",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
