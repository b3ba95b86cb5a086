"""The general traffic generator: reads a mix file (``bench/traffic/<mix>.json``)
and builds, from the seed and the database, the request pool and the
window's rounds of requests.

A mix file holds its templates as data. A GCDI template is a query spec::

    {"select": ["Customer.id", "t.tid"], "from": ["Customer"],
     "match": {"graph": "Interested_in",
               "hops": [["p", "Persons", "Interested_in", "t", "Tags"]]},
     "joins": [["Customer.person_id", "p.pid"]],
     "where": [["t.content", "==", "food"], ["Product.price", "range", "$lo", "$hi"]]}

A GCDIA template adds ``"analytics": {"op": ..., "inputs": [...]}`` beside
its ``"query"``. An input ``["purchase_labels", "Customer.id", <title>]``
is a label vector, built here from the data: one entry per distinct
customer id of the query's answer in ascending order (the rows of the
feature matrix), 1 where that customer ordered a product whose title
starts with ``<title>``; the program gets it as a ``const`` input. A value
written ``"$name"`` is drawn per pool entry by the
template's ``"params"``: ``order_keys`` picks random orders and binds
``$oid``, ``$cid`` and ``$pid`` (the order, its customer, that customer's
person); ``uniform_window`` binds ``$lo`` uniform in ``lo`` and
``$hi = $lo + width``. ``count`` entries are drawn per template.

The window is made of rounds: each round holds every template once, in an
order drawn from the seed, and each request of a template takes one of its
pool entries at random. Every seed thus sends the same mix of templates.
"""
from __future__ import annotations

import dataclasses
import json
import os

import numpy as np

from repro.core.schema import (AnalyticsTask, GCDIATask, JoinPred, Predicate,
                               Query, chain_pattern)

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class Entry:
    """One request of the pool: its template, its bound parameters, the
    spec with the parameters filled in, and the program's request object."""

    template: str
    index: int
    spec: dict
    request: object     # Query or GCDIATask


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _bind(obj, params: dict):
    if isinstance(obj, str) and obj.startswith("$"):
        return params[obj[1:]]
    if isinstance(obj, list):
        return [_bind(v, params) for v in obj]
    if isinstance(obj, dict):
        return {k: _bind(v, params) for k, v in obj.items()}
    return obj


def build_query(spec: dict) -> Query:
    match = None
    if spec.get("match"):
        m = spec["match"]
        match = chain_pattern(m["graph"], *[tuple(h) for h in m["hops"]])
    return Query(select=tuple(spec["select"]), froms=tuple(spec.get("from", ())),
                 match=match,
                 joins=tuple(JoinPred(a, b) for a, b in spec.get("joins", ())),
                 where=tuple(Predicate(*w) for w in spec.get("where", ())))


def build_request(spec: dict):
    """The program's request object for a bound spec: a ``Query`` for a GCDI
    template, a ``GCDIATask`` for one with ``analytics``."""
    if "analytics" not in spec:
        return build_query(spec)
    a = spec["analytics"]
    inputs = [("const", np.asarray(inp[1], np.float32)) if inp[0] == "const"
              else tuple(tuple(x) if isinstance(x, list) else x for x in inp)
              for inp in a["inputs"]]
    return GCDIATask(integration=build_query(spec["query"]),
                     analytics=AnalyticsTask(a["op"], inputs))


def _draw_params(kind: dict, count: int, rng, raw: dict) -> list[dict]:
    if kind["kind"] == "order_keys":
        orders = raw["tables"]["Orders"]
        person = raw["tables"]["Customer"]["person_id"]
        rows = rng.integers(0, len(orders["order_id"]), count)
        return [{"oid": int(orders["order_id"][r]),
                 "cid": int(orders["customer_id"][r]),
                 "pid": int(person[orders["customer_id"][r]])} for r in rows]
    if kind["kind"] == "uniform_window":
        a, b = kind["lo"]
        lo = rng.uniform(a, b, count)
        return [{"lo": float(x), "hi": float(x + kind["width"])} for x in lo]
    raise ValueError(f"unknown parameter kind {kind['kind']!r}")


def purchase_labels(raw: dict, query: dict, group: str, title: str
                    ) -> list[float]:
    """1.0 per distinct ``group`` (customer id) of the query's answer, in
    ascending order, that ordered a product whose title starts with
    ``title``; else 0.0."""
    from bench import reference
    ids = np.unique(reference.relation(raw, {**query, "select": [group]})[0])
    t = raw["tables"]
    hit = np.char.startswith(t["Product"]["title"].astype(str), title)
    o = t["Orders"]
    buyers = np.unique(o["customer_id"][hit[o["product_id"]]])
    return np.isin(ids, buyers).astype(np.float64).tolist()


def _labels(spec: dict, raw: dict) -> dict:
    if "analytics" not in spec:
        return spec
    a = spec["analytics"]
    inputs = [["const", purchase_labels(raw, spec["query"], inp[1], inp[2])]
              if inp[0] == "purchase_labels" else inp for inp in a["inputs"]]
    return {**spec, "analytics": {**a, "inputs": inputs}}


def build_pool(mix: dict, seed: int, raw: dict) -> dict[str, list[Entry]]:
    """template -> its pool entries, drawn from ``seed`` and the data."""
    rng = np.random.default_rng([seed, 1])
    pool: dict[str, list[Entry]] = {}
    for name, t in mix["templates"].items():
        spec = {k: v for k, v in t.items() if k != "params"}
        if "params" in t:
            bound = _draw_params(t["params"], int(t["params"]["count"]), rng,
                                 raw)
        else:
            bound = [{}]
        pool[name] = []
        for i, p in enumerate(bound):
            s = _labels(_bind(spec, p), raw)
            pool[name].append(Entry(name, i, s, build_request(s)))
    return pool


def rounds(pool: dict[str, list[Entry]], seed: int):
    """Endless rounds, drawn as they are asked for: each holds every
    template once in an order drawn from ``seed``."""
    rng = np.random.default_rng([seed, 2])
    names = sorted(pool)
    while True:
        order = rng.permutation(len(names))
        yield [pool[names[j]][int(rng.integers(len(pool[names[j]])))]
               for j in order]
