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
its ``"query"``. An input whose kind the configuration's generator lists in
its ``INPUTS`` table (``{kind: fn}``) is made here from the data:
``fn(raw, query, *args)`` gives a vector, which the program gets as a
``const`` input; the e-commerce generator's ``purchase_labels`` is one. A
value written ``"$name"`` is drawn per pool entry by the template's
``"params"``, whose ``kind`` is ``uniform_window`` (binds ``$lo`` uniform in
``lo`` and ``$hi = $lo + width``) or a kind in the generator's ``PARAMS``
table (``{kind: fn}``, ``fn(params, count, rng, raw)`` gives one dict of
bindings per entry). ``count`` entries are drawn per template.

The window is made of rounds. By default each round holds every template
once, in an order drawn from the seed, and each request of a template
takes one of its pool entries at random. Every seed thus sends the same
mix of templates. A mix that names a ``"session"``, an ordered list of its
templates, runs that list as each round instead: always in that order, all
of a round's requests on one pool index drawn from the seed, so their pools
are of one size. The harness clears the inter-buffer once, inside the
first request of each session, and never between its requests.
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
    """The mix ``bench/traffic/<name>.json``; a ``"session"`` must list every
    template, name no other, and not go with ``clear_interbuffer``."""
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    session = mix.get("session")
    if session is not None and (set(session) != set(mix["templates"])
                                or mix.get("clear_interbuffer")):
        raise ValueError(f"mix {name!r}: a session lists every template and "
                         "the inter-buffer is cleared only at its start")
    return mix


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


def uniform_window(params: dict, count: int, rng, raw: dict) -> list[dict]:
    """``$lo`` uniform in ``params["lo"]``, ``$hi = $lo + width``."""
    a, b = params["lo"]
    lo = rng.uniform(a, b, count)
    return [{"lo": float(x), "hi": float(x + params["width"])} for x in lo]


PARAMS = {"uniform_window": uniform_window}


def _inputs(spec: dict, raw: dict, gen) -> dict:
    """The spec with each analytics input of a kind in ``gen.INPUTS`` made
    into a ``const`` vector."""
    if "analytics" not in spec:
        return spec
    a = spec["analytics"]
    made = getattr(gen, "INPUTS", {})
    inputs = [["const", made[inp[0]](raw, spec["query"], *inp[1:])]
              if inp[0] in made else inp for inp in a["inputs"]]
    return {**spec, "analytics": {**a, "inputs": inputs}}


def build_pool(mix: dict, seed: int, raw: dict, gen
               ) -> dict[str, list[Entry]]:
    """template -> its pool entries, drawn from ``seed`` and the data made
    by ``gen``, the configuration's generator module."""
    rng = np.random.default_rng([seed, 1])
    kinds = {**PARAMS, **getattr(gen, "PARAMS", {})}
    pool: dict[str, list[Entry]] = {}
    for name, t in mix["templates"].items():
        spec = {k: v for k, v in t.items() if k != "params"}
        if "params" in t:
            kind = t["params"]["kind"]
            if kind not in kinds:
                raise ValueError(f"unknown parameter kind {kind!r}")
            bound = kinds[kind](t["params"], int(t["params"]["count"]), rng,
                                raw)
        else:
            bound = [{}]
        pool[name] = []
        for i, p in enumerate(bound):
            s = _inputs(_bind(spec, p), raw, gen)
            pool[name].append(Entry(name, i, s, build_request(s)))
    if len({len(pool[t]) for t in mix.get("session", ())}) > 1:
        raise ValueError("a session's templates need pools of one size")
    return pool


def rounds(pool: dict[str, list[Entry]], seed: int,
           session: list[str] | None = None):
    """Endless rounds, drawn as they are asked for: each holds every
    template once in an order drawn from ``seed``, or, for a ``session``,
    its templates in its order on one pool index drawn from ``seed``."""
    rng = np.random.default_rng([seed, 2])
    if session:
        n = len(pool[session[0]])
        while True:
            i = int(rng.integers(n))
            yield [pool[t][i] for t in session]
    names = sorted(pool)
    while True:
        order = rng.permutation(len(names))
        yield [pool[names[j]][int(rng.integers(len(pool[names[j]])))]
               for j in order]


def warmup(pool: dict[str, list[Entry]],
           session: list[str] | None = None) -> list[list[Entry]]:
    """Set-up's pass over the pool, as rounds: every entry once, each its
    own round, or for a ``session`` one whole session per pool index."""
    if session:
        return [[pool[t][i] for t in session]
                for i in range(len(pool[session[0]]))]
    return [[e] for entries in pool.values() for e in entries]
