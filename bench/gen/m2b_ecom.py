"""M2Bench-shaped e-commerce data, made from a seed.

A copy of the repository generator's scenario (relational Product and
Customer, the Orders document collection, the Interested_in and Follows
graphs), kept here so that a later change to the program's own generator
cannot change what the benchmark measures. Two departures, both stated in
each configuration's ``assumed``:

* Every random number is drawn vectorised, and the Orders collection is
  built as the columns that shredding its documents gives, which keeps
  set-up short.
* The shape of both graphs is drawn once from the fixed ``SHAPE_SEED``:
  the Follows out-degrees, and for Interested_in each person's out-degree
  with how many of those interests are food tags (a binomial draw, as
  uniform tags give). The customers' profiles and the other persons'
  profiles are handed out in an order drawn from the run's seed, and the
  tags themselves are drawn from the run's seed, food among food tags.
  A Follows self-loop is redrawn rather than dropped. So every seed has
  the same vertex and edge counts, the same degree multisets and the same
  number of customers with a food interest (the rows of A1-A3's
  matrices), and the device programs whose shapes follow those counts
  compile once.

``generate`` returns ``(db, raw)``: the program's ``Database`` and the same
data as plain numpy columns, which is all the plain reference reads.
``PARAMS`` and ``INPUTS`` are the schema's own parameter and input kinds,
which ``bench/workload.py`` looks up by the names a mix gives.
"""
from __future__ import annotations

import numpy as np

from repro.core.storage import Database, DictColumn, Graph, RaggedColumn, Table

N_TAGS = 200
FOOD_TAGS = 40          # tag ids [0, 40) are food-related
PRODUCT_TITLES = ("Yogurt", "Milk", "Bread", "Coffee", "Tea", "Chocolate",
                  "Laptop", "Phone", "Book", "Desk")
CITIES = ("wuhan", "beijing", "shanghai", "shenzhen", "chengdu")
COUNTRIES = ("cn", "us", "au", "uk")
SHAPE_SEED = 0          # the degree sequences, the same for every run seed


def counts(sf: int) -> dict:
    """Row counts per collection at scale factor ``sf`` (the repo
    generator's per-sf sizes)."""
    return {"Product": 1_000 * sf, "Customer": 2_000 * sf,
            "Orders": 10_000 * sf, "Persons": 2_500 * sf, "Tags": N_TAGS}


def generate(sf, seed: int):
    """The data at scale factor ``sf`` (a whole number, as the configuration
    writes it) from ``seed``."""
    n = counts(int(sf))
    n_products, n_customers = n["Product"], n["Customer"]
    n_orders, n_persons = n["Orders"], n["Persons"]
    rng = np.random.default_rng(seed)
    shape = np.random.default_rng(SHAPE_SEED)
    deg = shape.poisson(8, n_persons).clip(1, 40)
    fdeg = shape.poisson(5, n_persons).clip(0, 30)
    food = shape.binomial(deg, FOOD_TAGS / N_TAGS)

    raw: dict = {"tables": {}, "graphs": {}}
    titles = np.array([PRODUCT_TITLES[i % len(PRODUCT_TITLES)]
                       + (f" v{i // len(PRODUCT_TITLES)}"
                          if i >= len(PRODUCT_TITLES) else "")
                       for i in range(n_products)], dtype=object)
    raw["tables"]["Product"] = {
        "id": np.arange(n_products, dtype=np.int64),
        "title": titles,
        "price": rng.uniform(1, 500, n_products).round(2)}
    perm = rng.permutation(n_persons)
    raw["tables"]["Customer"] = {
        "id": np.arange(n_customers, dtype=np.int64),
        "person_id": perm[:n_customers].astype(np.int64),
        "name": np.array([f"cust_{i}" for i in range(n_customers)],
                         dtype=object),
        "age": rng.integers(18, 80, n_customers).astype(np.int64)}

    items_len = rng.integers(1, 4, n_orders)
    items_off = np.zeros(n_orders + 1, dtype=np.int64)
    np.cumsum(items_len, out=items_off[1:])
    raw["tables"]["Orders"] = {
        "order_id": np.arange(n_orders, dtype=np.int64),
        "customer_id": rng.integers(0, n_customers, n_orders),
        "product_id": rng.integers(0, n_products, n_orders),
        "quantity": rng.integers(1, 5, n_orders).astype(np.int64),
        "shipping.city": np.array(CITIES, dtype=object)[
            rng.integers(0, len(CITIES), n_orders)],
        "shipping.days": rng.integers(1, 10, n_orders).astype(np.int64)}
    items = rng.integers(0, N_TAGS, int(items_off[-1])).astype(np.int64)

    persons = {"pid": np.arange(n_persons, dtype=np.int64),
               "country": np.array(COUNTRIES, dtype=object)[
                   np.arange(n_persons) % len(COUNTRIES)]}
    tags = {"tid": np.arange(N_TAGS, dtype=np.int64),
            "content": np.array(["food"] * FOOD_TAGS
                                + [f"topic_{i}" for i in
                                   range(N_TAGS - FOOD_TAGS)], dtype=object),
            "popularity": rng.uniform(0, 1, N_TAGS)}
    # profile i (deg[i], food[i]) goes to a customer's person for
    # i < n_customers and to another person otherwise
    prof = np.empty(n_persons, dtype=np.int64)
    prof[perm[:n_customers]] = rng.permutation(n_customers)
    prof[perm[n_customers:]] = n_customers + rng.permutation(
        n_persons - n_customers)
    src = np.repeat(np.arange(n_persons), deg[prof])
    first = np.repeat(np.cumsum(deg[prof]) - deg[prof], deg[prof])
    is_food = np.arange(len(src)) - first < np.repeat(food[prof], deg[prof])
    tvid = np.where(is_food, rng.integers(0, FOOD_TAGS, len(src)),
                    rng.integers(FOOD_TAGS, N_TAGS, len(src)))
    tvid = tvid[np.lexsort((rng.random(len(src)), src))]
    raw["graphs"]["Interested_in"] = {
        "vertices": {"Persons": persons, "Tags": tags},
        "src_label": "Persons", "dst_label": "Tags",
        "edges": {"svid": src.astype(np.int64),
                  "tvid": tvid.astype(np.int64),
                  "weight": rng.uniform(0, 1, len(src))}}

    fsrc = np.repeat(np.arange(n_persons), rng.permutation(fdeg))
    fdst = rng.integers(0, n_persons, len(fsrc))
    loop = fdst == fsrc
    while loop.any():
        fdst[loop] = rng.integers(0, n_persons, int(loop.sum()))
        loop = fdst == fsrc
    raw["graphs"]["Follows"] = {
        "vertices": {"Persons": persons},
        "src_label": "Persons", "dst_label": "Persons",
        "edges": {"svid": fsrc.astype(np.int64),
                  "tvid": fdst.astype(np.int64),
                  "since": rng.integers(2000, 2026, len(fsrc)).astype(np.int64)}}

    db = Database()
    for name, cols in raw["tables"].items():
        cols = {k: _column(v) for k, v in cols.items()}
        if name == "Orders":
            cols["items"] = RaggedColumn(values=items, offsets=items_off)
        db.add_table(Table(name, cols))
    for name, g in raw["graphs"].items():
        vts = {lab: Table(lab, {k: _column(v) for k, v in vt.items()})
               for lab, vt in g["vertices"].items()}
        db.add_graph(Graph(name, vts,
                           Table(f"{name}_edges", dict(g["edges"])),
                           g["src_label"], g["dst_label"]))
    return db, raw


def _column(v: np.ndarray):
    return DictColumn(values=v) if v.dtype == object else v


def build_indexes(db: Database):
    """The secondary indexes of the repository's selective-access workload:
    table-side sorted/zone indexes on the join and lookup keys, and the
    graph-side composite (label, attr) vertex indexes."""
    im = db.indexes
    im.create("Customer", "person_id")
    im.create("Orders", "order_id", kind="zone")
    im.create("Product", "price")
    im.create("Interested_in", "pid", label="Persons")
    im.create("Interested_in", "popularity", label="Tags")
    im.create("Interested_in", "content", label="Tags")
    return im


def order_keys(params: dict, count: int, rng, raw: dict) -> list[dict]:
    """``count`` random orders: ``$oid``, its customer ``$cid`` and that
    customer's person ``$pid``."""
    orders = raw["tables"]["Orders"]
    person = raw["tables"]["Customer"]["person_id"]
    rows = rng.integers(0, len(orders["order_id"]), count)
    return [{"oid": int(orders["order_id"][r]),
             "cid": int(orders["customer_id"][r]),
             "pid": int(person[orders["customer_id"][r]])} for r in rows]


def purchase_labels(raw: dict, query: dict, group: str, title: str
                    ) -> list[float]:
    """A label vector: 1.0 per distinct ``group`` (customer id) of the
    query's answer, in ascending order (the rows of the feature matrix),
    that ordered a product whose title starts with ``title``; else 0.0."""
    from bench import reference
    ids = np.unique(reference.relation(raw, {**query, "select": [group]})[0])
    t = raw["tables"]
    hit = np.char.startswith(t["Product"]["title"].astype(str), title)
    o = t["Orders"]
    buyers = np.unique(o["customer_id"][hit[o["product_id"]]])
    return np.isin(ids, buyers).astype(np.float64).tolist()


PARAMS = {"order_keys": order_keys}
INPUTS = {"purchase_labels": purchase_labels}
