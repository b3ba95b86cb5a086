"""LDBC SNB Interactive's social graph, Person and ``knows``, made from a seed.

LDBC's Datagen makes the ``knows`` graph in three correlated passes: persons
are sorted by a key (where they studied, their main interest, then a random
key) and each befriends persons near it in that order, within a sliding
window, with a probability that falls with the distance. This module does
the same, vectorised, with what the configuration states under
``assumed``:

* the counts at each scale factor (``COUNTS``);
* target degrees from a heavy-tailed law (lognormal, sigma 1) whose mean is
  Datagen's n^(0.512 - 0.028 log10 n), clipped to [1, n/8];
* three passes taking 45%, 45% and 10% of each person's degree, each a
  window in which a partner lies a two-sided exponential distance away in
  the pass's order, the scale growing with the proposer's degree;
* the edge set trimmed (or topped up with uniform pairs) to the stated
  count exactly.

The run's seed draws the topology and every attribute. Every seed has the
same |V| and |E|, the only shapes the device programs take, so they
compile once. Person ids are 0..n-1. Names come from fixed dictionaries
with Zipf-like frequencies. ``email`` and ``speaks`` are multi-valued; each
person's values are one ``;``-separated string, as LDBC's composite CSV
files carry them.

``generate`` returns ``(db, raw)``: the program's ``Database``, with
``knows`` stored in both directions, and the same data as numpy columns,
``knows`` as its undirected edge list, which is all the plain reference
(``bench/ldbc_reference.py``) reads. ``PARAMS`` holds LDBC's parameter
curation for IC1 and IC13.
"""
from __future__ import annotations

import numpy as np

from repro.core.storage import Database, DictColumn, Graph, Table

# Person and knows (undirected) counts as Datagen's data sets are reported;
# the repository holds no copy of them
COUNTS = {0.1: (1_528, 14_073), 1: (9_892, 180_623), 10: (65_645, 1_938_516)}
PASSES = (0.45, 0.45, 0.10)     # study place, main interest, random
N_FIRST, N_LAST = 1_000, 2_000
BROWSERS = ("Firefox", "Chrome", "Internet Explorer", "Safari", "Opera")
BROWSER_P = (0.32, 0.36, 0.2, 0.08, 0.04)
MAX_EMAILS = 5
EMAIL_PROVIDERS = ("gmail.com", "yahoo.com", "hotmail.com", "gmx.com",
                   "zoho.com", "mail.com", "aol.com", "yandex.com",
                   "outlook.com", "protonmail.com")
LANGUAGES = ("zh", "en", "hi", "es", "ar", "bn", "pt", "ru", "ja", "de",
             "jv", "ko", "fr", "tr", "vi", "te", "mr", "ta", "it", "ur",
             "gu", "pl", "uk", "fa", "ml", "kn", "or", "my", "th", "nl")
P_ENGLISH, P_SECOND = 0.6, 0.2
DAY_MS = 86_400_000
BIRTH_DAYS = (3_652, 7_670)                     # 1980-01-01 .. 1990-12-31
CREATED_MS = (14_832 * DAY_MS, 15_706 * DAY_MS)  # 2010-08-10 .. 2013-01-01
SYLLABLES = ("an", "be", "ca", "di", "el", "fa", "go", "ha", "in", "jo", "ka",
             "li", "mo", "na", "or", "pa", "qu", "ra", "si", "to", "ul", "va",
             "wi", "xe", "ya", "zo", "ri", "lu", "me", "no")


def counts(sf) -> tuple[int, int]:
    """(persons, undirected knows edges) at scale factor ``sf``."""
    key = float(sf)
    for k, v in COUNTS.items():
        if float(k) == key:
            return v
    raise ValueError(f"no LDBC SNB counts for sf {sf!r}; known: "
                     f"{sorted(COUNTS)}")


def mean_degree(n: int) -> float:
    """Datagen's average ``knows`` degree for n persons."""
    return float(n ** (0.512 - 0.028 * np.log10(n)))


def _zipf_p(k: int, s: float = 1.0) -> np.ndarray:
    p = 1.0 / np.arange(1, k + 1) ** s
    return p / p.sum()


def _words(k: int, parts: int, offset: int) -> np.ndarray:
    """``k`` distinct capitalised names of ``parts`` syllables."""
    m = len(SYLLABLES)
    out = []
    for i in range(offset, offset + k):
        w = "".join(SYLLABLES[(i // m ** j) % m] for j in range(parts))
        out.append(w.capitalize())
    return np.array(out, dtype=object)


FIRST_NAMES = _words(N_FIRST, 3, 0)
LAST_NAMES = _words(N_LAST, 3, 7_000)


def topology(sf, rng) -> tuple[np.ndarray, np.ndarray]:
    """The undirected ``knows`` edges (u < v, each once, sorted) over
    persons 0..n-1, drawn from ``rng``."""
    n, n_edges = counts(sf)
    deg = rng.lognormal(0.0, 1.0, n)
    deg = np.clip(np.rint(deg * mean_degree(n) / deg.mean()), 1, n // 8)
    place = rng.choice(max(n // 200, 2), n, p=_zipf_p(max(n // 200, 2)))
    interest = rng.choice(1_000, n, p=_zipf_p(1_000))
    birth = rng.integers(*BIRTH_DAYS, n)
    orders = (np.lexsort((birth, place)), np.lexsort((birth, interest)),
              rng.permutation(n))
    window = max(n // 2 - 1, 1)
    us, vs = [], []
    for share, order in zip(PASSES, orders):
        pos = np.empty(n, dtype=np.int64)
        pos[order] = np.arange(n)
        k = np.rint(share * deg / 2).astype(np.int64)
        who = np.repeat(np.arange(n), k)
        scale = np.repeat(np.maximum(2.0 * k, 4.0), k)
        off = np.minimum(np.ceil(rng.exponential(scale)), window)
        off = off.astype(np.int64) * rng.choice((-1, 1), len(who))
        us.append(who)
        vs.append(order[(pos[who] + off) % n])
    u, v = np.concatenate(us), np.concatenate(vs)
    keys = np.unique(np.minimum(u, v) * n + np.maximum(u, v))
    keys = keys[keys // n != keys % n]
    while len(keys) < n_edges:          # top up with uniform pairs
        a = rng.integers(0, n, 2 * (n_edges - len(keys)))
        b = rng.integers(0, n, len(a))
        keys = np.union1d(keys, (np.minimum(a, b) * n + np.maximum(a, b))
                          [a != b])
    keys = np.sort(rng.choice(keys, n_edges, replace=False))
    return (keys // n).astype(np.int64), (keys % n).astype(np.int64)


def _emails(first: np.ndarray, rng) -> np.ndarray:
    """1..``MAX_EMAILS`` addresses a person, ``<firstName><k>@<provider>``
    with a number unique to the address and providers by Zipf frequency."""
    k = rng.integers(1, MAX_EMAILS + 1, len(first))
    who = np.repeat(np.arange(len(first)), k)
    prov = np.asarray(EMAIL_PROVIDERS, object)[
        rng.choice(len(EMAIL_PROVIDERS), len(who),
                   p=_zipf_p(len(EMAIL_PROVIDERS)))]
    addr = [f"{f}{i}@{p}" for f, i, p in zip(first[who], range(len(who)),
                                             prov)]
    ends = np.cumsum(k)
    return np.array([";".join(addr[e - c:e]) for c, e in zip(k, ends)],
                    dtype=object)


def _speaks(n: int, rng) -> np.ndarray:
    """A home language by Zipf frequency, English besides with probability
    ``P_ENGLISH`` and a second language with probability ``P_SECOND``."""
    p = _zipf_p(len(LANGUAGES))
    home = rng.choice(len(LANGUAGES), n, p=p)
    second = rng.choice(len(LANGUAGES), n, p=p)
    english = rng.random(n) < P_ENGLISH
    other = rng.random(n) < P_SECOND
    out = []
    for h, s2, e, o in zip(home, second, english, other):
        langs = [LANGUAGES[h]]
        if e and LANGUAGES[h] != "en":
            langs.append("en")
        if o and LANGUAGES[s2] not in langs:
            langs.append(LANGUAGES[s2])
        out.append(";".join(langs))
    return np.array(out, dtype=object)


def generate(sf, seed: int):
    """Person and ``knows`` at scale factor ``sf`` from ``seed``."""
    n, _ = counts(sf)
    rng = np.random.default_rng(seed)
    a, b = topology(sf, rng)
    octets = rng.integers(1, 255, (n, 4))
    first = FIRST_NAMES[rng.choice(N_FIRST, n, p=_zipf_p(N_FIRST))]
    person = {
        "id": np.arange(n, dtype=np.int64),
        "firstName": first,
        "lastName": LAST_NAMES[rng.choice(N_LAST, n, p=_zipf_p(N_LAST))],
        "gender": np.array(("male", "female"), dtype=object)[
            rng.integers(0, 2, n)],
        "birthday": rng.integers(*BIRTH_DAYS, n).astype(np.int64),
        "creationDate": rng.integers(*CREATED_MS, n).astype(np.int64),
        "browserUsed": np.array(BROWSERS, dtype=object)[
            rng.choice(len(BROWSERS), n, p=BROWSER_P)],
        "locationIP": np.array([".".join(map(str, o)) for o in octets],
                               dtype=object),
        "email": _emails(first, rng),
        "speaks": _speaks(n, rng)}
    raw = {"tables": {}, "graphs": {"knows": {
        "vertices": {"Person": person}, "src_label": "Person",
        "dst_label": "Person", "edges": {"svid": a, "tvid": b},
        "undirected": True}}}
    cols = {k: DictColumn(values=c) if c.dtype == object else c
            for k, c in person.items()}
    edges = Table("knows_edges", {"svid": np.concatenate([a, b]),
                                  "tvid": np.concatenate([b, a])})
    db = Database()
    db.add_graph(Graph("knows", {"Person": Table("Person", cols)}, edges,
                       "Person", "Person"))
    return db, raw


def build_indexes(db: Database):
    """A sorted index on ``Person.id``, the key IC1 and IC13 pin."""
    im = db.indexes
    im.create("knows", "id", kind="sorted", label="Person")
    return im


# ---------------------------------------------------------------------------
# Parameter curation (Gubichev and Boncz, TPCTC 2014): start persons whose
# neighbourhoods are of a typical size, so that requests cost alike
# ---------------------------------------------------------------------------

SAMPLE = 2_048


def _starts(count: int, rng, raw: dict) -> np.ndarray:
    """Of ``SAMPLE`` persons with degree >= 1 drawn from ``rng``, the
    ``count`` whose 2-hop reach is nearest the sample's median, in the
    sample's order."""
    from bench import ldbc_reference as ref
    row_ptr, col = ref.csr(raw, "knows")
    deg = np.diff(row_ptr)
    pool = np.flatnonzero(deg > 0)
    sample = rng.choice(pool, min(SAMPLE, len(pool)), replace=False)
    size = np.array([int((ref.bfs(row_ptr, col, int(s), 2) > 0).sum())
                     for s in sample])
    near = np.argsort(np.abs(size - np.median(size)), kind="stable")[:count]
    return sample[np.sort(near)]


def ic1_start_and_name(params: dict, count: int, rng, raw: dict
                       ) -> list[dict]:
    """IC1's ``$pid`` (a curated start) and ``$name``, a first name drawn,
    weighted by its count, from the persons 1..3 hops from the start, so
    that no answer is empty."""
    from bench import ldbc_reference as ref
    row_ptr, col = ref.csr(raw, "knows")
    first = raw["graphs"]["knows"]["vertices"]["Person"]["firstName"]
    out = []
    for s in _starts(count, rng, raw):
        d = ref.bfs(row_ptr, col, int(s), 3)
        names, freq = np.unique(first[d > 0], return_counts=True)
        out.append({"pid": int(s),
                    "name": str(names[rng.choice(len(names),
                                                 p=freq / freq.sum())])})
    return out


def ic13_pair(params: dict, count: int, rng, raw: dict) -> list[dict]:
    """IC13's ``$p1`` (a curated start) and ``$p2``, uniform over the other
    persons; pairs with no path stay in."""
    n = len(raw["graphs"]["knows"]["vertices"]["Person"]["id"])
    out = []
    for s in _starts(count, rng, raw):
        t = int(rng.integers(0, n - 1))
        out.append({"p1": int(s), "p2": t + (t >= s)})
    return out


PARAMS = {"ic1_start_and_name": ic1_start_and_name, "ic13_pair": ic13_pair}
