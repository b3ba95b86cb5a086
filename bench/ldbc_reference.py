"""The plain reference for the LDBC SNB configuration: IC1 and IC13 as a
breadth-first search over the undirected ``knows`` edge list, in numpy. It
imports nothing of the program and reads nothing the program made.

A spec's match is one quantified hop ``[src, label, edge, dst, label, min,
max]`` (``max`` None: unbounded). Its answer is one row per pair (s, f)
with s passing the src var's predicates, f the dst var's, and the
shortest-path distance d(s, f) in [min, max]; ``<edge var>.hops`` is d.
Answers are bags. The CSR of the undirected edges is built once per data
set and kept in ``raw``.

The control, ``distinct``, gives the reference's own answer with the rows
at its largest distance dropped: a search one level short. An IC13 answer
(one row) then has none.
"""
from __future__ import annotations

from collections import Counter

import numpy as np


class Answer(list):
    """The selected columns of an answer, and the index of its distance
    column (None when not selected)."""

    hops: int | None = None


def csr(raw: dict, graph: str) -> tuple[np.ndarray, np.ndarray]:
    """(row_ptr, col) over both directions of the graph's undirected edge
    list; built once and kept in ``raw``."""
    held = raw.setdefault("_reference_csr", {})
    if graph not in held:
        g = raw["graphs"][graph]
        n = len(next(iter(g["vertices"][g["src_label"]].values())))
        a = np.asarray(g["edges"]["svid"], np.int64)
        b = np.asarray(g["edges"]["tvid"], np.int64)
        src, dst = np.concatenate([a, b]), np.concatenate([b, a])
        order = np.argsort(src, kind="stable")
        row_ptr = np.zeros(n + 1, np.int64)
        np.cumsum(np.bincount(src, minlength=n), out=row_ptr[1:])
        held[graph] = (row_ptr, dst[order])
    return held[graph]


def bfs(row_ptr: np.ndarray, col: np.ndarray, start: int,
        max_hops: int | None = None) -> np.ndarray:
    """Hop distance from ``start`` to every vertex, -1 where it is farther
    than ``max_hops`` or unreachable."""
    dist = np.full(len(row_ptr) - 1, -1, np.int64)
    dist[start] = 0
    frontier = np.array([start])
    level = 0
    while len(frontier) and (max_hops is None or level < max_hops):
        lo, lens = row_ptr[frontier], np.diff(row_ptr)[frontier]
        first = np.repeat(np.cumsum(lens) - lens, lens)
        nbrs = col[np.arange(lens.sum()) - first + np.repeat(lo, lens)]
        frontier = np.unique(nbrs[dist[nbrs] < 0])
        level += 1
        dist[frontier] = level
    return dist


def _mask(cols: dict, var: str, where: list) -> np.ndarray:
    n = len(next(iter(cols.values())))
    keep = np.ones(n, bool)
    for attr, op, *vals in where:
        v, _, c = attr.partition(".")
        if v != var:
            continue
        col, x = cols[c], vals[0]
        if op == "==":
            keep &= col == x
        elif op == "!=":
            keep &= col != x
        elif op == "<":
            keep &= col < x
        elif op == "<=":
            keep &= col <= x
        elif op == ">":
            keep &= col > x
        elif op == ">=":
            keep &= col >= x
        elif op == "range":
            keep &= (col >= x) & (col <= vals[1])
        elif op == "in":
            keep &= np.isin(col, list(x))
        else:
            raise ValueError(op)
    return keep


def relation(raw: dict, spec: dict, lower: bool = False) -> Answer:
    """The answer to a bound reach spec, one array per selected attribute.
    ``lower`` changes nothing: no column or literal of these reads is a
    float."""
    m = spec["match"]
    (sv, sl, _el, dv, dl, lo, hi), = m["hops"]
    g = raw["graphs"][m["graph"]]
    where = [list(w) for w in spec.get("where", ())]
    src_cols, dst_cols = g["vertices"][sl], g["vertices"][dl]
    starts = np.flatnonzero(_mask(src_cols, sv, where))
    ends = _mask(dst_cols, dv, where)
    row_ptr, col = csr(raw, m["graph"])
    s_rows, f_rows, hops = [], [], []
    for s in starts:
        d = bfs(row_ptr, col, int(s), hi)
        f = np.flatnonzero((d >= lo) & ends)
        s_rows.append(np.full(len(f), s))
        f_rows.append(f)
        hops.append(d[f])
    s_rows, f_rows, hops = (np.concatenate(x) if x else np.zeros(0, np.int64)
                            for x in (s_rows, f_rows, hops))
    out = Answer()
    for attr in spec["select"]:
        v, _, c = attr.partition(".")
        if v == "e0" and c == "hops":
            out.hops = len(out)
            out.append(hops)
        elif v == sv:
            out.append(np.asarray(src_cols[c])[s_rows])
        elif v == dv:
            out.append(np.asarray(dst_cols[c])[f_rows])
        else:
            raise ValueError(f"cannot select {attr!r}")
    return out


def distinct(cols: Answer) -> list[np.ndarray]:
    """The control: the answer without the rows at its largest distance."""
    if cols.hops is None or not len(cols[cols.hops]):
        return list(cols)
    keep = cols[cols.hops] < cols[cols.hops].max()
    return [c[keep] for c in cols]


def _values(c) -> np.ndarray:
    """A column as the values it holds: a dictionary-encoded string column
    (an object with ``codes`` and ``vocab``) is decoded."""
    if isinstance(c, np.ndarray) and c.ndim == 0:
        c = c.item()
    if hasattr(c, "codes") and hasattr(c, "vocab"):
        return np.asarray(c.vocab, object)[np.asarray(c.codes)]
    return np.asarray(c)


def rows_off(got: list, want: list) -> int:
    """Rows in one bag and not the other (the size of the symmetric
    difference of the two row multisets)."""
    def bag(cols):
        cols = [_values(c).tolist() for c in cols]
        return Counter(zip(*cols)) if cols else Counter()
    if len(got) != len(want):
        return max(len(got[0]) if got else 0, len(want[0]) if want else 0, 1)
    a, b = bag(got), bag(want)
    return sum(((a - b) + (b - a)).values())
