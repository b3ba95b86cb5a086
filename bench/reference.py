"""The plain reference: the same semantics as the engine, written straight
over the generator's numpy columns with pandas and numpy. It imports nothing
of the program and reads nothing the program made.

GCDI (SFMW, paper Eq. 1): filter each collection by its predicates, expand
the chain pattern hop by hop over the edge list, apply the equi-joins in
order, and project the selected attributes. Answers are bags: one row per
combination of matching records and pattern edges.

GCDA: the multi-hot matrix of ``random`` inputs (one row per distinct group
key in ascending order, a 1 at each value), the float32 matrix of
``rel2matrix`` inputs, and MULTIPLY (the Gram product X Xᵀ), SIMILARITY
(cosine of every pair of rows) and REGRESSION (logistic regression by full
gradient descent from w = 0: w -= lr (Xᵀ(σ(Xw) - y)/n + l2 w)), in float64.

Controls, in the program's place: ``distinct`` gives a GCDI answer with
its repeated rows dropped (set semantics where the configuration states
bags); ``lower=True`` computes GCDA one precision below what the
configuration states, rounding its inputs and every intermediate to
bfloat16. ``lower=True`` on GCDI reads every float64 column and literal as
float32.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np
import pandas as pd

BF16 = ml_dtypes.bfloat16


def _bf(a):
    return np.asarray(a, np.float64).astype(BF16).astype(np.float64)


# ---------------------------------------------------------------------------
# GCDI
# ---------------------------------------------------------------------------


def _lower(v, lower: bool):
    if lower and isinstance(v, float):
        return float(np.float32(v))
    return v


def _frame(cols: dict, prefix: str, lower: bool) -> pd.DataFrame:
    out = {}
    for k, v in cols.items():
        if lower and v.dtype == np.float64:
            v = v.astype(np.float32)
        out[f"{prefix}.{k}"] = v
    return pd.DataFrame(out)


def _filter(df: pd.DataFrame, where: list, lower: bool) -> pd.DataFrame:
    keep = np.ones(len(df), bool)
    for attr, op, *vals in where:
        if attr not in df.columns:
            continue
        col = df[attr].to_numpy()
        v = _lower(vals[0], lower)
        if op == "==":
            keep &= col == v
        elif op == "!=":
            keep &= col != v
        elif op == "<":
            keep &= col < v
        elif op == "<=":
            keep &= col <= v
        elif op == ">":
            keep &= col > v
        elif op == ">=":
            keep &= col >= v
        elif op == "range":
            keep &= (col >= v) & (col <= _lower(vals[1], lower))
        elif op == "in":
            keep &= np.isin(col, list(v))
        else:
            raise ValueError(op)
    return df[keep]


def _pattern(raw: dict, match: dict, where: list, lower: bool) -> pd.DataFrame:
    g = raw["graphs"][match["graph"]]
    rel = None
    for i, (sv, sl, _el, dv, dl) in enumerate(match["hops"]):
        e = _filter(_frame(g["edges"], f"e{i}", lower), where, lower)
        e = e.rename(columns={f"e{i}.svid": f"{sv}.#", f"e{i}.tvid": f"{dv}.#"})
        for var, label in ((sv, sl), (dv, dl)):
            if rel is not None and f"{var}.#" in rel.columns:
                continue
            vt = g["vertices"][label]
            v = _frame(vt, var, lower)
            v[f"{var}.#"] = np.arange(len(next(iter(vt.values()))))
            e = e.merge(_filter(v, where, lower), on=f"{var}.#")
        rel = e if rel is None else rel.merge(e, on=f"{sv}.#")
    return rel


def relation(raw: dict, spec: dict, lower: bool = False) -> list[np.ndarray]:
    """The answer to a bound GCDI spec: one array per selected attribute."""
    where = [list(w) for w in spec.get("where", ())]
    parts: list[tuple[set, pd.DataFrame]] = []
    for name in spec.get("from", ()):
        cols = {k: v for k, v in raw["tables"][name].items()}
        parts.append(({name}, _filter(_frame(cols, name, lower), where,
                                      lower)))
    if spec.get("match"):
        m = spec["match"]
        names = {h[0] for h in m["hops"]} | {h[3] for h in m["hops"]}
        names |= {f"e{i}" for i in range(len(m["hops"]))}
        parts.append((names, _pattern(raw, m, where, lower)))

    def owner(attr: str) -> int:
        coll = attr.split(".", 1)[0]
        return next(i for i, (n, _) in enumerate(parts) if coll in n)

    for left, right in spec.get("joins", ()):
        i, j = owner(left), owner(right)
        if i == j:
            df = parts[i][1]
            parts[i] = (parts[i][0], df[df[left].to_numpy()
                                        == df[right].to_numpy()])
            continue
        merged = parts[i][1].merge(parts[j][1], left_on=left, right_on=right)
        parts[i] = (parts[i][0] | parts[j][0], merged)
        del parts[j]
    if len(parts) != 1:
        raise ValueError("the reference evaluates connected queries only")
    df = parts[0][1]
    return [df[a].to_numpy() for a in spec["select"]]


def distinct(cols: list[np.ndarray]) -> list[np.ndarray]:
    """The answer with each repeated row kept once."""
    if not cols or not len(cols[0]):
        return cols
    keep = pd.DataFrame({i: c for i, c in enumerate(cols)}).drop_duplicates()
    return [keep[i].to_numpy() for i in range(len(cols))]


def _rows(cols: list[np.ndarray]) -> np.ndarray:
    if not cols:
        return np.zeros((0, 0))
    a = np.column_stack([np.asarray(c, np.float64) for c in cols])
    return a[np.lexsort(a.T[::-1])] if len(a) else a


def rows_off(got: list[np.ndarray], want: list[np.ndarray]) -> int:
    """Rows in one bag and not the other (the size of the symmetric
    difference of the two row multisets)."""
    a, b = _rows(got), _rows(want)
    if a.shape == b.shape and np.array_equal(a, b):
        return 0
    count: dict = {}
    for r in map(tuple, a):
        count[r] = count.get(r, 0) + 1
    for r in map(tuple, b):
        count[r] = count.get(r, 0) - 1
    return int(sum(abs(v) for v in count.values()))


# ---------------------------------------------------------------------------
# GCDA
# ---------------------------------------------------------------------------


def matrix(raw: dict, spec: dict, inp: list) -> np.ndarray:
    """The matrix one analytics input builds from the query's answer."""
    q = spec["query"]
    if inp[0] == "random":
        _, group, value, width = inp
        cols = relation(raw, {**q, "select": [group, value]})
        ids, row = np.unique(cols[0], return_inverse=True)
        x = np.zeros((len(ids), int(width)))
        ok = (cols[1] >= 0) & (cols[1] < width)
        x[row[ok], cols[1][ok].astype(np.int64)] = 1.0
        return x
    if inp[0] == "const":
        return np.asarray(inp[1], np.float64)
    if inp[0] == "rel2matrix":
        cols = relation(raw, {**q, "select": list(inp[1])})
        return np.column_stack([np.asarray(c, np.float32) for c in cols]
                               ).astype(np.float64)
    raise ValueError(inp[0])


def gram_rows(x: np.ndarray, rows: np.ndarray, lower: bool = False):
    if lower:
        x = _bf(x)
        return _bf(x[rows] @ x.T)
    return x[rows] @ x.T


def cosine_rows(x: np.ndarray, rows: np.ndarray, lower: bool = False):
    if lower:
        x = _bf(x)
        inv = _bf(1.0 / _bf(np.sqrt(_bf((x * x).sum(1)))))
        return _bf(_bf(_bf(x[rows] @ x.T) * inv[rows, None]) * inv[None, :])
    norm = np.sqrt((x * x).sum(1))
    return (x[rows] @ x.T) / np.maximum(norm[rows, None] * norm[None, :],
                                         1e-300)


def regression(x: np.ndarray, y: np.ndarray, iters: int, lr: float,
               l2: float, lower: bool = False) -> np.ndarray:
    r = _bf if lower else (lambda a: a)
    x, y = r(x), r(y.reshape(-1))
    w = np.zeros(x.shape[1])
    for _ in range(iters):
        p = r(1.0 / (1.0 + np.exp(-r(x @ w))))
        g = r(r(x.T @ r(p - y)) / len(y))
        w = r(w - lr * r(g + l2 * w))
    return w


def rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """max |got - want| over max |want| (over 1 where the reference is all
    zero)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    scale = float(np.max(np.abs(want))) if want.size else 0.0
    return float(np.max(np.abs(got - want), initial=0.0) / (scale or 1.0))
