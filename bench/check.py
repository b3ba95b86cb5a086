"""The comparison that decides ``correct``.

After the window has closed, the answers the timed path produced (or a
sample of them drawn from the seed) are compared with the configuration's
plain reference, the module the caller passes as ``ref``
(``bench/reference.py`` unless the configuration names another). Each
number compared has its limit in ``bench/limits/<number>.json``, with the
readings it was set from.

GCDI (``kind: query``): ``wrong_rows``, the rows by which the sampled
answers and the reference's differ as bags, summed; ``failed_requests``,
the requests that raised. GCDIA (``kind: analyze``): ``mm_err`` and
``sim_err`` over the sampled rows of each kept MULTIPLY and SIMILARITY
output, and ``reg_err`` over each REGRESSION's weights, each as
max |answer - reference| / max |reference|, the worst kept answer; and
``failed_requests``.

``numbers(..., control=True)`` puts the control in the program's place:
for GCDI the reference's answer with repeated rows dropped (the bag
guarantee broken: no GCDI answer of the mixes holds a float, so a float32
reference reads the same rows), for GCDIA the reference computed in
bfloat16.
"""
from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
NUMBERS = {"query": ("wrong_rows", "failed_requests"),
           "analyze": ("mm_err", "sim_err", "reg_err", "failed_requests")}


def limit(name: str) -> float:
    with open(os.path.join(HERE, "limits", f"{name}.json")) as f:
        return float(json.load(f)["limit"])


def numbers(kind: str, kept: list, raw: dict, mix: dict, failed: int, *,
            ref, control: bool = False) -> dict:
    """``kept`` holds ``(entry, answer, rows)``: for a GCDI request the
    selected columns, for a MULTIPLY/SIMILARITY task the sampled output rows
    and their indices, for a REGRESSION the weights. ``ref`` is the
    reference module, with ``bench/reference.py``'s functions."""
    out = {"failed_requests": float(failed)}
    if kind == "query":
        want: dict = {}
        low: dict = {}
        off = 0
        for entry, cols, _ in kept:
            key = (entry.template, entry.index)
            if key not in want:
                want[key] = ref.relation(raw, entry.spec)
            if control:
                if key not in low:
                    low[key] = ref.distinct(want[key])
                cols = low[key]
            off += ref.rows_off(cols, want[key])
        out["wrong_rows"] = float(off)
        return out

    mats: dict = {}
    fits: dict = {}         # (key, control) -> reference weights
    errs = {"MULTIPLY": [0.0], "SIMILARITY": [0.0], "REGRESSION": [0.0]}
    reg = mix.get("regression", {})
    for entry, got, rows in kept:
        a = entry.spec["analytics"]
        key = (entry.template, entry.index)
        if key not in mats:
            mats[key] = [ref.matrix(raw, entry.spec, inp)
                         for inp in a["inputs"]]
        x = mats[key][0]
        if a["op"] == "REGRESSION":
            args = (x, mats[key][1], int(mix["iters"]), float(reg["lr"]),
                    float(reg["l2"]))
            for low in {False, control}:
                if (key, low) not in fits:
                    fits[key, low] = ref.regression(*args, lower=low)
            want = fits[key, False]
            if control:
                got = fits[key, True]
        else:
            fn = (ref.gram_rows if a["op"] == "MULTIPLY"
                  else ref.cosine_rows)
            if got.shape[1] != x.shape[0]:      # the program's n is wrong
                errs[a["op"]].append(float("inf"))
                continue
            want = fn(x, rows)
            if control:
                got = fn(x, rows, lower=True)
        errs[a["op"]].append(ref.rel_err(got, want))
    out["mm_err"] = max(errs["MULTIPLY"])
    out["sim_err"] = max(errs["SIMILARITY"])
    out["reg_err"] = max(errs["REGRESSION"])
    return out


def verdict(kind: str, nums: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value": v, "limit": l}})`` in a fixed order."""
    table = {n: {"value": nums[n], "limit": limit(n)} for n in NUMBERS[kind]}
    ok = all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in table.values())
    return bool(ok), table
