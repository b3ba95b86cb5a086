"""Operations and bytes that each GCDA operator needs, from its shapes.

The counts are the algorithm's, not an implementation's: an input is read
once and an output written once, in float32, and padding is not work. So
the least time they give is a lower bound for any implementation, and a
share of the roofline computed from them cannot pass 100% unless the device
time leaves out part of the work.

``roofline_s`` turns (flops, bytes) into the least time on a device kind,
from ``peaks.json`` beside this file; a kind that is not there is an error.
"""
from __future__ import annotations

import json
import os

F32 = 4
HERE = os.path.dirname(os.path.abspath(__file__))


def multiply(m: int, k: int) -> tuple[float, float]:
    """Gram product X Xᵀ of an (m, k) matrix: m·m dot products of length k."""
    return 2.0 * m * m * k, float(F32 * (m * k + m * m))


def similarity(m: int, k: int) -> tuple[float, float]:
    """Cosine of every pair of rows of an (m, k) matrix: the Gram product,
    the m row norms, and one scale per output."""
    return 2.0 * m * m * k + 2.0 * m * k + 2.0 * m * m, float(F32 * (m * k + m * m))


def regression(m: int, k: int, iters: int) -> tuple[float, float]:
    """``iters`` steps of full-gradient logistic regression on (m, k)
    features: per step a forward mat-vec, the sigmoid and residual, and the
    gradient mat-vec. The features and labels are read once."""
    per_step = 2.0 * m * k + 4.0 * m + 2.0 * m * k + 3.0 * k
    return per_step * iters, float(F32 * (m * k + m + 2 * k))


def work(op: str, m: int, k: int, iters: int = 1) -> tuple[float, float]:
    """(flops, bytes) of one GCDA operator call by its analytics op name."""
    if op == "MULTIPLY":
        return multiply(m, k)
    if op == "SIMILARITY":
        return similarity(m, k)
    if op == "REGRESSION":
        return regression(m, k, iters)
    raise KeyError(f"no work function for {op!r}")


def peaks(kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["kinds"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; known: "
                       f"{sorted(table)}")
    return table[kind]


def roofline_s(flops: float, nbytes: float, kind: str) -> float:
    p = peaks(kind)
    return max(flops / p["flops_per_s"], nbytes / p["hbm_bytes_per_s"])
