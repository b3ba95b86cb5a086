"""95th percentile of latency over every GCDI request completed in the
window (linear interpolation between order statistics)."""
import numpy as np


def read(run):
    ms = [r["ms"] for r in run.records if r["kind"] == "query"]
    return float(np.percentile(ms, 95)) if ms else None
