"""Reduce a profiler trace (``*.xplane.pb``) to what the metrics read.

``load(path, prefix)`` reads the trace with ``jax.profiler.ProfileData`` and
returns a :class:`Trace`: the device operations of each device plane
(``/device:TPU:<i>``, line ``XLA Ops``), the programs they ran in (line
``XLA Modules``), and the host annotations whose names start with
``prefix`` (the harness wraps every request in one). The window is the span
from the first such annotation's start to the last one's end.

Everything else works on plain ``(name, start_ns, end_ns)`` tuples, so the
tests can hand-build them.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Trace:
    ops: dict          # device plane name -> [(name, start_ns, end_ns)]
    modules: dict      # device plane name -> [(name, start_ns, end_ns)]
    annotations: list  # [(name, start_ns, end_ns)] sorted by start
    window: tuple      # (start_ns, end_ns)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over the device
        planes that ran any."""
        planes = [v for v in self.ops.values() if v]
        if not planes:
            return 0.0
        return sum(busy_ns(v, self.window) for v in planes) * 1e-9 / len(planes)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s


def find(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def load(path: str, prefix: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops: dict = {}
    modules: dict = {}
    ann: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events]
                    (ops if line.name == OPS_LINE else modules)[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                ann += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events if e.name.startswith(prefix)]
    ann.sort(key=lambda a: a[1])
    if not ann:
        raise RuntimeError(f"no host annotation starting with {prefix!r}")
    return Trace(ops, modules, ann, (ann[0][1], max(a[2] for a in ann)))


def union(intervals, window) -> list[tuple[float, float]]:
    """Disjoint sorted intervals covering ``intervals`` clipped to
    ``window``."""
    lo, hi = window
    out: list[list[float]] = []
    for _, s, e in sorted(intervals, key=lambda x: x[1]):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(intervals, window) -> float:
    return float(sum(e - s for s, e in union(intervals, window)))


def gaps(intervals, window) -> list[tuple[float, float]]:
    """The idle intervals of ``window`` between busy ones."""
    out, t = [], window[0]
    for s, e in union(intervals, window):
        if s > t:
            out.append((t, s))
        t = e
    if window[1] > t:
        out.append((t, window[1]))
    return out


def open_at(annotations, t: float) -> str:
    """Name of the annotation open at ``t`` (the innermost, i.e. the latest
    started), or ``"between requests"``."""
    name = "between requests"
    for a, s, e in annotations:
        if s > t:
            break
        if s <= t < e:
            name = a
    return name


def named_ops(ops, modules) -> list[tuple[str, float, float]]:
    """Each operation named ``<program>/<instruction>``: the module it ran
    in (without its fingerprint) and the instruction's name (the HLO text
    before `` = ``)."""
    mods = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    out = []
    for name, s, e in ops:
        j = bisect.bisect_right(starts, s) - 1
        prog = (mods[j][0].split("(")[0]
                if j >= 0 and s < mods[j][2] else "?")
        out.append((f"{prog}/{name.split(' = ')[0]}", s, e))
    return out


def top_ops(intervals, window, k: int = 10) -> list[list]:
    """The ``k`` operation names with the most device seconds."""
    tot: dict = {}
    lo, hi = window
    for name, s, e in intervals:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            tot[name] = tot.get(name, 0.0) + d * 1e-9
    return [[n, v] for n, v in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def top_gaps(intervals, window, annotations, k: int = 10) -> list[list]:
    """The ``k`` longest idle gaps, each named by the annotation open at its
    start."""
    gs = sorted(gaps(intervals, window), key=lambda g: g[0] - g[1])[:k]
    return [[open_at(annotations, s), (e - s) * 1e-9] for s, e in gs]


def module_seconds(trace: Trace, names) -> float:
    """Device seconds spent in programs whose name starts with one of
    ``names``, summed over every device plane."""
    tot = 0.0
    for evs in trace.modules.values():
        tot += sum(min(e, trace.window[1]) - max(s, trace.window[0])
                   for n, s, e in evs
                   if n.startswith(tuple(names)) and e > trace.window[0]
                   and s < trace.window[1])
    return tot * 1e-9
