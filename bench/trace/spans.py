"""What the engine's own spans add to a traced run.

With a telemetry session attached, the engine enters a profiler annotation
named ``gredo:<span>`` for each span of a request (the root ``query`` or
``analyze``, the phases ``plan`` and ``finish``, each operator, and the
phases of ``DeviceMatchPattern`` and of the matrix builders), so
``reduce.load(path, PREFIX)`` returns them as host events on the clock of
the device's operations. These functions work on the engine's
``QueryTrace`` and on plain ``(name, start_ns, end_ns)`` tuples, so the
tests can hand-build them.
"""
from __future__ import annotations

import bisect

from bench.trace import reduce

PREFIX = "gredo:"


def span_seconds(trace) -> dict:
    """Seconds of one request by span or phase name (a name that occurs
    more than once is summed), from the engine's ``QueryTrace``; the root
    is left out."""
    out: dict = {}
    for s in trace.spans[1:]:
        out[s.name] = out.get(s.name, 0.0) + s.dur
    return out


def timeline(intervals) -> list[tuple]:
    """The innermost open interval over time: ``(start, end, name)``
    pieces in order, covering the times at which some interval is open.
    The intervals nest, as the spans of one thread do."""
    out: list = []
    stack: list = []
    t = 0.0

    def emit(a, b, name):
        if b > a:
            out.append((a, b, name))

    for name, s, e in sorted(intervals, key=lambda a: (a[1], -a[2])):
        while stack and stack[-1][2] <= s:
            top = stack.pop()
            emit(t, top[2], top[0])
            t = top[2]
        if stack:
            emit(t, s, stack[-1][0])
        stack.append((name, s, e))
        t = s
    while stack:
        top = stack.pop()
        emit(t, top[2], top[0])
        t = top[2]
    return out


def idle_by_span(ops, window, spans, annotations, k: int = 10) -> list[list]:
    """The window's idle seconds by the innermost engine span open at each
    idle moment; where none is open, by the request annotation open there,
    else ``"between requests"``. A gap that runs across several spans is
    split at their bounds. The ``k`` names with the most idle time."""
    pieces = timeline(list(spans) + list(annotations))
    starts = [p[0] for p in pieces]
    tot: dict = {}
    for gs, ge in reduce.gaps(ops, window):
        covered = 0.0
        j = max(bisect.bisect_right(starts, gs) - 1, 0)
        while j < len(pieces) and pieces[j][0] < ge:
            d = min(ge, pieces[j][1]) - max(gs, pieces[j][0])
            if d > 0:
                tot[pieces[j][2]] = tot.get(pieces[j][2], 0.0) + d * 1e-9
                covered += d
            j += 1
        if ge - gs > covered:
            tot["between requests"] = (tot.get("between requests", 0.0)
                                       + (ge - gs - covered) * 1e-9)
    return [[n, v] for n, v in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def busy_within(ops, windows) -> list[float]:
    """Device-busy seconds (the union of ``ops``) inside each of the
    ``(start_ns, end_ns)`` windows."""
    if not windows:
        return []
    lo = min(s for s, _ in windows)
    hi = max(e for _, e in windows)
    u = reduce.union(ops, (lo, hi))
    starts = [s for s, _ in u]
    out = []
    for s, e in windows:
        j = max(bisect.bisect_right(starts, s) - 1, 0)
        ns = 0.0
        while j < len(u) and u[j][0] < e:
            ns += max(0.0, min(e, u[j][1]) - max(s, u[j][0]))
            j += 1
        out.append(ns * 1e-9)
    return out
