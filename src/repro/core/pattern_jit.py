"""Device-resident (jit-able) pattern matching: fixed-capacity padded
frontiers with overflow-detect-and-retry (the DESIGN §2 static-shape
adaptation — the TPU analogue of buffer-pool spill).

The host engine (core.pattern) is the system of record; this module is the
accelerator glue. Two device flavors share the predicate-lowering code:

  * ``DevicePatternMatcher`` — the per-hop jit path: one ``expand_frontier``
    dispatch per hop with a host overflow sync between hops, dense
    predicate tables built by full column scans. Compiled once per
    (capacity, graph-shape) and reused across queries.
  * ``device_match(flavor="chain")`` — the whole-chain path
    (:mod:`repro.kernels.traversal`): the whole chain is one jit'd XLA
    program on every backend, predicate tables are built through zone-map
    skip-scans (predicate-dead chunks are never read) and the
    chunk-survivor bitmap rides into each hop as a filter; the host syncs
    once at the end of the chain.

Both flavors are epoch-stamped against the graph: a snapshot taken before a
write burst refuses to serve (pending deltas) or re-syncs (compacted) before
the next match — mirroring the ``IndexManager`` refresh discipline.

The planner's cardinality estimates choose the initial capacity; on overflow
the wrapper doubles and re-runs (amortized O(1) recompiles thanks to
power-of-two capacities). ``COUNTERS``/``metrics()`` surface recompiles,
per-capacity retries and kernel launch counts to the telemetry registry.
"""
from __future__ import annotations

import dataclasses
import functools
import weakref
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.traversal import ops as kernel_ops

from . import cost as cost_mod
from . import pattern as pattern_mod
from .storage import Graph, Table


class StaleSnapshotError(ValueError):
    """The device CSR snapshot no longer matches the graph and cannot be
    refreshed (pending deltas — compact first)."""


@dataclasses.dataclass
class _Counters:
    matches: int = 0            # device_match invocations
    recompiles: int = 0         # jit-path capacity doublings
    retries: int = 0            # chain-path capacity doublings
    refreshes: int = 0          # snapshot re-syncs after epoch bumps
    stale_rejects: int = 0      # refused matches on pending deltas
    retry_caps: dict = dataclasses.field(default_factory=dict)

    def bump_retry(self, cap: int) -> None:
        self.retry_caps[cap] = self.retry_caps.get(cap, 0) + 1

    def metrics(self) -> dict:
        out = {"matches": self.matches, "recompiles": self.recompiles,
               "retries": self.retries, "refreshes": self.refreshes,
               "stale_rejects": self.stale_rejects}
        for cap, k in sorted(self.retry_caps.items()):
            out[f"retries.cap_{cap}"] = k
        return out


COUNTERS = _Counters()


def metrics() -> dict:
    """Telemetry registry source: matcher counters + chain-program launch
    counters, one flat namespace (cumulative; the engine's per-query view
    comes from registry snapshot deltas)."""
    out = COUNTERS.metrics()
    for k, v in kernel_ops.COUNTERS.metrics().items():
        out[f"kernel.{k}"] = v
    return out


@functools.partial(jax.jit, static_argnames=("capacity",))
def expand_frontier(row_ptr: jax.Array, col_idx: jax.Array,
                    edge_id: jax.Array, frontier: jax.Array,
                    frontier_mask: jax.Array, member: jax.Array,
                    edge_mask: jax.Array, *, capacity: int):
    """One hybrid-traversal hop on device.

    frontier: (C,) padded nids; member/edge_mask: boolean tables over nids /
    edge tids (the pushed predicates). Returns (src_slot, dst, eid, mask,
    overflowed): all (capacity,) padded outputs where ``src_slot`` indexes
    into the INPUT frontier (so callers can join path prefixes).
    """
    C = frontier.shape[0]
    deg = jnp.where(frontier_mask,
                    row_ptr[frontier + 1] - row_ptr[frontier], 0)
    out_off = jnp.cumsum(deg) - deg                     # exclusive prefix sum
    total = jnp.sum(deg)
    overflowed = total > capacity

    # slot i of the output belongs to the frontier entry whose out_off range
    # covers i: searchsorted over the (sorted) offsets
    slots = jnp.arange(capacity)
    src_slot = jnp.clip(
        jnp.searchsorted(out_off, slots, side="right") - 1, 0, C - 1)
    within = slots - out_off[src_slot]
    valid = slots < jnp.minimum(total, capacity)
    src_nid = frontier[src_slot]
    pos = jnp.clip(row_ptr[src_nid] + within, 0, col_idx.shape[0] - 1)
    dst = col_idx[pos].astype(jnp.int32)
    eid = edge_id[pos].astype(jnp.int32)
    valid &= member[jnp.clip(dst, 0, member.shape[0] - 1)]
    valid &= edge_mask[jnp.clip(eid, 0, edge_mask.shape[0] - 1)]
    return src_slot, dst, eid, valid, overflowed


class DevicePatternMatcher:
    """Chain-pattern matching fully on device with capacity retry. The CSR
    snapshot is epoch-stamped: ``refresh()`` re-syncs after a compaction
    and refuses (``StaleSnapshotError``) while deltas are pending, so the
    matcher can be cached on the graph and reused across write bursts."""

    def __init__(self, g: Graph, initial_capacity: int = 1 << 12,
                 max_capacity: int = 1 << 26):
        self.g = g
        self.initial_capacity = initial_capacity
        self.max_capacity = max_capacity
        self.recompiles = 0
        self.refreshes = 0
        self.last_capacity = 0
        self._snapshot()

    def _snapshot(self) -> None:
        g = self.g
        if g.delta.has_pending():
            # the device snapshot reads base CSRs only; compacting here
            # would silently renumber edge tids under the caller's feet
            COUNTERS.stale_rejects += 1
            raise StaleSnapshotError(
                f"graph {g.name!r} has pending delta writes; call "
                "g.compact() before building a DevicePatternMatcher")
        self.row_ptr = jnp.asarray(g.fwd.row_ptr)
        self.col_idx = jnp.asarray(g.fwd.col_idx)
        self.edge_id = jnp.asarray(g.fwd.edge_id)
        self.row_ptr_r = jnp.asarray(g.rev.row_ptr)
        self.col_idx_r = jnp.asarray(g.rev.col_idx)
        self.edge_id_r = jnp.asarray(g.rev.edge_id)
        self.epoch = g.epoch

    def refresh(self) -> None:
        """Refuse-or-refresh before serving: no-op while the graph epoch is
        unchanged; re-snapshot after a compaction settled the writes; raise
        while deltas are pending (mirrors ``ColumnIndex.refresh``)."""
        if self.g.epoch == self.epoch:
            return
        self._snapshot()
        self.refreshes += 1
        COUNTERS.refreshes += 1

    def csr(self, reverse: bool = False):
        if reverse:
            return self.row_ptr_r, self.col_idx_r, self.edge_id_r
        return self.row_ptr, self.col_idx, self.edge_id

    def match_chain(self, start_nids: np.ndarray,
                    vertex_members: list,
                    edge_masks: list, reverse: bool = False,
                    initial_capacity: Optional[int] = None):
        """vertex_members[h]: bool table over nids for hop-h target (None =
        label-unconstrained); edge_masks[h] likewise over edge tids.
        Returns (vcols, ecols): per-hop nid columns and per-hop edge-tid
        columns of the matched paths (compacted, host arrays).
        """
        self.refresh()
        cap = max(initial_capacity or self.initial_capacity,
                  1 << int(np.ceil(np.log2(max(len(start_nids), 1)))))

        while True:
            self.last_capacity = cap
            cols, ecols, ok = self._run(start_nids, vertex_members,
                                        edge_masks, cap, reverse)
            if ok:
                return cols, ecols
            if cap >= self.max_capacity:
                raise RuntimeError(f"pattern frontier exceeded max capacity "
                                   f"{self.max_capacity}")
            cap *= 2
            self.recompiles += 1
            COUNTERS.recompiles += 1
            COUNTERS.bump_retry(cap)

    def _run(self, start_nids, vertex_members, edge_masks, cap, reverse):
        n, m = self.g.n_vertices, self.g.edges.nrows
        ones_v = jnp.ones((n,), bool)
        ones_e = jnp.ones((max(m, 1),), bool)
        row_ptr, col_idx, edge_id = self.csr(reverse)

        C0 = len(start_nids)
        frontier = jnp.zeros((cap,), jnp.int32).at[:C0].set(
            jnp.asarray(start_nids, jnp.int32))
        fmask = jnp.zeros((cap,), bool).at[:C0].set(True)
        path_cols = [frontier]
        path_ecols: list = []
        path_mask = fmask

        for vm, em in zip(vertex_members, edge_masks):
            member = ones_v if vm is None else jnp.asarray(vm)
            emask = ones_e if em is None else jnp.asarray(em)
            src_slot, dst, eid, valid, overflow = expand_frontier(
                row_ptr, col_idx, edge_id,
                path_cols[-1], path_mask, member, emask, capacity=cap)
            if bool(overflow):          # per-hop host sync
                return None, None, False
            # re-join path prefixes through src_slot
            path_cols = [c[src_slot] for c in path_cols]
            path_ecols = [c[src_slot] for c in path_ecols]
            path_cols.append(dst)
            path_ecols.append(eid)
            path_mask = valid & path_mask[src_slot]

        # compact on host (final materialization = the graph-relation)
        keep = np.asarray(path_mask)
        return ([np.asarray(c)[keep] for c in path_cols],
                [np.asarray(c)[keep] for c in path_ecols], True)


def get_matcher(g: Graph, initial_capacity: int = 1 << 12
                ) -> DevicePatternMatcher:
    """The graph's cached matcher (holds the device CSR snapshot across
    queries); built lazily, kept fresh via ``refresh()``."""
    m = getattr(g, "_device_matcher", None)
    if m is None or m.g is not g:
        m = DevicePatternMatcher(g, initial_capacity)
        g._device_matcher = m
    return m


# ---------------------------------------------------------------------------
# Plan lowering: PatternPlan -> device tables (shared by both flavors)
# ---------------------------------------------------------------------------


def prepare_chain(g: Graph, pplan, zone: bool = True) -> Optional[dict]:
    """Lower a chain PatternPlan to the device-table form: start nids, a
    per-hop member table over the nid space (pushed vertex predicates and
    the multi-label constraint folded in), per-hop edge-predicate tables
    over the tid space, and — with ``zone=True`` — the zone-map chunk
    survivor bitmap per hop (built via ``masked_eval`` skip-scans, so
    predicate-dead chunks are never read even while building the table).
    Uses the same ``pattern._candidate_set`` logic as the host matcher, so
    index-seeded start frontiers carry over. Returns None for non-chain
    patterns (the host matcher keeps those)."""
    pattern = pplan.pattern
    if not pattern.is_chain or not pattern.edges:
        return None
    chain_vars = [pattern.vertices[0].var] + [e.dst for e in pattern.edges]
    edge_vars = [e.var for e in pattern.edges]
    hop_vars = chain_vars[::-1] if pplan.reverse else chain_vars
    hop_edges = edge_vars[::-1] if pplan.reverse else edge_vars

    cand = {v: pattern_mod._candidate_set(g, pattern, v,
                                          pplan.pushed.get(v, []))
            for v in chain_vars}

    members = [pattern_mod.nid_mask(g, pattern.vertex(v).label, cand[v],
                                    label_only=True) for v in hop_vars[1:]]

    im = getattr(g, "_index_manager", None)
    chunk = 0
    edge_preds: list = []
    chunk_alives: list = []
    for evar in hop_edges:
        preds = pplan.pushed.get(evar, [])
        if not preds:
            edge_preds.append(None)
            chunk_alives.append(None)
            continue
        mask: Optional[np.ndarray] = None
        alive: Optional[np.ndarray] = None
        for p in preds:
            pm = None
            ch = None
            idx = im.get(g.name, p.column) if (zone and im is not None) \
                else None
            if idx is not None:
                pm = idx.zone_mask(p)       # skip-scan: dead chunks unread
                if pm is not None and idx.zones is not None:
                    ch = idx.zones.candidate_chunks(p)
                    chunk = idx.zones.chunk
                    kernel_ops.COUNTERS.chunks_alive += int(ch.sum())
                    kernel_ops.COUNTERS.chunks_total += len(ch)
            if pm is None:
                pm = np.asarray(g.edges.eval_predicate(p))
            mask = pm if mask is None else mask & pm
            if ch is not None:
                alive = ch if alive is None else alive & ch
        edge_preds.append(mask)
        chunk_alives.append(alive)

    v0 = hop_vars[0]
    start_nids = pattern_mod.start_nids_of(g, pattern.vertex(v0).label,
                                           cand[v0])

    from .cost import ZONE_CHUNK
    return {"start_nids": start_nids, "members": members,
            "edge_preds": edge_preds, "chunk_alives": chunk_alives,
            "reverse": bool(pplan.reverse),
            "chunk": chunk or ZONE_CHUNK,
            "chain_vars": chain_vars, "edge_vars": edge_vars}


def _round_capacity(n: int) -> int:
    return 1 << max(7, int(np.ceil(np.log2(max(n, 1)))))


def _estimate_capacity(g: Graph, prep: dict) -> int:
    """Pick the launch capacity from the lowered plan itself: walk the hops
    with the label-aware fan-out and the *actual* predicate-table survivor
    fractions, and size for the peak pre-predicate candidate count (the
    kernel must hold every candidate before compaction). Headroom 2x; the
    overflow-retry loop still backstops underestimates, this just keeps the
    steady state at one launch."""
    fan = g.hop_expansion(reverse=prep["reverse"])
    fr = float(len(prep["start_nids"]))
    peak = max(fr, 64.0)
    for mem, ep in zip(prep["members"], prep["edge_preds"]):
        cand = fr * fan
        peak = max(peak, cand)
        s_e = float(np.mean(ep)) if ep is not None else 1.0
        s_m = float(np.mean(mem)) if mem is not None else 1.0
        fr = cand * s_e * s_m
    return _round_capacity(int(2.0 * peak))


def device_match(g: Graph, pplan, *, flavor: str = "chain",
                 initial_capacity: Optional[int] = None,
                 max_capacity: int = 1 << 24,
                 use_kernel: bool = False, trace=None):
    """Execute a chain PatternPlan on the device path and build the same
    graph-relation Table as ``pattern.match`` (vertex columns hold vids,
    edge columns hold tids; deferred predicates applied). Returns
    (rel, counts): the run's ``flavor``, ``hops``, final ``capacity`` and
    chain ``launches``, for the operator's span.
    ``flavor``: "chain" (one program per chain, zone-filtered tables) or
    "jit" (per-hop ``DevicePatternMatcher``). ``use_kernel=True`` runs the
    chain's hops through the Pallas kernel (tests only; see
    ``repro.kernels.traversal.ops``). With a ``trace`` (the operator's
    ``QueryTrace``), the chain flavor records its phases: ``lower`` (the
    snapshot refresh, the plan lowered to tables, the capacity), ``stage``
    (tables and frontier to the device), ``launch`` (the program through
    its overflow sync; an overflow repeats ``stage`` and ``launch``) and
    ``readback`` (the trimmed columns to the host and the relation).
    Raises ``StaleSnapshotError`` on pending deltas; callers degrade to the
    host matcher."""
    if flavor != "chain":
        trace = None
    COUNTERS.matches += 1
    if trace is not None:
        trace.phase("lower")
    matcher = get_matcher(g)
    matcher.refresh()
    prep = prepare_chain(g, pplan, zone=(flavor == "chain"))
    if prep is None:
        raise ValueError(f"pattern {pplan.pattern.canonical()!r} is not a "
                         "chain; device path unavailable")
    pattern = pplan.pattern
    start = prep["start_nids"]
    hops = len(prep["edge_vars"])
    launches = 1

    if flavor == "jit":
        vcols, ecols = matcher.match_chain(
            start, prep["members"], prep["edge_preds"],
            reverse=prep["reverse"],
            initial_capacity=initial_capacity or _estimate_capacity(g, prep))
        cap = matcher.last_capacity
    else:
        row_ptr, col_idx, edge_id = matcher.csr(prep["reverse"])
        cap = initial_capacity or _estimate_capacity(g, prep)
        cap = max(cap, _round_capacity(len(start)))
        while True:
            if trace is not None:
                trace.phase("stage")
            staged = kernel_ops.stage_chain(
                row_ptr, col_idx, edge_id, g.n_vertices, g.edges.nrows,
                start, prep["members"], prep["edge_preds"],
                prep["chunk_alives"], capacity=cap, chunk=prep["chunk"])
            if trace is not None:
                trace.phase("launch")
            launched = kernel_ops.launch_chain(
                staged, capacity=cap, chunk=prep["chunk"],
                use_kernel=use_kernel)
            if launched is not None:
                break
            if cap >= max_capacity:
                raise RuntimeError(f"pattern frontier exceeded max capacity "
                                   f"{max_capacity}")
            cap *= 2
            launches += 1
            COUNTERS.retries += 1
            COUNTERS.bump_retry(cap)
        if trace is not None:
            trace.phase("readback")
        vcols, ecols = kernel_ops.read_chain(launched)

    if prep["reverse"]:
        vcols = vcols[::-1]
        ecols = ecols[::-1]
    cols: dict[str, np.ndarray] = {}
    for var, col in zip(prep["chain_vars"], vcols):
        cols[var] = g.vids_of(col)
    for evar, col in zip(prep["edge_vars"], ecols):
        cols[evar] = col
    rel = Table(f"match:{pattern.graph}", cols)
    rel = pattern_mod.apply_deferred(g, pattern, rel, pplan.deferred)
    if trace is not None:
        trace.phase(None)
    return rel, {"flavor": flavor, "hops": hops, "capacity": cap,
                 "launches": launches}


# ---------------------------------------------------------------------------
# Reach: one-hop shortest-distance search on the device
# ---------------------------------------------------------------------------

# Graphs that hold a dense adjacency on the device (the gauge reads them).
_ADJACENCY_GRAPHS: "weakref.WeakSet[Graph]" = weakref.WeakSet()
DEFAULT_DEVICE_BYTES = 16 * 10**9   # one TPU v5e, where the backend reports none


@dataclasses.dataclass
class _ReachCounters:
    launches: int = 0           # reach programs run (one per start vertex)
    levels: int = 0             # BFS levels they ran
    reached: int = 0            # vertices they reached, starts not counted

    def metrics(self) -> dict:
        return {"launches": self.launches, "levels": self.levels,
                "reached": self.reached,
                "adjacency_bytes": sum(
                    held[1].nbytes for g in list(_ADJACENCY_GRAPHS)
                    if (held := getattr(g, "_dense_adjacency", None)))}


REACH = _ReachCounters()


def reach_metrics() -> dict:
    """Telemetry registry source ``reach``: cumulative launch, level and
    reached-vertex counters, and the gauge ``adjacency_bytes``, the bytes of
    the dense adjacencies held on the device."""
    return REACH.metrics()


@functools.lru_cache(maxsize=1)
def device_memory_bytes() -> int:
    """The default device's memory as its backend reports it
    (``bytes_limit``), else ``DEFAULT_DEVICE_BYTES``; read once."""
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("bytes_limit") or DEFAULT_DEVICE_BYTES)


def _symmetric(g: Graph) -> bool:
    """Whether the graph's edge multiset equals its transpose (an
    undirected graph stored in both directions): the forward and reverse
    CSRs then hold the same (row, col) pairs."""
    keys = []
    for csr in (g.fwd, g.rev):
        rows = np.repeat(np.arange(len(csr.row_ptr) - 1, dtype=np.int64),
                         np.diff(csr.row_ptr))
        keys.append(np.sort(rows * g.n_vertices + csr.col_idx))
    return bool(np.array_equal(keys[0], keys[1]))


def get_adjacency(g: Graph, matcher: DevicePatternMatcher
                  ) -> tuple[jax.Array, bool]:
    """The graph's dense int8 adjacency on the device and whether it is
    symmetric, built from the (refreshed) matcher's CSR snapshot at the
    graph's first reach and again after a compaction changed the
    snapshot."""
    held = getattr(g, "_dense_adjacency", None)
    if held is None or held[0] != matcher.epoch:
        g._dense_adjacency = None       # free the old one before the build
        adj = kernel_ops.dense_adjacency(
            matcher.row_ptr, matcher.col_idx,
            n_pad=cost_mod.dense_side(g.n_vertices))
        g._dense_adjacency = held = (matcher.epoch, adj, _symmetric(g))
        _ADJACENCY_GRAPHS.add(g)
    return held[1], held[2]


def device_reach(g: Graph, pplan, *, trace=None):
    """Execute a reach PatternPlan on the device and build the same
    graph-relation as ``pattern.reach``. Returns (rel, counts): the run's
    ``launches``, ``levels`` (passes over the adjacency) and ``reached``,
    for the operator's span. Where the end side is one vertex and the graph
    is undirected, each launch searches from both ends of the pair at once,
    which reaches a distance d in ceil(d/2) passes instead of d. With a
    ``trace``, records the phases ``lower`` (the snapshot refresh and the
    plan lowered to start nids and the end mask), ``stage`` (the dense
    adjacency, built at the graph's first reach, and the mask to the
    device), ``launch`` (one program per start vertex, through its sync) and
    ``readback`` (distances to the host and the relation). Raises
    ``StaleSnapshotError`` on pending deltas; callers degrade to the host."""
    if trace is not None:
        trace.phase("lower")
    matcher = get_matcher(g)
    matcher.refresh()
    st = pattern_mod.prepare_reach(g, pplan)
    if trace is not None:
        trace.phase("stage")
    adj, symmetric = get_adjacency(g, matcher)
    target = None
    if symmetric and st.member is not None:
        ends = np.flatnonzero(st.member)
        target = int(ends[0]) if len(ends) == 1 else None
    member = kernel_ops.stage_reach(st.member, g.n_vertices, adj.shape[0])
    starts, ends, dists = [], [], []
    levels = reached = 0
    for s in st.start_nids:
        if trace is not None:
            trace.phase("launch")
        out = kernel_ops.launch_reach(adj, int(s), member, st.lo, st.hi,
                                      transpose=st.reverse, target=target)
        if trace is not None:
            trace.phase("readback")
        ids, d, lv, rc = kernel_ops.read_reach(out, g.n_vertices)
        starts.append(np.full(len(ids), s, dtype=np.int64))
        ends.append(ids)
        dists.append(d)
        levels += lv
        reached += rc
    launches = len(st.start_nids)
    REACH.launches += launches
    REACH.levels += levels
    REACH.reached += reached
    rel = pattern_mod.reach_relation(g, pplan, st, starts, ends, dists)
    if trace is not None:
        trace.phase(None)
    return rel, {"launches": launches, "levels": levels, "reached": reached}
