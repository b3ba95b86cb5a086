"""Whole-chain drivers for device traversal.

``traverse_chain``/``batched_traverse`` run a whole chain pattern as ONE
jit'd program — every hop's expansion, predicate evaluation, compaction and
path re-join stays on device, and the host synchronizes once at the end
(overflow flag + final count). That is the latency contrast with the
per-hop ``DevicePatternMatcher``, which dispatches and syncs every hop.

Each hop is ``ref.fused_hop_ref``, plain XLA, on every backend: this is
the program the optimizer's ``device-chain`` access path runs. The Pallas
hop kernel (``traversal.py``) is refused by the TPU compiler — its
``(1, C)`` blocks break the 8x128 tiling rule, its scalar count output
cannot live in VMEM, and its 1-D in-kernel gathers are not supported — so
``use_kernel=True`` (the kernel under the Pallas interpreter off the TPU)
is for the equivalence tests only.

COUNTERS feed the telemetry registry through
``core.pattern_jit.metrics`` (cumulative — per-query deltas come from
registry snapshots).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..backend import on_tpu
from . import ref
from . import traversal as kern


@dataclasses.dataclass
class _Counters:
    launches: int = 0           # chain launches (one per traverse_chain call)
    hops: int = 0               # fused hops executed
    batched_queries: int = 0    # queries carried by batched launches
    chunks_alive: int = 0       # zone-map chunks surviving the prefetch filter
    chunks_total: int = 0       # zone-map chunks examined

    def metrics(self) -> dict:
        return {"launches": self.launches, "hops": self.hops,
                "batched_queries": self.batched_queries,
                "chunks_alive": self.chunks_alive,
                "chunks_total": self.chunks_total}

    def reset(self) -> None:
        self.launches = self.hops = self.batched_queries = 0
        self.chunks_alive = self.chunks_total = 0


COUNTERS = _Counters()


# ---------------------------------------------------------------------------
# Whole-chain drivers (single launch window, one end-of-chain host sync)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit,
                   static_argnames=("capacity", "chunk", "use_kernel",
                                    "interpret"))
def _chain_device(row_ptr, col_idx, edge_id, frontier, fmask, members,
                  edge_preds, chunk_alives, *, capacity: int, chunk: int,
                  use_kernel: bool, interpret: bool):
    if use_kernel:
        hop = functools.partial(kern.fused_hop, interpret=interpret)
    else:
        hop = ref.fused_hop_ref
    vcols = [frontier.astype(jnp.int32)]
    ecols: list = []
    count = jnp.sum(fmask).astype(jnp.int32)
    ovf = jnp.zeros((), bool)
    for vm, ep, ca in zip(members, edge_preds, chunk_alives):
        src, dst, eid, count, o = hop(row_ptr, col_idx, edge_id, frontier,
                                      fmask, vm, ep, ca, capacity=capacity,
                                      chunk=chunk)
        # re-join path prefixes through the compacted src slots
        vcols = [c[src] for c in vcols]
        ecols = [c[src] for c in ecols]
        vcols.append(dst)
        ecols.append(eid)
        frontier = jnp.maximum(dst, 0)
        fmask = jnp.arange(capacity, dtype=jnp.int32) < count
        ovf |= o
    return vcols, ecols, count, ovf


@functools.partial(jax.jit,
                   static_argnames=("capacity", "chunk", "use_kernel",
                                    "interpret"))
def _batched_chain_device(row_ptr, col_idx, edge_id, frontiers, fmasks,
                          members, edge_preds, chunk_alives, *, capacity: int,
                          chunk: int, use_kernel: bool, interpret: bool):
    if use_kernel:
        hop = functools.partial(kern.batched_hop, interpret=interpret)
    else:
        hop = ref.batched_hop_ref
    B = frontiers.shape[0]
    vcols = [frontiers.astype(jnp.int32)]
    ecols: list = []
    counts = jnp.sum(fmasks, axis=1).astype(jnp.int32)
    ovf = jnp.zeros((B,), bool)
    for vm, ep, ca in zip(members, edge_preds, chunk_alives):
        src, dst, eid, counts, o = hop(row_ptr, col_idx, edge_id, frontiers,
                                       fmasks, vm, ep, ca, capacity=capacity,
                                       chunk=chunk)
        vcols = [jnp.take_along_axis(c, src, axis=1) for c in vcols]
        ecols = [jnp.take_along_axis(c, src, axis=1) for c in ecols]
        vcols.append(dst)
        ecols.append(eid)
        frontiers = jnp.maximum(dst, 0)
        fmasks = (jnp.arange(capacity, dtype=jnp.int32)[None, :]
                  < counts[:, None])
        ovf |= o
    return vcols, ecols, counts, ovf


def _device_tables(n_vertices, n_edges, chunk, members, edge_preds,
                   chunk_alives):
    """Normalize optional host tables to device arrays (None = all-true)."""
    m = max(int(n_edges), 1)
    nch = max(-(-m // chunk), 1)
    ones_v = jnp.ones((max(int(n_vertices), 1),), bool)
    ones_e = jnp.ones((m,), bool)
    ones_c = jnp.ones((nch,), bool)
    mem = tuple(ones_v if v is None else jnp.asarray(v) for v in members)
    epr = tuple(ones_e if e is None else jnp.asarray(e) for e in edge_preds)
    cal = tuple(ones_c if c is None else jnp.asarray(c) for c in chunk_alives)
    return mem, epr, cal


def _padded_csr(row_ptr, col_idx, edge_id, n_edges):
    rp = jnp.asarray(row_ptr)
    if n_edges:
        return rp, jnp.asarray(col_idx), jnp.asarray(edge_id)
    # degenerate graph: 1-entry dummies keep every gather in range (deg is
    # all zero, so no candidate is ever valid)
    return rp, jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32)


def stage_chain(row_ptr, col_idx, edge_id, n_vertices: int, n_edges: int,
                start_nids, members, edge_preds, chunk_alives, *,
                capacity: int, chunk: int) -> tuple:
    """Put a chain's inputs on the device: the CSR, the per-hop tables
    (None = unconstrained) and the padded start frontier. Returns the
    chain program's positional arguments, for :func:`launch_chain`."""
    rp, ci, ei = _padded_csr(row_ptr, col_idx, edge_id, n_edges)
    mem, epr, cal = _device_tables(n_vertices, n_edges, chunk, members,
                                   edge_preds, chunk_alives)
    C0 = len(start_nids)
    if capacity < C0 or capacity % 128:
        raise ValueError(f"capacity {capacity} must be a multiple of 128 "
                         f">= the start frontier ({C0})")
    frontier = jnp.zeros((capacity,), jnp.int32).at[:C0].set(
        jnp.asarray(start_nids, jnp.int32))
    fmask = jnp.zeros((capacity,), bool).at[:C0].set(True)
    return rp, ci, ei, frontier, fmask, mem, epr, cal


def launch_chain(staged: tuple, *, capacity: int, chunk: int,
                 use_kernel: bool = False):
    """Run the chain program on staged inputs; its one host sync reads the
    overflow flag. Returns the device's ``(vcols, ecols, count)``, or None
    on capacity overflow (caller doubles and retries)."""
    rp, ci, ei, frontier, fmask, mem, epr, cal = staged
    vcols, ecols, count, ovf = _chain_device(
        rp, ci, ei, frontier, fmask, mem, epr, cal, capacity=capacity,
        chunk=chunk, use_kernel=use_kernel,
        interpret=use_kernel and not on_tpu())
    COUNTERS.launches += 1
    COUNTERS.hops += len(mem)
    if bool(ovf):               # the chain's one host sync
        return None
    return vcols, ecols, count


def read_chain(launched) -> tuple[list, list]:
    """The matched path columns of a launch, trimmed to its count, as np
    arrays in hop order."""
    vcols, ecols, count = launched
    k = int(count)
    return ([np.asarray(c)[:k] for c in vcols],
            [np.asarray(c)[:k] for c in ecols])


def traverse_chain(row_ptr, col_idx, edge_id, n_vertices: int, n_edges: int,
                   start_nids, members, edge_preds, chunk_alives, *,
                   capacity: int, chunk: int, use_kernel: bool = False):
    """Run a whole chain in one jit'd program: :func:`stage_chain`,
    :func:`launch_chain`, :func:`read_chain`. Returns (vcols, ecols, ok):
    trimmed np arrays of the matched path columns (hop order), or
    ``ok=False`` on capacity overflow (caller doubles and retries)."""
    staged = stage_chain(row_ptr, col_idx, edge_id, n_vertices, n_edges,
                         start_nids, members, edge_preds, chunk_alives,
                         capacity=capacity, chunk=chunk)
    launched = launch_chain(staged, capacity=capacity, chunk=chunk,
                            use_kernel=use_kernel)
    if launched is None:
        return None, None, False
    return (*read_chain(launched), True)


def batched_traverse(row_ptr, col_idx, edge_id, n_vertices: int,
                     n_edges: int, start_nids, members, edge_preds,
                     chunk_alives, *, capacity: int, chunk: int,
                     use_kernel: bool = False):
    """Point-lookup batching: ``start_nids`` is (B,) — one start vertex per
    query; all B queries advance through the chain in single launches.
    Returns (vcols, ecols, counts, ok): per-query path columns as
    (B, capacity) np arrays valid up to ``counts[q]``, or ``ok=False`` if
    any query overflowed."""
    rp, ci, ei = _padded_csr(row_ptr, col_idx, edge_id, n_edges)
    mem, epr, cal = _device_tables(n_vertices, n_edges, chunk, members,
                                   edge_preds, chunk_alives)
    start = jnp.asarray(start_nids, jnp.int32)
    B = start.shape[0]
    if capacity % 128:
        raise ValueError(f"capacity {capacity} must be a multiple of 128")
    frontiers = jnp.zeros((B, capacity), jnp.int32).at[:, 0].set(start)
    fmasks = jnp.zeros((B, capacity), bool).at[:, 0].set(True)
    vcols, ecols, counts, ovf = _batched_chain_device(
        rp, ci, ei, frontiers, fmasks, mem, epr, cal, capacity=capacity,
        chunk=chunk, use_kernel=use_kernel,
        interpret=use_kernel and not on_tpu())
    COUNTERS.launches += 1
    COUNTERS.hops += len(mem)
    COUNTERS.batched_queries += int(B)
    if bool(jnp.any(ovf)):      # the batch's one host sync
        return None, None, None, False
    return ([np.asarray(c) for c in vcols], [np.asarray(c) for c in ecols],
            np.asarray(counts), True)


# ---------------------------------------------------------------------------
# Reach: a level-synchronous BFS over a dense 0/1 adjacency
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n_pad",))
def dense_adjacency(row_ptr, col_idx, *, n_pad: int):
    """The graph's (n_pad, n_pad) int8 adjacency, A[u, v] = 1 for an edge
    u -> v, built on the device from its CSR by one scatter. Each edge's
    source row is the number of row starts at or before its slot, a scatter
    of ones at the row starts and a running sum (empty rows share a start,
    so the scatter adds)."""
    n_edges = col_idx.shape[0]
    starts = jnp.zeros((n_edges,), jnp.int32).at[row_ptr[1:-1]].add(
        1, mode="drop")
    rows = jnp.cumsum(starts)
    return jnp.zeros((n_pad, n_pad), jnp.int8).at[rows, col_idx].set(
        1, mode="drop")


@functools.partial(jax.jit, static_argnames=("transpose",))
def reach_program(adj, ends, member, lo, hi, *, transpose: bool):
    """BFS from each of the k vertices in ``ends`` at once, as a
    ``lax.while_loop`` over levels: each level multiplies the (k, n) int8
    frontier block with the adjacency in one pass over it, int32
    accumulator, so exact (``transpose`` walks edges backwards).

    k = 1 searches from ``ends[0]`` and stops at level ``hi``, at an empty
    frontier, or once every vertex of ``member`` has its distance; a vertex's
    result is its distance. k = 2 searches an undirected (symmetric)
    adjacency from both ends of a pair: after l levels both balls have
    radius l, and once they meet, min(d_0 + d_1) over the vertices both
    reached is the distance (a shortest path's vertex min(d, l) levels from
    the first end lies in both). It stops when they meet, when either side
    stops growing (no path), or when a path could no longer be at most
    ``hi`` long; the result, at ``ends[1]``, is that distance.

    Returns the result of each ``member`` vertex with lo <= d <= hi (-1
    elsewhere), the levels run, and the vertices the searches reached (the
    ends not counted)."""
    k = ends.shape[0]
    idx = jnp.arange(adj.shape[0], dtype=jnp.int32)
    front = idx[None, :] == ends[:, None]
    dims = (((1,), (1 if transpose else 0,)), ((), ()))
    none = jnp.int32(2**30)

    def met(d):
        both = jnp.all(d >= 0, axis=0)
        return jnp.min(jnp.where(both, jnp.sum(d, axis=0), none))

    def cond(s):
        level, _, d, grew = s
        if k == 1:
            return grew & (level < hi) & jnp.any(member & (d[0] < 0))
        return grew & (met(d) == none) & (2 * level + 1 <= hi)

    def body(s):
        level, front, d, _ = s
        hits = jax.lax.dot_general(front, adj, dims,
                                   preferred_element_type=jnp.int32)
        new = (hits > 0) & (d < 0)
        level = level + 1
        return level, new.astype(jnp.int8), jnp.where(new, level, d), \
            jnp.all(jnp.any(new, axis=1))

    levels, _, d, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), front.astype(jnp.int8),
                     jnp.where(front, 0, -1).astype(jnp.int32),
                     jnp.bool_(True)))
    dist = d[0] if k == 1 else jnp.where(idx == ends[1], met(d), -1)
    keep = member & (dist >= lo) & (dist <= hi)
    return jnp.where(keep, dist, -1), levels, jnp.sum(d > 0, dtype=jnp.int32)


def stage_reach(member, n_vertices: int, n_pad: int):
    """The end side's member mask as a device (n_pad,) bool vector (None:
    every real vertex)."""
    m = np.zeros(n_pad, dtype=bool)
    if member is None:
        m[:n_vertices] = True
    else:
        m[:n_vertices] = member
    return jnp.asarray(m)


def launch_reach(adj, start: int, member, lo: int, hi, *,
                 transpose: bool = False, target: int | None = None):
    """Run the reach program from ``start``, or with a ``target`` from both
    ends of the pair; its one host sync reads the levels it ran. ``hi``
    None is unbounded. Returns the device's ``(dist, levels, reached)``,
    levels as an int."""
    big = adj.shape[0]
    ends = [start] if target is None else [start, target]
    dist, levels, reached = reach_program(
        adj, jnp.asarray(ends, jnp.int32), member, jnp.int32(lo),
        jnp.int32(big if hi is None else min(hi, big)), transpose=transpose)
    return dist, int(levels), reached


def read_reach(launched, n_vertices: int) -> tuple:
    """``(nids, dists, levels, reached)`` of a launch, as host values."""
    dist, levels, reached = launched
    d = np.asarray(dist)[:n_vertices]
    ids = np.flatnonzero(d >= 0)
    return ids, d[ids], levels, int(reached)
