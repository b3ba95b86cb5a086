"""Pure-jnp oracle for the fused traversal hop (DeviceMatchPattern).

One "fused hop" is the unit the Pallas kernel implements: CSR row-gather +
neighbor expansion + pushed-predicate evaluation + compaction, over a padded
fixed-capacity frontier. The oracle keeps the exact output contract the
kernel must hit so the equivalence tests compare arrays, not row sets:

  * candidates are laid out in slot order — frontier-slot-major, CSR
    position within a row (the same order the host matcher produces);
  * survivors are compacted to the front, preserving slot order;
  * padding is ``src=0, dst=-1, eid=-1`` beyond ``count``;
  * ``overflowed`` is true when the *pre-predicate* candidate total exceeds
    the capacity (the caller doubles and retries — survivors of a truncated
    expansion are never silently returned as complete).

``chunk_alive`` is the zone-map chunk survivor table over the edge-tid
space: a candidate whose edge lands in a predicate-dead chunk is dropped
whatever ``edge_pred`` says (here the chunk table is folded into the
predicate table, so one lookup does both).

On the TPU this hop's time is its count of capacity-wide gathers: each is a
random access per slot (about 9 ns an element on a v5e, 4.5 ms at capacity
2^19), while a sort of the same width costs a fraction of one. So the hop
finds each slot's frontier row by sorting the rows' first slots in among
the slots and taking a running max of the row index, not by a binary search
(one gather per search step), and the compaction sort carries the payload
instead of gathering it by the sorted order. What is left is one gather for
the CSR position, two for ``dst``/``eid`` and one each for the vertex and
edge tables, plus the two ``row_ptr`` gathers at frontier width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def _alive_pred(edge_pred, chunk_alive, chunk: int):
    """``edge_pred`` with the zone-map chunk table folded in, over edge tids:
    ``edge_pred[clip(e)] & chunk_alive[clip(e // chunk)]`` as one table, so a
    candidate needs one lookup. Built by broadcast and reshape, not by a
    gather; each table is padded with its last entry to the longer length,
    which keeps both clips of an out-of-range tid."""
    m, nch = edge_pred.shape[0], chunk_alive.shape[0]
    n = max(m, nch * chunk)
    per_tid = jnp.broadcast_to(chunk_alive[:, None], (nch, chunk)).reshape(-1)
    per_tid = jnp.concatenate(
        [per_tid, jnp.broadcast_to(chunk_alive[-1], (n - nch * chunk,))])
    ep = jnp.concatenate([edge_pred, jnp.broadcast_to(edge_pred[-1], (n - m,))])
    return ep & per_tid


@functools.partial(jax.jit, static_argnames=("capacity", "chunk"))
def fused_hop_ref(row_ptr: jax.Array, col_idx: jax.Array, edge_id: jax.Array,
                  frontier: jax.Array, fmask: jax.Array, member: jax.Array,
                  edge_pred: jax.Array, chunk_alive: jax.Array, *,
                  capacity: int, chunk: int):
    """One fused hop. frontier/fmask: (C,) padded nids + validity; member:
    (n,) bool over nids; edge_pred: (m,) bool over edge tids; chunk_alive:
    (ceil(m/chunk),) bool. Returns (src_slot, dst, eid, count, overflowed)
    with the first ``count`` slots holding the compacted survivors —
    ``src_slot`` indexes the INPUT frontier so callers re-join path
    prefixes.

    Expansion by running max: the rows' first slots (``out_off``) are
    sorted in among the slots with the row index as payload, so the running
    max at a slot is the last row whose first slot is at or below it; below
    ``total`` that is the row that owns the slot (slots past it are masked).
    Compaction is one sort keyed on slot order with ``(src_slot, dst, eid)``
    as its payload."""
    C = frontier.shape[0]
    fr = frontier.astype(jnp.int32)
    row_lo = row_ptr[fr].astype(jnp.int32)
    deg = jnp.where(fmask, row_ptr[fr + 1].astype(jnp.int32) - row_lo, 0)
    out_off = jnp.cumsum(deg) - deg                     # exclusive prefix sum
    total = jnp.sum(deg)
    overflowed = total > capacity

    # key 2*slot for a row's first slot, 2*slot+1 for a slot: a row sorts
    # before a slot at a tie, and a row whose first slot is past the
    # capacity sorts after every slot. Payload: the row index, -1 for a slot
    slots = jnp.arange(capacity, dtype=jnp.int32)
    _, rows = lax.sort(
        (jnp.concatenate([2 * jnp.minimum(out_off, capacity), 2 * slots + 1]),
         jnp.concatenate([jnp.arange(C, dtype=jnp.int32),
                          jnp.full((capacity,), -1, jnp.int32)])),
        num_keys=1)
    # the stable sort on the row/slot flag lays the slots out again in order
    _, owner = lax.sort(((rows >= 0).astype(jnp.int32), lax.cummax(rows)),
                        num_keys=1, is_stable=True)
    src_slot = owner[:capacity]
    # CSR position = slot + (row start - row's first slot): one gather
    base = row_lo - out_off
    pos = jnp.clip(slots + base[src_slot], 0, col_idx.shape[0] - 1)
    dst = col_idx[pos].astype(jnp.int32)
    eid = edge_id[pos].astype(jnp.int32)

    ok = slots < jnp.minimum(total, capacity)
    ok &= member[jnp.clip(dst, 0, member.shape[0] - 1)]
    alive = _alive_pred(edge_pred, chunk_alive, chunk)
    ok &= alive[jnp.clip(eid, 0, alive.shape[0] - 1)]

    # stable compaction in slot order: survivors sort before dead slots and
    # keep their relative order (keys are unique, so no stable-sort caveat)
    count = jnp.sum(ok).astype(jnp.int32)
    _, src_s, dst_s, eid_s = lax.sort(
        (jnp.where(ok, slots, capacity + slots), src_slot, dst, eid),
        num_keys=1)
    live = slots < count
    src_c = jnp.where(live, src_s, 0)
    dst_c = jnp.where(live, dst_s, -1)
    eid_c = jnp.where(live, eid_s, -1)
    return src_c, dst_c, eid_c, count, overflowed


@functools.partial(jax.jit, static_argnames=("capacity", "chunk"))
def batched_hop_ref(row_ptr: jax.Array, col_idx: jax.Array,
                    edge_id: jax.Array, frontiers: jax.Array,
                    fmasks: jax.Array, member: jax.Array,
                    edge_pred: jax.Array, chunk_alive: jax.Array, *,
                    capacity: int, chunk: int):
    """Batched variant: frontiers/fmasks are (B, C) — B independent queries
    share the CSR and predicate tables and advance in one call. Returns the
    per-query (src_slot, dst, eid) as (B, capacity), count as (B,), and a
    per-query overflow flag."""
    def one(fr, fm):
        return fused_hop_ref(row_ptr, col_idx, edge_id, fr, fm, member,
                             edge_pred, chunk_alive,
                             capacity=capacity, chunk=chunk)
    return jax.vmap(one)(frontiers, fmasks)
