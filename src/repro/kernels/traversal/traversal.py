"""Fused traversal Pallas kernel — the device-resident GCDI hot path.

One launch advances a whole batch of padded frontiers one hop: CSR
row-gather + neighbor expansion + pushed-predicate evaluation + in-kernel
compaction, with zone-map chunk metadata gating the edge-predicate reads.
The batched layout is the native one (grid = (B queries, capacity/blk
slot blocks)); a single query is the B=1 special case.

Layout notes (vs the per-hop jit matcher in ``core.pattern_jit``):

  * the degree prefix-sum and the overflow flag are computed in the jnp
    prelude (they are O(C) scans XLA fuses well); the kernel does the
    O(capacity) candidate work;
  * each (q, b) grid step owns ``blk`` candidate slots of query q. The
    slot->frontier-entry mapping is a broadcast compare against the
    offsets (the in-kernel searchsorted); gathers pull dst/eid, the
    member / chunk-alive / edge-predicate tables filter, and survivors are
    scattered to the query's running compaction offset held in SMEM —
    TPU grid steps run sequentially, so the scalar offset carries across
    slot blocks and resets at each query's first block;
  * a candidate whose edge tid lands in a zone-dead chunk is masked before
    the predicate gather — on compiled TPU the predicate table is blocked
    per chunk and dead chunks are never DMA'd into VMEM; interpret mode
    (the CI path) preserves the semantics with a masked gather;
  * ``.at[].set(mode="drop")`` gives the compaction scatter: dead slots
    target index ``capacity`` (one past the block) and vanish.

The TPU compiler refuses this kernel, so it runs only under
``interpret=True`` and the engine runs ``ref.fused_hop_ref`` instead
(``ops.py``). Compiled for a v5e it fails in three places: the ``(1, C)``
in/out blocks break the 8x128 tiling rule unless B is 1 or a multiple of
8; the scalar stores to the ``(1, 1)`` count output are refused in VMEM;
and with that output in SMEM, the 1-D in-kernel gathers (``rp[...]``,
``ci[pos]``, ``mem[...]``, ``ep[...]``) are refused ("Only 2D gather is
supported"). The last needs a redesign of the gathers, not a repair.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _hop_kernel(out_off_ref, frontier_ref, total_ref, row_ptr_ref,
                col_idx_ref, edge_id_ref, member_ref, edge_pred_ref,
                chunk_alive_ref, src_ref, dst_ref, eid_ref, cnt_ref,
                off_sm, *, blk: int, capacity: int, chunk: int):
    b = pl.program_id(1)

    @pl.when(b == 0)
    def _init():
        off_sm[0] = 0
        cnt_ref[0, 0] = 0
        src_ref[...] = jnp.zeros(src_ref.shape, jnp.int32)
        dst_ref[...] = jnp.full(dst_ref.shape, -1, jnp.int32)
        eid_ref[...] = jnp.full(eid_ref.shape, -1, jnp.int32)

    oo = out_off_ref[0, :]                                   # (C,)
    fr = frontier_ref[0, :]
    total = total_ref[0, 0]
    slots = b * blk + jax.lax.broadcasted_iota(jnp.int32, (blk,), 0)

    # in-kernel searchsorted: the frontier entry owning slot s is the last
    # offset <= s (broadcast compare; offsets are sorted)
    src_slot = jnp.sum((oo[None, :] <= slots[:, None]).astype(jnp.int32),
                       axis=1) - 1
    src_slot = jnp.clip(src_slot, 0, oo.shape[0] - 1)
    within = slots - oo[src_slot]

    rp = row_ptr_ref[...]
    ci = col_idx_ref[...]
    ei = edge_id_ref[...]
    pos = jnp.clip(rp[fr[src_slot]] + within, 0, ci.shape[0] - 1)
    dst = ci[pos].astype(jnp.int32)
    eid = ei[pos].astype(jnp.int32)

    ok = slots < jnp.minimum(total, capacity)
    mem = member_ref[...]
    ok &= mem[jnp.clip(dst, 0, mem.shape[0] - 1)]
    ca = chunk_alive_ref[...]
    ok &= ca[jnp.clip(eid // chunk, 0, ca.shape[0] - 1)]
    ep = edge_pred_ref[...]
    ok &= ep[jnp.clip(eid, 0, ep.shape[0] - 1)]

    # compact survivors to the query's running offset; dead slots scatter
    # out of range and drop
    off = off_sm[0]
    inc = jnp.cumsum(ok.astype(jnp.int32))
    posn = jnp.where(ok, off + inc - 1, capacity)
    src_ref[0, :] = src_ref[0, :].at[posn].set(src_slot.astype(jnp.int32),
                                               mode="drop")
    dst_ref[0, :] = dst_ref[0, :].at[posn].set(dst, mode="drop")
    eid_ref[0, :] = eid_ref[0, :].at[posn].set(eid, mode="drop")
    off_sm[0] = off + inc[-1]

    @pl.when(b == pl.num_programs(1) - 1)
    def _fin():
        cnt_ref[0, 0] = off + inc[-1]


@functools.partial(jax.jit,
                   static_argnames=("capacity", "chunk", "blk", "interpret"))
def batched_hop(row_ptr: jax.Array, col_idx: jax.Array, edge_id: jax.Array,
                frontiers: jax.Array, fmasks: jax.Array, member: jax.Array,
                edge_pred: jax.Array, chunk_alive: jax.Array, *,
                capacity: int, chunk: int, blk: int = 128,
                interpret: bool = False):
    """B queries, one launch. frontiers/fmasks: (B, C). Returns
    (src_slot, dst, eid) as (B, capacity), count (B,), overflowed (B,) —
    the same contract as ``ref.batched_hop_ref``."""
    B, C = frontiers.shape
    if capacity % blk:
        raise ValueError(f"capacity {capacity} not a multiple of blk {blk}")
    fr = jnp.asarray(frontiers, jnp.int32)
    deg = jnp.where(fmasks, (row_ptr[fr + 1] - row_ptr[fr]).astype(jnp.int32),
                    0)
    out_off = (jnp.cumsum(deg, axis=1) - deg).astype(jnp.int32)
    total = jnp.sum(deg, axis=1, dtype=jnp.int32)[:, None]
    overflowed = total[:, 0] > capacity

    n1, m = row_ptr.shape[0], col_idx.shape[0]
    nmem, nch = member.shape[0], chunk_alive.shape[0]
    kernel = functools.partial(_hop_kernel, blk=blk, capacity=capacity,
                               chunk=chunk)
    src, dst, eid, cnt = pl.pallas_call(
        kernel,
        grid=(B, capacity // blk),
        in_specs=[
            pl.BlockSpec((1, C), lambda q, b: (q, 0)),       # out_off
            pl.BlockSpec((1, C), lambda q, b: (q, 0)),       # frontier
            pl.BlockSpec((1, 1), lambda q, b: (q, 0)),       # total
            pl.BlockSpec((n1,), lambda q, b: (0,)),          # row_ptr
            pl.BlockSpec((m,), lambda q, b: (0,)),           # col_idx
            pl.BlockSpec((m,), lambda q, b: (0,)),           # edge_id
            pl.BlockSpec((nmem,), lambda q, b: (0,)),        # member
            pl.BlockSpec((m,), lambda q, b: (0,)),           # edge_pred
            pl.BlockSpec((nch,), lambda q, b: (0,)),         # chunk_alive
        ],
        out_specs=[
            pl.BlockSpec((1, capacity), lambda q, b: (q, 0)),
            pl.BlockSpec((1, capacity), lambda q, b: (q, 0)),
            pl.BlockSpec((1, capacity), lambda q, b: (q, 0)),
            pl.BlockSpec((1, 1), lambda q, b: (q, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, capacity), jnp.int32),
            jax.ShapeDtypeStruct((B, capacity), jnp.int32),
            jax.ShapeDtypeStruct((B, capacity), jnp.int32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
        ],
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        interpret=interpret,
    )(out_off, fr, total, row_ptr, col_idx, edge_id, member, edge_pred,
      chunk_alive)
    return src, dst, eid, cnt[:, 0], overflowed


def fused_hop(row_ptr, col_idx, edge_id, frontier, fmask, member, edge_pred,
              chunk_alive, *, capacity: int, chunk: int, blk: int = 128,
              interpret: bool = False):
    """Single-query fused hop (B=1 slice of the batched kernel); same
    contract as ``ref.fused_hop_ref``."""
    src, dst, eid, cnt, ovf = batched_hop(
        row_ptr, col_idx, edge_id, frontier[None, :], fmask[None, :],
        member, edge_pred, chunk_alive, capacity=capacity, chunk=chunk,
        blk=blk, interpret=interpret)
    return src[0], dst[0], eid[0], cnt[0], ovf[0]
