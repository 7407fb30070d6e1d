"""Paged attention — a Pallas TPU decode kernel over the block-pool KV cache.

The serve engine's decode hot path (``models/generate.py
_forward_decode_paged``) holds K/V in a SHARED pool of
``block_tokens``-sized blocks addressed through per-sequence block tables.
The straightforward JAX formulation gathers the whole table back out —
``k_pool[tables].reshape(S, max_len, H, D)`` — which materializes
S × max_len × H × D every token and reads every pool block a slot's table
points at, live or not. Decode is memory-bandwidth-bound, so that gather is
exactly the HBM traffic the roofline says we cannot afford.

This kernel WALKS the block table itself: the table, the per-slot lengths
and ``layer`` ride in as scalar-prefetch operands
(``pltpu.PrefetchScalarGridSpec``), the pools stay in HBM
(``memory_space=pl.ANY``), and the grid is ``(S, q tiles)`` with no table
axis. Inside a grid step a loop runs over GROUPS of ``G`` consecutive table
entries (``_blocks_per_group``: 128 kv positions, one lane row of scores),
its bound computed from ``lengths[s]`` and the tile's last real query: a slot
of 400 tokens does 4 iterations at 16-token blocks. A PARKED slot (the
engine dispatches every slot every step; one with no request has an
all-trash table) does none: its step is read off ``tables[s, 0] == 0``,
the trash block where a live slot's first block would be, and it starts and
awaits no copy, attends and finalizes nothing and writes zeros to its output
in one store (``_walk_live_groups`` says why that test is sound): a chat
engine with one stream in flight pays for one slot, not for 36. A group's
live blocks are fetched by one ``pltpu.make_async_copy`` each,
``pool.at[layer, tables[s, b]]`` into rows
``j * bt`` of one half of a double-buffered ``[2, G*bt, H*D]`` scratch, all
in flight at once; the next group's copies are started before the current
group is computed on, and a step's last iteration starts the FIRST group of
the next grid step, so no slot opens on an exposed DMA but the call's first
and one that follows a parked slot (whose step starts it). Entries past the
slot's last live block are NOT fetched (dead table entries are never
dereferenced); their rows of the V half are zeroed before the dot, because
a masked score gives p = 0 and 0 x NaN is NaN, whatever the scratch held.
Softmax is the online (m, l, acc) accumulator pattern shared with
``flash_attention._flash_kernel``, held in VMEM scratch across the loop, over
``G*bt`` kv positions at once: one dot a lane chunk where a block-a-step
kernel had ``G``.

Layout: ``q`` [S, T, H, D] — T > 1 is the multi-token speculative-decoding
verify (and the paged prefill, S == 1): query t of slot s sits at absolute
position ``lengths[s] + t`` and attends kv positions ``<= lengths[s] + t``.
``paged_attention`` reads: the T new tokens' K/V must already be scattered
into the pool at those positions (the caller writes K/V first, then attends:
the gather path's order; a prefill, the windowed walk and every family but
GPT-2 call it so). Queries are tiled ``_Q_TILE`` at a time so that VMEM is
bounded by the tile, not by T; decode and verify are a single tile, and each
tile walks only the blocks at or below its own last real query. Which rows
are real a PREFILL says at run time (``queries`` [S], a fourth
scalar-prefetch operand: of slot ``s``'s T rows the first ``queries[s]``; a
prompt fills its bucket to a half or three quarters, and the pad rows, all
causally AHEAD of the real ones, were three fifths of an 8,192 bucket's
walk). The tile that straddles the count walks to its last real query; a
tile behind it is a parked step of its own (no copy, nothing attended, zeros
stored, the next step's first group handed on), under a window too; every
pad row of the result is zeros. A caller that passes no count (a decode step,
a verify: every row real) traces the body it always did.

``paged_attention_append`` reads AND writes: a decode step (T = 1) hands it
each slot's new K and V row as operands beside pools that do not hold them
yet, and gets the pools back with the rows in (aliased outputs: the same
buffers for a caller that donates them). The walk is the same; when a slot's
last group has arrived, which holds the block of position ``lengths[s]``, the
row is laid into its place in VMEM, attended there, and the block is copied
back whole under the rest of the step (``_paged_append_kernel``). What it
replaces is a scatter a layer for K and one for V, each a fusion of its own
that wrote the row to HBM for the kernel to fetch back: 48 a token step, the
first operation of a chat token (PERF.md, PR 48). A parked slot and one at
table capacity write nothing. Which of the two a caller gets is what it
passes (rows or none), not a flag: with no row the traced body is the one
the digests hold.

Grouped queries: a pool row holds the ``KV`` heads that are STORED, ``KV`` a
divisor of the ``H`` query heads (``KV == H`` is plain multi-head attention
and the program it always was: the kernel's traced body is unchanged, byte
for byte). Query head ``h`` reads KV head ``h // R``, ``R = H // KV``; a KV
head's ``R`` query heads are ``R * T`` ROWS of the same dot against that
head's lanes of the fetched block (as the 64 heads of the latent kernel are
rows against one shared row), so a block is fetched once for the group and
the K/V bytes a step reads are the KV heads', not the query heads'. Below,
"heads" of the pool and of a lane chunk are KV heads; the accumulators' rows
and the output are the query heads'.

A window (``window=W``: a sliding-window layer) gives the walk a LOWER
bound too: query ``i`` sees key ``j`` iff ``j <= i`` and ``i - j < W``, so a
tile starts at the block that holds its first query's oldest visible key and
starts no copy of a block wholly behind it; the window's trailing edge is
masked inside a block. The table is then read MODULO its width, so that a
slot's rows may be a RING of ``nb_seq`` blocks (logical block ``b`` lies in
entry ``b mod nb_seq``; the ring holds at least ``W + T - 1`` rows and a
block more, so that the blocks a tile attends are distinct entries) whatever
the context: what a window layer keeps for a slot is bounded by the window.
A table that covers the whole context (a prefill over its fresh rows) is the
same walk, its modulo the identity. Rings are a pool with NO trash block:
slot 0's ring starts at block 0 (``models/afmoe.py:_window_attend``) and a
windowed prefill's table is ``arange`` over its fresh rows, so under a
window no slot is taken for parked and every step is walked. With no window
the traced body is held by digest (``tests/test_paged_attention_kernel.py``;
a PR that changes the walk on purpose takes new ones).

Two things a stack may ask of the walk besides (``models/mimo_v2.py``; a
caller that asks for neither traces the body the digests hold). A head of V
NARROWER than a head of K (192 / 128): the V pool's row is ``KV * Dv`` lanes
beside the K pool's ``KV * D``, each with a buffer of its own width, the
accumulators and the output ``Dv`` wide; a lane chunk is then as many KV
heads as make whole 128-lane tiles on BOTH sides (two heads of 192: three
tiles), and a query tile is halved again while its q block, that much
longer a row, would pass ``_Q_BLOCK_VMEM_BYTES``. And a SINK (``sinks`` [H]):
a learned score a query head that joins the softmax's denominator and
carries no value, which in the online form is only where the accumulators
START (``_init_accumulators``: ``m = b_h``, ``l = 1``, ``acc = 0``), in a
decode step and a prefill tile alike.

The pool is the WHOLE model's, ``[L, num_blocks, bt, KV*D]``: heads folded
into the lane dimension, so a block is one dense ``[bt, KV*D]`` tile in the
layout the array already has in HBM, read where it lies: a Mosaic call
cannot read through an XLA slice (handed ``pool[layer]`` it made XLA copy
that layer's slab out of the pool every call), so no program copies
pool-sized data. Head ``h`` is the static lane slice ``[:, h*D:(h+1)*D]``
of the block; the body takes the lanes in chunks of C heads
(``_heads_per_chunk``), every chunk a 128-lane-aligned slice, q and K
meeting in bfloat16 (exact products, float32 sums), p and V in float32, and
only the finalize step cuts single heads out of the accumulator.

A folded width that is no multiple of 128 lanes (gpt2-xl: 25 x 64 = 1600)
cannot take the loop: Mosaic (jaxlib 0.9.0) pads such a ref's last
dimension to whole lane tiles and then refuses every slice of it that is
not a multiple of 128 wide, the full width included, so no DMA can name one
block. There the same groups are a third grid axis and a group's blocks come
in through ``G`` BlockSpecs (``_paged_kernel_unaligned``): same body, same
bound, dead steps cost a grid step each. A pool padded to whole lanes would
take the loop at every width; it is ``models/generate.py``'s to make
(ROADMAP L1).

The walk itself is one function, ``_walk_live_groups``, that both kernels
of this module call: this one with two pools (K and V) and the latent kernel
(``latent_paged_attention``, below) with one pool whose rows are key and
value at once, so one DMA a block.

Runs compiled on TPU and in interpret mode on CPU (the tier-1 path);
``paged_attention_reference`` is the gather-path oracle the kernel is
validated against.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
# Queries per grid step: the prefill's accumulators are [H*tile, .] float32
# with 1-wide m and l columns padded to 128 lanes, three buffers of
# H*tile*512 bytes each. Compile-only for a v5e the 1024 bucket takes 5.9 MB
# of scoped VMEM at 16 heads and 8.9 MB at 25, of 16 MB: twice the tile
# would not fit gpt2-xl. Never swept on the chip: prefill is 1-2% of the
# serve cells' device time.
_Q_TILE = 128
# kv positions one iteration of the walk takes: one 128-lane row of scores.
_GROUP_KV = 128
# What the K and V buffers (two halves each) may take of VMEM.
_GROUP_VMEM_BYTES = 4 << 20


def _last_block(first_pos, q_tile: int, block_tokens: int):
    """Highest block index holding a position attended by the ``q_tile``
    queries that start at absolute position ``first_pos`` (the last of them
    sits at ``first_pos + q_tile - 1``). One formula for the kernel body,
    which skips later blocks, and the index map, which does not fetch them."""
    return jax.lax.div(first_pos + q_tile - 1, block_tokens)


def _tile_real_queries(queries_ref, s, i, q_tile: int, total: int):
    """How many of tile ``i``'s ``q_tile`` queries are REAL where slot ``s``
    brings ``queries_ref[s]`` real ones of ``total`` (a prefill's prompt in
    its bucket): ``q_tile`` before the count, the rest in the tile that
    straddles it, 0 or less in a tile of pad queries. None without a count:
    the caller's static ``total`` says it all."""
    if queries_ref is None:
        return None
    return jnp.minimum(q_tile,
                       jnp.minimum(queries_ref[s], total) - i * q_tile)


def _tile_last_block(lengths_ref, s, i, q_tile: int, total: int,
                     block_tokens: int, nb_seq: int, ring: bool = False,
                     queries_ref=None):
    """The last table entry that query tile ``i`` of slot ``s`` attends, by
    its last REAL query: of ``total`` queries the last tile may hold fewer
    than ``q_tile``, and its pad queries, like a slot at capacity, would pass
    the slot's live blocks or the table's end. ``ring``: the LOGICAL block,
    which a table read modulo its width has no end for. ``queries_ref`` [S]:
    the slot's count of real queries, read at run time where ``total`` is the
    caller's static one; a tile with none of them attends nothing, -1."""
    real = _tile_real_queries(queries_ref, s, i, q_tile, total)
    counted = real is not None
    if not counted:
        real = jnp.minimum(q_tile, total - i * q_tile)
    last = _last_block(lengths_ref[s] + i * q_tile, real, block_tokens)
    if counted:
        last = jnp.where(real > 0, last, -1)
    return last if ring else jnp.minimum(last, nb_seq - 1)


def _tile_first_block(lengths_ref, s, i, q_tile: int, block_tokens: int,
                      window: int):
    """The block that holds the oldest key tile ``i``'s FIRST query sees
    through a window of ``window`` positions (itself included): no query of
    the tile sees a block before it."""
    return jax.lax.div(
        jnp.maximum(lengths_ref[s] + i * q_tile - (window - 1), 0),
        block_tokens)


def _clamped_block_index(q_tile: int, block_tokens: int, step_blocks: int,
                         offset: int, nb_seq: int, total: int):
    """The index map of the kernel whose table walk is a grid axis
    (``_paged_kernel_unaligned``): grid step ``j`` of slot ``s``, query tile
    ``i`` takes ``step_blocks`` table entries, the pool handed in once per
    entry, each with its ``offset``; this one maps to pool block
    ``tables[s, b]`` of layer ``layer[0]``, ``b`` clamped onto the tile's
    last live block (by its real queries, ``total`` over all tiles), so that
    a dead entry is never dereferenced and a step that maps where the last
    one did costs no DMA. ``queries``: the slots' counts of real queries
    where they are a fourth prefetched operand; a tile of pad queries has
    no live block and maps onto the slot's first entry."""
    def kv_index(s, i, j, tbl, ln, lyr, queries=None):
        last_blk = _tile_last_block(ln, s, i, q_tile, total, block_tokens,
                                    nb_seq, queries_ref=queries)
        blk = jnp.minimum(j * step_blocks + offset, last_blk)
        if queries is not None:
            blk = jnp.maximum(blk, 0)
        return (lyr[0], tbl[s, blk], 0, 0)

    return kv_index


def _heads_per_chunk(num_heads: int, q_tile: int, head_dim: int,
                     v_dim: Optional[int] = None) -> int:
    """How many KV heads the kernel takes in one dot; ``q_tile`` is the rows
    a KV head brings (its query heads x the tile's queries). The block's
    lanes are cut into chunks of C heads; a chunk's C*T query rows, each zero
    outside its own head's lanes, meet the chunk's C*D lanes of K in ONE
    dot, so no head is sliced out of a 128-lane register on every block. The
    dot computes C times the products it needs: free while the rows fit one
    MXU pass (decode, verify: all heads at once), so beyond that C is only
    what makes whole 128-lane tiles of a chunk on BOTH sides, K's ``head_dim``
    and V's ``v_dim`` (prefill: two heads of 64, one of 128, two of 192: three
    tiles)."""
    if num_heads * q_tile <= 128:
        return num_heads
    per = math.lcm(*(math.lcm(d, 128) // d for d in (head_dim, v_dim or head_dim)))
    return min(num_heads, per)


def _blocks_per_group(block_tokens: int, width: int, itemsize: int,
                      group_kv: int = _GROUP_KV) -> int:
    """How many consecutive table entries one iteration of a kernel's walk
    takes: ``group_kv`` kv positions (``_GROUP_KV``: one lane row of scores),
    halved while the four halves of the K and V buffers (two each,
    ``[G*bt, width]``; a latent pool's one buffer is held to the same)
    would pass ``_GROUP_VMEM_BYTES``."""
    g = max(1, group_kv // block_tokens)
    while g > 1 and 4 * g * block_tokens * width * itemsize > _GROUP_VMEM_BYTES:
        g //= 2
    return g


def _attend_group(q_ref, k_rows, v_rows, g, ctx, m_scr, l_scr, acc_scr, *,
                  scale: float, num_heads: int, kv_heads: int, q_tile: int,
                  head_dim: int, group_tokens: int,
                  window: Optional[int] = None, v_dim: Optional[int] = None):
    """One online-softmax update over group ``g`` of a slot's kv positions,
    ``[g * group_tokens, (g+1) * group_tokens)``. ``k_rows(d0, d1)`` and
    ``v_rows(d0, d1)`` give the group's ``[group_tokens, d1 - d0]`` lanes.
    The ``num_heads`` query heads share the pool's ``kv_heads`` in
    consecutive runs (query head ``h`` reads KV head ``h // R``): a KV
    head's ``R`` query heads are ``R * q_tile`` rows of the same dot, so a
    fetched block is read once for all of them. ``window``: a key more than
    ``window - 1`` positions behind its query is masked too. ``v_dim``: the
    width of a head of V (and of the accumulators and the output) where it
    is not K's ``head_dim``."""
    KV, T, D = kv_heads, q_tile, head_dim
    Dv = D if v_dim is None else v_dim
    C = q_ref.shape[-1] // D                   # KV heads per lane chunk
    RT = num_heads // KV * T                   # rows a KV head brings
    # Causal + validity in one mask: kv position vs absolute q position.
    # Row r of a chunk is (query head r // T, query r % T).
    rows = C * RT
    kv_pos = g * group_tokens + jax.lax.broadcasted_iota(
        jnp.int32, (rows, group_tokens), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, group_tokens), 0)
    q_pos = ctx + (0 if T == 1 else jax.lax.rem(row, T))
    mask_all = kv_pos <= q_pos
    if window is not None:
        mask_all = jnp.logical_and(mask_all, kv_pos > q_pos - window)
    for c0 in range(0, KV, C):                   # static unroll
        c = min(C, KV - c0)                      # KV heads of this chunk
        d0, d1 = c0 * D, (c0 + c) * D            # their lanes of K
        e0, e1 = c0 * Dv, (c0 + c) * Dv          # and of V
        r0, r1 = c0 * RT, (c0 + c) * RT          # their query heads' rows
        mask = mask_all[: c * RT]
        # q and K meet in the dtype they share (bfloat16 on the chip: the
        # products are exact in the float32 they are summed in).
        qb = q_ref[0, r0:r1, : c * D]                       # [c*T, c*D]
        kb = k_rows(d0, d1)                                 # [kv, c*D]
        if qb.dtype != kb.dtype:
            qb, kb = qb.astype(jnp.float32), kb.astype(jnp.float32)
        scores = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                 # [c*T, kv]
        scores = jnp.where(mask, scores, _NEG_INF)
        m_prev = m_scr[r0:r1]                     # [c*T, 1]
        m_cur = jnp.max(scores, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)               # [c*T, kv]
        l_scr[r0:r1] = alpha * l_scr[r0:r1] + jnp.sum(
            p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v_rows(e0, e1).astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                         # [c*T, c*Dv]
        acc_scr[r0:r1, : c * Dv] = acc_scr[r0:r1, : c * Dv] * alpha + pv
        m_scr[r0:r1] = m_new


def _init_accumulators(m_scr, l_scr, acc_scr, sink_ref=None):
    """The online softmax's start. ``sink_ref`` [H, 1] float32: a SINK a
    query head, a learned score that enters the softmax's denominator and
    carries no value: ``p_ij = exp(s_ij) / (exp(b_h) + sum_j' exp(s_ij'))``.
    In the ``(m, l, acc)`` form it is where the accumulators start: ``m =
    b_h``, ``l = 1`` (``exp(b_h - m)``), ``acc = 0``; rows are (head, query)."""
    if sink_ref is None:
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
    else:
        H = sink_ref.shape[0]
        T = m_scr.shape[0] // H
        if T == 1:                                    # a decode step: one store
            m_scr[:] = sink_ref[:]
        else:
            for h in range(H):                        # static unroll
                m_scr[h * T:(h + 1) * T] = jnp.broadcast_to(
                    sink_ref[h:h + 1], (T, 1))
        l_scr[:] = jnp.ones_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)


def _real_rows(out, real, query_of_row):
    """``out`` [rows, lanes] with the rows of a tile's pad queries zeroed:
    ``query_of_row(row)`` is a row's query in the tile, real iff below
    ``real``. A select, not a product: a pad query's scores pass unfetched
    rows of the buffers, and whatever it summed must not flow on."""
    row = jax.lax.broadcasted_iota(jnp.int32, (out.shape[0], 1), 0)
    return jnp.where(query_of_row(row) < real, out, 0)


def _finalize(o_ref, l_scr, acc_scr, kv_heads: int, real=None):
    """The accumulators into the output block, head by head. ``real``: how
    many of the tile's queries are real (a counted call's straddling tile);
    the others' rows are written as zeros."""
    _, H, T, D = o_ref.shape
    C = acc_scr.shape[-1] // D
    R = H // kv_heads                                 # query heads a KV head
    out = (acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)).astype(o_ref.dtype)
    if real is not None:                              # rows are (head, query)
        out = _real_rows(out, real, lambda row: jax.lax.rem(row, T))
    for h in range(H):                                # static unroll
        e0 = (h // R % C) * D                # its KV head's lanes in its chunk
        o_ref[0, h] = out[h * T:(h + 1) * T, e0:e0 + D]


def _walk_live_groups(
    tables_ref, lengths_ref, layer_ref,   # scalar prefetch
    pools,                     # the pools in HBM, each [L, num_blocks, bt, W]
    bufs,                      # VMEM scratch, one a pool: [2, G*bt, W]
    sems,                      # DMA semaphores [pools, 2 (buffer half)]
    half_ref,                  # SMEM [1]: the half this step's first group is in
    accumulators,              # VMEM scratch (m, l, acc), reset here
    attend,                    # attend(g, ctx, half, fetched): group g is in
    finalize,                  # finalize(real): accumulators into ``o_ref``
    o_ref,                     # the step's output block
    *,
    block_tokens: int,
    q_tile: int,
    total: int,                # queries over all tiles (the last may be ragged)
    nb_seq: int,
    group_blocks: int,
    unroll_full: bool = False,
    window: Optional[int] = None,
    sink_ref=None,             # VMEM [H, 1]: where the softmax starts, or None
    queries_ref=None,          # scalar prefetch [S]: real queries a slot
):
    """The walk both kernels share: grid step ``(s, i)`` of a LIVE slot
    resets its accumulators, loops over the groups of ``G`` table entries its
    query tile attends, the bound read from ``lengths[s]``, and calls
    ``finalize``. A group's live blocks arrive by one DMA a pool each into
    one half of every ``buf`` while the other half is computed on; ``attend``
    gets the group's index, the tile's first position ``ctx``, the half the
    group lies in and ``fetched`` ``[G*bt, 1]``, which rows of it were
    brought in.

    A PARKED slot's step does none of that. Without a window, slot ``s`` is
    parked iff ``tables[s, 0] == 0``: block 0 is the trash block of every
    paged pool (``PagedFamily.init_pool``), the block manager never hands it
    out, ``serve/llm.py`` zeroes a freed slot's row (``_free_slot_locked``)
    and writes a live slot's row whole, its first entry the first block of
    the slot's chain (``_dispatch_prefill``; the tier and speculative paths
    write no row of their own), so a live slot's first entry is a real
    block. (Not ``lengths[s] == 0``: a prefill from position 0 has it.) Its
    last block reads -1, so it has no group and no live entry, on its own
    side and on the side of the step before it: no copy is started or awaited
    for it, nothing is attended or finalized, ``o_ref`` is zeros in one store
    (the caller's rows flow on into products and the trash block: finite),
    and, since every step counts on its first group being on its way, it
    starts the next step's into the half its own would have taken.

    ``unroll_full``: a full group's copies are started and awaited as ``G``
    straight-line DMAs, not by a loop (see ``live_copies``). ``window``: the
    walk starts at the group of the tile's first visible block
    (``_tile_first_block``), copies no entry before that block, and reads
    the table modulo ``nb_seq`` (a ring); group indices stay LOGICAL, so
    ``attend`` masks by position as ever. A ring's pool has NO trash block
    (slot 0's ring starts at block 0), so under a window every slot is
    walked.

    ``queries_ref``: of slot ``s``'s ``total`` queries the first
    ``queries_ref[s]`` are real (a prefill's prompt in its bucket; without the
    operand all are, and the traced body is the one the digests hold). The
    tile that straddles the count is bounded by its last real query, as the
    ragged last tile is, and ``finalize`` gets the count of its real queries
    (None without the operand) to zero the others' rows by. A tile of pad
    queries alone is a parked step, under a window too: its last block reads
    -1, so the step before it starts nothing for it, and it copies, attends
    and finalizes nothing, writes zeros and hands on the next step's first
    group (the next slot's: behind a pad tile the slot has only more of
    them)."""
    s = pl.program_id(0)
    i = pl.program_id(1)
    n_slots, n_tiles = pl.num_programs(0), pl.num_programs(1)
    bt, T, G = block_tokens, q_tile, group_blocks
    layer = layer_ref[0]
    ring = window is not None
    if ring and unroll_full:
        raise ValueError("the windowed walk starts its copies in a loop")

    def last_block(s_, i_):
        last = _tile_last_block(lengths_ref, s_, i_, T, total, bt, nb_seq,
                                ring, queries_ref)
        return last if ring else jnp.where(tables_ref[s_, 0] == 0, -1, last)

    def live_entries(last, g):
        """How many of group ``g``'s ``G`` entries are live, 0..G, where the
        tile's last live entry is ``last`` (-1: a parked slot's, none)."""
        return jnp.clip(last - g * G + 1, 0, G)

    def dead_entries(first, g):
        """How many of group ``g``'s leading entries lie wholly behind the
        window, 0..G, where the tile's first visible entry is ``first``."""
        return jnp.clip(first - g * G, 0, G)

    def live_copies(s_, g, n_live, half, act, n_dead=0):
        """``act`` on every pool's copy of entries ``n_dead .. n_live`` of
        group ``g`` of slot ``s_``, into rows ``j * bt`` of buffer ``half``.
        Start and wait both go through here, so a wait meets exactly the
        copies that were started. (A loop, not an unrolled ``pl.when`` an
        entry: a serve program lowers this kernel, and that tripled the time
        it takes.) A copy costs the scalar core ~46 ns to start and await
        inside the loop, twice what a 20 KB block takes to arrive, so the
        walk of a pool of small blocks is bound by that; ``unroll_full``
        takes a FULL group's, every group of a slot but its last, as ``G``
        straight-line copies at static offsets (~33 ns each: PERF.md, PR 34)
        and leaves the loop to the last."""
        def entry(j, _):
            b = g * G + j
            blk = tables_ref[s_, jax.lax.rem(b, nb_seq) if ring else b]
            rows = pl.ds(pl.multiple_of(j * bt, bt), bt)
            for n, (pool, buf) in enumerate(zip(pools, bufs)):
                act(pltpu.make_async_copy(
                    pool.at[layer, blk], buf.at[half, rows], sems.at[n, half]))

        if not unroll_full:
            jax.lax.fori_loop(n_dead, n_live, entry, None)
            return

        @pl.when(n_live == G)
        def _full():
            for j in range(G):
                entry(j, None)

        @pl.when(n_live < G)
        def _last():
            jax.lax.fori_loop(0, n_live, entry, None)

    def first_group(s_, i_):
        """(first visible entry, its group) of a tile; the walk's start."""
        first = _tile_first_block(lengths_ref, s_, i_, T, bt, window)
        return first, jax.lax.div(first, G)

    last_blk = last_block(s, i)
    n_groups = jax.lax.div(last_blk + G, G)           # ceil((last_blk+1)/G)
    if ring:
        first_blk, g0 = first_group(s, i)

    @pl.when(jnp.logical_and(s == 0, i == 0))
    def _first_step():
        half_ref[0] = 0
        first_g = g0 if ring else 0
        live_copies(s, first_g, live_entries(last_blk, first_g), 0,
                    lambda c: c.start(),
                    *([dead_entries(first_blk, first_g)] if ring else []))

    first_half = half_ref[0]

    # The step after this one: the next q tile of the slot, else the next
    # slot's first (clamped where there is none; ``has_next`` guards it).
    more_tiles = i + 1 < n_tiles
    next_s = jnp.minimum(jnp.where(more_tiles, s, s + 1), n_slots - 1)
    next_i = jnp.where(more_tiles, i + 1, 0)
    next_last = last_block(next_s, next_i)
    has_next = jnp.logical_or(more_tiles, s + 1 < n_slots)
    row = jax.lax.broadcasted_iota(jnp.int32, (G * bt, 1), 0)
    if ring:
        next_first, next_g0 = first_group(next_s, next_i)

    def group(g, _):
        half = jax.lax.rem(first_half + (g - g0 if ring else g), 2)
        # Start what comes next into the other half before computing on this
        # one: this step's next group, or the FIRST group of the next step,
        # so that no step opens with an exposed DMA.
        in_step = g + 1 < n_groups

        @pl.when(jnp.logical_or(in_step, has_next))
        def _prefetch():
            next_g = jnp.where(in_step, g + 1, next_g0 if ring else 0)
            live_copies(
                jnp.where(in_step, s, next_s), next_g,
                live_entries(jnp.where(in_step, last_blk, next_last), next_g),
                1 - half, lambda c: c.start(),
                *([dead_entries(jnp.where(in_step, first_blk, next_first),
                                next_g)] if ring else []))

        n_live = live_entries(last_blk, g)
        n_dead = dead_entries(first_blk, g) if ring else 0
        live_copies(s, g, n_live, half, lambda c: c.wait(), n_dead)
        # Entries past ``last_blk`` (the last group's tail) were not fetched,
        # nor those behind a window: their rows hold an earlier group's
        # data, or nothing yet.
        fetched = row < n_live * bt
        if ring:
            fetched = jnp.logical_and(fetched, row >= n_dead * bt)
        attend(g, lengths_ref[s] + i * T, half, fetched)

    def live_step():
        _init_accumulators(*accumulators, sink_ref)
        jax.lax.fori_loop(g0 if ring else 0, n_groups, group, None)
        # The half the prefetched first group of the next step went into.
        half_ref[0] = jax.lax.rem(
            first_half + (n_groups - g0 if ring else n_groups), 2)
        finalize(_tile_real_queries(queries_ref, s, i, T, total))

    if ring and queries_ref is None:
        live_step()
        return
    pl.when(last_blk >= 0)(live_step)

    @pl.when(last_blk < 0)
    def _parked_step():
        o_ref[...] = jnp.zeros_like(o_ref)

        # No group of this step's started the next step's first one: a live
        # slot that follows a parked one opens on that copy (a parked one's
        # is none), in the half ``half_ref`` still names.
        @pl.when(has_next)
        def _hand_on():
            first_g = next_g0 if ring else 0
            live_copies(next_s, first_g, live_entries(next_last, first_g),
                        first_half, lambda c: c.start(),
                        *([dead_entries(next_first, first_g)] if ring else []))


def _attend_buffers(q_ref, o_ref, k_buf, v_buf, accumulators, g, ctx, half,
                    fetched, *, scale: float, kv_heads: int, **walk):
    """``_attend_group`` on group ``g`` as it lies in half ``half`` of the K
    and V buffers of the kernels with two pools."""
    # Unfetched rows of K are masked whatever they hold; V's meet p = 0,
    # and 0 x NaN is NaN, so they are read as zero.
    _attend_group(
        q_ref, lambda d0, d1: k_buf[half, :, d0:d1],
        lambda d0, d1: jnp.where(fetched, v_buf[half, :, d0:d1], 0),
        g, ctx, *accumulators, scale=scale,
        num_heads=o_ref.shape[1], kv_heads=kv_heads,
        q_tile=walk["q_tile"], head_dim=k_buf.shape[-1] // kv_heads,
        group_tokens=walk["group_blocks"] * walk["block_tokens"],
        window=walk.get("window"), v_dim=o_ref.shape[-1])


def _paged_kernel(
    tables_ref, lengths_ref,   # scalar prefetch: [S, NB] int32, [S] int32
    layer_ref,                 # scalar prefetch: [1] int32
    q_ref,                     # [1, H*T, C*D] block — T = one q tile
    k_hbm, v_hbm,              # the whole pools [L, num_blocks, bt, KV*D], in HBM
    o_ref,                     # [1, H, T, D] block
    m_scr, l_scr, acc_scr,     # VMEM scratch: [H*T, 1], [H*T, 1], [H*T, C*D]
    k_buf, v_buf,              # VMEM scratch: [2, G*bt, KV*D] each
    sems,                      # DMA semaphores [2 (K, V), 2 (buffer half)]
    half_ref,                  # SMEM [1], the walk's
    *,
    scale: float,
    kv_heads: int,
    sink_ref=None,             # [H, 1] float32 (``_paged_sink_kernel``)
    queries_ref=None,          # scalar prefetch [S] (``_counted``)
    **walk,                    # _walk_live_groups' static arguments
):
    """Two pools, K and V, KV heads folded into the lanes: the walk
    (``_walk_live_groups``) with ``_attend_group`` on every group. A head of
    V may be narrower than a head of K: the pools' rows are ``KV * D`` and
    ``KV * Dv`` lanes, the accumulators and the output ``Dv`` wide."""
    def attend(g, ctx, half, fetched):
        _attend_buffers(q_ref, o_ref, k_buf, v_buf, (m_scr, l_scr, acc_scr),
                        g, ctx, half, fetched, scale=scale, kv_heads=kv_heads,
                        **walk)

    _walk_live_groups(
        tables_ref, lengths_ref, layer_ref, (k_hbm, v_hbm), (k_buf, v_buf),
        sems, half_ref, (m_scr, l_scr, acc_scr), attend,
        lambda real: _finalize(o_ref, l_scr, acc_scr, kv_heads, real), o_ref,
        sink_ref=sink_ref, queries_ref=queries_ref, **walk)


def _paged_sink_kernel(tables_ref, lengths_ref, layer_ref, q_ref, k_hbm, v_hbm,
                       sink_ref, *rest, **kw):
    """``_paged_kernel`` with one operand more, the sinks ``[H, 1]``."""
    _paged_kernel(tables_ref, lengths_ref, layer_ref, q_ref, k_hbm, v_hbm,
                  *rest, sink_ref=sink_ref, **kw)


def _counted(kernel):
    """``kernel`` with one scalar-prefetch operand more, the slots' counts of
    real queries ``[S]`` behind ``layer``: what a caller that passes
    ``queries`` lowers. The kernel without it is untouched."""
    def counted(tables_ref, lengths_ref, layer_ref, queries_ref, *rest, **kw):
        kernel(tables_ref, lengths_ref, layer_ref, *rest,
               queries_ref=queries_ref, **kw)

    return counted


def _paged_append_kernel(
    tables_ref, lengths_ref, layer_ref,   # scalar prefetch, as _paged_kernel
    q_ref,                     # [1, H, C*D] block: one query a head
    k_in, v_in,                # the pools as they came in: aliased, not read
    k_row_ref, v_row_ref,      # [S, KV*D] float32: every slot's new K and V row
    o_ref,                     # [1, H, 1, D] block
    k_hbm, v_hbm,              # the pools [L, num_blocks, bt, KV*D], in HBM
    m_scr, l_scr, acc_scr, k_buf, v_buf, sems, half_ref,   # _paged_kernel's
    back_sems,                 # DMA semaphores [2 (K, V)]: the write back
    *,
    scale: float,
    kv_heads: int,
    **walk,                    # _walk_live_groups' static arguments
):
    """``_paged_kernel`` for a decode step whose new row is not in the pool
    yet. The walk is the same (the slot's last group holds the block of
    position ``lengths[s]``, fetched as ever); when that group has arrived
    the row is laid into its place in the buffer, so the dots see it where
    the scatter would have put it, and the block goes back to
    ``pool[layer, tables[s, lengths[s] // bt]]`` whole, the rows beside the
    new one as they were fetched: a copy started before the group is
    attended and awaited after the output is written, before the next step
    may fetch into that half. (One row cannot go alone: two bfloat16 rows
    share the 32-bit words of the pool's tiles, and Mosaic slices neither
    side of a copy finer than a tile.) A block a decode step writes is its
    slot's own: the engine copies a shared one first. Nothing is laid in or
    written for a slot at table capacity (its output is dead) nor into the
    trash block, and a parked slot has no group at all."""
    del k_in, v_in
    s = pl.program_id(0)
    bt, nb_seq, G = walk["block_tokens"], walk["nb_seq"], walk["group_blocks"]
    pos = lengths_ref[s]
    entry = jnp.minimum(jax.lax.div(pos, bt), nb_seq - 1)
    blk = tables_ref[s, entry]
    # What is started in the slot's last group is awaited after the walk:
    # the walk's own test of a parked slot, which has no group, is in it.
    writes = jnp.logical_and(
        jnp.logical_and(tables_ref[s, 0] != 0, pos < nb_seq * bt), blk != 0)
    # the block's rows of its group's buffer
    rows = pl.ds(pl.multiple_of(jax.lax.rem(entry, G) * bt, bt), bt)

    def write_back(half, act):
        for n, (buf, pool) in enumerate(((k_buf, k_hbm), (v_buf, v_hbm))):
            act(pltpu.make_async_copy(
                buf.at[half, rows], pool.at[layer_ref[0], blk],
                back_sems.at[n]))

    def lay_in(half):
        new = jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0) == jax.lax.rem(
            pos, bt)
        for buf, row in ((k_buf, k_row_ref), (v_buf, v_row_ref)):
            # in float32: exact, and no select of packed rows
            buf[half, rows] = jnp.where(
                new, row[pl.ds(s, 1), :],
                buf[half, rows].astype(jnp.float32)).astype(buf.dtype)
        write_back(half, lambda c: c.start())

    def attend(g, ctx, half, fetched):
        pl.when(jnp.logical_and(writes, g == jax.lax.div(entry, G)))(
            lambda: lay_in(half))
        _attend_buffers(q_ref, o_ref, k_buf, v_buf, (m_scr, l_scr, acc_scr),
                        g, ctx, half, fetched, scale=scale, kv_heads=kv_heads,
                        **walk)

    _walk_live_groups(
        tables_ref, lengths_ref, layer_ref, (k_hbm, v_hbm), (k_buf, v_buf),
        sems, half_ref, (m_scr, l_scr, acc_scr), attend,
        lambda real: _finalize(o_ref, l_scr, acc_scr, kv_heads, real), o_ref,
        **walk)
    # The half the last group lay in: the one before the half the walk left
    # for the next step's first group.
    # raylint: ignore[untimed-wait] — a DMA semaphore inside the kernel
    pl.when(writes)(lambda: write_back(1 - half_ref[0], lambda c: c.wait()))


def _paged_kernel_unaligned(
    tables_ref, lengths_ref, layer_ref,   # scalar prefetch, as _paged_kernel
    q_ref,                                # [1, H*T, C*D] block
    *rest,                                # G K blocks, G V blocks [1, bt, KV*D];
                                          # out; m, l, acc scratch
    scale: float,
    kv_heads: int,
    block_tokens: int,
    q_tile: int,
    total: int,                # queries over all tiles (the last may be ragged)
    nb_seq: int,
    group_blocks: int,
    queries_ref=None,          # scalar prefetch [S] (``_counted``)
):
    """The same groups for a folded width off the 128-lane grid, where
    Mosaic refuses to slice a block out of the pool for a DMA (the ref's
    last dimension is padded to lanes, and a slice must be a multiple of
    128 of them): the groups are a grid axis, a group's ``G`` blocks come in
    through ``G`` BlockSpecs (``_clamped_block_index``), and a step past the
    slot's last group skips its body and re-fetches nothing. A tile of pad
    queries (``queries_ref``) has no live block: every step of it skips the
    body, and its accumulators finalize to zeros."""
    G, bt, T = group_blocks, block_tokens, q_tile
    k_refs, v_refs = rest[:G], rest[G:2 * G]
    o_ref = rest[2 * G]
    m_scr, l_scr, acc_scr = rest[2 * G + 1:]
    s, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    ctx = lengths_ref[s] + i * T
    last_blk = _tile_last_block(lengths_ref, s, i, T, total, bt, nb_seq,
                                queries_ref=queries_ref)

    @pl.when(j == 0)
    def _init():
        _init_accumulators(m_scr, l_scr, acc_scr)

    @pl.when(j * G <= last_blk)
    def _body():
        gather = lambda refs: lambda d0, d1: jnp.concatenate(  # noqa: E731
            [r[0, :, d0:d1] for r in refs], axis=0)
        _attend_group(
            q_ref, gather(k_refs), gather(v_refs), j, ctx, m_scr, l_scr,
            acc_scr, scale=scale, num_heads=o_ref.shape[1],
            kv_heads=kv_heads, q_tile=T, head_dim=o_ref.shape[-1],
            group_tokens=G * bt)

    @pl.when(j == pl.num_programs(2) - 1)
    def _done():
        _finalize(o_ref, l_scr, acc_scr, kv_heads,
                  _tile_real_queries(queries_ref, s, i, T, total))


def paged_attention(
    q: jax.Array,                # [S, T, H, D]
    k_pool: jax.Array,           # [L, num_blocks, bt, KV*D] (the whole pool)
    v_pool: jax.Array,
    tables: jax.Array,           # [S, NB] int32 — pool block ids, 0 = trash
    lengths: jax.Array,          # [S] int32 — valid context BEFORE the T tokens
    layer,                       # int or int32 scalar — which layer's blocks
    *,
    scale: Optional[float] = None,
    interpret: bool = False,
    window: Optional[int] = None,
    sinks: Optional[jax.Array] = None,
    queries: Optional[jax.Array] = None,     # [S] int32: real queries a slot
) -> jax.Array:
    """Fused paged-attention over the block pool; returns [S, T, H, Dv].

    Query t of slot s is at absolute position ``lengths[s] + t`` and attends
    positions ``<= lengths[s] + t`` of layer ``layer`` gathered through
    ``tables[s]``. No ``[S, max_len, H, D]`` intermediate exists at any
    point, and the pool is read in place: a caller holding one layer's pool
    passes ``pool[None]`` and layer 0.

    Block 0 is the pool's trash block, never a live slot's: a slot whose
    table begins with it (``tables[s, 0] == 0``, no window) is PARKED, is not
    walked, and its rows of the result are zeros (over a pool row of whole
    128-lane tiles; the form for other widths attends its trash block).

    The pool's row holds ``KV`` heads of ``D``, ``KV`` a divisor of ``H``
    (grouped-query attention): query head ``h`` reads KV head ``h // (H //
    KV)``, and a fetched block serves all of a KV head's query heads. With
    ``KV == H`` it is the multi-head program it always was.

    ``window``: a sliding-window layer. Query ``i`` sees key ``j`` iff ``j <=
    i`` and ``i - j < window``; the walk starts at the first visible block
    and ``tables[s]`` is read modulo its width: a RING of ``NB`` blocks in
    which position ``p`` lies in entry ``(p // bt) mod NB`` (the caller's to
    keep at ``NB * bt >= window + T - 1 + bt`` rows, or to cover the whole
    context with, as a prefill over its fresh rows does).

    A head of V may be NARROWER (or wider) than a head of K: ``v_pool``
    ``[L, num_blocks, bt, KV*Dv]`` beside ``k_pool`` ``[.., KV*D]``; the
    result is ``Dv`` wide. ``sinks`` [H] float32: a learned score a query
    head that enters the softmax's denominator and carries no value, ``p_ij
    = exp(s_ij) / (exp(sinks[h]) + sum_j' exp(s_ij'))`` (not scaled by
    ``scale``). Both take the walk over whole 128-lane rows, of both pools;
    a call that passes neither traces the body it always did.

    ``queries`` [S]: of slot ``s``'s ``T`` query rows the first
    ``queries[s]`` are real (a prefill hands in its prompt's length in the
    bucket). The walk then follows the real rows alone: a query tile with
    none of them starts no copy and attends nothing, under a window too, and
    every pad row of the result is zeros; the real rows are what the call
    without the operand gives, bit for bit. A call without it (a decode step,
    a verify: every row real) traces the body it always did."""
    S, T, H, D = q.shape
    if window is not None and (window < 1 or k_pool.shape[3] % 128):
        raise ValueError(
            f"window {window} over a pool row of {k_pool.shape[3]} lanes: "
            f"the windowed walk is the loop over whole 128-lane rows")
    if (k_pool.ndim != 4 or k_pool.shape[3] % D
            or H % max(1, k_pool.shape[3] // D)):
        raise ValueError(
            f"pool {k_pool.shape} is not [L, num_blocks, bt, KV*{D}] with KV "
            f"a divisor of {H} heads: the kernel reads the whole folded pool "
            f"(one layer's: pool[None])")
    W, Wv = k_pool.shape[3], v_pool.shape[3]
    if v_pool.shape[:3] != k_pool.shape[:3] or Wv % (W // D):
        raise ValueError(
            f"V pool {v_pool.shape} beside K pool {k_pool.shape}: want the "
            f"same blocks and a row of the same {W // D} KV heads")
    if (Wv != W or sinks is not None) and (W % 128 or Wv % 128):
        raise ValueError(
            f"pool rows of {W} and {Wv} lanes: a V head of another width and "
            f"a sink take the loop over whole 128-lane rows")
    if sinks is not None:
        if sinks.shape != (H,):
            raise ValueError(f"sinks {sinks.shape}: want one a query head, [{H}]")
        sinks = sinks.astype(jnp.float32).reshape(H, 1)
    return _paged_attention(
        q, k_pool, v_pool, tables.astype(jnp.int32),
        lengths.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
        sinks=sinks, queries=_real_query_counts(queries, S),
        scale=float(scale) if scale is not None else 1.0 / D**0.5,
        interpret=interpret, window=None if window is None else int(window))


def _real_query_counts(queries, slots: int):
    """``queries`` as the kernels' fourth prefetched operand, int32 ``[S]``
    (a prefill's one slot may hand in a scalar), or None."""
    if queries is None:
        return None
    queries = jnp.asarray(queries, jnp.int32).reshape(-1)
    if queries.shape != (slots,):
        raise ValueError(f"queries {queries.shape}: want one count a slot, "
                         f"[{slots}]")
    return queries


# The appending call holds every slot's new K and V row in VMEM for the whole
# call, in float32 and double-buffered: 2 operands x 2 buffers x S x W x 4
# bytes (0.6 MB at 36 slots of 1,024 lanes) beside the walk's 4 MB of groups
# under the 16 MB of scoped VMEM.
_APPEND_ROWS_VMEM_BYTES = 2 << 20


def append_rows_fit(slots: int, width: int) -> bool:
    """Whether :func:`paged_attention_append` takes ``slots`` rows of
    ``width`` lanes: whole 128-lane tiles, and all of them in VMEM at once
    (``slots * width <= 131,072``: 128 slots of 1,024 lanes). A caller that
    gets False scatters its rows and calls :func:`paged_attention`."""
    return width % 128 == 0 and 16 * slots * width <= _APPEND_ROWS_VMEM_BYTES


def paged_attention_append(
    q: jax.Array,                # [S, 1, H, D]: one decode step's queries
    k_row: jax.Array,            # [S, KV*D]: the step's new K row a slot
    v_row: jax.Array,
    k_pool: jax.Array,           # [L, num_blocks, bt, KV*D] (the whole pool)
    v_pool: jax.Array,
    tables: jax.Array,           # [S, NB] int32
    lengths: jax.Array,          # [S] int32: rows of the slot in the pool
    layer,
    *,
    scale: Optional[float] = None,
    interpret: bool = False,
):
    """A decode step's attention AND its pool write in one call; returns
    ``(out [S, 1, H, D], k_pool, v_pool)``, the pools updated where they lie
    (the call aliases them through: donate them, or XLA copies them first).

    Slot ``s``'s query sits at position ``lengths[s]`` and attends the
    slot's ``lengths[s]`` rows of the pool and ``k_row[s]`` / ``v_row[s]``,
    its own, which the kernel takes from the operand (rounded to the pool's
    type) and leaves at ``pool[layer, tables[s, lengths[s] // bt],
    lengths[s] % bt]``, its block written back under the walk: what
    ``pool.at[layer, blk, off].set(row)`` followed by
    :func:`paged_attention` gives, bit for bit, without the scatter's pass
    over HBM and the fetch of the row back. A parked slot (``tables[s, 0]
    == 0``) writes nothing and reads zeros, a slot at table capacity
    (``lengths[s] >= NB * bt``) writes nothing and its output is dead, and
    no row goes to the trash block: the scatter sent all three there. Pools
    whose row is whole 128-lane tiles only (the kernel that walks by DMA),
    and no more rows than :func:`append_rows_fit` allows: they all lie in
    VMEM for the whole call."""
    S, T, H, D = q.shape
    W = k_pool.shape[3] if k_pool.ndim == 4 else 0
    if (T != 1 or not W or W % D or H % (W // D) or not append_rows_fit(S, W)
            or k_row.shape != (S, W) or v_row.shape != (S, W)):
        raise ValueError(
            f"q {q.shape} and rows {k_row.shape} over a pool {k_pool.shape}: "
            f"the appending call takes one query a slot, [S, KV*{D}] rows "
            f"and a pool [L, num_blocks, bt, KV*{D}] of whole 128-lane tiles, "
            f"S x KV*{D} at most {_APPEND_ROWS_VMEM_BYTES // 16}")
    return _paged_attention(
        q, k_pool, v_pool, tables.astype(jnp.int32),
        lengths.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
        (k_row.astype(k_pool.dtype), v_row.astype(v_pool.dtype)),
        scale=float(scale) if scale is not None else 1.0 / D**0.5,
        interpret=interpret)


# Rows (query heads x queries) of a tile's float32 accumulators: m and l are
# padded to 128 lanes, so three buffers of rows x 512 bytes. 30 heads of 128
# at the full tile fit the 16 MB of scoped VMEM; 48 heads take half a tile.
_Q_TILE_ROWS = 4096
# What a tile's q block (double-buffered) may take of VMEM: 64 heads of 192
# in chunks of two take a tile of 32 queries (compile-only for a v5e).
_Q_BLOCK_VMEM_BYTES = 4 << 20


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "window"))
def _paged_attention(q, k_pool, v_pool, tables, lengths, layer, rows=None,
                     sinks=None, queries=None, *, scale, interpret,
                     window=None):
    """:func:`paged_attention` on checked operands, ``layer`` an int32[1]
    VALUE: a jit of its own, so that a program that calls it once a layer
    (24 unrolled layers, eight serve programs) traces and lowers the kernel
    once and calls it 24 times; XLA inlines the calls. ``rows``: the new
    ``(k_row, v_row)`` of :func:`paged_attention_append`, whose kernel and
    results (the pools beside the output) these are then. ``sinks``
    [H, 1] float32 or None. ``queries`` int32 [S] or None: a fourth
    prefetched operand and the kernel that reads it (``_counted``)."""
    S, T, H, D = q.shape
    bt = k_pool.shape[2]
    W = k_pool.shape[3]                               # a row: KV heads x D
    KV = W // D
    R = H // KV                                       # query heads a KV head
    Wv = v_pool.shape[3]                              # V's row: KV heads x Dv
    Dv = Wv // KV
    nb_seq = tables.shape[1]
    qt = q.transpose(0, 2, 1, 3)                      # [S, H, T, D]
    tq = min(T, _Q_TILE)
    while H * tq > _Q_TILE_ROWS and tq > 8:
        tq //= 2
    # ... and a tile's q block (two buffers of it): a chunk of heads wider
    # than 128 lanes (two heads of 192) makes its rows that much longer.
    while (H * tq > 128 and 2 * H * tq * _heads_per_chunk(KV, R * tq, D, Dv)
           * D * q.dtype.itemsize > _Q_BLOCK_VMEM_BYTES and tq > 8):
        tq //= 2
    q_tiles = pl.cdiv(T, tq)
    if T % tq:
        # Ragged last tile: its pad queries are causally AHEAD of every real
        # one, the walk is bounded by the real ones (``total``, and the
        # caller's ``queries`` below it) and their rows are sliced off below.
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, q_tiles * tq - T), (0, 0)))

    C = _heads_per_chunk(KV, R * tq, D, Dv)
    # q of head h sits in the lanes of its KV head, (h // R % C) * D of a
    # C*D-wide row, zeros beside it: one dot of a chunk's rows against the
    # chunk's lanes then gives every head its own scores. Rows of a tile are
    # (head, query).
    own = jnp.arange(C)[None, :] == (jnp.arange(H) // R % C)[:, None]  # [H, C]
    qw = jnp.where(own[None, :, None, :, None], qt[:, :, :, None, :], 0)
    qw = qw.reshape(S, H, q_tiles, tq, C * D).transpose(0, 2, 1, 3, 4)
    qw = qw.reshape(S, q_tiles, H * tq, C * D)

    # (the mean of the two rows: the K and V buffers' four halves together)
    G = _blocks_per_group(bt, (W + Wv) // 2, k_pool.dtype.itemsize)
    accumulators = [
        pltpu.VMEM((H * tq, 1), jnp.float32),
        pltpu.VMEM((H * tq, 1), jnp.float32),
        pltpu.VMEM((H * tq, C * Dv), jnp.float32),
    ]
    walk = dict(block_tokens=bt, q_tile=tq, total=T, nb_seq=nb_seq,
                group_blocks=G)
    if W % 128 == 0:
        if window is not None:
            walk["window"] = window
        kernel, grid = _paged_kernel, (S, q_tiles)
        kv_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 2
        scratch = accumulators + [
            pltpu.VMEM((2, G * bt, W), k_pool.dtype),
            pltpu.VMEM((2, G * bt, Wv), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ]
        pools = (k_pool, v_pool)
    else:
        kernel, grid = _paged_kernel_unaligned, (S, q_tiles, pl.cdiv(nb_seq, G))
        kv_specs = [
            pl.BlockSpec((None, 1, bt, W),
                         _clamped_block_index(tq, bt, G, g, nb_seq, T))
            for g in range(G)] * 2
        scratch = accumulators
        pools = (k_pool,) * G + (v_pool,) * G

    q_spec = pl.BlockSpec((None, 1, H * tq, C * D),
                          lambda s, i, *_: (s, i, 0, 0))
    out_spec = pl.BlockSpec((1, H, tq, Dv), lambda s, i, *_: (s, 0, i, 0))
    out_shape = jax.ShapeDtypeStruct((S, H, q_tiles * tq, Dv), q.dtype)
    # The name a profiler prints for the kernel, whatever calls it: a
    # windowed call has names of its own, so that a trace tells a stack's
    # window layers from its full ones.
    name = (("paged" if window is None else "window")
            + ("_decode_attn" if T == 1 else "_prefill_attn"))
    if rows is not None:
        # Every slot's row in VMEM for the whole call, in float32: the step
        # picks its own out, one row of 32-bit words (a bfloat16 row shares
        # its words with a neighbour, and a block of one row a slot made XLA
        # re-tile the projections' output before every call).
        row_spec = pl.BlockSpec((S, W), lambda s, i, *_: (0, 0))
        out, k_pool, v_pool = pl.pallas_call(
            functools.partial(_paged_append_kernel, scale=scale, kv_heads=KV,
                              **walk),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=grid,
                in_specs=[q_spec] + kv_specs + [row_spec] * 2,
                out_specs=[out_spec] + kv_specs,
                scratch_shapes=scratch + [pltpu.SemaphoreType.DMA((2,))]),
            out_shape=[out_shape, k_pool, v_pool],
            # operands 4 and 5 (after the three prefetched and q): the pools
            input_output_aliases={4: 1, 5: 2},
            interpret=interpret, name=name,
        )(tables, lengths, layer, qw, *pools,
          *(r.astype(jnp.float32) for r in rows))
        return out.transpose(0, 2, 1, 3), k_pool, v_pool
    extra = []
    if sinks is not None:
        # one block, the same at every grid step: fetched once
        kernel = _paged_sink_kernel
        extra = [pl.BlockSpec((H, 1), lambda s, i, *_: (0, 0))]
    counts = [] if queries is None else [queries]
    if counts:
        kernel = _counted(kernel)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 + len(counts),
        grid=grid,
        in_specs=[q_spec] + kv_specs + extra,
        out_specs=out_spec,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        functools.partial(kernel, scale=scale, kv_heads=KV, **walk),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name=name,
    )(tables, lengths, layer, *counts, qw, *pools,
      *([] if sinks is None else [sinks]))
    return out[:, :, :T].transpose(0, 2, 1, 3)        # [S, T, H, Dv]


def _softmax_with_sinks(scores, sinks):
    """Softmax over the last axis of ``scores`` [S, H, T, n]; ``sinks`` [H]
    adds one column a head that takes probability and is then dropped."""
    if sinks is None:
        return jax.nn.softmax(scores, axis=-1)
    col = jnp.broadcast_to(sinks.astype(jnp.float32)[None, :, None, None],
                           scores.shape[:3] + (1,))
    return jax.nn.softmax(jnp.concatenate([scores, col], axis=-1),
                          axis=-1)[..., :-1]


def paged_attention_reference(q, k_pool, v_pool, tables, lengths, layer, *,
                              scale: Optional[float] = None,
                              window: Optional[int] = None,
                              sinks: Optional[jax.Array] = None) -> jax.Array:
    """Gather-path oracle over the same operands: materializes
    [S, NB*bt, H, D] of ``layer`` through the table and runs masked dense
    attention — numerically what the pre-kernel decode did, kept as the
    equivalence target and the CPU fallback reference. ``window``: every
    query gathers the ``window`` positions at and before its own through the
    table read modulo its width (the ring of :func:`paged_attention`), so
    [S, T, window, H, D]: an oracle and a CPU path, for no chip's sizes.
    ``sinks`` and a V row of another width as :func:`paged_attention`."""
    S, T, H, D = q.shape
    bt = k_pool.shape[2]
    nb_seq = tables.shape[1]
    s_val = scale if scale is not None else 1.0 / D**0.5
    KV = k_pool.shape[3] // D
    Dv = v_pool.shape[3] // KV
    q_pos = lengths.reshape(-1, 1) + jnp.arange(T)[None, :]         # [S, T]
    if window is not None:
        pos = q_pos[:, :, None] - jnp.arange(window)[None, None, :]  # [S, T, W]
        seen = pos >= 0
        pos = jnp.maximum(pos, 0)
        blk = tables[jnp.arange(S)[:, None, None], (pos // bt) % nb_seq]
        kc = k_pool[layer, blk, pos % bt].reshape(S, T, window, KV, D)
        vc = v_pool[layer, blk, pos % bt].reshape(S, T, window, KV, Dv)
        if KV != H:
            kc, vc = (jnp.repeat(a, H // KV, axis=3) for a in (kc, vc))
        scores = jnp.einsum("bthd,btshd->bhts", q, kc,
                            preferred_element_type=jnp.float32) * s_val
        scores = jnp.where(seen[:, None], scores, _NEG_INF)
        probs = _softmax_with_sinks(scores, sinks)
        out = jnp.einsum("bhts,btshd->bthd", probs, vc.astype(jnp.float32))
        return out.astype(q.dtype)
    kc = k_pool[layer, tables].reshape(S, nb_seq * bt, KV, D)
    vc = v_pool[layer, tables].reshape(S, nb_seq * bt, KV, Dv)
    if KV != H:             # grouped: query head h reads KV head h // (H // KV)
        kc, vc = (jnp.repeat(a, H // KV, axis=2) for a in (kc, vc))
    scores = jnp.einsum("bthd,bshd->bhts", q, kc,
                        preferred_element_type=jnp.float32) * s_val
    kv_pos = jnp.arange(nb_seq * bt)[None, None, None, :]
    scores = jnp.where(kv_pos <= q_pos[:, None, :, None], scores, _NEG_INF)
    probs = _softmax_with_sinks(scores, sinks)
    out = jnp.einsum("bhts,bshd->bthd", probs, vc.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Latent attention (MLA) over a paged pool of latent rows
# ---------------------------------------------------------------------------
# One row a token a sublayer: ``[c_kv (value_lanes) | k_rope | zero pad]``,
# shared by every query head. Keys are the whole row, values its first
# ``value_lanes`` lanes, and the up-projections are absorbed into q and into
# the output by the caller, so the kernel's dot is a real ``[T*H, W] x
# [W, tokens]`` product: the rows are the heads, where the kernel above needs
# a block-diagonal q to give each head its own lanes. The table walk is the
# kernel above's (``_walk_live_groups``: grid (slots, query tiles), a loop
# over a slot's live groups, the pool in HBM) with ONE pool and so one DMA a
# block: keys and values are read out of the same buffer. A row's width is
# padded to whole 128-lane tiles by its family (``LatentSpec.pool_width``),
# so there is no unaligned form.

_LATENT_Q_TILE = 16          # x 64 heads = 1024 rows of f32 accumulators


def _latent_group_kv(rows: int) -> int:
    """kv positions one iteration of the latent walk takes, by the rows
    (queries x heads) of its dots. An iteration is a chain dot -> max -> exp
    -> dot that costs ~0.5 us whatever it holds, so decode's 64 rows want
    many positions; a group is computed whole, so a short context pays for
    all of them. Measured on a v5e (PERF.md, PR 34; the copies still
    started in a loop), 128 / 256 / 512 / 1,024 positions: 96 slots of ~1,700 tokens 1.24 / 0.91 / 0.77 / 0.74 ms a
    call, 128 slots of ~480 tokens 0.50 / 0.39 / 0.35 / 0.36. A prefill
    tile's 1,024 rows fill the MXU at any of them (the 2,048 bucket 4.9 /
    2.75 / 2.76 ms), and at 512 its float32 scores leave 2 of the 16 MB of
    scoped VMEM."""
    return 512 if rows <= 128 else 256


def _latent_kernel(
    tables_ref, lengths_ref, layer_ref,   # scalar prefetch, as _paged_kernel
    q_ref,                     # [1, T*H, W] block; row = (query, head)
    pool_hbm,                  # the whole pool [A, num_blocks, bt, W], in HBM
    o_ref,                     # [1, T*H, value_lanes] block
    m_scr, l_scr, acc_scr,     # VMEM scratch: [T*H, 1] x 2, [T*H, value_lanes]
    kv_buf,                    # VMEM scratch: [2, G*bt, W]
    sems,                      # DMA semaphores [1, 2 (buffer half)]
    half_ref,                  # SMEM [1], the walk's
    *,
    scale: float,
    num_heads: int,
    value_lanes: int,
    keep_ref=None,             # [n_groups, T, G*bt] block: see _keep_rows
    queries_ref=None,          # scalar prefetch [S] (``_counted``)
    **walk,                    # _walk_live_groups' static arguments
):
    H, T = num_heads, walk["q_tile"]
    rows, tokens = T * H, walk["group_blocks"] * walk["block_tokens"]

    def attend(g, ctx, half, fetched):
        kv = kv_buf[half]                                       # [G*bt, W]
        scores = jax.lax.dot_general(
            q_ref[0], kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale         # [rows, G*bt]
        kv_pos = g * tokens + jax.lax.broadcasted_iota(
            jnp.int32, (rows, tokens), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, tokens), 0)
        q_pos = ctx + (0 if T == 1 else jax.lax.div(row, H))
        visible = kv_pos <= q_pos
        if keep_ref is not None:
            visible = jnp.logical_and(visible, _keep_rows(keep_ref[g], H))
        scores = jnp.where(visible, scores, _NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=-1, keepdims=True)
        # Unfetched rows are masked as keys whatever they hold; as values
        # they meet p = 0, and 0 x NaN is NaN, so they are read as zero.
        pv = jax.lax.dot_general(
            p.astype(kv.dtype), jnp.where(fetched, kv[:, :value_lanes], 0),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                 # [rows, V]
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = m_new

    def finalize(real):
        out = (acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)).astype(o_ref.dtype)
        if real is not None:                          # rows are (query, head)
            out = _real_rows(out, real, lambda row: jax.lax.div(row, H))
        o_ref[0] = out

    _walk_live_groups(
        tables_ref, lengths_ref, layer_ref, (pool_hbm,), (kv_buf,), sems,
        half_ref, (m_scr, l_scr, acc_scr), attend, finalize, o_ref,
        queries_ref=queries_ref, **walk)


def _keep_rows(keep, num_heads: int):
    """A group's keep bits ``[T, tokens]`` (1.0 or 0.0, one row a QUERY) as
    the ``[T * num_heads, tokens]`` booleans of the latent kernel's score
    rows (query-major: row ``t * H + h``). A decode step's one row
    broadcasts; a tile's rows are repeated by a product with the one-hot
    ``[T * H, T]``, which the matrix unit does beside the scores' own
    (``T / W`` of their work) and Mosaic lowers whatever ``T`` and ``H``."""
    T, tokens = keep.shape
    if T == 1:        # (a v5e compares no bfloat16)
        return jnp.broadcast_to(keep.astype(jnp.float32),
                                (num_heads, tokens)) > 0
    row = jax.lax.broadcasted_iota(jnp.int32, (T * num_heads, T), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (T * num_heads, T), 1)
    own = (jax.lax.div(row, num_heads) == col).astype(keep.dtype)
    return jax.lax.dot_general(
        own, keep, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) > 0


def _latent_keep_kernel(tables_ref, lengths_ref, layer_ref, q_ref, keep_ref,
                        pool_hbm, o_ref, *scratch, **kw):
    """``_latent_kernel`` with one more operand, a keep bit a (query, kv
    position): a query attends a position only if its bit is set (and the
    position is visible). The body without the operand is untouched."""
    _latent_kernel(tables_ref, lengths_ref, layer_ref, q_ref, pool_hbm, o_ref,
                   *scratch, keep_ref=keep_ref, **kw)


def latent_paged_attention(
    q: jax.Array,                # [S, T, H, W] — absorbed queries, W = row width
    pool: jax.Array,             # [A, num_blocks, bt, W] latent rows, A sublayers
    tables: jax.Array,           # [S, NB] int32
    lengths: jax.Array,          # [S] int32 — valid context BEFORE the T tokens
    layer,                       # which attention sublayer's blocks
    *,
    value_lanes: int,
    scale: float,
    interpret: bool = False,
    keep: Optional[jax.Array] = None,    # [S, T, NB * bt] bool
    queries: Optional[jax.Array] = None,     # [S] int32: real queries a slot
) -> jax.Array:
    """Softmax over the latent rows, returns ``sum_j p_j row_j[:value_lanes]``
    as [S, T, H, value_lanes]: the caller up-projects it per head. Query t of
    slot s sits at ``lengths[s] + t`` and attends positions ``<=`` it; the T
    new rows must already be in the pool. A slot whose table begins with the
    trash block 0 is parked: not walked, its rows zeros (as
    :func:`paged_attention`). ``keep``: query t of slot s attends position j
    only if ``keep[s, t, j]`` as well (a learned selection,
    ``ops/sparse_select.py``); the walk still fetches every live block.
    ``queries``: as :func:`paged_attention`'s, the walk follows the slot's
    first ``queries[s]`` query rows alone and the others read zeros."""
    W = q.shape[3]
    if pool.ndim != 4 or pool.shape[3] != W or W % 128:
        raise ValueError(
            f"pool {pool.shape} is not [A, num_blocks, bt, {W}] of whole "
            f"128-lane tiles")
    operands = (q, pool, tables.astype(jnp.int32), lengths.astype(jnp.int32),
                jnp.asarray(layer, jnp.int32).reshape(1))
    if keep is not None and keep.shape != (
            *q.shape[:2], tables.shape[1] * pool.shape[2]):
        raise ValueError(f"keep {keep.shape} is not [slots, queries, table "
                         f"entries x block tokens]")
    return _latent_attention(*operands, keep,
                             _real_query_counts(queries, q.shape[0]),
                             value_lanes=value_lanes, scale=float(scale),
                             interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("value_lanes", "scale", "interpret"))
def _latent_attention(q, pool, tables, lengths, layer, keep=None,
                      queries=None, *, value_lanes, scale, interpret):
    """:func:`latent_paged_attention` on checked operands: a jit of its own,
    as :func:`_paged_attention` is and for its reason (a serve program calls
    it once a sublayer, and there are ten of them a family)."""
    S, T, H, W = q.shape
    bt = pool.shape[2]
    tq = min(T, _LATENT_Q_TILE)
    q_tiles = pl.cdiv(T, tq)
    if T % tq:
        # Ragged last tile: as ``_paged_attention``'s, bounded by its real
        # queries and sliced off below.
        q = jnp.pad(q, ((0, 0), (0, q_tiles * tq - T), (0, 0), (0, 0)))
    qr = q.reshape(S, q_tiles, tq * H, W)
    G = _blocks_per_group(bt, W, pool.dtype.itemsize,
                          _latent_group_kv(tq * H))

    def q_index(s, i, *_):
        return (s, i, 0, 0)

    kernel, keep_ops, keep_specs = _latent_kernel, (), []
    if keep is not None:
        # The bits as the walk takes them: a block a (slot, query tile) of
        # ``[groups, tq, G*bt]``, so that a group's are one index of a leading
        # dimension; 1.0 / 0.0 in q's type (a tile of 16 queries is one
        # bfloat16 sublane tile). Pad queries and pad positions keep nothing.
        tokens = G * bt
        n_groups = pl.cdiv(keep.shape[2], tokens)
        keep = jnp.pad(keep, ((0, 0), (0, q_tiles * tq - T),
                              (0, n_groups * tokens - keep.shape[2])))
        keep = keep.reshape(S, q_tiles, tq, n_groups, tokens).swapaxes(2, 3)
        kernel, keep_ops = _latent_keep_kernel, (keep.astype(q.dtype),)
        keep_specs = [pl.BlockSpec((None, None, n_groups, tq, tokens),
                                   lambda s, i, *_: (s, i, 0, 0, 0))]

    counts = [] if queries is None else [queries]
    if counts:
        kernel = _counted(kernel)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 + len(counts),
        grid=(S, q_tiles),
        in_specs=[pl.BlockSpec((None, 1, tq * H, W), q_index), *keep_specs,
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, 1, tq * H, value_lanes), q_index),
        scratch_shapes=[
            pltpu.VMEM((tq * H, 1), jnp.float32),
            pltpu.VMEM((tq * H, 1), jnp.float32),
            pltpu.VMEM((tq * H, value_lanes), jnp.float32),
            pltpu.VMEM((2, G * bt, W), pool.dtype),
            pltpu.SemaphoreType.DMA((1, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            kernel, scale=scale, num_heads=H, value_lanes=value_lanes,
            block_tokens=bt, q_tile=tq, total=T, nb_seq=tables.shape[1],
            group_blocks=G, unroll_full=True),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, q_tiles, tq * H, value_lanes),
                                       q.dtype),
        interpret=interpret,
        name="mla_decode_attn" if T == 1 else "mla_prefill_attn",
    )(tables, lengths, layer, *counts, qr, *keep_ops, pool)
    return out.reshape(S, q_tiles * tq, H, value_lanes)[:, :T]


def latent_paged_attention_reference(q, pool, tables, lengths, layer, *,
                                     value_lanes: int, scale: float,
                                     keep=None) -> jax.Array:
    """Gather-path oracle of :func:`latent_paged_attention` (and the CPU
    path of the serve programs): the table's rows gathered into
    [S, NB*bt, W], masked dense attention over them (and over ``keep``
    [S, T, NB*bt] where given)."""
    S, T, H, W = q.shape
    bt = pool.shape[2]
    n = tables.shape[1] * bt
    rows = pool[layer, tables].reshape(S, n, W)
    scores = jnp.einsum("sthw,snw->shtn", q, rows,
                        preferred_element_type=jnp.float32) * scale
    kv_pos = jnp.arange(n)[None, None, None, :]
    q_pos = lengths.reshape(-1, 1, 1, 1) + jnp.arange(T)[None, None, :, None]
    visible = kv_pos <= q_pos
    if keep is not None:
        visible = jnp.logical_and(visible, keep[:, None] != 0)
    probs = jax.nn.softmax(jnp.where(visible, scores, _NEG_INF), axis=-1)
    out = jnp.einsum("shtn,snv->sthv", probs,
                     rows[..., :value_lanes].astype(jnp.float32))
    return out.astype(q.dtype)
