"""Paged attention — a Pallas TPU decode kernel over the block-pool KV cache.

The serve engine's decode hot path (``models/generate.py
_forward_decode_paged``) holds K/V in a SHARED pool of
``block_tokens``-sized blocks addressed through per-sequence block tables.
The straightforward JAX formulation gathers the whole table back out —
``k_pool[tables].reshape(S, max_len, H, D)`` — which materializes
S × max_len × H × D every token and reads every pool block a slot's table
points at, live or not. Decode is memory-bandwidth-bound, so that gather is
exactly the HBM traffic the roofline says we cannot afford.

This kernel reads the block table NATIVELY instead: the table and the
per-slot lengths ride in as scalar-prefetch operands
(``pltpu.PrefetchScalarGridSpec``), so the BlockSpec index map dereferences
``tables[s, j]`` on the host side of the DMA pipeline and each grid program
streams pool blocks straight from HBM into VMEM — only the
``ceil(len/block_tokens)`` LIVE blocks of its slot do real work. Grid steps
past the last live block re-map onto it, and because consecutive grid steps
that map to the same pool block skip the re-fetch, the dead tail of a table
costs no traffic, not ``NB - live`` blocks of it. Softmax is the online
(m, l, acc) accumulator pattern shared with ``flash_attention._flash_kernel``,
held in VMEM scratch across the kv sweep.

Layout: ``q`` [S, T, H, D] — T > 1 is the multi-token speculative-decoding
verify (and the paged prefill, S == 1): query t of slot s sits at absolute
position ``lengths[s] + t`` and attends kv positions ``<= lengths[s] + t``.
The T new tokens' K/V must already be scattered into the pool at those
positions (the caller writes K/V first, then attends — same order as the
gather path).

The pool is the WHOLE model's, ``[L, num_blocks, bt, H*D]``: heads folded
into the lane dimension, so a block is one dense ``[bt, H*D]`` tile in the
layout the array already has in HBM, and ``layer`` rides as a third
scalar-prefetch operand into the index map. A Mosaic call cannot read
through an XLA slice: handed ``pool[layer]`` it made XLA copy that layer's
slab out of the pool every call (and a ``[.., H, D]`` pool with D = 64 minor
was re-tiled whole on the way into and out of every program). Addressed in
place, no program copies pool-sized data. Head ``h`` is the static lane
slice ``[:, h*D:(h+1)*D]`` of the block; the body takes the lanes in chunks
of G heads (``_heads_per_chunk``), every chunk a 128-lane-aligned slice, and
only the finalize step cuts single heads out of the accumulator.

Grid: ``(S, q tiles, nb_seq)``, kv innermost. Queries are tiled
``_Q_TILE`` at a time so the per-step VMEM footprint (q/out blocks plus the
``[H*tile, ·]`` f32 accumulators, whose 1-wide m/l columns pad to a full
128-lane tile) is bounded by the tile, not by T: a 1024-token prefill as
ONE block asked the v5e compiler for 18 MB of scoped VMEM against its 16 MB
limit. Decode and verify (T <= ``_Q_TILE``) are a single tile — the same
program as before the tile axis existed. Each tile sweeps only the blocks
at or below its own last query; the index map clamps later steps onto that
block, so the revisit-skip makes their DMA free.

Runs compiled on TPU and in interpret mode on CPU (the tier-1 path);
``paged_attention_reference`` is the gather-path oracle the kernel is
validated against.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
# Queries per grid step. 128 rows x 12 heads of f32 accumulators is ~2.4 MB
# of scratch on v5e; untuned (ROADMAP S2) — chosen to fit, not to be fast.
_Q_TILE = 128


def _last_block(first_pos, q_tile: int, block_tokens: int):
    """Highest block index holding a position attended by the ``q_tile``
    queries that start at absolute position ``first_pos`` (the last of them
    sits at ``first_pos + q_tile - 1``). One formula for the kernel body,
    which skips later blocks, and the index map, which does not fetch them."""
    return jax.lax.div(first_pos + q_tile - 1, block_tokens)


def _clamped_block_index(q_tile: int, block_tokens: int, step_blocks: int = 1,
                         offset: int = 0, nb_seq: int = 0):
    """The index map both kernels address the pool with: grid step ``j`` of
    slot ``s``, query tile ``i`` maps to pool block ``tables[s, b]`` of layer
    ``layer[0]``, ``b`` clamped onto the tile's last live block so that later
    steps revisit it and cost no DMA. A kernel that takes ``step_blocks``
    blocks a step passes the pool once per block, each with its ``offset``
    (and ``nb_seq``, the table's width, which ``j * step_blocks + offset``
    may pass)."""
    def kv_index(s, i, j, tbl, ln, lyr):
        last_blk = _last_block(ln[s] + i * q_tile, q_tile, block_tokens)
        if step_blocks == 1:
            blk = jnp.minimum(j, last_blk)
        else:
            blk = jnp.minimum(jnp.minimum(j * step_blocks + offset, last_blk),
                              nb_seq - 1)
        return (lyr[0], tbl[s, blk], 0, 0)

    return kv_index


def _heads_per_chunk(num_heads: int, q_tile: int, head_dim: int) -> int:
    """How many heads the kernel takes in one dot. The block's lanes are cut
    into chunks of G heads; a chunk's G*T query rows, each zero outside its
    own head's lanes, meet the chunk's G*D lanes of K in ONE dot, so no head
    is sliced out of a 128-lane register on every block. The dot computes G
    times the products it needs: free while the rows fit one MXU pass
    (decode, verify: all heads at once), so beyond that G is only what fills
    128 lanes (prefill: two heads of 64)."""
    if num_heads * q_tile <= 128:
        return num_heads
    return min(num_heads, max(1, 128 // head_dim))


def _paged_kernel(
    tables_ref, lengths_ref,   # scalar prefetch: [S, NB] int32, [S] int32
    layer_ref,                 # scalar prefetch: [1] int32 (index map only)
    q_ref,                     # [1, H*T, G*D] block — T = one q tile
    k_ref, v_ref,              # [1, bt, H*D] block — pool block tables[s, j]
    o_ref,                     # [1, H, T, D] block
    m_scr, l_scr, acc_scr,     # VMEM scratch: [H*T, 1], [H*T, 1], [H*T, G*D]
    *,
    scale: float,
    block_tokens: int,
    num_heads: int,
    q_tile: int,
    nb_seq: int,
):
    s = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    bt, H, T = block_tokens, num_heads, q_tile
    D = o_ref.shape[-1]
    G = q_ref.shape[-1] // D                   # heads per lane chunk
    # Absolute position of this tile's first query.
    ctx = lengths_ref[s] + i * T
    # Grid steps past this block re-map onto it in the index map, so they
    # cost no DMA, and the body skips them.
    last_blk = _last_block(ctx, T, bt)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(j <= last_blk)
    def _body():
        # Causal + validity in one mask: kv position vs absolute q position.
        # Row r of a chunk is (head r // T, query r % T).
        rows = G * T
        kv_pos = j * bt + jax.lax.broadcasted_iota(jnp.int32, (rows, bt), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, bt), 0)
        q_pos = ctx + (0 if T == 1 else jax.lax.rem(row, T))
        mask_all = kv_pos <= q_pos
        for c0 in range(0, H, G):                    # static unroll
            g = min(G, H - c0)                       # heads of this chunk
            d0, d1 = c0 * D, (c0 + g) * D            # their lanes
            r0, r1 = c0 * T, (c0 + g) * T            # their rows
            mask = mask_all[: g * T]
            qb = q_ref[0, r0:r1, : g * D].astype(jnp.float32)   # [g*T, g*D]
            kb = k_ref[0, :, d0:d1].astype(jnp.float32)         # [bt, g*D]
            vb = v_ref[0, :, d0:d1].astype(jnp.float32)
            scores = jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                 # [g*T, bt]
            scores = jnp.where(mask, scores, _NEG_INF)
            m_prev = m_scr[r0:r1]                     # [g*T, 1]
            m_cur = jnp.max(scores, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(scores - m_new)               # [g*T, bt]
            l_scr[r0:r1] = alpha * l_scr[r0:r1] + jnp.sum(
                p, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                p, vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                         # [g*T, g*D]
            acc_scr[r0:r1, : g * D] = acc_scr[r0:r1, : g * D] * alpha + pv
            m_scr[r0:r1] = m_new

    @pl.when(j == nb_seq - 1)
    def _finalize():
        for h in range(H):                            # static unroll
            r0, r1 = h * T, (h + 1) * T
            e0 = (h % G) * D                          # head h's lanes in its chunk
            denom = jnp.maximum(l_scr[r0:r1], 1e-30)  # [T, 1]
            o_ref[0, h] = (acc_scr[r0:r1, e0:e0 + D] / denom).astype(
                o_ref.dtype)


def paged_attention(
    q: jax.Array,                # [S, T, H, D]
    k_pool: jax.Array,           # [L, num_blocks, bt, H*D] (the whole pool)
    v_pool: jax.Array,
    tables: jax.Array,           # [S, NB] int32 — pool block ids, 0 = trash
    lengths: jax.Array,          # [S] int32 — valid context BEFORE the T tokens
    layer,                       # int or int32 scalar — which layer's blocks
    *,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """Fused paged-attention over the block pool; returns [S, T, H, D].

    Query t of slot s is at absolute position ``lengths[s] + t`` and attends
    positions ``<= lengths[s] + t`` of layer ``layer`` gathered through
    ``tables[s]``. No ``[S, max_len, H, D]`` intermediate exists at any
    point, and the pool is read in place: a caller holding one layer's pool
    passes ``pool[None]`` and layer 0."""
    S, T, H, D = q.shape
    if k_pool.ndim != 4 or k_pool.shape[3] != H * D:
        raise ValueError(
            f"pool {k_pool.shape} is not [L, num_blocks, bt, {H}*{D}]: the "
            f"kernel reads the whole folded pool (one layer's: pool[None])")
    bt = k_pool.shape[2]
    nb_seq = tables.shape[1]
    s_val = scale if scale is not None else 1.0 / D**0.5
    tables = tables.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    qt = q.transpose(0, 2, 1, 3)                      # [S, H, T, D]
    tq = min(T, _Q_TILE)
    q_tiles = pl.cdiv(T, tq)
    if T % tq:
        # Ragged last tile: pad queries are causally AHEAD of every real one
        # and their rows are sliced off below.
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, q_tiles * tq - T), (0, 0)))

    G = _heads_per_chunk(H, tq, D)
    # q of head h sits in lanes (h % G) * D of a G*D-wide row, zeros beside
    # it: one dot of a chunk's rows against the chunk's lanes then gives
    # every head its own scores. Rows of a tile are (head, query).
    own = jnp.arange(G)[None, :] == (jnp.arange(H) % G)[:, None]   # [H, G]
    qw = jnp.where(own[None, :, None, :, None], qt[:, :, :, None, :], 0)
    qw = qw.reshape(S, H, q_tiles, tq, G * D).transpose(0, 2, 1, 3, 4)
    qw = qw.reshape(S, q_tiles, H * tq, G * D)

    kv_index = _clamped_block_index(tq, bt)

    def q_index(s, i, j, tbl, ln, lyr):
        return (s, i, 0, 0)

    def o_index(s, i, j, tbl, ln, lyr):
        return (s, 0, i, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, q_tiles, nb_seq),
        in_specs=[
            pl.BlockSpec((None, 1, H * tq, G * D), q_index),
            pl.BlockSpec((None, 1, bt, H * D), kv_index),
            pl.BlockSpec((None, 1, bt, H * D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, H, tq, D), o_index),
        scratch_shapes=[
            pltpu.VMEM((H * tq, 1), jnp.float32),
            pltpu.VMEM((H * tq, 1), jnp.float32),
            pltpu.VMEM((H * tq, G * D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_kernel, scale=s_val, block_tokens=bt, num_heads=H,
            q_tile=tq, nb_seq=nb_seq),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, q_tiles * tq, D), q.dtype),
        interpret=interpret,
        # The name a profiler prints for the kernel, whatever calls it.
        name="paged_decode_attn" if T == 1 else "paged_prefill_attn",
    )(tables, lengths, layer, qw, k_pool, v_pool)
    return out[:, :, :T].transpose(0, 2, 1, 3)        # [S, T, H, D]


def paged_attention_reference(q, k_pool, v_pool, tables, lengths, layer, *,
                              scale: Optional[float] = None) -> jax.Array:
    """Gather-path oracle over the same operands: materializes
    [S, NB*bt, H, D] of ``layer`` through the table and runs masked dense
    attention — numerically what the pre-kernel decode did, kept as the
    equivalence target and the CPU fallback reference."""
    S, T, H, D = q.shape
    bt = k_pool.shape[2]
    nb_seq = tables.shape[1]
    s_val = scale if scale is not None else 1.0 / D**0.5
    kc = k_pool[layer, tables].reshape(S, nb_seq * bt, H, D)
    vc = v_pool[layer, tables].reshape(S, nb_seq * bt, H, D)
    scores = jnp.einsum("bthd,bshd->bhts", q, kc,
                        preferred_element_type=jnp.float32) * s_val
    kv_pos = jnp.arange(nb_seq * bt)[None, None, None, :]
    q_pos = (lengths.reshape(-1, 1, 1, 1)
             + jnp.arange(T)[None, None, :, None])
    scores = jnp.where(kv_pos <= q_pos, scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
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
# a block-diagonal q to give each head its own lanes. Same grid (slots, query
# tiles, table steps, kv innermost), same scalar prefetch (tables, lengths,
# layer) and the same clamped index map; a grid step takes
# ``_LATENT_STEP_BLOCKS`` pool blocks (the pool handed in once per block),
# because one 16-token block of one shared row is 20 KB, far too little work
# for a step's fixed cost.

_LATENT_STEP_BLOCKS = 8      # x 16-token blocks = one 128-lane row of scores
_LATENT_Q_TILE = 16          # x 64 heads = 1024 rows of f32 accumulators


def _latent_kernel(
    tables_ref, lengths_ref, layer_ref,   # scalar prefetch, as _paged_kernel
    q_ref,                                # [1, T*H, W] block; row = (query, head)
    *rest,                                # G pool blocks [1, bt, W]; out; scratch
    scale: float,
    block_tokens: int,
    num_heads: int,
    q_tile: int,
    nb_steps: int,
    step_blocks: int,
    value_lanes: int,
):
    kv_refs = rest[:step_blocks]
    o_ref = rest[step_blocks]              # [1, T*H, value_lanes]
    m_scr, l_scr, acc_scr = rest[step_blocks + 1:]
    s = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    bt, H, T, G = block_tokens, num_heads, q_tile, step_blocks
    ctx = lengths_ref[s] + i * T
    last_blk = _last_block(ctx, T, bt)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(j * G <= last_blk)
    def _body():
        rows = T * H
        kv = jnp.concatenate([r[0] for r in kv_refs], axis=0)   # [G*bt, W]
        scores = jax.lax.dot_general(
            q_ref[0], kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale         # [rows, G*bt]
        kv_pos = j * (G * bt) + jax.lax.broadcasted_iota(
            jnp.int32, (rows, G * bt), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, G * bt), 0)
        q_pos = ctx + (0 if T == 1 else jax.lax.div(row, H))
        scores = jnp.where(kv_pos <= q_pos, scores, _NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(kv.dtype), kv[:, :value_lanes], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                 # [rows, V]
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = m_new

    @pl.when(j == nb_steps - 1)
    def _finalize():
        o_ref[0] = (acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)).astype(
            o_ref.dtype)


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
) -> jax.Array:
    """Softmax over the latent rows, returns ``sum_j p_j row_j[:value_lanes]``
    as [S, T, H, value_lanes]: the caller up-projects it per head. Query t of
    slot s sits at ``lengths[s] + t`` and attends positions ``<=`` it; the T
    new rows must already be in the pool."""
    S, T, H, W = q.shape
    if pool.ndim != 4 or pool.shape[3] != W:
        raise ValueError(f"pool {pool.shape} is not [A, num_blocks, bt, {W}]")
    bt = pool.shape[2]
    nb_seq = tables.shape[1]
    G = _LATENT_STEP_BLOCKS
    nb_steps = pl.cdiv(nb_seq, G)
    tq = min(T, _LATENT_Q_TILE)
    q_tiles = pl.cdiv(T, tq)
    if T % tq:
        q = jnp.pad(q, ((0, 0), (0, q_tiles * tq - T), (0, 0), (0, 0)))
    qr = q.reshape(S, q_tiles, tq * H, W)
    tables = tables.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def q_index(s, i, j, tbl, ln, lyr):
        return (s, i, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, q_tiles, nb_steps),
        in_specs=[pl.BlockSpec((None, 1, tq * H, W), q_index)] + [
            pl.BlockSpec((None, 1, bt, W),
                         _clamped_block_index(tq, bt, G, g, nb_seq))
            for g in range(G)],
        out_specs=pl.BlockSpec((None, 1, tq * H, value_lanes), q_index),
        scratch_shapes=[
            pltpu.VMEM((tq * H, 1), jnp.float32),
            pltpu.VMEM((tq * H, 1), jnp.float32),
            pltpu.VMEM((tq * H, value_lanes), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _latent_kernel, scale=scale, block_tokens=bt, num_heads=H,
            q_tile=tq, nb_steps=nb_steps, step_blocks=G,
            value_lanes=value_lanes),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, q_tiles, tq * H, value_lanes),
                                       q.dtype),
        interpret=interpret,
        name="mla_decode_attn" if T == 1 else "mla_prefill_attn",
    )(tables, lengths, layer, qr, *([pool] * G))
    return out.reshape(S, q_tiles * tq, H, value_lanes)[:, :T]


def latent_paged_attention_reference(q, pool, tables, lengths, layer, *,
                                     value_lanes: int, scale: float) -> jax.Array:
    """Gather-path oracle of :func:`latent_paged_attention` (and the CPU
    path of the serve programs): the table's rows gathered into
    [S, NB*bt, W], masked dense attention over them."""
    S, T, H, W = q.shape
    bt = pool.shape[2]
    n = tables.shape[1] * bt
    rows = pool[layer, tables].reshape(S, n, W)
    scores = jnp.einsum("sthw,snw->shtn", q, rows,
                        preferred_element_type=jnp.float32) * scale
    kv_pos = jnp.arange(n)[None, None, None, :]
    q_pos = lengths.reshape(-1, 1, 1, 1) + jnp.arange(T)[None, None, :, None]
    probs = jax.nn.softmax(jnp.where(kv_pos <= q_pos, scores, _NEG_INF), axis=-1)
    out = jnp.einsum("shtn,snv->sthv", probs,
                     rows[..., :value_lanes].astype(jnp.float32))
    return out.astype(q.dtype)
