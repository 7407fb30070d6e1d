"""Latent attention (MLA) over the paged latent pool: the one sublayer that
every latent-attention family calls.

The pool holds ONE row a token a sublayer, ``[c_kv after its norm | k_rope
after rotary]``, ``rank + rope`` numbers padded to whole 128-lane tiles and
shared by all heads. The up-projection ``W_kvb`` is absorbed: its key half
``W_kb`` goes into the query, its value half ``W_vb`` into the output, so
attention runs on the rows as they lie in the pool
(``ops/paged_attention.py:latent_paged_attention``).

What differs between families is a :class:`LatentSpec`: the factors some
multiply the two latents by (LongCat's ``mla_scale_q_lora`` and
``mla_scale_kv_lora``; Kimi-K2 has none), the rotary's frequencies (plain,
or YaRN's blend), the softmax scale (Kimi-K2's carries YaRN's ``mscale``
squared) and how the two absorbed projections are laid out in memory.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass
from typing import Any, Callable, Mapping, Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops.layers import mm, rms_norm, rope, rope_frequencies
from ray_tpu.ops.paged_attention import (latent_paged_attention,
                                         latent_paged_attention_reference)


@dataclass(frozen=True)
class LatentSpec:
    """One family's latent attention. ``nope`` | ``rope`` are a query head's
    two parts, ``rank`` the latent's width, ``pool_width`` a stored row's.
    ``q_scale`` / ``kv_scale`` multiply ``W_qb c_q`` / the normed ``c_kv`` in
    float32 (None: no such factor). ``heads_major``: ``W_kb`` is stored
    ``[H, nope, rank]`` and ``W_vb`` ``[H, rank, v]``, the order the two
    absorbed products (batched over heads) read them in; else both are
    ``[rank, H, .]`` as ``W_kvb`` is published, and XLA re-lays them before
    each call's products."""
    nope: int
    rope: int
    rank: int
    pool_width: int
    eps: float
    dtype: Any
    softmax_scale: float
    rope_theta: float
    rope_scaling: Optional[Mapping] = None
    q_scale: Optional[float] = None
    kv_scale: Optional[float] = None
    heads_major: bool = False


def init_latent_pool(spec: LatentSpec, sublayers: int, num_blocks: int,
                     block_tokens: int):
    """The latent rows of ``sublayers`` attention sublayers, ``[sublayers,
    num_blocks, block_tokens, spec.pool_width]``: one array where GPT-2 has
    a K and a V pool. Block 0 is the trash block and blocks are dimension 1,
    so the generator's block copy indexes it as it indexes GPT-2's."""
    return jnp.zeros((sublayers, num_blocks, block_tokens, spec.pool_width),
                     spec.dtype)


def latent_attention(ap, x, pool, sub, blk, off, tables, lengths, positions,
                     spec: LatentSpec, kernel: str,
                     select: Optional[Callable] = None, queries=None):
    """One latent-attention sublayer over the paged rows, ``W_kvb`` absorbed.

    ``x`` [S, T, D] (normed); the T new rows are written to blocks ``blk``
    [S, T] of sublayer ``sub`` at offsets ``off`` first, then attended with
    the rest through ``tables``. ``ap`` holds ``w_qa``, ``q_norm``, ``w_qb``
    [r, H, nope + rope], ``w_kva`` [D, rank + rope], ``kv_norm``, ``w_kb``,
    ``w_vb`` (see ``LatentSpec.heads_major``) and ``w_o`` [H, v, D].
    ``select(c_q)`` (the query latent after its norm, [S, T, r]) gives keep
    bits [S, T, NB * bt]: a query attends a row only if its bit is set
    (``ops/sparse_select.py``; a family whose indexer reads the same latent).
    ``queries`` [S]: a prefill's count of real rows of ``x``; the kernel
    walks for those alone and its pad rows read zeros, the gather path
    attends every row as ever (nobody reads a pad row).
    Returns (out [S, T, D], pool)."""
    p, dt = spec, spec.dtype
    R = p.rank
    S, T, _ = x.shape
    cq = rms_norm(mm("std,dr->str", x, ap["w_qa"], dt), ap["q_norm"], p.eps)
    if p.q_scale is None:
        q = mm("str,rhk->sthk", cq, ap["w_qb"], dt)
    else:
        q = (mm("str,rhk->sthk", cq, ap["w_qb"], jnp.float32)
             * p.q_scale).astype(dt)
    q_nope, q_rope = q[..., :p.nope], q[..., p.nope:]
    kva = mm("std,dw->stw", x, ap["w_kva"], dt)
    c_kv = rms_norm(kva[..., :R], ap["kv_norm"], p.eps)
    if p.kv_scale is not None:
        c_kv = (c_kv.astype(jnp.float32) * p.kv_scale).astype(dt)
    freqs = rope_frequencies(p.rope_theta, p.rope, p.rope_scaling)
    k_rope = rope(kva[..., None, R:], positions, freqs=freqs)[:, :, 0]
    q_rope = rope(q_rope, positions, freqs=freqs)
    pad = p.pool_width - (R + p.rope)
    row = jnp.concatenate(
        [c_kv, k_rope, jnp.zeros((S, T, pad), dt)], axis=-1)
    with jax.named_scope("kv_pool_write"):
        pool = pool.at[sub, blk, off].set(row)
    kb, vb = (("sthn,hnr->sthr", "sthr,hrv->sthv") if p.heads_major else
              ("sthn,rhn->sthr", "sthr,rhv->sthv"))
    H = q.shape[2]
    q_abs = jnp.concatenate(
        [mm(kb, q_nope, ap["w_kb"], dt), q_rope,
         jnp.zeros((S, T, H, pad), dt)], axis=-1)
    keep = None if select is None else select(cq)
    with (contextlib.nullcontext() if keep is None
          else jax.named_scope("attn_sparse")):
        if kernel in ("pallas", "interpret"):
            o_lat = latent_paged_attention(
                q_abs, pool, tables, lengths, sub, value_lanes=R,
                scale=p.softmax_scale, interpret=kernel == "interpret",
                keep=keep, queries=queries)
        else:
            o_lat = latent_paged_attention_reference(
                q_abs, pool, tables, lengths, sub, value_lanes=R,
                scale=p.softmax_scale, keep=keep)
    o = mm(vb, o_lat, ap["w_vb"], dt)
    return mm("sthv,hvd->std", o, ap["w_o"], dt), pool
