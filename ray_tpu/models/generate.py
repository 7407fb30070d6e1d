"""Autoregressive generation with KV cache.

The inference fast path the Serve replicas use. The reference serves models
through vLLM/framework engines; TPU-native the decode loop is two jitted XLA
programs with static shapes:

- ``prefill``: one full forward over the (padded) prompt, writing K/V for
  every layer into a preallocated cache [L, B, max_len, H, Dh];
- ``decode_step``: single-token forward reading the cache — O(1) FLOPs in
  context length per token instead of the O(ctx) full-window forward.

The cache is a pytree carried through ``lax.scan``-style stepping on the
host; batch/beam layouts stay static so both programs compile exactly once.
"""

from __future__ import annotations

import bisect
import functools
import itertools
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.transformer import TransformerConfig
from ray_tpu.ops import moe
from ray_tpu.ops.layers import gelu, layer_norm, linear, rope
from ray_tpu.ops.paged_attention import (append_rows_fit, paged_attention,
                                         paged_attention_append)


def resolve_attention_kernel(mode: Optional[str]) -> str:
    """Resolve the ``serve_paged_attention_kernel`` knob to a concrete mode:
    ``pallas`` (compiled kernel), ``interpret`` (Pallas interpret mode — the
    CPU tier-1 path exercising the same kernel), or ``gather`` (the XLA
    table-gather formulation). ``auto`` picks pallas on TPU and gather on
    CPU, where interpret-mode per-token dispatch would tax the test suite."""
    mode = (mode or "auto").lower()
    if mode == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "gather"
    if mode not in ("pallas", "interpret", "gather"):
        raise ValueError(
            f"serve_paged_attention_kernel must be auto|pallas|interpret|"
            f"gather, got {mode!r}")
    return mode


def init_cache(config: TransformerConfig, batch: int, max_len: Optional[int] = None) -> Dict:
    c = config
    max_len = max_len or c.max_seq_len
    shape = (c.n_layers, batch, max_len, c.n_heads, c.head_dim)
    return {
        "k": jnp.zeros(shape, c.dtype),
        "v": jnp.zeros(shape, c.dtype),
        "length": jnp.zeros((), jnp.int32),
    }


def _attend_cached(q, k_cache, v_cache, valid_len, *, scale: float):
    """q: [B, T, H, D] against cache [B, S, H, D]; positions >= valid_len are
    masked. For prefill T>1 a causal mask also applies within the window.

    ``valid_len`` may be a scalar (every row at the same position — the
    single-sequence path) or a [B] vector (per-slot positions — the
    continuous-batching path, where each cache row holds an independent
    sequence at its own decode offset)."""
    B, T, H, D = q.shape
    S = k_cache.shape[1]
    scores = jnp.einsum(
        "bthd,bshd->bhts", q, k_cache, preferred_element_type=jnp.float32
    ) * scale
    kv_pos = jnp.arange(S)[None, None, None, :]          # [1,1,1,S]
    vl = jnp.asarray(valid_len)
    if vl.ndim:                                           # per-row [B]
        vl = vl.reshape(-1, 1, 1, 1)                      # [B,1,1,1]
    q_pos = (vl - T) + jnp.arange(T)[None, None, :, None]
    mask = kv_pos <= q_pos                                # causal + validity
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", probs, v_cache.astype(jnp.float32))
    return out.astype(q.dtype)


def _forward_cached(params, tokens, cache, config: TransformerConfig, start_pos):
    """Forward ``tokens`` [B, T] at positions [start_pos, start_pos+T),
    updating the cache. Returns (logits[B, T, V], new_cache)."""
    c = config
    cast = lambda p: p.astype(c.dtype)
    B, T = tokens.shape
    h = jnp.take(cast(params["tok_embed"]), tokens, axis=0)
    positions = start_pos + jnp.arange(T)
    if c.pos == "learned":
        h = h + cast(params["pos_embed"])[positions]
    scale = 1.0 / c.head_dim**0.5
    valid_len = start_pos + T

    new_k, new_v = [], []
    for layer in range(c.n_layers):
        bp = jax.tree.map(lambda p: cast(p[layer]), params["blocks"])
        x = layer_norm(h, bp["ln1_g"], bp["ln1_b"])
        q = jnp.einsum("btd,dhk->bthk", x, bp["wq"], preferred_element_type=jnp.float32).astype(c.dtype) + bp["bq"]
        k = jnp.einsum("btd,dhk->bthk", x, bp["wk"], preferred_element_type=jnp.float32).astype(c.dtype) + bp["bk"]
        v = jnp.einsum("btd,dhk->bthk", x, bp["wv"], preferred_element_type=jnp.float32).astype(c.dtype) + bp["bv"]
        if c.pos == "rope":
            q = rope(q, positions)
            k = rope(k, positions)
        k_cache = lax.dynamic_update_slice(
            cache["k"][layer], k, (0, start_pos, 0, 0)
        )
        v_cache = lax.dynamic_update_slice(
            cache["v"][layer], v, (0, start_pos, 0, 0)
        )
        new_k.append(k_cache)
        new_v.append(v_cache)
        o = _attend_cached(q, k_cache, v_cache, valid_len, scale=scale)
        o = jnp.einsum("bthk,hkd->btd", o, bp["wo"], preferred_element_type=jnp.float32).astype(c.dtype) + bp["bo"]
        h = h + o
        x = layer_norm(h, bp["ln2_g"], bp["ln2_b"])
        u = gelu(linear(x, bp["w_up"], bp["b_up"]))
        h = h + linear(u, bp["w_down"], bp["b_down"])

    h = layer_norm(h, cast(params["lnf_g"]), cast(params["lnf_b"]))
    w_out = params["tok_embed"].T if c.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("btd,dv->btv", h, cast(w_out), preferred_element_type=jnp.float32)
    new_cache = {
        "k": jnp.stack(new_k),
        "v": jnp.stack(new_v),
        "length": jnp.asarray(valid_len, jnp.int32),
    }
    return logits, new_cache


def init_block_pool(config: TransformerConfig, num_blocks: int,
                    block_tokens: int) -> Tuple[jax.Array, jax.Array]:
    """Shared paged KV pool: ``num_blocks`` fixed-size blocks of
    ``block_tokens`` K/V rows each, shared by every sequence through
    per-sequence block TABLES instead of private max_len slabs. Block 0 is
    the reserved TRASH block: freed table rows and pad positions point at
    it, so out-of-range scatter writes land somewhere harmless instead of
    corrupting a live sequence.

    Shape ``[n_layers, num_blocks, block_tokens, KV heads * head_dim]``: a
    row holds the heads that are STORED, ``config.n_kv_heads``: fewer than
    ``n_heads`` where the family groups its queries (query head ``h`` reads
    KV head ``h // (n_heads // n_kv_heads)``). The heads are folded into the
    lane dimension, so a token's K (or V) row is dense in the layout the array
    has in HBM, and the one layout serves the scatter that writes it, the
    kernel that reads it in place (``ops/paged_attention.py``) and every
    program it passes through. With a trailing ``[.., n_heads, head_dim]`` the
    64-wide minor dimension made every serve program re-tile the whole pool on
    entry and on exit."""
    c = config
    shape = (c.n_layers, num_blocks, block_tokens, c.n_kv_heads * c.head_dim)
    return jnp.zeros(shape, c.dtype), jnp.zeros(shape, c.dtype)


def prefill_cells(table, start_pos, suffix_len, P: int, block_tokens: int):
    """The pool cells of a prefill's ``P`` rows (a suffix bucket at positions
    ``[start_pos, start_pos + P)``, the first ``suffix_len`` real) through
    ``table`` [NB]: position ``p`` lives in block ``table[p // block_tokens]``
    at row ``p % block_tokens``, and a pad row goes to trash block 0. Returns
    (positions [P], valid [P], blk [P], off [P])."""
    positions = start_pos + jnp.arange(P)
    valid = jnp.arange(P) < suffix_len
    blk = jnp.where(valid, table[jnp.clip(positions // block_tokens, 0,
                                          table.shape[0] - 1)], 0)
    return positions, valid, blk, positions % block_tokens


def decode_cells(tables, lengths, T: int, block_tokens: int):
    """The pool cells of a decode step's ``T`` rows a slot through ``tables``
    [S, NB]: slot ``s``'s row ``t`` sits at position ``lengths[s] + t``. A
    position at or past the table's capacity writes to trash block 0 rather
    than clamping onto the last cell: a slot at capacity is finished as
    ``length_cap`` by the engine BEFORE dispatch, so in-range rows never see
    a silently overwritten chain, and the redirect only shields a parked
    slot's overhang writes (its table is all trash). Returns (positions
    [S, T], blk [S, T], off [S, T])."""
    S, NB = tables.shape
    max_len = NB * block_tokens
    positions = lengths[:, None] + jnp.arange(T)[None, :]
    pos_c = jnp.minimum(positions, max_len - 1)
    blk = jnp.where(positions < max_len,
                    tables[jnp.arange(S)[:, None], pos_c // block_tokens], 0)
    return positions, blk, pos_c % block_tokens


def _paged_attend(q, k_pool, v_pool, tables, lengths, layer, *, scale,
                  kernel, queries=None):
    """Attention over ``layer`` of the whole paged pool, switched by
    ``kernel``: the Pallas kernel streams only live blocks, in place
    (compiled on TPU, interpret on CPU); ``gather`` is the legacy
    table-gather + dense-mask path. A pool row narrower than ``q``'s heads
    holds grouped KV heads (``init_block_pool``): both paths give query head
    ``h`` KV head ``h // (H // KV)``. ``queries``: a prefill's count of real
    query rows; the kernel walks for those alone and zeroes the rest, the
    gather path attends every row as ever (nobody reads a pad row)."""
    if kernel in ("pallas", "interpret"):
        return paged_attention(q, k_pool, v_pool, tables, lengths, layer,
                               scale=scale, interpret=kernel == "interpret",
                               queries=queries)
    S, T, H, D = q.shape
    max_len = tables.shape[1] * k_pool.shape[2]
    KV = k_pool.shape[3] // D
    kc = k_pool[layer, tables].reshape(S, max_len, KV, D)
    vc = v_pool[layer, tables].reshape(S, max_len, KV, D)
    if KV != H:
        kc, vc = (jnp.repeat(a, H // KV, axis=2) for a in (kc, vc))
    return _attend_cached(q, kc, vc, lengths + T, scale=scale)


def _forward_prefill_paged(params, tokens, k_pool, v_pool, table, start_pos,
                           suffix_len, config: TransformerConfig,
                           block_tokens: int, kernel: str = "gather"):
    """Prefill ``tokens`` [1, P] (a SUFFIX bucket) at absolute positions
    [start_pos, start_pos+P) into the paged pool through ``table`` [NB].

    Prefix reuse is what makes ``start_pos`` nonzero: positions below it
    were written by earlier sequences sharing the same blocks, so attention
    gathers them back through the table without recomputing. Only the first
    ``suffix_len`` positions are real — pad writes redirect to trash block
    0, and the kernel is handed the count: it walks the table for the real
    queries alone and a pad query's row of its output is zeros (on the
    gather path a pad query attends, causally ahead of every real row, and
    nobody reads what it gives).

    ``params`` is the working tree of :func:`gpt2_working_params`: every
    matrix an array of its own in the compute type, read where it lies."""
    c = config
    B, P = tokens.shape  # B == 1
    h = jnp.take(params["tok_embed"], tokens, axis=0)[..., :c.d_model]
    positions, _, blk, off = prefill_cells(table, start_pos, suffix_len, P,
                                           block_tokens)
    if c.pos == "learned":
        h = h + params["pos_embed"][jnp.minimum(
            positions, c.max_seq_len - 1)][None, :, :c.d_model]
    scale = 1.0 / c.head_dim**0.5
    lengths1 = jnp.reshape(start_pos, (1,)).astype(jnp.int32)

    for layer, bp in enumerate(params["layers"]):
        x = layer_norm(h, bp["ln1_g"], bp["ln1_b"])
        q = jnp.einsum("btd,dhk->bthk", x, bp["wq"], preferred_element_type=jnp.float32).astype(c.dtype) + bp["bq"]
        k = jnp.einsum("btd,dhk->bthk", x, bp["wk"], preferred_element_type=jnp.float32).astype(c.dtype) + bp["bk"]
        v = jnp.einsum("btd,dhk->bthk", x, bp["wv"], preferred_element_type=jnp.float32).astype(c.dtype) + bp["bv"]
        if c.pos == "rope":
            q = rope(q, positions[None])
            k = rope(k, positions[None])
        with jax.named_scope("kv_pool_write"):
            k_pool = k_pool.at[layer, blk, off].set(k.reshape(P, -1))
            v_pool = v_pool.at[layer, blk, off].set(v.reshape(P, -1))
        o = _paged_attend(q, k_pool, v_pool, table[None], lengths1, layer,
                          scale=scale, kernel=kernel, queries=suffix_len)
        o = jnp.einsum("bthk,hkd->btd", o, bp["wo"], preferred_element_type=jnp.float32).astype(c.dtype) + bp["bo"]
        h = h + o
        x = layer_norm(h, bp["ln2_g"], bp["ln2_b"])
        u = gelu(linear(x, bp["w_up"], bp["b_up"]))
        h = h + linear(u, bp["w_down"], bp["b_down"])

    h = layer_norm(h, params["lnf_g"], params["lnf_b"])
    logits = jnp.einsum("btd,dv->btv", h, params["head"], preferred_element_type=jnp.float32)
    return logits, k_pool, v_pool


def _forward_decode_paged(params, tokens, k_pool, v_pool, tables, lengths,
                          config: TransformerConfig, block_tokens: int,
                          kernel: str = "gather"):
    """Decode ``tokens`` [S, T] for S sequences over the paged pool: slot
    s's token t sits at absolute position ``lengths[s] + t``, its K/V
    written into block
    ``tables[s, pos // bt]`` row ``pos % bt`` and attention run back through
    the table row. One token a slot through the Pallas kernel (the serve
    engine's decode step) hands the kernel the new rows and gets the pools
    back with them in (``paged_attention_append``: no pass over HBM to
    write a row that the next operation fetches); otherwise they are
    scattered first (:func:`decode_cells`). Inactive slots carry all-trash
    tables: the kernel writes nothing for them, the scatter writes block 0,
    and their outputs are dead.

    ``params`` is the working tree of :func:`gpt2_working_params`."""
    c = config
    S, T = tokens.shape
    h = jnp.take(params["tok_embed"], tokens, axis=0)[..., :c.d_model]
    positions = lengths[:, None] + jnp.arange(T)[None, :]  # [S, T]
    if c.pos == "learned":
        h = h + params["pos_embed"][jnp.minimum(
            positions, c.max_seq_len - 1)][..., :c.d_model]
    scale = 1.0 / c.head_dim**0.5
    # One token a slot through the kernel that walks by DMA: the kernel takes
    # the new rows as operands and writes them itself. Otherwise (the gather
    # path, several tokens a slot, a pool row off the 128-lane grid, more
    # rows than the kernel holds in VMEM) the rows are scattered into the
    # pool first and attended there: the cells are taken in that arm alone
    # (the order in which the step first reads its operands is part of the
    # compiled program, and the appending program reads ``tables`` in its
    # kernel's call first).
    appends = (kernel in ("pallas", "interpret") and T == 1
               and append_rows_fit(S, k_pool.shape[3]))
    if not appends:
        _, blk, off = decode_cells(tables, lengths, T, block_tokens)

    for layer, bp in enumerate(params["layers"]):
        x = layer_norm(h, bp["ln1_g"], bp["ln1_b"])
        q = jnp.einsum("btd,dhk->bthk", x, bp["wq"], preferred_element_type=jnp.float32).astype(c.dtype) + bp["bq"]
        k = jnp.einsum("btd,dhk->bthk", x, bp["wk"], preferred_element_type=jnp.float32).astype(c.dtype) + bp["bk"]
        v = jnp.einsum("btd,dhk->bthk", x, bp["wv"], preferred_element_type=jnp.float32).astype(c.dtype) + bp["bv"]
        if c.pos == "rope":
            q = rope(q, positions)
            k = rope(k, positions)
        if appends:
            o, k_pool, v_pool = paged_attention_append(
                q, k.reshape(S, -1), v.reshape(S, -1), k_pool, v_pool, tables,
                lengths, layer, scale=scale, interpret=kernel == "interpret")
        else:
            with jax.named_scope("kv_pool_write"):
                k_pool = k_pool.at[layer, blk, off].set(k.reshape(S, T, -1))
                v_pool = v_pool.at[layer, blk, off].set(v.reshape(S, T, -1))
            o = _paged_attend(q, k_pool, v_pool, tables, lengths, layer,
                              scale=scale, kernel=kernel)
        o = jnp.einsum("bthk,hkd->btd", o, bp["wo"], preferred_element_type=jnp.float32).astype(c.dtype) + bp["bo"]
        h = h + o
        x = layer_norm(h, bp["ln2_g"], bp["ln2_b"])
        u = gelu(linear(x, bp["w_up"], bp["b_up"]))
        h = h + linear(u, bp["w_down"], bp["b_down"])

    h = layer_norm(h, params["lnf_g"], params["lnf_b"])
    logits = jnp.einsum("btd,dv->btv", h, params["head"], preferred_element_type=jnp.float32)
    return logits, k_pool, v_pool


class AuxCount(NamedTuple):
    """One entry of a family's per-call counts: the ``stats()`` key the
    decode programs' value adds to, the key a prefill's adds to (None: a
    prefill's is not kept), and the ``llm.step`` span attribute that carries
    the decode chunk's value (None: none)."""
    decode: str
    prefill: Optional[str] = None
    step_attr: Optional[str] = None


# ``stats()`` names of an expert family's counts (:func:`expert_aux`), the same
# for every family so that the same readers read them all (a family without
# zero-compute experts leaves ``moe_picks_zero_total`` at 0). A prefill's picks
# are kept apart (the per-step means stay the decode step's), and how often its
# expert layers took the bounded row buffer and walked past its first window;
# its busiest expert, experts hit and step are not kept. The decode chunk's
# held pairs ride ``llm.step``.
_PREFILL_KEPT = ("picks", "picks_zero", "picks_held", "bounded_calls",
                 "extra_windows")
EXPERT_AUX_COUNTS = tuple(
    AuxCount(f"moe_{n}_total",
             f"moe_prefill_{n}_total" if n in _PREFILL_KEPT else None,
             "moe_held_pairs" if n == "picks_held" else None)
    for n in moe.PICK_COUNT_NAMES) + (AuxCount("moe_steps_total"),)


def expert_aux(counts, *more):
    """A forward pass's ``aux`` in ``EXPERT_AUX_COUNTS``' order: the expert
    layers' pick counts summed over layers (``moe.expert_layer``'s), a 1 for
    this token step, then ``more``: the values of the entries a family
    appends to the tuple, scalars or vectors."""
    return jnp.concatenate(
        [counts, jnp.ones((1,), jnp.int32)]
        + [jnp.ravel(m).astype(jnp.int32) for m in more])


class PagedFamily(NamedTuple):
    """What a model family gives :class:`PagedGenerator`: what its pool is
    and how one forward pass writes and attends it. The generator keeps the
    programs (their names, buckets, donation, the sampling tail), the engine
    and the block manager know only block ids.

    - ``init_pool(config, num_blocks, block_tokens)`` -> a tuple of arrays,
      each ``[layers or sublayers, num_blocks, block_tokens, ...]``: blocks
      are dimension 1 of every one, block 0 the trash block, so the block
      copy is the generator's, whatever a row holds;
    - ``init_slot_state(config, slots)`` -> a tuple of arrays, each
      ``[layers, slots, ...]``: what a SLOT carries between tokens beside
      its rows in the pool (a linear-attention layer's recurrent state).
      None: the family keeps none, its state is the empty tuple and its
      programs take no operand for it;
    - ``prefill(params, tokens [1, P], pool, state, table, start_pos,
      suffix_len, slot, config, block_tokens, kernel)`` -> ``(logits, pool,
      state, aux)``: writes slot ``slot``'s state from zero. ``logits`` is
      ``[1, P, V]``, or ``[1, 1, V]`` where the family hands the head the
      last real position alone;
    - ``decode(params, tokens [S, T], pool, state, tables, lengths, config,
      block_tokens, kernel, active)`` -> ``(logits, pool, state, aux)``:
      advances the state of active slots and leaves a parked slot's bit for
      bit; ``aux`` is None or a small integer array of per-call counts that
      the programs hand back beside the tokens (summed over a decode chunk);
    - ``aux_counts``: one :class:`AuxCount` for each entry of ``aux``, in its
      order: the engine folds them into ``stats()`` under these names and
      knows nothing else about them;
    - ``logits_dim(params, config)``: rows of the ``last`` carry;
    - ``working_params(params, config)`` -> the tree the family's programs
      are CALLED with, made once from the stored tree when a generator is
      built (jitted; the stored tree is not donated: it stays whoever's it
      was). None: the programs read the stored tree as it is (LongCat and
      Olmo-Hybrid store bfloat16, some of Olmo-Hybrid's leaves float32 on
      purpose, one array a matrix). GPT-2 shares ``transformer.init_params``
      with training, which keeps float32 masters in stacked ``[layers, ...]``
      slabs: read as they are stored, every serve program began by
      converting all of them, cutting each layer's matrices out of the
      converted slab and transposing the tied embedding for the head, on
      every call (:func:`gpt2_working_params`);
    - ``describe(config)`` -> a dict of names and counts that the engine's
      ``describe()`` carries beside its own (how many of a stack's layers
      are of which kind): for an operator to print, read by no code path;
    - ``walk_group_blocks(config, pool)`` -> how many consecutive table
      entries one iteration of the family's DECODE walk fetches together
      (the engine counts a chain's groups by it, ``kv_groups_total``). None:
      the pool is a K and a V array under the grouped-query kernels
      (``ops.paged_attention.group_blocks``);
    - ``unsupported``: engine features the family cannot run:
      ``prefix_cache`` makes the engine neither look up nor register a
      chain (a family with a slot state: a K/V hit at position p is usable
      only with the state at p).

    A config object names its family through a ``paged_family()`` method;
    one without it is GPT-2 (``transformer.TransformerConfig``)."""
    init_pool: Callable
    prefill: Callable
    decode: Callable
    logits_dim: Callable
    unsupported: Tuple[str, ...] = ()
    aux_counts: Tuple[AuxCount, ...] = ()
    init_slot_state: Optional[Callable] = None
    working_params: Optional[Callable] = None
    describe: Optional[Callable] = None
    walk_group_blocks: Optional[Callable] = None


def gpt2_working_params(params, config: TransformerConfig) -> Dict:
    """GPT-2's weights in the form its serve programs' products read, so
    that no program converts, slices out or transposes one again: every leaf
    in ``config.dtype`` (the value ``astype`` gives is the same whenever it
    is taken), ``blocks`` cut into ``layers``, a tuple of one dict a layer
    with one array a matrix (XLA copies each matrix out of a stacked slab
    before it multiplies by it), and ``head`` ``[d_model, vocab]``, the
    matrix the logits' product contracts with: the tied embedding's
    transposed copy, or ``lm_head``. The two embedding tables' rows are
    padded to whole 128-lane tiles (the forwards cut a gathered row back to
    ``d_model``; nothing at GPT-2's aligned widths)."""
    cast = lambda p: p.astype(config.dtype)
    blocks = jax.tree.map(cast, params["blocks"])
    out = {name: cast(leaf) for name, leaf in params.items()
           if name not in ("blocks", "lm_head")}
    out["layers"] = tuple(jax.tree.map(lambda p: p[layer], blocks)
                          for layer in range(config.n_layers))
    out["head"] = (out["tok_embed"].T if config.tie_embeddings
                   else cast(params["lm_head"]))
    # A table is read by rows: rows of whole 128-lane tiles keep it
    # row-major on the device (gpt2-xl's 1,600 lanes do not, and each call
    # then copied the table row-major before its gather).
    lanes = -config.d_model % 128
    for name in {"tok_embed", "pos_embed"} & set(out):
        out[name] = jnp.pad(out[name], ((0, 0), (0, lanes)))
    return out


def _gpt2_prefill(params, tokens, pool, state, table, start_pos, suffix_len,
                  slot, config, block_tokens, kernel="gather"):
    logits, k_pool, v_pool = _forward_prefill_paged(
        params, tokens, *pool, table, start_pos, suffix_len, config,
        block_tokens, kernel=kernel)
    return logits, (k_pool, v_pool), state, None


def _gpt2_decode(params, tokens, pool, state, tables, lengths, config,
                 block_tokens, kernel="gather", active=None):
    logits, k_pool, v_pool = _forward_decode_paged(
        params, tokens, *pool, tables, lengths, config, block_tokens,
        kernel=kernel)
    return logits, (k_pool, v_pool), state, None


GPT2_FAMILY = PagedFamily(
    init_pool=init_block_pool, prefill=_gpt2_prefill, decode=_gpt2_decode,
    logits_dim=lambda params, config: (
        params["tok_embed"].shape[0] if config.tie_embeddings
        else params["lm_head"].shape[-1]),
    working_params=gpt2_working_params)


def paged_family(config) -> PagedFamily:
    named = getattr(config, "paged_family", None)
    return named() if named is not None else GPT2_FAMILY


@functools.partial(jax.jit, static_argnums=(0, 2))
def _make_working(make, params, config):
    return make(params, config)


def working_params(params, config):
    """The tree ``config``'s family's serve programs are called with
    (:attr:`PagedFamily.working_params`): made from the stored tree in one
    jitted call, or the stored tree itself for a family that gives no
    function."""
    make = paged_family(config).working_params
    return params if make is None else _make_working(make, params, config)


class PagedGenerator:
    """Device half of the serving engine (``serve/llm.py LLMEngine``): one
    program per prompt bucket, one per chunk size, greedy and sampled slots
    riding the same program through per-slot operands. K/V lives in a
    SHARED block pool addressed through per-sequence block tables — the
    layout that makes hash-based prefix reuse and copy-on-write forks
    possible.

    Device state is ``(pool, slot_state, last, keys)`` threaded with buffer
    donation: ``pool`` is the family's tuple of pool arrays
    (:class:`PagedFamily`; K and V for GPT-2, one latent array for LongCat),
    ``slot_state`` its tuple of per-slot arrays (empty for those two; a
    recurrent state and a convolution tail a linear-attention layer for
    Olmo-Hybrid), pytrees that the prefill and decode programs take and
    return whole. Block tables and per-slot lengths are
    plain numpy operands owned by the host-side :class:`KVBlockManager` +
    engine.

    ``params`` is the WORKING tree, what every
    program here is called with: :func:`working_params` of the stored tree
    the generator was built from, made once in the constructor. The
    generator keeps no reference to the stored tree: a float32 tree that
    training or a reference shares stays its owner's, and a deployment whose
    factory lets go of it holds the working copy alone.
    """

    def __init__(self, params, config, *, slots: int,
                 num_blocks: int, block_tokens: int,
                 max_len: Optional[int] = None,
                 attention_kernel: str = "auto"):
        self.config = config
        self.family = paged_family(config)
        self.slots = slots
        self.max_len = max_len or config.max_seq_len
        self.block_tokens = int(block_tokens)
        if self.max_len % self.block_tokens:
            raise ValueError(
                f"max_len {self.max_len} not a multiple of "
                f"serve_kv_block_tokens {self.block_tokens}")
        self.blocks_per_seq = self.max_len // self.block_tokens
        self.num_blocks = int(num_blocks)
        self.attention_kernel = resolve_attention_kernel(attention_kernel)
        self.logits_dim = self.family.logits_dim(params, config)
        self.set_params(params)
        self._prefill_fns = {}   # suffix bucket -> jitted paged prefill
        self._decode_fns = {}    # chunk -> jitted paged decode
        self._copy_fn = None

    def set_params(self, params) -> None:
        """Make the working tree anew from a stored tree of the same shapes
        (a weight update; the compiled programs stay). The caller resets
        whatever device state the old weights wrote."""
        self.params = None      # let go first: two copies may not fit
        self.params = working_params(params, self.config)

    @property
    def params_working_bytes(self) -> int:
        """Bytes of the working copy made for the target model's programs;
        0 where they read the stored tree."""
        if self.family.working_params is None:
            return 0
        return sum(leaf.nbytes for leaf in jax.tree.leaves(self.params))

    def init_state(self):
        pool = tuple(self.family.init_pool(self.config, self.num_blocks,
                                           self.block_tokens))
        make = self.family.init_slot_state
        state = () if make is None else tuple(make(self.config, self.slots))
        last = jnp.zeros((self.slots, self.logits_dim), jnp.float32)
        keys = jnp.zeros((self.slots, 2), jnp.uint32)
        return pool, state, last, keys

    def prefill_fn(self, bucket: int):
        """paged_prefill(params, pool, state, last, keys, table [NB], padded
        [1,P], start_pos, suffix_len, slot, seed) -> (pool, state, last,
        keys, aux): prefill the SUFFIX bucket at start_pos (the prefix-hit
        length), write the slot's state from zero and park last-token
        logits + PRNG key in the slot rows. ``aux`` is the family's per-call
        counts (None for GPT-2)."""
        fn = self._prefill_fns.get(bucket)
        if fn is not None:
            return fn
        c = self.config
        bt = self.block_tokens
        kernel = self.attention_kernel
        forward = self.family.prefill

        @functools.partial(jax.jit, donate_argnums=(1, 2, 3, 4))
        def paged_prefill(params, pool, state, last, keys, table, padded,
                          start_pos, suffix_len, slot, seed):
            logits, pool, state, aux = forward(
                params, padded, pool, state, table, start_pos, suffix_len,
                slot, c, bt, kernel=kernel)
            # A family that gave the head one row gave the last real one.
            row = logits[:, 0] if logits.shape[1] == 1 else (
                jax.lax.dynamic_index_in_dim(
                    logits, suffix_len - 1, axis=1, keepdims=False))  # [1, V]
            last = lax.dynamic_update_slice(last, row, (slot, 0))
            keys = lax.dynamic_update_slice(
                keys, jax.random.PRNGKey(seed)[None], (slot, 0))
            return pool, state, last, keys, aux

        self._prefill_fns[bucket] = paged_prefill
        return paged_prefill

    def decode_fn(self, chunk: int):
        """paged_decode(params, pool, state, last, keys, tables [S,NB],
        lengths [S], active, greedy, temps) -> (toks [S, chunk], pool,
        state, last, keys, aux): ``chunk`` scan steps advancing every active
        slot through its block table in one dispatch; ``aux`` the family's
        counts summed over the chunk (None for GPT-2)."""
        fn = self._decode_fns.get(chunk)
        if fn is not None:
            return fn
        c = self.config
        bt = self.block_tokens
        kernel = self.attention_kernel
        forward = self.family.decode

        @functools.partial(jax.jit, donate_argnums=(1, 2, 3, 4))
        def paged_decode(params, pool, state, last, keys, tables, lengths,
                         active, greedy, temps):
            adv = active.astype(jnp.int32)
            act_col = active[:, None]
            temp_safe = jnp.maximum(temps, 1e-6)[:, None]

            def step(carry, _):
                pool, state, lens, last, keys = carry
                # Scope names are what a profiler's op metadata carries:
                # stable across refactors of the code inside them.
                with jax.named_scope("sample"):
                    real = last[:, : c.vocab_size]
                    split = jax.vmap(jax.random.split)(keys)   # [S, 2, 2]
                    keys2, subs = split[:, 0], split[:, 1]
                    samp = jax.vmap(jax.random.categorical)(
                        subs, real / temp_safe)
                    nxt = jnp.where(greedy, jnp.argmax(real, axis=-1),
                                    samp).astype(jnp.int32)
                with jax.named_scope("decode_step"):
                    logits, pool, state, aux = forward(
                        params, nxt[:, None], pool, state, tables, lens, c,
                        bt, kernel=kernel, active=active)
                lens = lens + adv
                last = jnp.where(act_col, logits[:, -1], last)
                keys = jnp.where(act_col, keys2, keys)
                return (pool, state, lens, last, keys), (nxt, aux)

            (pool, state, _lens, last, keys), (toks, aux) = lax.scan(
                step, (pool, state, jnp.asarray(lengths), last, keys),
                None, length=chunk)
            if aux is not None:
                aux = jnp.sum(aux, axis=0)
            return toks.T, pool, state, last, keys, aux

        self._decode_fns[chunk] = paged_decode
        return paged_decode

    def copy_fn(self):
        """copy_block(pool, src, dst) -> pool: the copy-on-write primitive
        — duplicate one shared block (a prefix-hit partial tail) into a
        private block before divergent writes, in every array of the pool."""
        if self._copy_fn is None:

            @functools.partial(jax.jit, donate_argnums=(0,))
            def copy_block(pool, src, dst):
                return jax.tree.map(
                    lambda a: a.at[:, dst].set(a[:, src]), pool)

            self._copy_fn = copy_block
        return self._copy_fn


class NoFreeBlocks(RuntimeError):
    """The pool cannot supply an allocation even after evicting every
    unpinned cached block — the caller should keep the request queued."""


class KVBlockManager:
    """Host-side bookkeeping for the paged KV pool: free runs, refcounts,
    and the prefix-reuse hash table.

    Block states (block 0, the trash block, is never managed):

    - FREE: in a free RUN, content garbage;
    - ACTIVE: refcount > 0, pinned by one or more live sequences;
    - CACHED: refcount 0 but hash-retained — the block's content is a
      registered prefix and future lookups may hit it; evicted LRU-first
      when the free runs hold too few.

    The free blocks are held as address-ordered RUNS ``(first, count)`` of
    consecutive block ids, and a chain is cut from them in runs: ``alloc(n)``
    hands out the blocks of the smallest free run that holds ``n`` whole
    (the lowest of equals) and, where none does, of the largest runs first
    until ``n`` are found (a second view of the runs, sorted by size, finds
    both without looking at a run it does not take); ``release`` merges what
    it frees with its free neighbours, so a break heals when both sides of
    it are free again. Where
    the free runs hold too few, the LRU-first CACHED blocks are evicted INTO
    the runs, as many as are short and no more, and the request is cut from
    what is then there (a retired chain was a run and is evicted in chain
    order, so it comes back as one). Why a chain wants to be runs: the paged
    kernels fetch a group of consecutive table entries that are consecutive
    BLOCKS by one copy a pool (``ops/paged_attention.py:live_copies``),
    where a scattered group costs one copy an entry; a FIFO of single
    blocks cut every new chain from pieces of old ones, and under the
    benchmark's closed traffic 0-55% of full groups were runs where these
    runs give 100% (PERF.md, PR 57). What the runs cannot mend: a pool whose
    every block is pinned or CACHED (a prefix family's, once its cache has
    filled it) leaves nothing to choose, a request is then exactly the next
    ``n`` blocks of the LRU stream, every cut splits a piece once more and
    pieces meet again only by chance, so there the share decays towards
    none (``tests/test_kv_block_manager_runs.py``).

    Full blocks are keyed by the chained digest of the token prefix ending
    at them (``util.blockhash``); a retired sequence's PARTIAL tail block is
    additionally keyed by ``(parent_digest, tail_token_tuple)`` so a
    follow-up turn (history + new text) can reuse it — hit tail blocks are
    handed out COPY-ON-WRITE (the engine duplicates them via
    ``PagedGenerator.copy_fn`` before the divergent suffix writes into
    them; full hit blocks are read-only to every sharer and share by
    refcount alone).

    Thread-safe behind one internal lock; never calls out while holding it
    (safe under the engine's state lock — lock order: engine state → here).
    """

    def __init__(self, num_blocks: int, block_tokens: int):
        import threading as _t

        if num_blocks < 2:
            raise ValueError("pool needs at least one block beyond trash")
        self.num_blocks = int(num_blocks)
        self.block_tokens = int(block_tokens)
        self._lock = _t.Lock()
        # The FREE blocks as runs of consecutive ids: the runs' first blocks
        # in address order, and each run's count by its first block.
        self._run_firsts: List[int] = [1]
        self._run_count: Dict[int, int] = {1: num_blocks - 1}
        # The same runs as (-count, first), sorted: the largest first, and
        # the smallest that holds a request by bisection.
        self._by_size: List[Tuple[int, int]] = [(1 - num_blocks, 1)]
        self._n_free = num_blocks - 1
        self._ref: Dict[int, int] = {}
        self._by_hash: Dict[bytes, int] = {}       # full-block digest -> id
        self._hash_of: Dict[int, bytes] = {}
        self._tail_by_key: Dict[tuple, int] = {}   # (parent, tokens) -> id
        self._tail_key_of: Dict[int, tuple] = {}
        # CACHED blocks in LRU order (oldest first).
        self._cached: "Dict[int, None]" = {}
        self.hit_tokens = 0
        self.miss_tokens = 0
        self.cow_copies = 0
        # CACHED blocks dropped to serve an alloc (``kv.alloc``'s span attr)
        self.evicted_blocks = 0

    # -- allocation -----------------------------------------------------------
    def alloc(self, n: int) -> List[int]:
        """Take ``n`` blocks out of the free runs: the smallest run that
        holds them whole, else the largest runs first. Where the runs hold
        fewer than ``n``, the LRU cached blocks are evicted into them first
        (dropping their hash entries), as many as are short; raises
        :class:`NoFreeBlocks` without side effects when the pool can't
        supply them."""
        with self._lock:
            if self._n_free + len(self._cached) < n:
                raise NoFreeBlocks(
                    f"need {n} blocks; {self._n_free} free + "
                    f"{len(self._cached)} cached of {self.num_blocks - 1}")
            short = n - self._n_free
            if short > 0:
                lru = list(itertools.islice(self._cached, short))
                for b in lru:
                    self._drop_cached_locked(b)
                self.evicted_blocks += short
                self._free_locked(lru)
            out = self._cut_locked(n)
            for b in out:
                self._ref[b] = 1
            return out

    def _cut_locked(self, n: int) -> List[int]:
        """``n`` blocks off the free runs, which hold at least as many: the
        front of the smallest run that holds them whole (of equals the
        lowest), else whole runs, the largest first (of equals the lowest),
        and the front of the last one taken. No run is looked at that is not
        taken: ``_by_size`` finds them."""
        by_size = self._by_size
        fit = bisect.bisect_right(by_size, (-n, self.num_blocks)) - 1
        if fit >= 0:        # the smallest count that holds n: its lowest run
            fit = bisect.bisect_left(by_size, (by_size[fit][0], 0))
            order = [by_size[fit][1]]
        else:
            order, found = [], 0
            for size, first in by_size:
                order.append(first)
                found -= size
                if found >= n:
                    break
        out: List[int] = []
        for first in order:
            at = bisect.bisect_left(self._run_firsts, first)
            count = self._drop_run_locked(at)
            take = min(count, n - len(out))
            out.extend(range(first, first + take))
            if take < count:
                self._add_run_locked(at, first + take, count - take)
        self._n_free -= n
        return out

    def _free_locked(self, blocks: Sequence[int]) -> None:
        """``blocks`` into the free runs: what of them is consecutive in the
        order given goes in as one run (a chain released is few), each
        merged with the free neighbours it has."""
        firsts, counts = self._run_firsts, self._run_count
        runs: List[List[int]] = []
        for b in blocks:
            if runs and runs[-1][0] + runs[-1][1] == b:
                runs[-1][1] += 1
            else:
                runs.append([b, 1])
        for first, count in runs:
            at = bisect.bisect_left(firsts, first)
            if at < len(firsts) and firsts[at] == first + count:
                count += self._drop_run_locked(at)            # the run behind
            if at and firsts[at - 1] + counts[firsts[at - 1]] == first:
                at -= 1                                       # the run before
                first = firsts[at]
                count += self._drop_run_locked(at)
            self._add_run_locked(at, first, count)
        self._n_free += len(blocks)

    def _add_run_locked(self, at: int, first: int, count: int) -> None:
        """A free run in, ``at`` its place in address order."""
        self._run_firsts.insert(at, first)
        self._run_count[first] = count
        bisect.insort(self._by_size, (-count, first))

    def _drop_run_locked(self, at: int) -> int:
        """The free run at place ``at`` in address order out; its count."""
        first = self._run_firsts.pop(at)
        count = self._run_count.pop(first)
        del self._by_size[bisect.bisect_left(self._by_size, (-count, first))]
        return count

    def _drop_cached_locked(self, b: int) -> None:
        self._cached.pop(b, None)
        d = self._hash_of.pop(b, None)
        if d is not None and self._by_hash.get(d) == b:
            del self._by_hash[d]
        tk = self._tail_key_of.pop(b, None)
        if tk is not None and self._tail_by_key.get(tk) == b:
            del self._tail_by_key[tk]

    def release(self, block_ids: Sequence[int]) -> None:
        """Unpin blocks; at refcount 0 a hash-registered block becomes
        CACHED (reusable by future lookups, LRU-evictable), an unregistered
        one goes straight back into the free runs, merged with its free
        neighbours."""
        with self._lock:
            freed = []
            for b in block_ids:
                r = self._ref.get(b, 0) - 1
                if r > 0:
                    self._ref[b] = r
                    continue
                self._ref.pop(b, None)
                if b in self._hash_of or b in self._tail_key_of:
                    self._cached.pop(b, None)
                    self._cached[b] = None         # move to MRU end
                else:
                    freed.append(b)
            self._free_locked(freed)

    # -- prefix reuse ---------------------------------------------------------
    def lookup(self, tokens: Sequence[int]) -> Tuple[List[int], Optional[int], int]:
        """Longest reusable prefix of ``tokens``: returns ``(full_blocks,
        tail_block, hit_len)`` with every returned block PINNED (refcount
        bumped; caller must ``release`` them with the sequence). hit_len is
        capped at ``len(tokens) - 1`` so at least one suffix token is always
        recomputed — prefill must produce last-token logits.

        ``tail_block`` (a retired sequence's partial last block) is SHARED
        CONTENT: the caller must copy it before writing (COW)."""
        from ray_tpu.util import blockhash

        bt = self.block_tokens
        cap = len(tokens) - 1
        if cap <= 0:
            return [], None, 0
        digests = blockhash.block_hashes(tokens, bt, max_blocks=cap // bt)
        with self._lock:
            full: List[int] = []
            parent = blockhash.SEED
            for d in digests:
                b = self._by_hash.get(d)
                if b is None:
                    break
                full.append(b)
                parent = d
            k = len(full)
            hit_len = k * bt
            tail = None
            for t in range(min(bt - 1, cap - hit_len), 0, -1):
                key = (parent, tuple(int(x) for x in
                                     tokens[hit_len:hit_len + t]))
                b = self._tail_by_key.get(key)
                if b is not None:
                    tail = b
                    hit_len += t
                    break
            for b in full + ([tail] if tail is not None else []):
                if self._ref.get(b, 0) == 0:
                    self._cached.pop(b, None)      # CACHED -> ACTIVE
                self._ref[b] = self._ref.get(b, 0) + 1
            self.hit_tokens += hit_len
            self.miss_tokens += len(tokens) - hit_len
            return full, tail, hit_len

    def register_chain(self, tokens: Sequence[int], block_ids: Sequence[int],
                       n_real: int) -> None:
        """Publish a sequence's blocks into the reuse table: every block
        fully covered by the first ``n_real`` REAL tokens gets its chained
        digest, and the partial remainder (if any) gets a tail entry.
        First registration wins — a concurrent sequence that produced the
        same prefix keeps the existing mapping and its own blocks simply
        retire unhashed."""
        from ray_tpu.util import blockhash

        bt = self.block_tokens
        n_full = min(n_real // bt, len(block_ids))
        digests = blockhash.block_hashes(tokens[:n_real], bt,
                                         max_blocks=n_full)
        with self._lock:
            parent = blockhash.SEED
            for i, d in enumerate(digests):
                b = block_ids[i]
                if d not in self._by_hash and b not in self._hash_of \
                        and b not in self._tail_key_of:
                    self._by_hash[d] = b
                    self._hash_of[b] = d
                parent = d
            t = n_real - n_full * bt
            if t > 0 and n_full < len(block_ids):
                b = block_ids[n_full]
                key = (parent, tuple(int(x) for x in
                                     tokens[n_full * bt:n_real]))
                if key not in self._tail_by_key and b not in self._hash_of \
                        and b not in self._tail_key_of:
                    self._tail_by_key[key] = b
                    self._tail_key_of[b] = key

    def peek_hit_len(self, tokens: Sequence[int]) -> int:
        """Advisory hit length: same walk as :meth:`lookup` but pins nothing
        and skips the counters — the engine's admission-budget estimate."""
        from ray_tpu.util import blockhash

        bt = self.block_tokens
        cap = len(tokens) - 1
        if cap <= 0:
            return 0
        digests = blockhash.block_hashes(tokens, bt, max_blocks=cap // bt)
        with self._lock:
            hit_len = 0
            parent = blockhash.SEED
            for d in digests:
                if d not in self._by_hash:
                    break
                hit_len += bt
                parent = d
            for t in range(min(bt - 1, cap - hit_len), 0, -1):
                key = (parent, tuple(int(x) for x in
                                     tokens[hit_len:hit_len + t]))
                if key in self._tail_by_key:
                    return hit_len + t
            return hit_len

    def note_cow(self) -> None:
        with self._lock:
            self.cow_copies += 1

    # -- introspection --------------------------------------------------------
    def stats(self) -> Dict[str, float]:
        with self._lock:
            active = len(self._ref)
            return {
                "kv_blocks_total": float(self.num_blocks - 1),
                "kv_blocks_active": float(active),
                "kv_blocks_cached": float(len(self._cached)),
                "kv_blocks_free": float(self._n_free),
                "kv_hit_tokens": float(self.hit_tokens),
                "kv_miss_tokens": float(self.miss_tokens),
                "kv_cow_copies": float(self.cow_copies),
            }

    def active_blocks(self) -> int:
        """Blocks pinned by live sequences — must drop to 0 when every
        request retires (the leak-check invariant)."""
        with self._lock:
            return len(self._ref)


class Generator:
    """Compiled prefill + decode for one (config, batch, max_len) shape.

    Two decode granularities:

    - ``_decode``: one token per dispatch — simple, but each host sync pays
      a full host↔device round trip.
    - ``_prefill_decode`` / ``_decode_chunk``: prefill fused with a
      ``lax.scan`` over K decode steps in ONE dispatch — the sampling loop
      lives on device, so K tokens cost one round trip. This is the serving
      fast path (`serve/llm.py`).
    """

    def __init__(self, params, config: TransformerConfig, *, batch: int = 1,
                 max_len: Optional[int] = None):
        self.params = params
        self.config = config
        self.batch = batch
        self.max_len = max_len or config.max_seq_len

        c = config

        @jax.jit
        def prefill(params, cache, tokens):  # tokens [B, P] (P static)
            return _forward_cached(params, tokens, cache, c, 0)

        @functools.partial(jax.jit, donate_argnums=(1,))
        def decode(params, cache, token, pos):  # token [B, 1]
            logits, cache = _forward_cached(params, token, cache, c, pos)
            return logits[:, -1], cache

        self._prefill = prefill
        self._decode = decode
        self._chunked = {}  # (chunk, sampled) -> (prefill_decode, decode_chunk)

    def chunked_fns(self, chunk: int, sampled: bool):
        """Jitted (prefill+scan-decode, scan-decode) pair for a chunk size."""
        key = (chunk, sampled)
        if key in self._chunked:
            return self._chunked[key]
        c = self.config

        def make_step(params, temp):
            # A FRESH closure per jit trace: lax.scan caches traced jaxprs
            # by (function identity, avals), so sharing one step function
            # across the two jitted wrappers would leak the first trace's
            # closure tracers into the second as stale constants.
            def step(carry, _):
                last, cache, pos, rng = carry
                real = last[:, : c.vocab_size]
                if sampled:
                    rng, sub = jax.random.split(rng)
                    nxt = jax.random.categorical(sub, real / temp, axis=-1)
                else:
                    nxt = jnp.argmax(real, axis=-1)
                logits, cache = _forward_cached(
                    params, nxt[:, None].astype(jnp.int32), cache, c, pos
                )
                return (logits[:, -1], cache, pos + 1, rng), nxt

            return step

        @functools.partial(jax.jit, donate_argnums=(1,))
        def prefill_decode(params, cache, padded, real_len, rng, temp):
            """One dispatch: full prefill + K sampled/greedy decode steps.

            ``padded`` [B, P]: prompt padded to a bucket; first-token logits
            are read at the REAL last position, and decode starts at
            ``real_len`` so pad garbage in the cache is overwritten before
            the causal mask could ever expose it.
            """
            logits, cache = _forward_cached(params, padded, cache, c, 0)
            last = jax.lax.dynamic_index_in_dim(
                logits, real_len - 1, axis=1, keepdims=False)   # [B, V]
            (last, cache, pos, rng), toks = lax.scan(
                make_step(params, temp), (last, cache, real_len, rng),
                None, length=chunk)
            return toks.T, last, cache, pos, rng                 # [B, chunk]

        @functools.partial(jax.jit, donate_argnums=(1,))
        def decode_chunk(params, cache, last, pos, rng, temp):
            (last, cache, pos, rng), toks = lax.scan(
                make_step(params, temp), (last, cache, pos, rng),
                None, length=chunk)
            return toks.T, last, cache, pos, rng

        self._chunked[key] = (prefill_decode, decode_chunk)
        return self._chunked[key]

    def generate(
        self,
        prompt_tokens,
        *,
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        seed: int = 0,
        stream: bool = False,
    ):
        """Greedy (temperature=0) or sampled generation. Returns token list
        (or a generator of tokens when ``stream``)."""
        import numpy as np

        def run():
            prompt = jnp.asarray(np.asarray(prompt_tokens, np.int32)).reshape(self.batch, -1)
            P = prompt.shape[1]
            cache = init_cache(self.config, self.batch, self.max_len)
            logits, cache = self._prefill(self.params, cache, prompt)
            key = jax.random.key(seed)
            last = logits[:, -1]
            pos = P
            for _ in range(max_new_tokens):
                # mask vocab padding before picking
                last_real = last[:, : self.config.vocab_size]
                if temperature > 0:
                    key, sub = jax.random.split(key)
                    nxt = jax.random.categorical(sub, last_real / temperature, axis=-1)
                else:
                    nxt = jnp.argmax(last_real, axis=-1)
                # Accepted host-sync finding (lint baseline): this is the
                # single-sequence oracle/debug path — one token per yield
                # IS the contract, so the per-token sync stays. Batched
                # serving goes through the engines, which fetch per chunk.
                yield int(nxt[0])
                if pos >= self.max_len:
                    return
                last, cache = self._decode(
                    self.params, cache, nxt[:, None].astype(jnp.int32), pos
                )
                pos += 1

        if stream:
            return run()
        return list(run())
