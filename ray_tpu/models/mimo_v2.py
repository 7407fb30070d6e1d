"""MiMo-V2-Flash's language model (``model_type: mimo_v2_flash``) on the paged
serve path.

The eighth model family of the zoo and the second with two kinds of ATTENTION
layer in one stack (``models/afmoe.py`` is the first): ``hybrid_layer_pattern``
names each layer full (0) or window (1), one to five. What this family has
that Trinity's has not:

- the two kinds differ in their KV HEADS (``num_key_value_heads`` 4 in a full
  layer, ``swa_num_key_value_heads`` 8 in a window layer), so the full layers'
  paged pool and the window layers' rings have rows of different widths;
- a head of K (and of q) is ``head_dim`` 192 wide, a head of V ``v_head_dim``
  128: a K row and a V row of one layer differ too, and the attention's
  output is 128 a head;
- a window layer's softmax has a SINK, a learned scalar a query head that
  joins the denominator and carries no value
  (``add_swa_attention_sink_bias``);
- the window is 128 positions: a ring is a window and a block;
- only the first ``rotary_dim`` (64) of a head's 192 dimensions are rotated,
  with a base that differs by kind (``rope_theta`` full, ``swa_rope_theta``
  window); ``v`` is scaled by ``attention_value_scale``.

Keys as in huggingface.co/XiaomiMiMo/MiMo-V2-Flash ``config.json``; the layer,
with ``rms`` an RMSNorm at ``layernorm_epsilon`` (what the keys do not say is
listed under ``assumed`` in ``benchmark/configs/mimo-v2-flash.json``)::

    a       = rms(x; g_attn)
    q, k    = a Wq (64 heads of 192), a Wk (KV_l heads of 192)
    v       = attention_value_scale * (a Wv)  (KV_l heads of 128)
    q, k    : the first 64 of 192 dimensions rotated (half-split pairs inside
              those 64, absolute positions, base by the layer's kind)
    s_ij    = q_i . k_j / sqrt(192);  j <= i  (window: and i - j < 128)
    full:   p = softmax_j(s)
    window: p_ij = exp(s_ij) / (exp(b_h) + sum_j' exp(s_ij'))
    x       = x + concat_h(p v) Wo,  query head h on KV head h // (64 / KV_l)
    f       = rms(x; g_ffn)
    layer with moe_layer_freq 0:  x = x + Wdown(silu(Wgate f) * Wup f)
    else:   s = sigmoid(f Wr) in float32;  picks = top-8 of (s + bias);
            w = s[picks] / sum s[picks];  x = x + sum_{e held} w_e expert_e(f)
    logits  = rms(x_last; g_f) W_head                        (untied)

``held = (first, count)`` says which routed experts' weights live here, as
``kimi_k2`` and ``afmoe``: the layer routes over all ``n_routed_experts``,
normalises over ALL of a token's picks and adds only what its own experts
give (``ops/moe.py:held_experts_ffn``). There is no shared expert.

The full layers' rows lie in the paged pool (``PagedFamily.init_pool``, the
block manager's), a window layer's in a RING a slot
(``PagedFamily.init_slot_state``), as Trinity's; the windowed walk, the sink
and the two widths are ``ops/paged_attention.py:paged_attention``'s. The
prefix cache is not supported (a hit at position p would need every ring at
p). Weights are one array a matrix, stored in ``param_dtype`` with the heads
folded into the columns (a head of 192 is no whole lane tile: ``[D, heads *
192]`` is), read as stored.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.generate import (EXPERT_AUX_COUNTS, AuxCount,
                                     PagedFamily, decode_cells, expert_aux,
                                     prefill_cells)
from ray_tpu.ops import moe, window_ring
from ray_tpu.ops.layers import (gated_ffn, mm as _mm, rms_norm, rope,
                                rope_frequencies)
from ray_tpu.ops.paged_attention import (paged_attention,
                                         paged_attention_reference)

FULL, WINDOW = 0, 1


@dataclass(frozen=True)
class MimoV2Config:
    """Field names are the published ``config.json`` keys (MiMo-V2-Flash's
    values); ``held``, ``window_block_tokens``, ``max_seq_len`` and the two
    dtypes are this program's."""
    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    swa_num_key_value_heads: int = 8
    head_dim: int = 192
    v_head_dim: int = 128
    sliding_window: int = 128
    # 0: a full layer, 1: a window layer. Empty: the published period, a
    # full layer first and then every sixth (layers 0, 5, 11, 17, ...).
    hybrid_layer_pattern: Tuple[int, ...] = ()
    # 0: a dense feed-forward, 1: experts. Empty: the first layer dense.
    moe_layer_freq: Tuple[int, ...] = ()
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    routed_scaling_factor: Optional[float] = None        # null: 1.0
    rope_theta: float = 5000000.0
    swa_rope_theta: float = 10000.0
    partial_rotary_factor: float = 0.334
    attention_value_scale: float = 0.707
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    layernorm_epsilon: float = 1e-5
    max_seq_len: int = 262144
    # Routed experts whose weights live on this chip: (first, count).
    held: Tuple[int, int] = (0, 256)
    # Rows of one block of a window layer's ring: what one copy of the
    # decode kernel brings. A ring is the window and one block more.
    window_block_tokens: int = 64
    dtype: Any = jnp.bfloat16          # activation/compute dtype
    param_dtype: Any = jnp.bfloat16    # storage dtype

    def __post_init__(self):
        L = self.num_hidden_layers
        kinds = tuple(self.hybrid_layer_pattern) or tuple(
            FULL if l == 0 or l % 6 == 5 else WINDOW for l in range(L))
        ffn = tuple(self.moe_layer_freq) or tuple(int(l > 0) for l in range(L))
        object.__setattr__(self, "hybrid_layer_pattern", kinds)
        object.__setattr__(self, "moe_layer_freq", ffn)
        object.__setattr__(self, "held", tuple(self.held))
        if (len(kinds) != L or len(ffn) != L or set(kinds) - {FULL, WINDOW}
                or set(ffn) - {0, 1}):
            raise ValueError(
                f"hybrid_layer_pattern {kinds} and moe_layer_freq {ffn}: want "
                f"{L} entries each, of 0 and 1")
        if FULL not in kinds:
            raise ValueError("a stack with no full layer has no paged pool")
        for kv in (self.num_key_value_heads, self.swa_num_key_value_heads):
            if self.num_attention_heads % kv:
                raise ValueError("query heads divide into KV heads in whole runs")
        if self.scoring_func != "sigmoid":
            raise ValueError(f"no scoring rule {self.scoring_func!r} here")

    # What the generator and the pool read: the pool is the FULL layers'.
    @property
    def n_layers(self) -> int:
        """Layers whose K/V rows lie in the paged pool: the full ones."""
        return self.hybrid_layer_pattern.count(FULL)

    @property
    def window_layers(self) -> int:
        return self.hybrid_layer_pattern.count(WINDOW)

    @property
    def expert_layers(self) -> int:
        return sum(self.moe_layer_freq)

    @property
    def rotary_dim(self) -> int:
        """How many of a head's leading dimensions are rotated:
        ``partial_rotary_factor * head_dim`` rounded down to an even number
        (0.334 x 192 = 64.1: 64)."""
        return int(self.head_dim * self.partial_rotary_factor) // 2 * 2

    @property
    def route_scale(self) -> float:
        s = self.routed_scaling_factor
        return 1.0 if s is None else float(s)

    def is_window(self, layer: int) -> bool:
        return self.hybrid_layer_pattern[layer] == WINDOW

    def kv_heads(self, layer: int) -> int:
        return (self.swa_num_key_value_heads if self.is_window(layer)
                else self.num_key_value_heads)

    def rope_base(self, layer: int) -> float:
        return self.swa_rope_theta if self.is_window(layer) else self.rope_theta

    def has_sink(self, layer: int) -> bool:
        return (self.add_swa_attention_sink_bias if self.is_window(layer)
                else self.add_full_attention_sink_bias)

    @property
    def ring_blocks(self) -> int:
        """Blocks of a slot's ring: the window and a block more, so that the
        blocks one decode step attends are distinct entries of the ring."""
        return -(-self.sliding_window // self.window_block_tokens) + 1

    @property
    def ring_rows(self) -> int:
        return self.ring_blocks * self.window_block_tokens

    @property
    def ring_bytes_per_slot(self) -> int:
        """K and V rings of every window layer, one slot."""
        return (self.window_layers * self.ring_rows
                * self.swa_num_key_value_heads
                * (self.head_dim + self.v_head_dim)
                * jnp.dtype(self.dtype).itemsize)

    def kind_index(self, layer: int) -> int:
        """Layer ``layer``'s index among the layers of its own kind: which
        layer of the pool, or of the rings, is its."""
        kinds = self.hybrid_layer_pattern
        return kinds[:layer].count(kinds[layer])

    def replace(self, **kw) -> "MimoV2Config":
        return replace(self, **kw)

    def paged_family(self) -> PagedFamily:
        return PAGED_FAMILY


def flash_share(*, num_hidden_layers: int = 7,
                hybrid_layer_pattern: Tuple[int, ...] = (0, 1, 1, 1, 1, 0, 1),
                moe_layer_freq: Tuple[int, ...] = (0, 1, 1, 1, 1, 1, 1),
                held: Tuple[int, int] = (0, 8), vocab_size: int = 19072,
                max_seq_len: int = 4096, **kw) -> MimoV2Config:
    """MiMo-V2-Flash at its published widths, cut to one chip of a
    deployment that shares each layer 32 ways: the first seven published
    layers (the leading full, dense one and a whole period of five window
    layers to one full), 8 of 256 experts held, an eighth of the vocabulary
    (``benchmark/configs/mimo-v2-flash.json``)."""
    return MimoV2Config(
        num_hidden_layers=num_hidden_layers,
        hybrid_layer_pattern=tuple(hybrid_layer_pattern),
        moe_layer_freq=tuple(moe_layer_freq), held=held,
        vocab_size=vocab_size, max_seq_len=max_seq_len, **kw)


def tiny(**kw) -> MimoV2Config:
    """Test-sized: the cut's seven layers (full and dense, four window, full,
    window), width 64, 16 query heads of 96 (the first 32 rotated) over 8 KV
    heads in a window layer and 4 in a full one (rows of whole 128-lane
    tiles), V heads of 64, a window of 16 in ring blocks of 8, 32 routed
    experts of which 4 held, top-4, float32."""
    defaults = dict(
        vocab_size=256, hidden_size=64, intermediate_size=160,
        moe_intermediate_size=32, num_hidden_layers=7,
        hybrid_layer_pattern=(0, 1, 1, 1, 1, 0, 1),
        moe_layer_freq=(0, 1, 1, 1, 1, 1, 1), num_attention_heads=16,
        num_key_value_heads=4, swa_num_key_value_heads=8, head_dim=96,
        v_head_dim=64, sliding_window=16, window_block_tokens=8,
        n_routed_experts=32, num_experts_per_tok=4, held=(0, 4),
        rope_theta=5000.0, swa_rope_theta=100.0, max_seq_len=64,
        dtype=jnp.float32, param_dtype=jnp.float32)
    defaults.update(kw)
    return MimoV2Config(**defaults)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_params(config: MimoV2Config, key: jax.Array) -> Dict:
    """Seeded weights, made in ``param_dtype``: every matrix normal with
    standard deviation ``1/sqrt(fan_in)``, ``w_q`` and ``w_k`` ``sqrt(2)``
    times that (no norm follows them: scores then have a standard deviation
    of 2; over a context of thousands of keys a softmax of unit scores is
    nearly a mean, ``falcon_h1.init_params``); the embedding's rows of unit
    variance. Norm gains are LOG-NORMAL, ``exp(n - 1)`` (unit mean square, a
    few channels several times the rest, as a trained model's are): a
    bfloat16 program is indifferent to a channel's scale and an int8 one,
    with one scale a tensor, is not, which is what lets the benchmark's
    check tell the two apart (near-constant gains read 0.32 for this program
    beside 0.36 for the int8 control: ``benchmark/traffic/swa-decode.json``,
    ``check.why``). A routed expert's ``w_down`` counts ``4 route_scale ** 2``
    into its fan-in (``kimi_k2.init_params``'s lesson taken one step further:
    a token's 8 picks weigh one in sum and an expert's output half a unit,
    so a pick that changes hands between this program and a float32
    reference on bfloat16 rounding of the router's input moves a logit by
    hundredths). ``sink`` (a window layer's, float32 [heads]) is seeded ``6 +
    n``: beside a window of 128 keys whose ``exp(s)`` average ``e^2`` the sink
    then takes a tenth to two thirds of a head's probability, so that
    leaving it out is seen. ``router_bias`` (the selection bias) is a seeded
    NON-zero float32 buffer of standard deviation 0.02, as Trinity's."""
    c = config
    dt = c.param_dtype
    D, H, dk, dv = (c.hidden_size, c.num_attention_heads, c.head_dim,
                    c.v_head_dim)
    n_held = c.held[1]
    counter = iter(range(1 << 30))
    sub = lambda: jax.random.fold_in(key, next(counter))  # noqa: E731

    def nrm(shape, fan_in):
        return (jax.random.normal(sub(), shape, jnp.float32)
                * fan_in ** -0.5).astype(dt)

    def gain(n):
        # log-normal with unit mean square: exp(sigma n - sigma^2), sigma 1
        return jnp.exp(jax.random.normal(sub(), (n,), jnp.float32) - 1.0
                       ).astype(dt)

    def layer(l):
        KV, F = c.kv_heads(l), c.moe_intermediate_size
        lp = {"norm_attn": gain(D), "norm_ffn": gain(D),
              # heads folded into the columns, as the pool's rows are
              "w_q": nrm((D, H * dk), D / 2), "w_k": nrm((D, KV * dk), D / 2),
              "w_v": nrm((D, KV * dv), D), "w_o": nrm((H * dv, D), H * dv)}
        if c.has_sink(l):
            lp["sink"] = 6.0 + jax.random.normal(sub(), (H,), jnp.float32)
        if not c.moe_layer_freq[l]:
            W = c.intermediate_size
            lp["ffn"] = {"w_gate": nrm((D, W), D), "w_up": nrm((D, W), D),
                         "w_down": nrm((W, D), W)}
            return lp
        lp.update(
            router=nrm((D, c.n_routed_experts), D),
            router_bias=jax.random.normal(
                sub(), (c.n_routed_experts,), jnp.float32) * 0.02,
            experts={"w_gate_up": nrm((n_held, D, 2 * F), D),
                     "w_down": nrm((n_held, F, D), 4 * F * c.route_scale ** 2)})
        return lp

    return {
        "tok_embed": nrm((c.vocab_size, D), 1),
        "layers": [layer(l) for l in range(c.num_hidden_layers)],
        "norm_f": gain(D),
        "lm_head": nrm((D, c.vocab_size), D),
    }


# ---------------------------------------------------------------------------
# The two kinds of memory: the full layers' pool, the window layers' rings
# ---------------------------------------------------------------------------

def init_pool(config: MimoV2Config, num_blocks: int,
              block_tokens: int) -> Tuple[jax.Array, jax.Array]:
    """``(K pool, V pool)`` of the FULL layers: ``[full layers, num_blocks,
    block_tokens, KV heads * 192]`` and ``[.., KV heads * 128]``; block 0 is
    the trash block (``generate.init_block_pool``)."""
    c = config
    shape = (c.n_layers, num_blocks, block_tokens)
    KV = c.num_key_value_heads
    return (jnp.zeros(shape + (KV * c.head_dim,), c.dtype),
            jnp.zeros(shape + (KV * c.v_head_dim,), c.dtype))


def init_slot_state(config: MimoV2Config,
                    slots: int) -> Tuple[jax.Array, jax.Array]:
    """``(K rings, V rings)`` as ``ops/window_ring.py`` lays them:
    ``[window layers, slots, ring blocks, window_block_tokens, swa KV heads
    * 192]`` and ``[.., swa KV heads * 128]``."""
    c = config
    shape = (c.window_layers, slots, c.ring_blocks, c.window_block_tokens)
    KV = c.swa_num_key_value_heads
    return (jnp.zeros(shape + (KV * c.head_dim,), c.dtype),
            jnp.zeros(shape + (KV * c.v_head_dim,), c.dtype))


def _attend(q, operands, kernel: str, ctx, **kw):
    if kernel in ("pallas", "interpret"):
        # a prefill's walk follows its real rows alone
        return paged_attention(q, *operands, interpret=kernel == "interpret",
                               queries=ctx.get("suffix_len"), **kw)
    # the gather path: a CPU's sizes
    return paged_attention_reference(q, *operands, **kw)


def _window_attend(q, k, v, sink, rings, wl: int, ctx, c: MimoV2Config,
                   kernel: str):
    """A window layer's attention and its ring's update; ``k`` / ``v`` the
    new rows ``[S, T, KV*192]`` / ``[S, T, KV*128]``. Decode: the new row
    goes into the ring first, then the walk reads the ring's live rows
    through a table that is each slot's own blocks. Prefill: the walk attends
    the prompt's FRESH rows, viewed as blocks behind an identity table (a
    tile's walk starts at its window's first block, so a prompt of any
    length does a window's work a tile), and the prompt's last ``ring`` rows
    go into the ring."""
    S, T = q.shape[:2]
    rows = lambda a: a.reshape(S * T, -1)  # noqa: E731
    pos = ctx["positions"].reshape(-1)
    if ctx["prefill"]:
        # Of two positions a ring apart the later one's row stays.
        keep = ctx["valid"].reshape(-1) & (pos >= ctx["suffix_len"] - c.ring_rows)
        rings = window_ring.write(rings, wl, ctx["slot"], pos, keep, rows(k),
                                  rows(v))
        pb = math.gcd(T, 128)
        view = lambda a: a.reshape(1, T // pb, pb, -1)  # noqa: E731
        operands = (view(k), view(v), jnp.arange(T // pb)[None],
                    jnp.zeros((1,), jnp.int32), 0)
    else:
        rings = window_ring.write(rings, wl, jnp.arange(S), pos, ctx["active"],
                                  rows(k), rows(v))
        operands = (*map(window_ring.as_blocks, rings),
                    window_ring.slot_tables(rings[0]), ctx["lengths"], wl)
    with jax.named_scope("attn_window"):
        o = _attend(q, operands, kernel, ctx, scale=c.head_dim ** -0.5,
                    window=c.sliding_window, sinks=sink)
    return o, rings


def _rotate(x, positions, layer: int, c: MimoV2Config):
    """``x`` [S, T, heads, head_dim]: its first ``rotary_dim`` dimensions
    rotated at ``positions`` with the base of ``layer``'s kind (half-split
    pairs inside those dimensions), the rest as they are."""
    n = c.rotary_dim
    turned = rope(x[..., :n], positions,
                  freqs=rope_frequencies(c.rope_base(layer), n))
    return jnp.concatenate([turned, x[..., n:]], axis=-1)


def _project_kv(lw, a, layer: int, c: MimoV2Config):
    """``a`` [S, T, D] -> (k [S, T, KV, 192] before its rotation, v [S, T,
    KV, 128] scaled by ``attention_value_scale``), KV the layer's kind's."""
    S, T, _ = a.shape
    KV = c.kv_heads(layer)
    k = _mm("std,de->ste", a, lw["w_k"], c.dtype)
    v = jnp.einsum("std,de->ste", a, lw["w_v"],
                   preferred_element_type=jnp.float32)
    v = (v * c.attention_value_scale).astype(c.dtype)
    return k.reshape(S, T, KV, c.head_dim), v.reshape(S, T, KV, c.v_head_dim)


def _attention(lw, a, pool, rings, layer: int, ctx, c: MimoV2Config,
               kernel: str):
    """One layer's attention sublayer: ``a`` [S, T, D] the normed input.
    Returns (``concat_h(o) Wo``, pool, rings)."""
    dt = c.dtype
    S, T, _ = a.shape
    q = _mm("std,de->ste", a, lw["w_q"], dt).reshape(
        S, T, c.num_attention_heads, c.head_dim)
    k, v = _project_kv(lw, a, layer, c)
    q = _rotate(q, ctx["positions"], layer, c)
    k = _rotate(k, ctx["positions"], layer, c).reshape(S, T, -1)
    v = v.reshape(S, T, -1)
    idx = c.kind_index(layer)
    sink = lw.get("sink")
    if c.is_window(layer):
        o, rings = _window_attend(q, k, v, sink, rings, idx, ctx, c, kernel)
    else:
        k_pool, v_pool = pool
        with jax.named_scope("kv_pool_write"):
            k_pool = k_pool.at[idx, ctx["blk"], ctx["off"]].set(k)
            v_pool = v_pool.at[idx, ctx["blk"], ctx["off"]].set(v)
        with jax.named_scope("attn_full"):
            o = _attend(q, (k_pool, v_pool, ctx["tables"], ctx["lengths"], idx),
                        kernel, ctx, scale=c.head_dim ** -0.5, sinks=sink)
        pool = (k_pool, v_pool)
    return _mm("ste,ed->std", o.reshape(S, T, -1), lw["w_o"], dt), pool, rings


def expert_layer(lp, x, valid, c: MimoV2Config):
    """``moe.expert_layer`` under this family's names; no shared expert."""
    return moe.expert_layer(
        lp, x, valid, topk=c.num_experts_per_tok, scale=c.route_scale,
        score=c.scoring_func, renormalise=c.norm_topk_prob, held=c.held,
        n_routed=c.n_routed_experts)


def _forward(params, tokens, pool, rings, ctx, c: MimoV2Config, kernel: str,
             last_row=None):
    """tokens [S, T]; ``ctx`` holds the positions, the pool's cells and
    tables, and what the mode's window layers need. Returns (logits float32,
    pool, rings, the expert layers' pick counts summed over layers)."""
    dt, eps = c.dtype, c.layernorm_epsilon
    x = jnp.take(params["tok_embed"], tokens, axis=0).astype(dt)
    counts = jnp.zeros((moe.PICK_COUNTS,), jnp.int32)
    for l, lp in enumerate(params["layers"]):
        o, pool, rings = _attention(
            lp, rms_norm(x, lp["norm_attn"], eps), pool, rings, l, ctx, c,
            kernel)
        x = x + o
        f = rms_norm(x, lp["norm_ffn"], eps)
        if "ffn" in lp:                  # moe_layer_freq[l] == 0
            with jax.named_scope("dense_ffn"):
                x = x + gated_ffn(lp["ffn"], f, dt)
        else:
            out, cnt = expert_layer(lp, f, ctx["valid"], c)
            x = x + out
            counts = counts + cnt
    if last_row is not None:
        x = jax.lax.dynamic_slice_in_dim(x, last_row, 1, axis=1)
    x = rms_norm(x, params["norm_f"], eps)
    logits = jnp.einsum("std,dv->stv", x, params["lm_head"],
                        preferred_element_type=jnp.float32)
    return logits, pool, rings, counts


def forward_prefill_paged(params, tokens, pool, state, table, start_pos,
                          suffix_len, slot, config: MimoV2Config,
                          block_tokens: int, kernel: str = "gather"):
    """The family's ``prefill``: ``tokens`` [1, P] (a bucket) from the
    sequence's start (``start_pos`` is 0: no prefix hit is ever served to
    this family), the first ``suffix_len`` real. Writes the full layers' rows
    through ``table`` (pad rows to trash block 0) and the last ``ring`` rows
    of every window layer into slot ``slot``'s rings; pad tokens route to no
    expert. The head sees ONE row, the last real position: logits
    ``[1, 1, V]``."""
    positions, valid, blk, off = prefill_cells(
        table, start_pos, suffix_len, tokens.shape[1], block_tokens)
    ctx = {"prefill": True, "slot": jnp.asarray(slot, jnp.int32),
           "suffix_len": jnp.asarray(suffix_len, jnp.int32),
           "positions": positions[None], "valid": valid[None],
           "blk": blk[None], "off": off[None], "tables": table[None],
           "lengths": jnp.reshape(start_pos, (1,)).astype(jnp.int32)}
    logits, pool, rings, counts = _forward(
        params, tokens, tuple(pool), tuple(state), ctx, config, kernel,
        last_row=suffix_len - 1)
    return logits, pool, rings, expert_aux(counts, 0)


def forward_decode_paged(params, tokens, pool, state, tables, lengths,
                         config: MimoV2Config, block_tokens: int,
                         kernel: str = "gather",
                         active: Optional[jax.Array] = None):
    """The family's ``decode``: ``tokens`` [S, 1], slot s's token at position
    ``lengths[s]``. Active slots' rings take the new row; a parked slot's
    stay bit for bit, its full layers' write lands in trash block 0 and it
    routes to no expert."""
    c = config
    S, T = tokens.shape
    if T != 1:
        raise ValueError("a ring takes one row a step: got "
                         f"{T} (speculative verify is not supported)")
    positions, blk, off = decode_cells(tables, lengths, T, block_tokens)
    if active is None:
        active = jnp.ones((S,), bool)
    ctx = {"prefill": False, "active": active, "positions": positions,
           "valid": active[:, None], "blk": blk, "off": off,
           "tables": tables, "lengths": lengths}
    logits, pool, rings, counts = _forward(
        params, tokens, tuple(pool), tuple(state), ctx, c, kernel)
    capped = jnp.sum(active & (lengths >= c.sliding_window))
    return logits, pool, rings, expert_aux(counts, capped)


def describe(config: MimoV2Config) -> Dict[str, int]:
    """What the stack is made of, for ``engine.describe()``."""
    c = config
    return {"window_layers": c.window_layers, "full_layers": c.n_layers,
            "window_tokens": c.sliding_window,
            "kv_heads_window": c.swa_num_key_value_heads,
            "kv_heads_full": c.num_key_value_heads,
            "ring_rows": c.ring_rows,
            "window_ring_bytes_per_slot": c.ring_bytes_per_slot,
            "expert_layers": c.expert_layers,
            "dense_layers": c.num_hidden_layers - c.expert_layers}


PAGED_FAMILY = PagedFamily(
    # The pool is the FULL layers' alone: the block manager, the reservation
    # at admission and serve_kv_pool_blocks count rows that live as long as
    # the request. The window layers' rows are a state a slot.
    init_pool=init_pool,
    prefill=forward_prefill_paged,
    decode=forward_decode_paged,
    logits_dim=lambda params, config: params["lm_head"].shape[-1],
    init_slot_state=init_slot_state,
    unsupported=("prefix_cache",),
    # The expert layers' counts, then the active slot-steps whose context
    # was past the window (Trinity's names: the same readers read both).
    aux_counts=EXPERT_AUX_COUNTS + (
        AuxCount("window_capped_slot_steps_total"),),
    describe=describe,
)
