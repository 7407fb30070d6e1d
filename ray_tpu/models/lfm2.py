"""LFM2's expert model (``model_type: lfm2_moe``) on the paged serve path.

The tenth model family of the zoo and the first whose usual mixer keeps NO
recurrent state: entry ``l`` of the published ``layer_types`` names layer
``l``'s mixer a gated short convolution (``conv``) or grouped-query attention
(``full_attention``), three of the first to one of the second. What a slot
carries between tokens for a convolution layer is the last ``conv_L_cache -
1`` = two rows of its input (``PagedFamily.init_slot_state``: the tail
alone, no scan, no state kernel); an attention layer keeps a K and a V row a
TOKEN in the paged pool (``PagedFamily.init_pool``, the attention layers
alone). Field names are the keys of the source's ``config.json``
(huggingface.co/LiquidAI/LFM2-8B-A1B). No bias anywhere, RMSNorm at
``norm_eps`` with a learned weight, the residual in the model's dtype::

    h       = x_l + Mixer_l(RMSNorm_op(x_l))
    x_(l+1) = h + FFN_l(RMSNorm_ffn(h))
    logits  = RMSNorm_f(x_L) E^T             E the embedding: the table is tied

*conv* (``a`` the normed input, all products elementwise but the two
projections): ``[B | C | u] = a W_in`` (three chunks of ``hidden_size``);
``p_t = B_t * u_t``; ``c_t = sum_j w[j] p_(t - (K-1) + j)`` a channel
(``ops/causal_conv.py``: depthwise, causal, zeros before the sequence's
start, ``K = conv_L_cache``, no bias, NO activation); ``Mixer(a)_t = (C_t *
c_t) W_out``. A slot carries ``p_(t-1), p_(t-2)``.

*full_attention*: ``num_attention_heads`` query heads over
``num_key_value_heads`` KV heads of ``hidden_size / num_attention_heads``
(query head ``i`` reads KV head ``i // (heads / kv heads)``); every query
and key head RMS-normed over its own numbers with ONE learned weight the
kind (``q_norm``, ``k_norm``) BEFORE the rotation; rotary over the whole
head at ``rope_theta``, half-split pairs (``ops/layers.py:rope``), absolute
positions, no scaling; scores x ``head_dim^-1/2``, causal softmax, ``W_o``.

*FFN*: the first ``num_dense_layers`` layers ``W_2 (silu(W_1 f) * W_3 f)``
at ``intermediate_size``; the others the expert layer: ``s = sigmoid(W_r
f)`` in float32 over ``num_experts`` outputs, the ``num_experts_per_tok``
largest of ``s + expert_bias`` picked (the bias selects, it never weighs), a
pick's weight ``routed_scaling_factor x s_i / sum of the picked s``
(``norm_topk_prob``), experts of the same gated form at
``moe_intermediate_size``, no shared expert (``ops/moe.py:expert_layer``).
``held =
(first, count)`` says which experts' weights live here; the published model
on one chip of a pipeline holds them all, ``(0, num_experts)``.

*Assumed* (also under ``assumed`` in ``benchmark/configs/lfm2-8b-a1b.json``):
the table tied; the chunk order ``B, C, u``; the final norm (the source's
``embedding_norm``) on the stack's OUTPUT; the router and its bias float32;
the head's width ``hidden_size / num_attention_heads``; the initialisation
(:func:`init_params`).

Weights are one array a matrix, no stacking over layers; a program calls ONE
jitted function a KIND of layer (mixer x feed-forward), once a layer of that
kind (``nemotron_h._layer_fn``'s reason). The prefix cache is not supported
(``PagedFamily.unsupported``): a K/V hit at position p is usable only with
every convolution layer's tail at p, which nothing keeps yet (4 KB a layer
a snapshot at the published widths: ``ROADMAP.md`` R4(a)). So ``start_pos`` is
always 0 and a prefill writes its slot's tail from zero.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.generate import (EXPERT_AUX_COUNTS, PagedFamily,
                                     _paged_attend, decode_cells, expert_aux,
                                     init_block_pool, prefill_cells)
from ray_tpu.ops import causal_conv, moe
from ray_tpu.ops.layers import gated_ffn, mm as _mm, rms_norm, rope

CONV, ATTENTION = "conv", "full_attention"
DENSE, EXPERTS = "dense", "experts"
# The published 24 layers: two convolution layers (the dense ones), then
# ``full_attention, conv, conv, conv`` four times, then two periods a layer
# shorter.
LAYER_TYPES = ((CONV, CONV) + (ATTENTION, CONV, CONV, CONV) * 4
               + (ATTENTION, CONV, CONV) * 2)


@dataclass(frozen=True)
class Lfm2Config:
    """Field names are the published ``config.json`` keys (LFM2-8B-A1B's
    values); ``held``, ``max_seq_len`` and the two dtypes are this
    program's."""
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_hidden_layers: int = 24
    layer_types: Tuple[str, ...] = LAYER_TYPES
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3
    conv_bias: bool = False
    num_experts: int = 32
    num_experts_per_tok: int = 4
    use_expert_bias: bool = True
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 128000
    # Experts whose weights live on this chip: (first, count).
    held: Tuple[int, int] = (0, 32)
    dtype: Any = jnp.bfloat16          # activation/compute dtype
    param_dtype: Any = jnp.bfloat16    # storage dtype

    def __post_init__(self):
        object.__setattr__(self, "held", tuple(self.held))
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        kinds = self.layer_types
        if (len(kinds) != self.num_hidden_layers
                or set(kinds) - {CONV, ATTENTION}):
            raise ValueError(
                f"layer_types names {len(kinds)} layers of kinds "
                f"{sorted(set(kinds))}: want {self.num_hidden_layers} of "
                f"{CONV!r} / {ATTENTION!r}")
        if ATTENTION not in kinds:
            raise ValueError("a stack with no attention layer has no paged pool")
        if CONV not in kinds:
            raise ValueError("a stack with no convolution layer has no slot state")
        if (self.hidden_size % self.num_attention_heads
                or self.num_attention_heads % self.num_key_value_heads):
            raise ValueError("the width divides into query heads, and query "
                             "heads into KV heads, in whole runs")
        if (self.conv_bias or not self.use_expert_bias
                or not self.norm_topk_prob):
            raise ValueError(
                "the family's convolution has no bias, its router a selection "
                "bias and weights normalised over the picks: no other form here")
        if not 0 <= self.num_dense_layers <= self.num_hidden_layers:
            raise ValueError(f"{self.num_dense_layers} dense layers of "
                             f"{self.num_hidden_layers}")
        first, count = self.held
        if not 0 <= first <= first + count <= self.num_experts:
            raise ValueError(f"held {self.held} is no run of "
                             f"{self.num_experts} experts")

    # What the generator and the pool read: the pool is the ATTENTION
    # layers'.
    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def n_heads(self) -> int:
        return self.num_attention_heads

    @property
    def n_kv_heads(self) -> int:
        return self.num_key_value_heads

    @property
    def n_layers(self) -> int:
        """Layers whose K/V rows lie in the paged pool: the attention ones."""
        return self.attention_layers

    @property
    def conv_layers(self) -> int:
        return self.layer_types.count(CONV)

    @property
    def attention_layers(self) -> int:
        return self.layer_types.count(ATTENTION)

    @property
    def expert_layers(self) -> int:
        return self.num_hidden_layers - self.num_dense_layers

    @property
    def n_routed_experts(self) -> int:
        """The router's outputs, under the other expert families' name."""
        return self.num_experts

    @property
    def state_bytes_per_slot(self) -> int:
        """The tail of every convolution layer, one slot."""
        return (self.conv_layers * (self.conv_L_cache - 1) * self.hidden_size
                * jnp.dtype(self.dtype).itemsize)

    def kind_index(self, layer: int) -> int:
        """Layer ``layer``'s index among the layers of its own mixer: which
        layer of the pool, or of the tail, is its."""
        kinds = self.layer_types
        return kinds[:layer].count(kinds[layer])

    def ffn_kind(self, layer: int) -> str:
        return DENSE if layer < self.num_dense_layers else EXPERTS

    def replace(self, **kw) -> "Lfm2Config":
        return replace(self, **kw)

    def paged_family(self) -> PagedFamily:
        return PAGED_FAMILY


def lfm2_8b_a1b_stage(*, num_hidden_layers: int = 10,
                      max_seq_len: int = 2176, **kw) -> Lfm2Config:
    """LFM2-8B-A1B at its published widths, cut in DEPTH alone to the first
    of two pipeline stages: the first ``num_hidden_layers`` entries of
    ``layer_types`` (10: the two dense convolution layers and two whole
    periods ``full_attention, conv, conv, conv``; 14, three periods, ran
    cold in 346 s of the benchmark's 360), all 32 experts of every expert
    layer, every head and the whole vocabulary
    (``benchmark/configs/lfm2-8b-a1b.json``)."""
    kw.setdefault("layer_types", LAYER_TYPES[:num_hidden_layers])
    return Lfm2Config(num_hidden_layers=num_hidden_layers,
                      max_seq_len=max_seq_len, **kw)


def tiny(**kw) -> Lfm2Config:
    """Test-sized: six layers ``conv, conv, full_attention, conv, conv,
    full_attention`` (the first dense, five expert layers; the later layers
    of a mixer index the tail and the pool past the first's), width 64, 4
    query heads over 2 KV heads of 16, a dense width of 96, 8 experts of 32,
    all held, top-2, float32."""
    defaults = dict(
        vocab_size=256, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=6,
        layer_types=(CONV, CONV, ATTENTION, CONV, CONV, ATTENTION),
        num_dense_layers=1, num_attention_heads=4, num_key_value_heads=2,
        num_experts=8, num_experts_per_tok=2, held=(0, 8), rope_theta=100.0,
        max_seq_len=64, dtype=jnp.float32, param_dtype=jnp.float32)
    defaults.update(kw)
    return Lfm2Config(**defaults)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

# For unit normals g, u: the mean square of silu(g) * u (what W_down's rows
# see).
_SILU_GATE_MEAN_SQUARE = 0.355
# What a ROUTED expert's W_2 counts into its fan-in beside that: a pick that
# changes hands then moves the stream by half of what it would (below).
_ROUTED_DOWN_FAN_IN = 4.0
# What a query's and a key's numbers are multiplied by BEFORE their norms
# (the norms take it out again) and what the norms' gains are seeded around.
_QK_PRE_NORM_SCALE = 4.0
_QK_GAIN = 2.0 ** 0.5


def init_params(config: Lfm2Config, key: jax.Array) -> Dict:
    """Seeded weights, made in ``param_dtype``: ``"layers"`` is a list of one
    dict a layer holding its two norms, its mixer's matrices under
    ``"mixer"`` and its feed-forward's (``"ffn"``, or ``router``,
    ``router_bias`` and ``experts``, ``kimi_k2``'s names).

    Every product has unit variance at its input to the next nonlinearity:
    the embedding's rows have unit mean square; ``B``, ``C`` and ``u`` are
    unit normal, so ``B * u`` has unit variance, and the taps are normal(0,
    1/K) so the convolution keeps it; a feed-forward's ``W_1 f`` and ``W_3
    f`` and the router's logits are unit normal (a router of unit logits:
    sigmoid scores spread over (0.1, 0.9)); ``silu(g) * u`` has mean square
    0.355, which goes into a ``W_2``'s fan-in, so that a mixer or a dense
    feed-forward adds about 1 to the stream's mean square. A ROUTED expert's
    ``W_2`` counts ``routed_scaling_factor ** 2`` (here 1) and
    ``_ROUTED_DOWN_FAN_IN`` = 4 more into its fan-in: ``kimi_k2.init_params``'s
    lesson taken one step further. A token's four picks weigh one in sum
    already, but this stack's feed-forward is routed experts and nothing
    else (no shared expert) in every layer but two: where a token's fourth
    and fifth scores of 32 lie within bfloat16's rounding of the router's
    input, the program and a float32 reference pick different experts, the
    stream moves by a pick's worth, the NEXT layers' routers see that and
    flip in turn; with experts of unit scale the cell's first runs read a
    worst gap of 2.0-2.3 where a run that is wrong throughout reads ~4 (with
    the routed ``W_2`` zeroed: 0.10; PR 55, on the chip). With an expert at
    half scale an expert layer adds 1/16 to the stream's mean square and a
    pick that changes hands moves it by 0.18, Kimi's figure. Queries and keys
    leave their projections FOUR times unit scale and their norms' gains are seeded
    around sqrt(2) (``sqrt(2) + 0.1 n``): scores then have a standard
    deviation of 2 (over hundreds of keys a softmax of unit scores is nearly
    a mean), and a norm left out moves them by a factor that a check sees.
    The other norm gains are ``1 + 0.1 n``. The router and ``expert_bias``
    are float32; the bias is a seeded NON-zero buffer of standard deviation
    0.02, a tenth of the spread of a sigmoid score, so that it does select.
    The table is tied, so one scale serves both its uses: its rows have
    mean square ``1 / hidden_size``, which gives logits a standard deviation
    near 1 (the final norm's output has unit mean square); as an embedding
    a row is that much smaller than what the first sublayer adds, and the
    first norm brings it to unit scale."""
    c = config
    dt_ = c.param_dtype
    D, hd = c.hidden_size, c.head_dim
    Hq, Hkv = c.num_attention_heads, c.num_key_value_heads
    counter = iter(range(1 << 30))
    sub = lambda: jax.random.fold_in(key, next(counter))  # noqa: E731

    def nrm(shape, fan_in, dtype=dt_):
        return (jax.random.normal(sub(), shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    def gain(n, around=1.0):
        return (around + 0.1 * jax.random.normal(sub(), (n,), jnp.float32)
                ).astype(dt_)

    def conv():
        return {"w_in": nrm((D, 3 * D), D),
                "conv": nrm((c.conv_L_cache, D), c.conv_L_cache),
                "w_out": nrm((D, D), D)}

    def attention():
        # q a head first, [heads, D, head_dim]: the form the decode program's
        # product reads where it lies (stored [D, heads * head_dim] it was
        # copied into another layout on every call: compile-only for a v5e);
        # K's heads, then V's, in one matrix: one product a token step
        return {"w_q": nrm((Hq, D, hd), D / _QK_PRE_NORM_SCALE ** 2),
                "w_kv": jnp.concatenate(
                    [nrm((D, Hkv * hd), D / _QK_PRE_NORM_SCALE ** 2),
                     nrm((D, Hkv * hd), D)], axis=1),
                "q_norm": gain(hd, _QK_GAIN), "k_norm": gain(hd, _QK_GAIN),
                "w_o": nrm((Hq * hd, D), Hq * hd)}

    def dense():
        F = c.intermediate_size
        return {"ffn": {"w_gate": nrm((D, F), D), "w_up": nrm((D, F), D),
                        "w_down": nrm((F, D), _SILU_GATE_MEAN_SQUARE * F)}}

    def experts():
        F, n_held = c.moe_intermediate_size, c.held[1]
        return {"router": nrm((D, c.num_experts), D, jnp.float32),
                "router_bias": jax.random.normal(
                    sub(), (c.num_experts,), jnp.float32) * 0.02,
                "experts": {
                    "w_gate_up": nrm((n_held, D, 2 * F), D),
                    "w_down": nrm((n_held, F, D), _SILU_GATE_MEAN_SQUARE * F
                                  * c.routed_scaling_factor ** 2
                                  * _ROUTED_DOWN_FAN_IN)}}

    mixer = {CONV: conv, ATTENTION: attention}
    ffn = {DENSE: dense, EXPERTS: experts}
    return {
        "tok_embed": nrm((c.vocab_size, D), D),
        "layers": [dict(ffn[c.ffn_kind(l)](), mixer=mixer[kind](),
                        norm_op=gain(D), norm_ffn=gain(D))
                   for l, kind in enumerate(c.layer_types)],
        "norm_f": gain(D),
    }


# ---------------------------------------------------------------------------
# The convolution layers' memory: a two-row tail a slot
# ---------------------------------------------------------------------------

def init_slot_state(config: Lfm2Config, slots: int) -> Tuple[jax.Array]:
    """``(tail [conv layers, conv_L_cache - 1, slots, hidden_size] dtype,)``:
    all a slot carries between tokens, the last two rows ``B * u`` of every
    CONVOLUTION layer, as ``ops/causal_conv.py`` lays them."""
    c = config
    return (jnp.zeros((c.conv_layers, c.conv_L_cache - 1, slots,
                       c.hidden_size), c.dtype),)


def _conv_in(mw, a, c: Lfm2Config):
    """a [..., D] -> (``B * u`` [..., D] dtype: the convolution's input and
    what the tail keeps; the gate ``C`` [..., D] float32)."""
    D = c.hidden_size
    p = jnp.einsum("...d,dc->...c", a, mw["w_in"],
                   preferred_element_type=jnp.float32)
    return (p[..., :D] * p[..., 2 * D:]).astype(c.dtype), p[..., D:2 * D]


def _conv_out(mw, gate, y, c: Lfm2Config):
    """``(C * c) W_out``: ``gate`` and the convolved ``y`` float32."""
    return _mm("...d,de->...e", (gate * y).astype(c.dtype), mw["w_out"],
               c.dtype)


def _short_conv(mw, a, state, cl, ctx, c: Lfm2Config, prefill: bool):
    """The gated short convolution of convolution layer ``cl``. Prefill:
    ``a`` [1, P, D] from the sequence's start, the first ``suffix_len`` rows
    real; slot ``slot``'s tail is written as it stands after them (the last
    two REAL rows, not the bucket's). Decode: ``a`` [S, 1, D], one token a
    slot; an active slot's tail takes the new row, a parked one's stays bit
    for bit."""
    (tail,) = state
    with jax.named_scope("short_conv"):
        pre, gate = _conv_in(mw, a[0] if prefill else a[:, 0], c)
        if prefill:
            y, tail = causal_conv.prefill(pre, mw["conv"], None, tail, cl,
                                          ctx["slot"], ctx["suffix_len"])
        else:
            y, tail = causal_conv.decode(pre, mw["conv"], None, tail, cl,
                                         ctx["active"])
        out = _conv_out(mw, gate, y, c)
    return (out[None] if prefill else out[:, None]), (tail,)


def _attention(mw, a, pool, al, ctx, c: Lfm2Config, kernel: str):
    """Grouped-query attention over the paged rows of attention layer
    ``al``: ``a`` [S, T, D]; every head normed, then rotated; the T new rows
    go to pool cells (``blk``, ``off``) first."""
    dt = c.dtype
    S, T, _ = a.shape
    KV, hd = c.num_key_value_heads, c.head_dim
    k_pool, v_pool = pool
    q = _mm("std,hdk->sthk", a, mw["w_q"], dt)
    kv = _mm("std,de->ste", a, mw["w_kv"], dt)
    k = kv[..., :KV * hd].reshape(S, T, KV, hd)
    q = rope(rms_norm(q, mw["q_norm"], c.norm_eps), ctx["positions"],
             base=c.rope_theta)
    k = rope(rms_norm(k, mw["k_norm"], c.norm_eps), ctx["positions"],
             base=c.rope_theta)
    with jax.named_scope("kv_pool_write"):
        k_pool = k_pool.at[al, ctx["blk"], ctx["off"]].set(
            k.reshape(S, T, -1))
        v_pool = v_pool.at[al, ctx["blk"], ctx["off"]].set(kv[..., KV * hd:])
    with jax.named_scope("attn_full"):
        o = _paged_attend(q, k_pool, v_pool, ctx["tables"], ctx["lengths"],
                          al, scale=hd ** -0.5, kernel=kernel,
                          queries=ctx.get("suffix_len"))
    return _mm("ste,ed->std", o.reshape(S, T, -1), mw["w_o"], dt), (k_pool, v_pool)


def expert_layer(lp, x, valid, c: Lfm2Config):
    """``moe.expert_layer`` under this family's names: sigmoid scores
    renormalised over the picks, no shared expert."""
    return moe.expert_layer(
        lp, x, valid, topk=c.num_experts_per_tok,
        scale=c.routed_scaling_factor, score="sigmoid", renormalise=True,
        held=c.held, n_routed=c.num_experts)


@functools.lru_cache(maxsize=None)
def _layer_fn(c: Lfm2Config, kind: str, ffn: str, prefill: bool, kernel: str):
    """One KIND of layer (its mixer x its feed-forward) as a jit of its own,
    built once a (config, kind, mode, kernel): a program that calls it once a
    layer of that kind traces and lowers it once whatever the depth, and XLA
    inlines the calls. ``mem`` is the memory the mixer keeps (the tail or
    the pool), ``i`` the layer's index among its mixer's as a VALUE (the same
    avals every call), ``ctx`` the arrays the mode needs. Returns (x, mem,
    pick counts or None)."""
    eps = c.norm_eps

    @jax.jit
    def layer(x, mem, i, lw, ctx):
        a = rms_norm(x, lw["norm_op"], eps)
        if kind == CONV:
            o, mem = _short_conv(lw["mixer"], a, mem, i, ctx, c, prefill)
        else:
            o, mem = _attention(lw["mixer"], a, mem, i, ctx, c, kernel)
        h = (x + o).astype(c.dtype)
        f = rms_norm(h, lw["norm_ffn"], eps)
        counts = None
        if ffn == DENSE:
            with jax.named_scope("dense_ffn"):
                f = gated_ffn(lw["ffn"], f, c.dtype)
        else:
            f, counts = expert_layer(lw, f, ctx["valid"], c)
        return (h + f).astype(c.dtype), mem, counts

    return layer


def _forward(params, tokens, pool, state, c: Lfm2Config, prefill: bool,
             kernel: str, ctx, last_row=None):
    """Embedding, the layers by ``layer_types`` (one jitted call each), the
    final norm, the tied head. ``last_row``: hand the head that one position
    alone. Returns (logits float32, pool, state, the expert layers' pick
    counts summed)."""
    x = jnp.take(params["tok_embed"], tokens, axis=0).astype(c.dtype)
    mem = {CONV: tuple(state), ATTENTION: tuple(pool)}
    counts = jnp.zeros((moe.PICK_COUNTS,), jnp.int32)
    for l, (kind, lw) in enumerate(zip(c.layer_types, params["layers"])):
        x, mem[kind], cnt = _layer_fn(c, kind, c.ffn_kind(l), prefill, kernel)(
            x, mem[kind], jnp.int32(c.kind_index(l)), lw, ctx)
        if cnt is not None:
            counts = counts + cnt
    if last_row is not None:
        x = lax.dynamic_slice_in_dim(x, last_row, 1, axis=1)
    x = rms_norm(x, params["norm_f"], c.norm_eps)
    logits = jnp.einsum("std,vd->stv", x, params["tok_embed"],
                        preferred_element_type=jnp.float32)
    return logits, mem[ATTENTION], mem[CONV], counts


def forward_prefill_paged(params, tokens, pool, state, table, start_pos,
                          suffix_len, slot, config: Lfm2Config,
                          block_tokens: int, kernel: str = "gather"):
    """The family's ``prefill``: ``tokens`` [1, P] (a bucket) from the
    sequence's start (``start_pos`` is 0: no prefix hit is ever served to
    this family), the first ``suffix_len`` real. Writes the attention
    layers' rows through ``table`` (pad rows to trash block 0) and slot
    ``slot``'s tails from zero; pad tokens route to no expert. The head sees
    ONE row, the last real position: logits ``[1, 1, V]``."""
    positions, valid, blk, off = prefill_cells(
        table, start_pos, suffix_len, tokens.shape[1], block_tokens)
    ctx = {"slot": jnp.asarray(slot, jnp.int32),
           "suffix_len": jnp.asarray(suffix_len, jnp.int32),
           "valid": valid[None], "positions": positions[None],
           "blk": blk[None], "off": off[None], "tables": table[None],
           "lengths": jnp.reshape(start_pos, (1,)).astype(jnp.int32)}
    logits, pool, state, counts = _forward(
        params, tokens, pool, state, config, True, kernel, ctx,
        last_row=suffix_len - 1)
    return logits, pool, state, expert_aux(counts)


def forward_decode_paged(params, tokens, pool, state, tables, lengths,
                         config: Lfm2Config, block_tokens: int,
                         kernel: str = "gather",
                         active: Optional[jax.Array] = None):
    """The family's ``decode``: ``tokens`` [S, 1], slot s's token at position
    ``lengths[s]``. Active slots' tails take the token's row; a parked
    slot's stay bit for bit, its K/V write lands in trash block 0 and it
    routes to no expert."""
    S, T = tokens.shape
    if T != 1:
        raise ValueError("the convolution's tail advances one token a step: "
                         f"got {T} (speculative verify is not supported)")
    positions, blk, off = decode_cells(tables, lengths, T, block_tokens)
    if active is None:
        active = jnp.ones((S,), bool)
    ctx = {"active": active, "valid": active[:, None], "positions": positions,
           "blk": blk, "off": off, "tables": tables, "lengths": lengths}
    logits, pool, state, counts = _forward(
        params, tokens, pool, state, config, False, kernel, ctx)
    return logits, pool, state, expert_aux(counts)


def describe(config: Lfm2Config) -> Dict[str, int]:
    """What the stack is made of, for ``engine.describe()``: layer counts by
    kind, read off ``layer_types`` and ``num_dense_layers``."""
    c = config
    return {"conv_layers": c.conv_layers,
            "attention_layers": c.attention_layers,
            "dense_layers": c.num_dense_layers,
            "expert_layers": c.expert_layers, "held": c.held[1],
            "kv_heads": c.num_key_value_heads,
            "state_bytes_per_slot": c.state_bytes_per_slot}


PAGED_FAMILY = PagedFamily(
    # The pool is the ATTENTION layers' alone (``config.n_layers``) and the
    # slot state the CONVOLUTION layers' tails alone.
    init_pool=init_block_pool,
    prefill=forward_prefill_paged,
    decode=forward_decode_paged,
    logits_dim=lambda params, config: params["tok_embed"].shape[0],
    init_slot_state=init_slot_state,
    # As the other families with a state a slot: a hit at position p would
    # need every convolution layer's tail at p (ROADMAP R4).
    unsupported=("prefix_cache",),
    aux_counts=EXPERT_AUX_COUNTS,
    describe=describe,
)
