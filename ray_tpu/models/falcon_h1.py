"""Falcon-H1's language model on the paged serve path.

The fifth model family of the zoo, and the first in which ONE layer holds
both kinds of per-sequence memory: every layer runs a Mamba-2 mixer (a
float32 state a slot, ``PagedFamily.init_slot_state``) and grouped-query
attention (a K/V row a token in the paged pool) SIDE BY SIDE on the same
normed input and sums them. Field names are the keys of the model's
``config.json`` (huggingface.co/tiiuae/Falcon-H1-34B-Instruct); the layer,
as ``modeling_falcon_h1.py`` states it, with ``x`` the layer's input, RMSNorm
at ``rms_norm_eps``, no biases but the convolution's::

    u  = RMSNorm_in(x)
    x' = x + ssm_out_multiplier * Mixer(u)
           + attention_out_multiplier * Attn(attention_in_multiplier * u)
    y  = x' + FFN(RMSNorm_ff(x'))
    FFN(h) = mlp_multipliers[1] * W_down(W_up h * silu(mlp_multipliers[0] * W_gate h))

*Attn*: ``q, k, v = W_q a, key_multiplier * W_k a, W_v a``;
``num_attention_heads`` query heads over ``num_key_value_heads`` KV heads of
``head_dim`` (query head ``i`` reads KV head ``i // (heads / kv heads)``);
rotary over the whole head at ``rope_theta``, half-split pairing
(``ops/layers.py:rope``), no scaling; scores x ``head_dim^-1/2``; causal
softmax; ``W_o``. The pool's row is the KV heads' (``generate.init_block_pool``
reads ``n_kv_heads``), the kernel ``ops/paged_attention.py:paged_attention``.

*Mixer* (Mamba-2; ``mamba_d_ssm`` = ``mamba_n_heads`` x ``mamba_d_head``
channels, state size ``mamba_d_state``, ``mamba_n_groups`` groups,
convolution ``mamba_d_conv``): ``[z | xBC | dt] = mup * (W_in
(ssm_in_multiplier * u))`` with ``mup`` the per-channel vector that carries
``ssm_multipliers[0..4]`` over the z, x, B, C and dt segments; ``xBC``
through a causal depthwise convolution with bias, then SiLU; ``dt =
softplus(dt + dt_bias)``; ``A = -exp(A_log)`` a head; per head ``h`` of
group ``g``: ``S_t = exp(dt_t A) S_(t-1) + dt_t x_t B_(g,t)^T``, ``y_t = S_t
C_(g,t) + D x_t`` (``ops/ssd.py``); then (``mamba_rms_norm`` true,
``mamba_norm_before_gate`` false) ``RMSNorm_group(y * silu(z))``, the norm
taken over each group's channels with one learned weight of ``mamba_d_ssm``;
``W_out``. What a slot carries between tokens is ``S`` for every head and
the last ``mamba_d_conv - 1`` pre-convolution rows of ``xBC``.

*Around the stack*: ``embedding_multiplier * E[token]`` in; one RMSNorm and
``lm_head_multiplier * W_head h`` out, untied.

*Assumed* (also under ``assumed`` in
``benchmark/configs/falcon-h1-34b.json``): the state float32 (as
``olmo_hybrid``; HF's cache takes the model's dtype, vLLM offers both), the
convolution tail in ``dtype``; ``mamba_use_mlp`` true = every layer has its
feed-forward; ``dt`` not clamped above; weights and activations ``dtype``
(bfloat16) with float32 accumulation; the initialisation
(:func:`init_params`).

Weights are one array a matrix and no stacking over layers; the programs call
ONE jitted layer function once a layer (``_layer_fn``; ``olmo_hybrid``'s
``_period_fn`` and its reason: a ``lax.scan`` over stacked weights made XLA
copy every matrix out of its slab on every step).

The prefix cache is not supported (``PagedFamily.unsupported``): a K/V hit
at position p is usable only with every layer's state at p, which nothing
keeps. So ``start_pos`` is always 0 here and a prefill writes its slot's
state from zero.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass, replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.generate import (PagedFamily, _paged_attend,
                                     decode_cells, init_block_pool,
                                     prefill_cells)
from ray_tpu.ops import causal_conv, ssd
from ray_tpu.ops.layers import mm as _mm, rms_norm, rope, rope_frequencies


@dataclass(frozen=True)
class FalconH1Config:
    """Field names are the published ``config.json`` keys (Falcon-H1-34B's
    values); ``max_seq_len`` and the two dtypes are this program's."""
    vocab_size: int = 261120
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    mamba_d_ssm: int = 4096
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_d_state: int = 256
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    rope_theta: float = 1e11
    rms_norm_eps: float = 1e-5
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    ssm_multipliers: Tuple[float, ...] = (
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738)
    mlp_multipliers: Tuple[float, ...] = (0.1767766952966369,
                                          0.011160714285714284)
    max_seq_len: int = 262144
    dtype: Any = jnp.bfloat16          # activation/compute dtype
    param_dtype: Any = jnp.bfloat16    # storage dtype

    def __post_init__(self):
        if self.mamba_d_ssm != self.mamba_n_heads * self.mamba_d_head:
            raise ValueError(
                f"mamba_d_ssm {self.mamba_d_ssm} is not mamba_n_heads x "
                f"mamba_d_head ({self.mamba_n_heads} x {self.mamba_d_head})")
        if (self.num_attention_heads % self.num_key_value_heads
                or self.mamba_n_heads % self.mamba_n_groups):
            raise ValueError("query heads divide into KV heads, and SSM "
                             "heads into groups, in whole runs")
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers has five entries (z, x, B, C, "
                             "dt), mlp_multipliers two (gate, down)")

    # What the generator and the pool read.
    @property
    def n_heads(self) -> int:
        return self.num_attention_heads

    @property
    def n_kv_heads(self) -> int:
        return self.num_key_value_heads

    @property
    def n_layers(self) -> int:
        """Layers that keep K/V rows (and a state a slot): all of them."""
        return self.num_hidden_layers

    @property
    def conv_channels(self) -> int:
        """What the convolution runs over: x, then B and C of every group."""
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def in_proj_width(self) -> int:
        return self.mamba_d_ssm + self.conv_channels + self.mamba_n_heads

    def replace(self, **kw) -> "FalconH1Config":
        return replace(self, **kw)

    def paged_family(self) -> PagedFamily:
        return PAGED_FAMILY


def falcon_h1_stage(*, num_hidden_layers: int = 6, max_seq_len: int = 1024,
                    **kw) -> FalconH1Config:
    """Falcon-H1-34B at its published widths, one of twelve pipeline stages:
    6 of 72 layers, the vocabulary whole
    (``benchmark/configs/falcon-h1-34b.json``)."""
    return FalconH1Config(num_hidden_layers=num_hidden_layers,
                          max_seq_len=max_seq_len, **kw)


def tiny(**kw) -> FalconH1Config:
    """Test-sized: two layers, width 64, 4 query heads over 2 KV heads of
    16, 4 SSM heads of 16 in 2 groups, convolution 4, chunks of 16, every
    multiplier another number than 1, float32. The state size is 128, half
    the published one and no smaller: what the state adds to a head's output
    beside ``D x`` grows with it (``B . C`` sums ``N`` products of one sign
    on average), and at 8 a zeroed state moved no logit that a check reads."""
    defaults = dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16,
        mamba_d_state=128, mamba_n_groups=2, mamba_chunk_size=16,
        rope_theta=1e4, embedding_multiplier=2.0, lm_head_multiplier=0.5,
        attention_in_multiplier=0.8, attention_out_multiplier=0.6,
        key_multiplier=0.4, ssm_in_multiplier=0.5, ssm_out_multiplier=0.7,
        ssm_multipliers=(0.9, 0.5, 0.6, 0.75, 0.8),
        mlp_multipliers=(0.45, 0.3), max_seq_len=64,
        dtype=jnp.float32, param_dtype=jnp.float32)
    defaults.update(kw)
    return FalconH1Config(**defaults)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def mup_vector(c: FalconH1Config) -> jax.Array:
    """``ssm_multipliers`` spread over the in-projection's channels: z, x,
    B, C, dt. float32 [in_proj_width]."""
    gn = c.mamba_n_groups * c.mamba_d_state
    widths = (c.mamba_d_ssm, c.mamba_d_ssm, gn, gn, c.mamba_n_heads)
    return jnp.concatenate([jnp.full((w,), m, jnp.float32)
                            for w, m in zip(widths, c.ssm_multipliers)])


def init_params(config: FalconH1Config, key: jax.Array) -> Dict:
    """Seeded weights, made in ``param_dtype``: ``"layers"`` is a list of one
    dict a layer.

    The published multipliers are muP's: they presume weights of the scale
    the model was trained to, which a seeded tree has not (unit-variance rows
    under ``lm_head_multiplier`` 1/128 give logits no check can read). So
    every matrix is normal with the standard deviation that, WITH the
    multipliers the program and the reference both apply, gives its product
    unit variance: ``1 / (sqrt(fan_in) x the multipliers between its input
    and the next nonlinearity)``. Then: the embedding's rows times
    ``embedding_multiplier`` have unit mean square; z, x, B, C and the raw dt
    are unit normal; attention scores have a standard deviation of 2 (``W_q``
    is doubled: over some hundred keys a softmax of unit scores is nearly a
    mean, and its output nearly nothing); the mixer and the attention branch
    each add about 0.7 to the stream's mean square, the feed-forward about
    1; logits have a standard deviation near 1. Norm weights and ``D`` one,
    the convolution's bias normal(0, 0.1). ``A_log`` and ``dt_bias`` by
    Mamba-2's own rule: ``A`` uniform in [1, 16], ``dt_bias =
    softplus^-1(dt)`` with ``dt`` log-uniform in [0.001, 0.1], so a head
    forgets over ``1 / (dt A)``: one to a thousand tokens, a median near
    twelve, and a zeroed state is seen."""
    c = config
    dt_ = c.param_dtype
    D, F, E = c.hidden_size, c.intermediate_size, c.mamba_d_ssm
    Hq, Hkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    H = c.mamba_n_heads
    counter = iter(range(1 << 30))
    sub = lambda: jax.random.fold_in(key, next(counter))  # noqa: E731

    def nrm(shape, std, dtype=dt_):
        return (jax.random.normal(sub(), shape, jnp.float32)
                * std).astype(dtype)

    ones = lambda *shape: jnp.ones(shape, dt_)  # noqa: E731
    gate_m, down_m = c.mlp_multipliers

    def layer():
        a = 1.0 + 15.0 * jax.random.uniform(sub(), (H,), jnp.float32)
        dt = jnp.exp(jax.random.uniform(
            sub(), (H,), jnp.float32, math.log(1e-3), math.log(1e-1)))
        # A column's scale undoes the multipliers its channel will meet.
        w_in = (jax.random.normal(sub(), (D, c.in_proj_width), jnp.float32)
                / (D ** 0.5 * c.ssm_in_multiplier * mup_vector(c)))
        return {
            "norm_in": ones(D), "norm_ff": ones(D),
            "w_in": w_in.astype(dt_),
            "conv": nrm((c.mamba_d_conv, c.conv_channels),
                        c.mamba_d_conv ** -0.5),
            "conv_bias": nrm((c.conv_channels,), 0.1),
            "A_log": jnp.log(a),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),       # softplus^-1(dt)
            "D": jnp.ones((H,), jnp.float32),
            "ssm_norm": ones(E),
            "w_out": nrm((E, D), 0.7 / (E ** 0.5 * c.ssm_out_multiplier)),
            # q, k and v a head: [D, heads, head_dim], as the products give
            # them to the rotation and the pool (a [D, heads * head_dim]
            # matrix was re-laid on every call: compile-only for a v5e).
            "w_q": nrm((D, Hq, hd),
                       2.0 / (D ** 0.5 * c.attention_in_multiplier)),
            # K's heads, then V's, in one matrix, a head first: [2 KV
            # heads, D, head_dim], the form the decode program's product
            # reads (as [D, heads, head_dim] it was re-laid on every call).
            "w_kv": jnp.concatenate([
                nrm((Hkv, D, hd), 1.0 / (
                    D ** 0.5 * c.attention_in_multiplier * c.key_multiplier)),
                nrm((Hkv, D, hd),
                    1.0 / (D ** 0.5 * c.attention_in_multiplier))]),
            "w_o": nrm((Hq * hd, D), 2.0 / (
                (Hq * hd) ** 0.5 * c.attention_out_multiplier)),
            "ffn": {"w_gate": nrm((D, F), 1.0 / (D ** 0.5 * gate_m)),
                    "w_up": nrm((D, F), D ** -0.5),
                    "w_down": nrm((F, D), 1.0 / (0.6 * F ** 0.5 * down_m))}}

    return {
        "tok_embed": nrm((c.vocab_size, D), 1.0 / c.embedding_multiplier),
        "layers": [layer() for _ in range(c.num_hidden_layers)],
        "norm_f": ones(D),
        "lm_head": nrm((D, c.vocab_size),
                       1.0 / (D ** 0.5 * c.lm_head_multiplier)),
    }


# ---------------------------------------------------------------------------
# The two kinds of device state
# ---------------------------------------------------------------------------

def init_slot_state(config: FalconH1Config, slots: int) -> Tuple[jax.Array, jax.Array]:
    """``(S [layers, slots, N, heads * channels] float32, conv tail [layers,
    width - 1, slots, conv channels] dtype)``: what a slot carries between
    tokens for every layer. ``S`` as ``ops/ssd.py``'s kernel folds it; the
    tail with the slots beside the channels, so that its two minor
    dimensions are whole tiles (three rows a slot would pad to a tile of
    sixteen)."""
    c = config
    return (jnp.zeros((c.num_hidden_layers, slots, c.mamba_d_state,
                       c.mamba_d_ssm), jnp.float32),
            jnp.zeros((c.num_hidden_layers, c.mamba_d_conv - 1, slots,
                       c.conv_channels), c.dtype))


def _in_proj(lw, u, c: FalconH1Config):
    """u [..., D] -> (z [..., E] float32, xBC [..., conv channels] dtype (before
    the convolution), dt [..., H] float32 (before its bias)."""
    p = jnp.einsum("...d,dc->...c", u * c.ssm_in_multiplier, lw["w_in"],
                   preferred_element_type=jnp.float32) * mup_vector(c)
    E, cc = c.mamba_d_ssm, c.conv_channels
    return p[..., :E], p[..., E:E + cc].astype(c.dtype), p[..., E + cc:]


def _ssm_operands(lw, y, dt_raw, c: FalconH1Config):
    """Convolved, activated channels [..., conv channels] float32 and the raw
    step -> (x [..., H, P], B, C [..., G, N], dt [..., H], A [H])."""
    H, P, G, N = (c.mamba_n_heads, c.mamba_d_head, c.mamba_n_groups,
                  c.mamba_d_state)
    lead, E = y.shape[:-1], c.mamba_d_ssm
    x = y[..., :E].reshape(lead + (H, P))
    B = y[..., E:E + G * N].reshape(lead + (G, N))
    C = y[..., E + G * N:].reshape(lead + (G, N))
    dt = jax.nn.softplus(dt_raw + lw["dt_bias"].astype(jnp.float32))
    return x, B, C, dt, -jnp.exp(lw["A_log"].astype(jnp.float32))


def _gated_out(lw, y, z, c: FalconH1Config):
    """``W_out RMSNorm_group(y * silu(z))``: ``y`` [..., H, P] float32 (with
    its ``D x``), ``z`` [..., E] float32."""
    G = c.mamba_n_groups
    g = (y.reshape(z.shape) * jax.nn.silu(z)).reshape(z.shape[:-1] + (G, -1))
    g = rms_norm(g, lw["ssm_norm"].reshape(G, -1), c.rms_norm_eps)
    return _mm("...e,ed->...d", g.reshape(z.shape).astype(c.dtype),
               lw["w_out"], c.dtype)


def _mixer_prefill(lw, u, state, layer, slot, suffix_len, c: FalconH1Config):
    """One sequence from its start: ``u`` [1, P, D], of which the first
    ``suffix_len`` positions are real. Writes slot ``slot``'s state of
    ``layer`` as it stands after them."""
    S, tail = state
    P = u.shape[1]
    z, pre, dt_raw = _in_proj(lw, u[0], c)
    y, tail = causal_conv.prefill(pre, lw["conv"], lw["conv_bias"], tail,
                                  layer, slot, suffix_len)
    x, B, C, dt, A = _ssm_operands(lw, jax.nn.silu(y), dt_raw, c)
    real = (jnp.arange(P) < suffix_len)[:, None]
    o, S_new = ssd.chunked(x, jnp.where(real, dt, 0.0), A, B, C, lw["D"],
                           chunk=c.mamba_chunk_size)
    S = lax.dynamic_update_slice(
        S, ssd.fold_state(S_new)[None, None], (layer, slot, 0, 0))
    return _gated_out(lw, o, z, c)[None], (S, tail)


def _mixer_decode(lw, u, state, layer, active, c: FalconH1Config, kernel: str):
    """One token a slot: ``u`` [S, 1, D]. Active slots' states advance;
    parked ones stay bit for bit."""
    S, tail = state
    z, pre, dt_raw = _in_proj(lw, u[:, 0], c)
    y, tail = causal_conv.decode(pre, lw["conv"], lw["conv_bias"], tail,
                                 layer, active)
    x, B, C, dt, A = _ssm_operands(lw, jax.nn.silu(y), dt_raw, c)
    if kernel in ("pallas", "interpret"):
        S, o = ssd.ssd_decode(S, x, dt, A, B, C, active, layer,
                              interpret=kernel == "interpret")
    else:
        S, o = ssd.ssd_decode_reference(S, x, dt, A, B, C, active, layer)
    o = o + lw["D"].astype(jnp.float32)[:, None] * x
    return _gated_out(lw, o, z, c)[:, None], (S, tail)


def _attention(lw, u, pool, layer, ctx, c: FalconH1Config, kernel: str):
    """Grouped-query attention over the paged rows: ``u`` [S, T, D]; the T
    new rows go to pool cells (``blk``, ``off``) first."""
    dt = c.dtype
    S, T, _ = u.shape
    k_pool, v_pool = pool
    a = u * c.attention_in_multiplier
    freqs = rope_frequencies(c.rope_theta, c.head_dim)
    q = rope(_mm("std,dhk->sthk", a, lw["w_q"], dt), ctx["positions"],
             freqs=freqs)
    kv = _mm("std,hdk->sthk", a, lw["w_kv"], dt)
    k = rope(kv[:, :, :c.n_kv_heads] * c.key_multiplier, ctx["positions"],
             freqs=freqs)
    v = kv[:, :, c.n_kv_heads:]
    with jax.named_scope("kv_pool_write"):
        k_pool = k_pool.at[layer, ctx["blk"], ctx["off"]].set(
            k.reshape(S, T, -1))
        v_pool = v_pool.at[layer, ctx["blk"], ctx["off"]].set(
            v.reshape(S, T, -1))
    o = _paged_attend(q, k_pool, v_pool, ctx["tables"], ctx["lengths"], layer,
                      scale=c.head_dim ** -0.5, kernel=kernel,
                      queries=ctx.get("suffix_len"))
    return _mm("ste,ed->std", o.reshape(S, T, -1), lw["w_o"], dt), (k_pool, v_pool)


def _ffn(fw, h, c: FalconH1Config):
    gate_m, down_m = c.mlp_multipliers
    g = jnp.einsum("...d,df->...f", h, fw["w_gate"],
                   preferred_element_type=jnp.float32) * gate_m
    up = jnp.einsum("...d,df->...f", h, fw["w_up"],
                    preferred_element_type=jnp.float32)
    return _mm("...f,fd->...d", (jax.nn.silu(g) * up).astype(c.dtype),
               fw["w_down"], c.dtype) * down_m


@functools.lru_cache(maxsize=None)
def _layer_fn(c: FalconH1Config, prefill: bool, kernel: str):
    """One layer as a jit of its own, built once a (config, mode, kernel): a
    program that calls it once a layer traces and lowers it once whatever
    the depth, and XLA inlines the calls. ``ctx`` holds the arrays the
    mode's mixers need (tables, cells, positions, the slot or the active
    mask)."""
    eps = c.rms_norm_eps

    @jax.jit
    def layer(x, pool, state, i, lw, ctx):
        u = rms_norm(x, lw["norm_in"], eps)
        m, state = (
            _mixer_prefill(lw, u, state, i, ctx["slot"], ctx["suffix_len"], c)
            if prefill else
            _mixer_decode(lw, u, state, i, ctx["active"], c, kernel))
        a, pool = _attention(lw, u, pool, i, ctx, c, kernel)
        x = x + m * c.ssm_out_multiplier + a * c.attention_out_multiplier
        x = x + _ffn(lw["ffn"], rms_norm(x, lw["norm_ff"], eps), c)
        return x.astype(c.dtype), pool, state

    return layer


def _forward(params, tokens, pool, state, c: FalconH1Config, prefill: bool,
             kernel: str, ctx, head_rows=None):
    """Embedding, the layers (one jitted call each), final norm, head.
    ``head_rows`` picks the positions the head sees (None: all)."""
    x = (jnp.take(params["tok_embed"], tokens, axis=0).astype(c.dtype)
         * c.embedding_multiplier)
    layer = _layer_fn(c, prefill, kernel)
    pool, state = tuple(pool), tuple(state)
    for i, lw in enumerate(params["layers"]):
        # The same avals every call (``i`` a value): one trace, one lowering.
        x, pool, state = layer(x, pool, state, jnp.int32(i), lw, ctx)
    if head_rows is not None:
        x = head_rows(x)
    x = rms_norm(x, params["norm_f"], c.rms_norm_eps)
    logits = jnp.einsum("std,dv->stv", x, params["lm_head"],
                        preferred_element_type=jnp.float32)
    return logits * c.lm_head_multiplier, pool, state


def forward_prefill_paged(params, tokens, pool, state, table, start_pos,
                          suffix_len, slot, config: FalconH1Config,
                          block_tokens: int, kernel: str = "gather"):
    """The family's ``prefill``: ``tokens`` [1, P] (a bucket) from the
    sequence's start (``start_pos`` is 0: no prefix hit is ever served to
    this family), the first ``suffix_len`` real. Writes the K/V rows through
    ``table`` (pad rows to trash block 0) and slot ``slot``'s state from
    zero. The head sees ONE row, the last real position: logits come back
    ``[1, 1, V]``."""
    positions, _, blk, off = prefill_cells(
        table, start_pos, suffix_len, tokens.shape[1], block_tokens)
    ctx = {"slot": jnp.asarray(slot, jnp.int32),
           "suffix_len": jnp.asarray(suffix_len, jnp.int32),
           "positions": positions[None], "blk": blk[None], "off": off[None],
           "tables": table[None],
           "lengths": jnp.reshape(start_pos, (1,)).astype(jnp.int32)}
    logits, pool, state = _forward(
        params, tokens, pool, state, config, True, kernel, ctx,
        head_rows=lambda x: lax.dynamic_slice_in_dim(
            x, suffix_len - 1, 1, axis=1))
    return logits, pool, state, None


def forward_decode_paged(params, tokens, pool, state, tables, lengths,
                         config: FalconH1Config, block_tokens: int,
                         kernel: str = "gather",
                         active: Optional[jax.Array] = None):
    """The family's ``decode``: ``tokens`` [S, 1], slot s's token at position
    ``lengths[s]``. Active slots' states advance by the token; a parked
    slot's stay bit for bit, its K/V write lands in trash block 0."""
    S, T = tokens.shape
    if T != 1:
        raise ValueError("a recurrent state advances one token a step: "
                         f"got {T} (speculative verify is not supported)")
    positions, blk, off = decode_cells(tables, lengths, T, block_tokens)
    if active is None:
        active = jnp.ones((S,), bool)
    ctx = {"active": active, "positions": positions, "blk": blk, "off": off,
           "tables": tables, "lengths": lengths}
    logits, pool, state = _forward(
        params, tokens, pool, state, config, False, kernel, ctx)
    return logits, pool, state, None


def describe(config: FalconH1Config) -> Dict[str, int]:
    """What the stack is made of, for ``engine.describe()``."""
    c = config
    return {"kv_heads": c.num_key_value_heads, "ssm_heads": c.mamba_n_heads,
            "ssm_state": c.mamba_d_state,
            "state_layers": c.num_hidden_layers}


PAGED_FAMILY = PagedFamily(
    init_pool=init_block_pool,
    prefill=forward_prefill_paged,
    decode=forward_decode_paged,
    logits_dim=lambda params, config: params["lm_head"].shape[-1],
    init_slot_state=init_slot_state,
    # As olmo_hybrid: the prefix cache hands out rows at a position p,
    # usable only with every layer's state at p (ROADMAP R4).
    unsupported=("prefix_cache",),
    describe=describe,
)
