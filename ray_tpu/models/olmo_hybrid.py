"""Olmo-Hybrid's language model on the paged serve path.

The third model family of the zoo: layers in periods (``layer_types``; three
``linear_attention`` layers, then one ``full_attention`` layer), so a
sequence's memory is of TWO kinds: rows a token in the paged K/V pool for
the full-attention layers, and ONE recurrent state a slot for each
linear-attention layer (``PagedFamily.init_slot_state``). Field names are
the keys of the model's ``config.json``
(huggingface.co/allenai/Olmo-Hybrid-7B); the layer equations, with every
departure from the source noted (also under ``assumed`` in
``benchmark/configs/olmo-hybrid-7b.json``):

*Residual form* (the Olmo 2/3 family's reordered norm, assumed): with ``x``
the layer's input, RMSNorm at ``rms_norm_eps``, no biases::

    h = x + RMSNorm(mixer(x))
    y = h + RMSNorm(ffn(h)),   ffn(h) = W_down (silu(W_gate h) * W_up h)

After the last layer one RMSNorm, then the untied head.

*``linear_attention`` mixer* (Gated DeltaNet, arXiv:2412.06464).
``[q~ | k~ | v~] = W_qkv x`` (``Hk dk | Hk dk | Hv dv`` channels). Every
channel passes a causal depthwise convolution over time of width
``linear_conv_kernel_dim`` and then SiLU. Per head: ``q``, ``k``
L2-normalised, ``q`` scaled by ``dk^-1/2``; ``beta = 2 sigmoid(w_b . x)``
(the factor 2 is ``linear_allow_neg_eigval``); ``g = -exp(A_log) *
softplus(w_a . x + dt_bias)``, ``alpha = exp(g)``. The state ``S`` in
R^(dv x dk), zero at a sequence's start: ``S_t = alpha_t S_(t-1) (I - beta_t
k_t k_t^T) + beta_t v_t k_t^T``, ``o_t = S_t q_t`` (``ops/gated_delta.py``,
which holds ``S`` transposed). Output ``W_o [RMSNorm_dv(o_h) * silu((W_g
x)_h)]_h``. What a slot carries between tokens is ``S`` and the last
``linear_conv_kernel_dim - 1`` pre-convolution inputs of every channel.

*``full_attention`` mixer*: ``q, k, v = W_q x, W_k x, W_v x``, RMSNorm with a
learned weight over the whole width of ``q`` and of ``k`` (the family's
QK-norm, assumed), heads of ``hidden_size / num_attention_heads``, scores
scaled by ``head_dim^-1/2``, causal softmax, NO positional rotation
(``rope_theta`` is null in the source), ``W_o``. As many KV heads as query
heads: the pool row is ``generate.init_block_pool``'s and the kernel
``ops/paged_attention.py:paged_attention``, as GPT-2's.

*Dtypes* (assumed): weights and activations ``dtype`` (bfloat16) with
float32 accumulation; the state float32 (the reference implementations keep
it so: a running sum rounded to bfloat16 every token drifts); the
convolution tail in ``dtype``.

Weights are one array a matrix and no stacking over layers or periods: a
``lax.scan`` over stacked periods made XLA copy every matrix out of its slab
on every step (a ``dynamic-slice`` fusion a matrix, compile-only for a v5e:
1.66 GB of temporaries, three times the weights' traffic a decode step). The
programs instead call ONE jitted period function once a period
(``_period_fn``): it is traced and lowered once whatever the depth, as
``ops/paged_attention.py``'s kernel is, and XLA inlines the calls.

The prefix cache is not supported (``PagedFamily.unsupported``): a K/V hit
at position p is usable only with every linear layer's state at p, which
nothing keeps. So ``start_pos`` is always 0 here and a prefill writes its
slot's state from zero.
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
from ray_tpu.ops import causal_conv, gated_delta
from ray_tpu.ops.layers import gated_ffn as _ffn, mm as _mm, rms_norm

LINEAR, FULL = "linear_attention", "full_attention"
_PERIOD = (LINEAR, LINEAR, LINEAR, FULL)


@dataclass(frozen=True)
class OlmoHybridConfig:
    """Field names are the published ``config.json`` keys; ``max_seq_len``
    and the two dtypes are this program's."""
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    layer_types: Tuple[str, ...] = _PERIOD * 8
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 65536
    dtype: Any = jnp.bfloat16          # activation/compute dtype
    param_dtype: Any = jnp.bfloat16    # storage dtype

    def __post_init__(self):
        p = len(self.period)
        if (len(self.layer_types) != self.num_hidden_layers
                or self.layer_types != self.period * (self.num_hidden_layers // p)):
            raise ValueError(
                f"layer_types must be {self.num_hidden_layers} entries in "
                f"whole periods, got {self.layer_types}")
        if (self.num_key_value_heads != self.num_attention_heads
                or self.linear_num_key_heads != self.linear_num_value_heads):
            raise ValueError(
                "Olmo-Hybrid is published with as many key/value heads as "
                "query heads, in both kinds of layer, and this family states "
                "no other: grouped heads are ops/paged_attention.py:"
                "paged_attention's (a pool row of n_kv_heads, as "
                "models/falcon_h1.py), not gdn_decode's")

    # What the generator and the GPT-2 pool read.
    @property
    def n_heads(self) -> int:
        return self.num_attention_heads

    @property
    def n_kv_heads(self) -> int:
        return self.num_key_value_heads

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def period(self) -> Tuple[str, ...]:
        """The shortest prefix of ``layer_types`` that ends on a
        full-attention layer: one period of the pattern."""
        return self.layer_types[:self.layer_types.index(FULL) + 1]

    @property
    def n_periods(self) -> int:
        return self.num_hidden_layers // len(self.period)

    @property
    def n_layers(self) -> int:
        """Layers that keep K/V rows: what ``init_block_pool`` sizes."""
        return self.layer_types.count(FULL)

    @property
    def n_linear(self) -> int:
        return self.layer_types.count(LINEAR)

    @property
    def conv_channels(self) -> int:
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)

    def replace(self, **kw) -> "OlmoHybridConfig":
        return replace(self, **kw)

    def paged_family(self) -> PagedFamily:
        return PAGED_FAMILY


def olmo_hybrid_stage(*, num_hidden_layers: int = 16, max_seq_len: int = 2048,
                      **kw) -> OlmoHybridConfig:
    """Olmo-Hybrid-7B at its published widths, one of two pipeline stages:
    the first 16 of 32 layers (four whole periods), the vocabulary whole
    (``benchmark/configs/olmo-hybrid-7b.json``)."""
    return OlmoHybridConfig(
        num_hidden_layers=num_hidden_layers, max_seq_len=max_seq_len,
        layer_types=_PERIOD * (num_hidden_layers // len(_PERIOD)), **kw)


def tiny(**kw) -> OlmoHybridConfig:
    """Test-sized: one period, width 64, 4 heads of 16, key 8 / value 16,
    convolution 4, float32."""
    defaults = dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, layer_types=_PERIOD, num_attention_heads=4,
        num_key_value_heads=4, linear_num_key_heads=4,
        linear_num_value_heads=4, linear_key_head_dim=8,
        linear_value_head_dim=16, max_seq_len=64,
        dtype=jnp.float32, param_dtype=jnp.float32)
    defaults.update(kw)
    return OlmoHybridConfig(**defaults)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_params(config: OlmoHybridConfig, key: jax.Array) -> Dict:
    """Seeded weights, made in ``param_dtype``: ``"periods"`` is a list of
    periods, each a list of its layers' dicts in ``config.period``'s order.

    Matrices normal with standard deviation ``1/sqrt(fan_in)``, norm weights
    one. The residual stream grows (a unit-RMS vector is added twice a
    layer and nothing norms a mixer's input), so the two gate projections
    ``w_a`` and ``w_b`` count the stream's largest mean square, ``1 + 2
    layers``, into their fan-in: their outputs stay within a standard
    deviation of one and the decay init below decides ``alpha``. That init
    is the family's: ``A_log = log(uniform(0, 16))``, ``dt_bias =
    softplus^-1(dt)`` with ``dt`` log-uniform in [0.001, 0.1], so ``alpha``
    is spread over (0, 1) with a median near 0.9: a memory of tens to
    hundreds of tokens in most heads. (With ``alpha`` driven to 0 the state
    does nothing and no check can see a wrong one.)"""
    c = config
    dt_ = c.param_dtype
    D, F = c.hidden_size, c.intermediate_size
    H, dk, dv = (c.linear_num_value_heads, c.linear_key_head_dim,
                 c.linear_value_head_dim)
    counter = iter(range(1 << 30))
    sub = lambda: jax.random.fold_in(key, next(counter))  # noqa: E731

    def nrm(shape, fan_in, dtype=dt_):
        return (jax.random.normal(sub(), shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    ones = lambda *shape: jnp.ones(shape, dt_)  # noqa: E731

    def shared():
        return {"norm_mixer": ones(D), "norm_ffn": ones(D),
                "ffn": {"w_gate": nrm((D, F), D), "w_up": nrm((D, F), D),
                        "w_down": nrm((F, D), F)}}

    def linear():
        gate_fan = D * (1 + 2 * c.num_hidden_layers)
        a = jnp.maximum(jax.random.uniform(sub(), (H,), jnp.float32) * 16.0,
                        1e-4)
        dt = jnp.exp(jax.random.uniform(
            sub(), (H,), jnp.float32, math.log(1e-3), math.log(1e-1)))
        return {
            "w_qkv": nrm((D, c.conv_channels), D),
            "conv": nrm((c.linear_conv_kernel_dim, c.conv_channels),
                        c.linear_conv_kernel_dim),
            "w_a": nrm((D, H), gate_fan), "w_b": nrm((D, H), gate_fan),
            "A_log": jnp.log(a),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),       # softplus^-1(dt)
            "w_g": nrm((D, H * dv), D), "o_norm": ones(dv),
            "w_o": nrm((H * dv, D), H * dv), **shared()}

    def full():
        return {"w_q": nrm((D, D), D), "w_k": nrm((D, D), D),
                "w_v": nrm((D, D), D), "q_norm": ones(D),
                "k_norm": ones(D), "w_o": nrm((D, D), D), **shared()}

    return {
        "tok_embed": nrm((c.vocab_size, D), 1),
        "periods": [[linear() if kind == LINEAR else full()
                     for kind in c.period] for _ in range(c.n_periods)],
        "norm_f": jnp.ones((D,), dt_),
        "lm_head": nrm((D, c.vocab_size), D),
    }


# ---------------------------------------------------------------------------
# The two kinds of device state
# ---------------------------------------------------------------------------

def init_slot_state(config: OlmoHybridConfig, slots: int) -> Tuple[jax.Array, jax.Array]:
    """``(S [linear layers, slots, dk, H * dv] float32, conv tail [linear
    layers, width - 1, slots, channels] dtype)``: what a slot carries
    between tokens for every linear-attention layer (the tail with the
    slots beside the channels, so that its two minor dimensions are whole
    tiles)."""
    c = config
    return (jnp.zeros((c.n_linear, slots, c.linear_key_head_dim,
                       c.linear_num_value_heads * c.linear_value_head_dim),
                      jnp.float32),
            jnp.zeros((c.n_linear, c.linear_conv_kernel_dim - 1, slots,
                       c.conv_channels), c.dtype))


def _gates(lw, x):
    """(g, beta) [..., H] float32 from the layer's input."""
    f32 = jnp.float32
    a = jnp.einsum("...d,dh->...h", x, lw["w_a"], preferred_element_type=f32)
    b = jnp.einsum("...d,dh->...h", x, lw["w_b"], preferred_element_type=f32)
    g = -jnp.exp(lw["A_log"].astype(f32)) * jax.nn.softplus(
        a + lw["dt_bias"].astype(f32))
    return g, 2.0 * jax.nn.sigmoid(b)


def _heads(y, c: OlmoHybridConfig):
    """Convolved, activated channels [..., C] float32 -> (q, k, v) per head,
    q and k L2-normalised, q scaled."""
    H, dk, dv = (c.linear_num_value_heads, c.linear_key_head_dim,
                 c.linear_value_head_dim)
    lead = y.shape[:-1]
    q = y[..., :H * dk].reshape(lead + (H, dk))
    k = y[..., H * dk:2 * H * dk].reshape(lead + (H, dk))
    v = y[..., 2 * H * dk:].reshape(lead + (H, dv))
    unit = lambda a: a * lax.rsqrt(  # noqa: E731
        jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    return unit(q) * dk ** -0.5, unit(k), v


def _gated_out(lw, o, x, c: OlmoHybridConfig):
    """``W_o [RMSNorm_dv(o_h) * silu((W_g x)_h)]_h``; ``o`` [..., H, dv]
    float32, ``x`` the layer's input."""
    dt = c.dtype
    gate = jnp.einsum("...d,de->...e", x, lw["w_g"],
                      preferred_element_type=jnp.float32)
    o = rms_norm(o, lw["o_norm"], c.rms_norm_eps)
    o = o.reshape(gate.shape) * jax.nn.silu(gate)
    return _mm("...e,ed->...d", o.astype(dt), lw["w_o"], dt)


def _linear_prefill(lw, x, state, layer, slot, suffix_len, c: OlmoHybridConfig):
    """One sequence from its start: ``x`` [1, P, D], of which the first
    ``suffix_len`` positions are real. Writes slot ``slot``'s state of
    ``layer`` as it stands after them."""
    S, tail = state
    P = x.shape[1]
    pre = _mm("pd,dc->pc", x[0], lw["w_qkv"], c.dtype)          # [P, C]
    y, tail = causal_conv.prefill(pre, lw["conv"], None, tail, layer, slot,
                                  suffix_len)
    q, k, v = _heads(jax.nn.silu(y), c)
    g, beta = _gates(lw, x[0])
    real = (jnp.arange(P) < suffix_len)[:, None]
    o, S_new = gated_delta.chunked(q, k, v, jnp.where(real, g, 0.0),
                                   jnp.where(real, beta, 0.0))
    S = lax.dynamic_update_slice(
        S, gated_delta.fold_state(S_new)[None, None], (layer, slot, 0, 0))
    return _gated_out(lw, o[None], x, c), (S, tail)


def _linear_decode(lw, x, state, layer, active, c: OlmoHybridConfig,
                   kernel: str):
    """One token a slot: ``x`` [S, 1, D]. Active slots' states advance;
    parked ones stay bit for bit."""
    S, tail = state
    x1 = x[:, 0]
    pre = _mm("sd,dc->sc", x1, lw["w_qkv"], c.dtype)            # [S, C]
    y, tail = causal_conv.decode(pre, lw["conv"], None, tail, layer, active)
    q, k, v = _heads(jax.nn.silu(y), c)
    g, beta = _gates(lw, x1)
    if kernel in ("pallas", "interpret"):
        S, o = gated_delta.gdn_decode(S, q, k, v, jnp.exp(g), beta, active,
                                      layer, interpret=kernel == "interpret")
    else:
        S, o = gated_delta.gdn_decode_reference(S, q, k, v, jnp.exp(g), beta,
                                                active, layer)
    return _gated_out(lw, o[:, None], x, c), (S, tail)


def _full_mixer(lw, x, pool, layer, blk, off, tables, lengths,
                c: OlmoHybridConfig, kernel: str, queries=None):
    """Multi-head attention over the paged rows, no rotation: ``x`` [S, T,
    D]; the T new rows go to pool cells (``blk``, ``off``) first.
    ``queries``: a prefill's count of real rows (``_paged_attend``)."""
    dt = c.dtype
    S, T, D = x.shape
    k_pool, v_pool = pool
    q = rms_norm(_mm("std,de->ste", x, lw["w_q"], dt), lw["q_norm"],
                 c.rms_norm_eps)
    k = rms_norm(_mm("std,de->ste", x, lw["w_k"], dt), lw["k_norm"],
                 c.rms_norm_eps)
    v = _mm("std,de->ste", x, lw["w_v"], dt)
    with jax.named_scope("kv_pool_write"):
        k_pool = k_pool.at[layer, blk, off].set(k)
        v_pool = v_pool.at[layer, blk, off].set(v)
    o = _paged_attend(q.reshape(S, T, c.n_heads, c.head_dim), k_pool, v_pool,
                      tables, lengths, layer, scale=c.head_dim ** -0.5,
                      kernel=kernel, queries=queries)
    return _mm("ste,ed->std", o.reshape(S, T, D), lw["w_o"], dt), (k_pool, v_pool)


@functools.lru_cache(maxsize=None)
def _period_fn(c: OlmoHybridConfig, prefill: bool, kernel: str):
    """One period of layers as a jit of its own, built once a (config, mode,
    kernel): a program that calls it once a period traces and lowers it once
    whatever the depth, and XLA inlines the calls. ``ctx`` holds the arrays
    the mode's mixers need (tables, cells, the slot or the active mask)."""
    dt, eps = c.dtype, c.rms_norm_eps
    n_lin = c.period.count(LINEAR)
    n_full = len(c.period) - n_lin

    @jax.jit
    def period(x, pool, state, i, pw, ctx):
        li = fi = 0
        for kind, lw in zip(c.period, pw, strict=True):
            if kind == LINEAR:
                layer = i * n_lin + li
                o, state = (
                    _linear_prefill(lw, x, state, layer, ctx["slot"],
                                    ctx["suffix_len"], c) if prefill else
                    _linear_decode(lw, x, state, layer, ctx["active"], c,
                                   kernel))
                li += 1
            else:
                o, pool = _full_mixer(
                    lw, x, pool, i * n_full + fi, ctx["blk"], ctx["off"],
                    ctx["tables"], ctx["lengths"], c, kernel,
                    ctx.get("suffix_len"))
                fi += 1
            h = x + rms_norm(o, lw["norm_mixer"], eps)
            x = h + rms_norm(_ffn(lw["ffn"], h, dt), lw["norm_ffn"], eps)
        return x, pool, state

    return period


def _forward(params, tokens, pool, state, c: OlmoHybridConfig, prefill: bool,
             kernel: str, ctx, head_rows=None):
    """Embedding, the periods (one jitted call each), final norm, head.
    ``head_rows`` picks the positions the head sees (None: all)."""
    x = jnp.take(params["tok_embed"], tokens, axis=0).astype(c.dtype)
    period = _period_fn(c, prefill, kernel)
    pool, state = tuple(pool), tuple(state)
    for i, pw in enumerate(params["periods"]):
        # The same avals every call (``i`` a value): one trace, one lowering.
        x, pool, state = period(x, pool, state, jnp.int32(i), pw, ctx)
    if head_rows is not None:
        x = head_rows(x)
    x = rms_norm(x, params["norm_f"], c.rms_norm_eps)
    logits = jnp.einsum("std,dv->stv", x, params["lm_head"],
                        preferred_element_type=jnp.float32)
    return logits, pool, state


def forward_prefill_paged(params, tokens, pool, state, table, start_pos,
                          suffix_len, slot, config: OlmoHybridConfig,
                          block_tokens: int, kernel: str = "gather"):
    """The family's ``prefill``: ``tokens`` [1, P] (a bucket) from the
    sequence's start (``start_pos`` is 0: no prefix hit is ever served to
    this family), the first ``suffix_len`` real. Writes the K/V rows through
    ``table`` (pad rows to trash block 0) and slot ``slot``'s recurrent
    state from zero. The head sees ONE row, the last real position: logits
    come back ``[1, 1, V]``."""
    _, _, blk, off = prefill_cells(
        table, start_pos, suffix_len, tokens.shape[1], block_tokens)
    ctx = {"slot": jnp.asarray(slot, jnp.int32),
           "suffix_len": jnp.asarray(suffix_len, jnp.int32),
           "blk": blk[None], "off": off[None], "tables": table[None],
           "lengths": jnp.reshape(start_pos, (1,)).astype(jnp.int32)}
    logits, pool, state = _forward(
        params, tokens, pool, state, config, True, kernel, ctx,
        head_rows=lambda x: lax.dynamic_slice_in_dim(
            x, suffix_len - 1, 1, axis=1))
    return logits, pool, state, None


def forward_decode_paged(params, tokens, pool, state, tables, lengths,
                         config: OlmoHybridConfig, block_tokens: int,
                         kernel: str = "gather",
                         active: Optional[jax.Array] = None):
    """The family's ``decode``: ``tokens`` [S, 1], slot s's token at position
    ``lengths[s]``. Active slots' states advance by the token; a parked
    slot's stay bit for bit, its K/V write lands in trash block 0."""
    S, T = tokens.shape
    if T != 1:
        raise ValueError("a recurrent state advances one token a step: "
                         f"got {T} (speculative verify is not supported)")
    _, blk, off = decode_cells(tables, lengths, T, block_tokens)
    if active is None:
        active = jnp.ones((S,), bool)
    ctx = {"active": active, "blk": blk, "off": off, "tables": tables,
           "lengths": lengths}
    logits, pool, state = _forward(
        params, tokens, pool, state, config, False, kernel, ctx)
    return logits, pool, state, None


PAGED_FAMILY = PagedFamily(
    init_pool=init_block_pool,
    prefill=forward_prefill_paged,
    decode=forward_decode_paged,
    logits_dim=lambda params, config: params["lm_head"].shape[-1],
    init_slot_state=init_slot_state,
    # The prefix cache hands out rows at a position p, usable only with
    # every linear layer's state at p, which nothing keeps yet (ROADMAP R4:
    # snapshots at block boundaries).
    unsupported=("prefix_cache",),
)
