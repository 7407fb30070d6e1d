"""Nemotron-H's language model (``model_type: nemotron_h``) on the paged serve
path.

The seventh model family of the zoo and the first whose every layer is ONE
thing: character ``i`` of ``hybrid_override_pattern`` names layer ``i`` a
Mamba-2 mixer (``M``), an expert feed-forward (``E``) or grouped-query
attention (``*``), and that is all the layer holds. So most layers keep no
per-sequence memory at all, and the two kinds that do keep different kinds:
a mixer layer a float32 state a SLOT (``PagedFamily.init_slot_state``, the
mixer layers alone), an attention layer a K/V row a TOKEN in the paged pool
(``PagedFamily.init_pool``, the attention layers alone, as ``afmoe``'s pool
counts its full layers alone). Field names are the keys of the source's
``config.json`` (huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16;
the family's paper is Nemotron-H, arXiv:2504.03624, its mixer Mamba-2,
arXiv:2405.21060). RMSNorm at ``layer_norm_epsilon``, no bias but the
convolution's, the residual in the model's dtype::

    x_(i+1) = x_i + f_i(RMSNorm_i(x_i))      f_i = Mixer, Experts or Attn
    logits  = W_head RMSNorm_f(x_L)          E and W_head untied

*Mixer* (``d_inner = mamba_num_heads x mamba_head_dim``, NOT ``expand x
hidden_size``): ``[z | xBC | dt] = W_in u``; ``xBC`` through a causal
depthwise convolution with bias (``ops/causal_conv.py``), then SiLU; ``dt =
softplus(dt + dt_bias)``, not clamped above; ``A = -exp(A_log)`` a head; per
head ``h`` of group ``g``: ``S_t = exp(dt_t A) S_(t-1) + dt_t x_t B_(g,t)^T``,
``y_t = S_t C_(g,t) + D x_t`` (``ops/ssd.py``); ``RMSNorm_group(y *
silu(z))`` over each of the ``n_groups`` groups' channels with one learned
weight of ``d_inner``; ``W_out``. A slot carries ``S`` and the last
``conv_kernel - 1`` pre-convolution rows of ``xBC``.

*Experts*: ``s = sigmoid(W_r u)`` in float32 over ``n_routed_experts``
outputs; the ``num_experts_per_tok`` largest of ``s +
e_score_correction_bias`` are picked (``n_group`` = ``topk_group`` = 1: no
group limit); a pick's weight is ``routed_scaling_factor x s_i / sum of the
picked s`` (``norm_topk_prob``); ``Expert_e(u) = W_down,e relu(W_up,e u)^2``
(``mlp_hidden_act: relu2``: TWO matrices an expert, no gate) at
``moe_intermediate_size``; plus, for every token, the shared expert of the
same form at ``moe_shared_expert_intermediate_size``. ``held = (first,
count)`` says which routed experts' weights live here, as ``kimi_k2``: the
layer routes over all of them, normalises over ALL of a token's picks and
adds only what its own experts give (``ops/moe.py:held_experts_ffn``, form
``relu2``); the shared expert is whole on every chip.

*Attn*: ``num_attention_heads`` query heads over ``num_key_value_heads`` KV
heads of ``head_dim`` (query head ``i`` reads KV head ``i // (heads / kv
heads)``), scores x ``head_dim^-1/2``, causal softmax, ``W_o``; NO rotation
and no positional table (the family's paper; its ``NemotronHAttention``
applies none, although the file carries ``rope_theta``): an attention layer
orders tokens by the causal mask alone, as ``afmoe``'s full layer.

*Assumed* (also under ``assumed`` in
``benchmark/configs/nemotron-3-nano-30b-a3b.json``): no rotary; ``dt``
unclamped (``time_step_limit`` is absent, the family's default ``(0, inf)``);
the state float32 and the convolution tail in ``dtype`` (as ``falcon_h1``);
the router and its bias float32; the initialisation (:func:`init_params`).

Weights are one array a matrix, no stacking over layers; a program calls ONE
jitted function a KIND of layer, once a layer of that kind (``falcon_h1``'s
``_layer_fn`` and its reason). The prefix cache is not supported
(``PagedFamily.unsupported``): a K/V hit at position p is usable only with
every mixer layer's state at p, which nothing keeps. So ``start_pos`` is
always 0 and a prefill writes its slot's state from zero.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass, replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.generate import (EXPERT_AUX_COUNTS, PagedFamily,
                                     _paged_attend, decode_cells, expert_aux,
                                     init_block_pool, prefill_cells)
from ray_tpu.ops import causal_conv, moe, ssd
from ray_tpu.ops.layers import mm as _mm, rms_norm

MIXER, EXPERTS, ATTENTION = "M", "E", "*"
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclass(frozen=True)
class NemotronHConfig:
    """Field names are the published ``config.json`` keys
    (Nemotron-3-Nano-30B-A3B's values); ``held``, ``max_seq_len`` and the
    two dtypes are this program's."""
    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = PATTERN
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    mlp_hidden_act: str = "relu2"
    layer_norm_epsilon: float = 1e-5
    max_seq_len: int = 262144
    # Routed experts whose weights live on this chip: (first, count).
    held: Tuple[int, int] = (0, 128)
    dtype: Any = jnp.bfloat16          # activation/compute dtype
    param_dtype: Any = jnp.bfloat16    # storage dtype

    def __post_init__(self):
        object.__setattr__(self, "held", tuple(self.held))
        kinds = self.hybrid_override_pattern
        if (len(kinds) != self.num_hidden_layers
                or set(kinds) - {MIXER, EXPERTS, ATTENTION}):
            raise ValueError(
                f"hybrid_override_pattern {kinds!r} names {len(kinds)} "
                f"layers of kinds {sorted(set(kinds))}: want "
                f"{self.num_hidden_layers} of 'M' / 'E' / '*'")
        if ATTENTION not in kinds:
            raise ValueError("a stack with no attention layer has no paged pool")
        if MIXER not in kinds:
            raise ValueError("a stack with no mixer layer has no slot state")
        if (self.num_attention_heads % self.num_key_value_heads
                or self.mamba_num_heads % self.n_groups):
            raise ValueError("query heads divide into KV heads, and mixer "
                             "heads into groups, in whole runs")
        if self.mlp_hidden_act != "relu2":
            raise ValueError(f"no expert form {self.mlp_hidden_act!r} here")
        first, count = self.held
        if not 0 <= first <= first + count <= self.n_routed_experts:
            raise ValueError(f"held {self.held} is no run of "
                             f"{self.n_routed_experts} experts")

    # What the generator and the pool read: the pool is the ATTENTION
    # layers'.
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
    def mixer_layers(self) -> int:
        return self.hybrid_override_pattern.count(MIXER)

    @property
    def expert_layers(self) -> int:
        return self.hybrid_override_pattern.count(EXPERTS)

    @property
    def attention_layers(self) -> int:
        return self.hybrid_override_pattern.count(ATTENTION)

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        """What the convolution runs over: x, then B and C of every group."""
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def in_proj_width(self) -> int:
        return self.d_inner + self.conv_channels + self.mamba_num_heads

    @property
    def expert_width_stored(self) -> int:
        """``moe_intermediate_size`` rounded up to whole 128-lane tiles: the
        routed experts' matrices are STORED that wide, the columns of
        ``w_up`` and the rows of ``w_down`` past the published width zero
        (``relu(0)^2 = 0`` times a zero row: they add nothing). 1,856 is 14.5
        tiles: stored as published, the grouped product's kernel was handed a
        re-laid COPY of every expert layer's ``w_up`` on every token step
        (compile-only for a v5e: five copies of ``bf16[64, 2688, 1856]``,
        3.2 GB of temporaries written and read again a step)."""
        return -(-self.moe_intermediate_size // 128) * 128

    @property
    def state_bytes_per_slot(self) -> int:
        """The state and the convolution tail of every mixer layer, one slot."""
        return self.mixer_layers * (
            self.d_inner * self.ssm_state_size * 4
            + (self.conv_kernel - 1) * self.conv_channels
            * jnp.dtype(self.dtype).itemsize)

    def kind_index(self, layer: int) -> int:
        """Layer ``layer``'s index among the layers of its own kind: which
        layer of the pool, or of the slot state, is its."""
        kinds = self.hybrid_override_pattern
        return kinds[:layer].count(kinds[layer])

    def replace(self, **kw) -> "NemotronHConfig":
        return replace(self, **kw)

    def paged_family(self) -> PagedFamily:
        return PAGED_FAMILY


def nemotron_nano_share(*, num_hidden_layers: int = 13,
                        hybrid_override_pattern: str = PATTERN[:13],
                        held: Tuple[int, int] = (0, 64),
                        vocab_size: int = 65536, max_seq_len: int = 2176,
                        **kw) -> NemotronHConfig:
    """Nemotron-3-Nano-30B-A3B at its published widths, cut to one of the TWO
    chips that share each layer of the first of four pipeline stages: the
    pattern's first 13 characters ``MEMEM*EMEMEM*`` (its opening block and
    one whole repeating block: 6 mixers, 5 expert layers, 2 attention
    layers), 64 of 128 experts held, half the vocabulary
    (``benchmark/configs/nemotron-3-nano-30b-a3b.json``)."""
    return NemotronHConfig(
        num_hidden_layers=num_hidden_layers,
        hybrid_override_pattern=hybrid_override_pattern, held=held,
        vocab_size=vocab_size, max_seq_len=max_seq_len, **kw)


def tiny(**kw) -> NemotronHConfig:
    """Test-sized: seven layers ``MEM*EM*`` (3 mixers, 2 expert layers, 2
    attention layers), width 64, 4 query heads over 2 KV heads of 64 (a
    128-lane row), 4 mixer heads of 16 in 2 groups, a state size of 128
    (``falcon_h1.tiny``'s reason: at 8 a zeroed state moves no logit a check
    reads), convolution 4, chunks of 16, 8 routed experts of which 4 held,
    top-3, experts of 32 and a shared expert of 64, float32."""
    defaults = dict(
        vocab_size=256, hidden_size=64, num_hidden_layers=7,
        hybrid_override_pattern="MEM*EM*", num_attention_heads=4,
        num_key_value_heads=2, head_dim=64, mamba_num_heads=4,
        mamba_head_dim=16, n_groups=2, ssm_state_size=128, chunk_size=16,
        n_routed_experts=8, num_experts_per_tok=3, moe_intermediate_size=32,
        moe_shared_expert_intermediate_size=64, held=(0, 4), max_seq_len=64,
        dtype=jnp.float32, param_dtype=jnp.float32)
    defaults.update(kw)
    return NemotronHConfig(**defaults)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

# For a unit normal z: the mean square of relu(z)^2 (what W_down's rows see).
_RELU2_MEAN_SQUARE = 1.5


def init_params(config: NemotronHConfig, key: jax.Array) -> Dict:
    """Seeded weights, made in ``param_dtype``: ``"layers"`` is a list of one
    dict a layer, holding its norm and its kind's matrices.

    Every product has unit variance at its input to the next nonlinearity:
    the embedding's rows have unit mean square; z, xBC, the raw dt, an
    expert's ``W_up u`` and the router's logits are unit normal (a router of
    unit logits: its sigmoid scores spread over (0.1, 0.9), where a router of
    0.01 scores everything 0.5 and every pick changes hands on rounding).
    For a unit normal ``z``, ``relu(z)^2`` has mean 1/2 and mean square 3/2:
    that goes into ``W_down``'s fan-in, and ``routed_scaling_factor ** 2``
    into a routed expert's (``kimi_k2.init_params``'s lesson: a token's picks
    then weigh one in sum, and a pick that changes hands on bfloat16 rounding
    moves a logit by a fraction, not by a whole unit), so that a sublayer
    adds about 1 to the stream's mean square. Attention scores have a
    standard deviation of 2 (``W_q`` is doubled: over hundreds of keys a
    softmax of unit scores is nearly a mean; ``W_o`` carries the 2 back);
    logits have a standard deviation near 1. Norm gains are seeded near one
    (``1 + 0.1 n``, so that a norm left out, or its gain, is seen), ``D``
    one, the convolution's bias normal(0, 0.1). ``A_log`` and ``dt_bias`` by
    Mamba-2's own rule: ``A`` uniform in [1, 16], ``dt_bias =
    softplus^-1(dt)`` with ``dt`` log-uniform in [``time_step_min``,
    ``time_step_max``], floored at ``time_step_floor``: a head forgets over
    ``1 / (dt A)``, one to a thousand tokens, so a zeroed state is seen. The
    router and ``e_score_correction_bias`` are float32; the bias is a seeded
    NON-zero buffer of standard deviation 0.02, a tenth of the spread of a
    sigmoid score."""
    c = config
    dt_ = c.param_dtype
    D, E, H = c.hidden_size, c.d_inner, c.mamba_num_heads
    Hq, Hkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    F, Fs = c.moe_intermediate_size, (c.n_shared_experts
                                      * c.moe_shared_expert_intermediate_size)
    n_held, pad = c.held[1], c.expert_width_stored - c.moe_intermediate_size
    counter = iter(range(1 << 30))
    sub = lambda: jax.random.fold_in(key, next(counter))  # noqa: E731

    def nrm(shape, fan_in, dtype=dt_):
        return (jax.random.normal(sub(), shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    def gain(n):
        return (1.0 + 0.1 * jax.random.normal(sub(), (n,), jnp.float32)
                ).astype(dt_)

    def mixer():
        a = 1.0 + 15.0 * jax.random.uniform(sub(), (H,), jnp.float32)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            sub(), (H,), jnp.float32, math.log(c.time_step_min),
            math.log(c.time_step_max))), c.time_step_floor)
        return {"w_in": nrm((D, c.in_proj_width), D),
                "conv": nrm((c.conv_kernel, c.conv_channels), c.conv_kernel),
                "conv_bias": nrm((c.conv_channels,), 100.0),
                "A_log": jnp.log(a),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),   # softplus^-1(dt)
                "D": jnp.ones((H,), jnp.float32),
                "ssm_norm": gain(E),
                "w_out": nrm((E, D), E)}

    def experts():
        return {"router": nrm((D, c.n_routed_experts), D, jnp.float32),
                "router_bias": jax.random.normal(
                    sub(), (c.n_routed_experts,), jnp.float32) * 0.02,
                # stored ``expert_width_stored`` wide, the rest zero
                "experts": {
                    "w_up": jnp.pad(nrm((n_held, D, F), D),
                                    ((0, 0), (0, 0), (0, pad))),
                    "w_down": jnp.pad(
                        nrm((n_held, F, D), _RELU2_MEAN_SQUARE * F
                            * c.routed_scaling_factor ** 2),
                        ((0, 0), (0, pad), (0, 0)))},
                "shared": {"w_up": nrm((D, Fs), D),
                           "w_down": nrm((Fs, D), _RELU2_MEAN_SQUARE * Fs)}}

    def attention():
        # q a head first: [heads, D, head_dim]; K's heads, then V's, a head
        # first and TRANSPOSED: [2 KV heads, head_dim, D]: the forms the
        # decode program's products read where they lie. With four heads in
        # all XLA lays this product's weight D-minor; stored [heads, D,
        # head_dim] (``afmoe``'s form, sixteen heads) or [D, heads,
        # head_dim] it was copied into that layout on every token step
        # (compile-only for a v5e).
        return {"w_q": nrm((Hq, D, hd), D / 4.0),
                "w_kv": nrm((2 * Hkv, hd, D), D),
                "w_o": nrm((Hq * hd, D), Hq * hd / 4.0)}

    make = {MIXER: mixer, EXPERTS: experts, ATTENTION: attention}
    return {
        "tok_embed": nrm((c.vocab_size, D), 1),
        "layers": [dict(make[kind](), norm=gain(D))
                   for kind in c.hybrid_override_pattern],
        "norm_f": gain(D),
        "lm_head": nrm((D, c.vocab_size), D),
    }


# ---------------------------------------------------------------------------
# The mixer layers' memory: a state a slot
# ---------------------------------------------------------------------------

def init_slot_state(config: NemotronHConfig, slots: int) -> Tuple[jax.Array, jax.Array]:
    """``(S [mixer layers, slots, N, heads * channels] float32, conv tail
    [mixer layers, width - 1, slots, conv channels] dtype)``: what a slot
    carries between tokens for every MIXER layer (the other layers keep
    nothing a slot). ``S`` as ``ops/ssd.py``'s kernel folds it, the tail as
    ``ops/causal_conv.py`` lays it."""
    c = config
    return (jnp.zeros((c.mixer_layers, slots, c.ssm_state_size, c.d_inner),
                      jnp.float32),
            jnp.zeros((c.mixer_layers, c.conv_kernel - 1, slots,
                       c.conv_channels), c.dtype))


def _in_proj(lw, u, c: NemotronHConfig):
    """u [..., D] -> (z [..., E] float32, xBC [..., conv channels] dtype
    (before the convolution), dt [..., H] float32 (before its bias)."""
    p = jnp.einsum("...d,dc->...c", u, lw["w_in"],
                   preferred_element_type=jnp.float32)
    E, cc = c.d_inner, c.conv_channels
    return p[..., :E], p[..., E:E + cc].astype(c.dtype), p[..., E + cc:]


def _ssm_operands(lw, y, dt_raw, c: NemotronHConfig):
    """Convolved, activated channels [..., conv channels] float32 and the raw
    step -> (x [..., H, P], B, C [..., G, N], dt [..., H], A [H])."""
    H, P, G, N = (c.mamba_num_heads, c.mamba_head_dim, c.n_groups,
                  c.ssm_state_size)
    lead, E = y.shape[:-1], c.d_inner
    x = y[..., :E].reshape(lead + (H, P))
    B = y[..., E:E + G * N].reshape(lead + (G, N))
    C = y[..., E + G * N:].reshape(lead + (G, N))
    dt = jax.nn.softplus(dt_raw + lw["dt_bias"].astype(jnp.float32))
    return x, B, C, dt, -jnp.exp(lw["A_log"].astype(jnp.float32))


def _gated_out(lw, y, z, c: NemotronHConfig):
    """``W_out RMSNorm_group(y * silu(z))``: ``y`` [..., H, P] float32 (with
    its ``D x``), ``z`` [..., E] float32."""
    G = c.n_groups
    g = (y.reshape(z.shape) * jax.nn.silu(z)).reshape(z.shape[:-1] + (G, -1))
    g = rms_norm(g, lw["ssm_norm"].reshape(G, -1), c.layer_norm_epsilon)
    return _mm("...e,ed->...d", g.reshape(z.shape).astype(c.dtype),
               lw["w_out"], c.dtype)


def _mixer_prefill(lw, u, state, ml, ctx, c: NemotronHConfig):
    """One sequence from its start: ``u`` [1, P, D], of which the first
    ``suffix_len`` positions are real. Writes slot ``slot``'s state of mixer
    layer ``ml`` as it stands after them."""
    S, tail = state
    P = u.shape[1]
    z, pre, dt_raw = _in_proj(lw, u[0], c)
    with jax.named_scope("ssm_conv"):
        y, tail = causal_conv.prefill(pre, lw["conv"], lw["conv_bias"], tail,
                                      ml, ctx["slot"], ctx["suffix_len"])
        y = jax.nn.silu(y)
    x, B, C, dt, A = _ssm_operands(lw, y, dt_raw, c)
    real = (jnp.arange(P) < ctx["suffix_len"])[:, None]
    with jax.named_scope("ssm_mixer"):
        o, S_new = ssd.chunked(x, jnp.where(real, dt, 0.0), A, B, C, lw["D"],
                               chunk=c.chunk_size)
        S = lax.dynamic_update_slice(
            S, ssd.fold_state(S_new)[None, None], (ml, ctx["slot"], 0, 0))
    return _gated_out(lw, o, z, c)[None], (S, tail)


def _mixer_decode(lw, u, state, ml, ctx, c: NemotronHConfig, kernel: str):
    """One token a slot: ``u`` [S, 1, D]. Active slots' states advance;
    parked ones stay bit for bit."""
    S, tail = state
    active = ctx["active"]
    z, pre, dt_raw = _in_proj(lw, u[:, 0], c)
    with jax.named_scope("ssm_conv"):
        y, tail = causal_conv.decode(pre, lw["conv"], lw["conv_bias"], tail,
                                     ml, active)
        y = jax.nn.silu(y)
    x, B, C, dt, A = _ssm_operands(lw, y, dt_raw, c)
    with jax.named_scope("ssm_mixer"):
        if kernel in ("pallas", "interpret"):
            S, o = ssd.ssd_decode(S, x, dt, A, B, C, active, ml,
                                  interpret=kernel == "interpret")
        else:
            S, o = ssd.ssd_decode_reference(S, x, dt, A, B, C, active, ml)
        o = o + lw["D"].astype(jnp.float32)[:, None] * x
    return _gated_out(lw, o, z, c)[:, None], (S, tail)


def _attention(lw, u, pool, al, ctx, c: NemotronHConfig, kernel: str):
    """Grouped-query attention over the paged rows of attention layer
    ``al``, no rotation: ``u`` [S, T, D]; the T new rows go to pool cells
    (``blk``, ``off``) first."""
    dt = c.dtype
    S, T, _ = u.shape
    KV = c.num_key_value_heads
    k_pool, v_pool = pool
    q = _mm("std,hdk->sthk", u, lw["w_q"], dt)
    kv = _mm("std,hkd->sthk", u, lw["w_kv"], dt)
    with jax.named_scope("kv_pool_write"):
        k_pool = k_pool.at[al, ctx["blk"], ctx["off"]].set(
            kv[:, :, :KV].reshape(S, T, -1))
        v_pool = v_pool.at[al, ctx["blk"], ctx["off"]].set(
            kv[:, :, KV:].reshape(S, T, -1))
    with jax.named_scope("attn_full"):
        o = _paged_attend(q, k_pool, v_pool, ctx["tables"], ctx["lengths"],
                          al, scale=c.head_dim ** -0.5, kernel=kernel,
                          queries=ctx.get("suffix_len"))
    return _mm("ste,ed->std", o.reshape(S, T, -1), lw["w_o"], dt), (k_pool, v_pool)


def relu2_ffn(fp, x, dtype):
    """``W_down relu(W_up x)^2``; ``fp`` holds ``w_up`` [D, F] and ``w_down``
    [F, D]: the family's feed-forward, two matrices and no gate."""
    h = jnp.einsum("...d,df->...f", x, fp["w_up"],
                   preferred_element_type=jnp.float32)
    return _mm("...f,fd->...d", jnp.square(jax.nn.relu(h)).astype(dtype),
               fp["w_down"], dtype)


def expert_layer(lp, x, valid, c: NemotronHConfig):
    """``moe.expert_layer`` under this family's names: two-matrix ``relu2``
    experts, the shared one too."""
    return moe.expert_layer(
        lp, x, valid, topk=c.num_experts_per_tok,
        scale=c.routed_scaling_factor, score="sigmoid",
        renormalise=c.norm_topk_prob, held=c.held,
        n_routed=c.n_routed_experts, form="relu2", w_in="w_up",
        shared=lambda fp, rows: relu2_ffn(fp, rows, c.dtype))


@functools.lru_cache(maxsize=None)
def _layer_fn(c: NemotronHConfig, kind: str, prefill: bool, kernel: str):
    """One KIND of layer as a jit of its own, built once a (config, kind,
    mode, kernel): a program that calls it once a layer of that kind traces
    and lowers it once whatever the depth, and XLA inlines the calls.
    ``mem`` is the memory the kind keeps (the slot state, the pool, or
    nothing), ``i`` the layer's index among its kind as a VALUE (the same
    avals every call), ``ctx`` the arrays the mode needs. Returns (x, mem,
    pick counts or None)."""
    eps = c.layer_norm_epsilon

    @jax.jit
    def layer(x, mem, i, lw, ctx):
        u = rms_norm(x, lw["norm"], eps)
        counts = None
        if kind == MIXER:
            f, mem = (_mixer_prefill(lw, u, mem, i, ctx, c) if prefill
                      else _mixer_decode(lw, u, mem, i, ctx, c, kernel))
        elif kind == ATTENTION:
            f, mem = _attention(lw, u, mem, i, ctx, c, kernel)
        else:
            f, counts = expert_layer(lw, u, ctx["valid"], c)
        return (x + f).astype(c.dtype), mem, counts

    return layer


def _forward(params, tokens, pool, state, c: NemotronHConfig, prefill: bool,
             kernel: str, ctx, last_row=None):
    """Embedding, the layers by the pattern (one jitted call each), final
    norm, head. ``last_row``: hand the head that one position alone. Returns
    (logits float32, pool, state, the expert layers' pick counts summed)."""
    x = jnp.take(params["tok_embed"], tokens, axis=0).astype(c.dtype)
    mem = {MIXER: tuple(state), ATTENTION: tuple(pool), EXPERTS: None}
    counts = jnp.zeros((moe.PICK_COUNTS,), jnp.int32)
    for l, (kind, lw) in enumerate(zip(c.hybrid_override_pattern,
                                       params["layers"])):
        x, mem[kind], cnt = _layer_fn(c, kind, prefill, kernel)(
            x, mem[kind], jnp.int32(c.kind_index(l)), lw, ctx)
        if cnt is not None:
            counts = counts + cnt
    if last_row is not None:
        x = lax.dynamic_slice_in_dim(x, last_row, 1, axis=1)
    x = rms_norm(x, params["norm_f"], c.layer_norm_epsilon)
    logits = jnp.einsum("std,dv->stv", x, params["lm_head"],
                        preferred_element_type=jnp.float32)
    return logits, mem[ATTENTION], mem[MIXER], counts


def forward_prefill_paged(params, tokens, pool, state, table, start_pos,
                          suffix_len, slot, config: NemotronHConfig,
                          block_tokens: int, kernel: str = "gather"):
    """The family's ``prefill``: ``tokens`` [1, P] (a bucket) from the
    sequence's start (``start_pos`` is 0: no prefix hit is ever served to
    this family), the first ``suffix_len`` real. Writes the attention
    layers' rows through ``table`` (pad rows to trash block 0) and slot
    ``slot``'s mixer states from zero; pad tokens route to no expert. The
    head sees ONE row, the last real position: logits ``[1, 1, V]``."""
    _, valid, blk, off = prefill_cells(
        table, start_pos, suffix_len, tokens.shape[1], block_tokens)
    ctx = {"slot": jnp.asarray(slot, jnp.int32),
           "suffix_len": jnp.asarray(suffix_len, jnp.int32),
           "valid": valid[None], "blk": blk[None], "off": off[None],
           "tables": table[None],
           "lengths": jnp.reshape(start_pos, (1,)).astype(jnp.int32)}
    logits, pool, state, counts = _forward(
        params, tokens, pool, state, config, True, kernel, ctx,
        last_row=suffix_len - 1)
    return logits, pool, state, expert_aux(counts)


def forward_decode_paged(params, tokens, pool, state, tables, lengths,
                         config: NemotronHConfig, block_tokens: int,
                         kernel: str = "gather",
                         active: Optional[jax.Array] = None):
    """The family's ``decode``: ``tokens`` [S, 1], slot s's token at position
    ``lengths[s]``. Active slots' states advance by the token; a parked
    slot's stay bit for bit, its K/V write lands in trash block 0 and it
    routes to no expert."""
    S, T = tokens.shape
    if T != 1:
        raise ValueError("a recurrent state advances one token a step: "
                         f"got {T} (speculative verify is not supported)")
    _, blk, off = decode_cells(tables, lengths, T, block_tokens)
    if active is None:
        active = jnp.ones((S,), bool)
    ctx = {"active": active, "valid": active[:, None], "blk": blk,
           "off": off, "tables": tables, "lengths": lengths}
    logits, pool, state, counts = _forward(
        params, tokens, pool, state, config, False, kernel, ctx)
    return logits, pool, state, expert_aux(counts)


def describe(config: NemotronHConfig) -> Dict[str, int]:
    """What the stack is made of, for ``engine.describe()``: layer counts by
    kind, read off the pattern."""
    c = config
    return {"mixer_layers": c.mixer_layers, "expert_layers": c.expert_layers,
            "attention_layers": c.attention_layers, "held": c.held[1],
            "kv_heads": c.num_key_value_heads,
            "state_bytes_per_slot": c.state_bytes_per_slot}


PAGED_FAMILY = PagedFamily(
    # The pool is the ATTENTION layers' alone (``config.n_layers``) and the
    # slot state the MIXER layers' alone; an expert layer keeps neither.
    init_pool=init_block_pool,
    prefill=forward_prefill_paged,
    decode=forward_decode_paged,
    logits_dim=lambda params, config: params["lm_head"].shape[-1],
    init_slot_state=init_slot_state,
    # As the other families with a state a slot: a hit at position p would
    # need every mixer layer's state at p (ROADMAP R4).
    unsupported=("prefix_cache",),
    aux_counts=EXPERT_AUX_COUNTS,
    describe=describe,
)
