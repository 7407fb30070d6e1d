"""LongCat-Flash's language model on the paged serve path.

The second model family of the zoo (after ``transformer.py``'s GPT-2), as one
chip of an expert-parallel serving deployment holds it. A block has TWO
latent-attention (MLA) sublayers and two dense gated FFNs, with a top-k
expert layer on a shortcut around the second half (LongCat-Flash technical
report, arXiv:2509.01322; keys as in the model's ``config.json``)::

    a = x + MLA_0(RMSNorm(x))
    m = MoE(RMSNorm'(a))            # the shortcut: taken here, added at the end
    b = a + FFN_0(RMSNorm'(a))      # the same normed input as the expert layer
    c = b + MLA_1(RMSNorm(b))
    d = c + FFN_1(RMSNorm(c))
    x_next = d + m

The router scores ``n_routed_experts + zero_expert_num`` outputs; a pick past
the routed experts is a zero-compute (identity) expert. ``held = (first,
count)`` says which routed experts' weights live here: the layer routes over
all of them and adds only what its own experts (and the zero-compute ones)
give (``ops/moe.py:held_experts_ffn``).

The paged cache holds ONE row a token an attention sublayer: ``[c_kv after
norm and scale | k_rope after rotary]``, ``kv_lora_rank + qk_rope_head_dim``
numbers padded to a multiple of 128 lanes, shared by all heads. Decode and
prefill absorb ``W_kvb`` into q and into the output, so attention runs on the
rows as they lie in the pool (``ops/paged_attention.py:
latent_paged_attention``). The serve programs, buckets, donation and sampling
are ``generate.PagedGenerator``'s; this file supplies the family seam
(``PAGED_FAMILY``): what the pool is, the paged prefill and decode forward.

Weights are created and stored in ``param_dtype`` (bfloat16), one array a
matrix and no stacking over layers, so no program slices a stacked slab.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.generate import (EXPERT_AUX_COUNTS, PagedFamily,
                                     decode_cells, expert_aux, prefill_cells)
from ray_tpu.ops import moe
from ray_tpu.ops.layers import gated_ffn as _ffn, rms_norm
from ray_tpu.ops.mla import LatentSpec, init_latent_pool, latent_attention
from ray_tpu.ops.paged_attention import latent_group_blocks


@dataclass(frozen=True)
class LongCatConfig:
    """Field names are the published ``config.json`` keys; ``held``,
    ``max_seq_len`` and the two dtypes are this program's."""
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_rope_head_dim: int = 64
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    max_seq_len: int = 131072
    # Routed experts whose weights live on this chip: (first, count).
    held: Tuple[int, int] = (0, 512)
    dtype: Any = jnp.bfloat16          # activation/compute dtype
    param_dtype: Any = jnp.bfloat16    # storage dtype

    @property
    def latent_width(self) -> int:
        """Numbers a token a sublayer the cache must hold."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def pool_width(self) -> int:
        """The row as the pool stores it: padded to whole 128-lane tiles
        (which is how a 576-wide minor dimension lies in HBM anyway)."""
        return -(-self.latent_width // 128) * 128

    @property
    def attn_sublayers(self) -> int:
        return 2 * self.num_layers

    def latent_spec(self) -> LatentSpec:
        """This family's latent attention (``ops/mla.py``): both latents
        scaled up (``mla_scale_q_lora``, ``mla_scale_kv_lora``), plain
        rotary, scores over the root of a query head's width."""
        D = self.hidden_size
        return LatentSpec(
            nope=self.qk_nope_head_dim, rope=self.qk_rope_head_dim,
            rank=self.kv_lora_rank, pool_width=self.pool_width,
            eps=self.rms_norm_eps, dtype=self.dtype,
            softmax_scale=(self.qk_nope_head_dim
                           + self.qk_rope_head_dim) ** -0.5,
            rope_theta=self.rope_theta,
            q_scale=(D / self.q_lora_rank) ** 0.5,
            kv_scale=(D / self.kv_lora_rank) ** 0.5)

    def replace(self, **kw) -> "LongCatConfig":
        return replace(self, **kw)

    def paged_family(self) -> PagedFamily:
        return PAGED_FAMILY


def longcat_flash_share(*, num_layers: int = 4, held: Tuple[int, int] = (0, 16),
                        vocab_size: int = 16384, max_seq_len: int = 1024,
                        **kw) -> LongCatConfig:
    """LongCat-Flash(-Omni)'s language model at its published widths, cut to
    one chip of a deployment that shares each layer 32 ways: four layers (the
    rest lie on further pipeline stages), 16 of 512 experts held, an eighth
    of the vocabulary (``benchmark/configs/longcat-flash-omni.json``)."""
    return LongCatConfig(num_layers=num_layers, held=held,
                         vocab_size=vocab_size, max_seq_len=max_seq_len, **kw)


def tiny(**kw) -> LongCatConfig:
    """Test-sized: 2 layers, width 64, 4 heads (16 + 8 | 16), 16 routed + 8
    zero-compute experts of which 4 held, top-3, float32."""
    defaults = dict(
        vocab_size=256, hidden_size=64, ffn_hidden_size=128,
        expert_ffn_hidden_size=32, num_layers=2, num_attention_heads=4,
        kv_lora_rank=32, q_lora_rank=48, qk_rope_head_dim=8,
        qk_nope_head_dim=16, v_head_dim=16, n_routed_experts=16,
        zero_expert_num=8, moe_topk=3, max_seq_len=64, held=(0, 4),
        dtype=jnp.float32, param_dtype=jnp.float32)
    defaults.update(kw)
    return LongCatConfig(**defaults)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_params(config: LongCatConfig, key: jax.Array) -> Dict:
    """Seeded weights, made in ``param_dtype``: every matrix normal with
    standard deviation ``1/sqrt(fan_in)``, so that each projection keeps its
    input's scale; the three that follow a latent the model scales up
    (``W_qb`` after ``mla_scale_q_lora``, ``W_kb`` and ``W_vb`` after
    ``mla_scale_kv_lora``) count the scale into their fan-in, so that q, k
    and v come out at unit scale and attention's scores at a standard
    deviation near one, as a trained model's are: with them at ~7 the
    softmax is one-hot and the logits turn on bf16 rounding. Norms at one;
    the router's selection bias a seeded NON-zero float32 buffer, a tenth of
    a mean router probability: enough that the biased top-k differs from the
    unbiased one at the margin, small enough that no expert's popularity
    turns on its bias (a trained bias balances load; at the scale of a
    whole probability it made the held experts' load swing 0.9-1.7 tokens
    a step from seed to seed)."""
    c = config
    dt = c.param_dtype
    D, H = c.hidden_size, c.num_attention_heads
    n_router = c.n_routed_experts + c.zero_expert_num
    n_held = c.held[1]
    counter = iter(range(1 << 30))

    def nrm(shape, fan_in):
        k = jax.random.fold_in(key, next(counter))
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(dt)

    def mla():
        return {
            "w_qa": nrm((D, c.q_lora_rank), D),
            "q_norm": jnp.ones((c.q_lora_rank,), dt),
            "w_qb": nrm((c.q_lora_rank, H,
                         c.qk_nope_head_dim + c.qk_rope_head_dim), D),
            "w_kva": nrm((D, c.latent_width), D),
            "kv_norm": jnp.ones((c.kv_lora_rank,), dt),
            "w_kb": nrm((c.kv_lora_rank, H, c.qk_nope_head_dim), D),
            "w_vb": nrm((c.kv_lora_rank, H, c.v_head_dim), D),
            "w_o": nrm((H, c.v_head_dim, D), H * c.v_head_dim),
        }

    def ffn(width):
        return {"w_gate": nrm((D, width), D), "w_up": nrm((D, width), D),
                "w_down": nrm((width, D), width)}

    def layer():
        F = c.expert_ffn_hidden_size
        kb = jax.random.fold_in(key, next(counter))
        return {
            "attn": [mla(), mla()],
            "ffn": [ffn(c.ffn_hidden_size), ffn(c.ffn_hidden_size)],
            "norm_attn": [jnp.ones((D,), dt), jnp.ones((D,), dt)],
            "norm_ffn": [jnp.ones((D,), dt), jnp.ones((D,), dt)],
            "router": nrm((D, n_router), D),
            "router_bias": (jax.random.normal(kb, (n_router,), jnp.float32)
                            * (0.1 / n_router)),
            "experts": {"w_gate_up": nrm((n_held, D, 2 * F), D),
                        "w_down": nrm((n_held, F, D), F)},
        }

    return {
        "tok_embed": nrm((c.vocab_size, D), 1),
        "layers": [layer() for _ in range(c.num_layers)],
        "norm_f": jnp.ones((D,), dt),
        "lm_head": nrm((D, c.vocab_size), D),
    }


# ---------------------------------------------------------------------------
# Forward over the paged latent pool
# ---------------------------------------------------------------------------

def _mla(ap, x, pool, sub: int, blk, off, tables, lengths, positions,
         c: LongCatConfig, kernel: str, queries=None):
    """The shared latent sublayer (``ops/mla.py``) with this family's spec."""
    return latent_attention(ap, x, pool, sub, blk, off, tables, lengths,
                            positions, c.latent_spec(), kernel,
                            queries=queries)


def expert_layer(lp, x, valid, c: LongCatConfig):
    """``moe.expert_layer`` under this family's names: a softmax over the
    routed AND the zero-compute outputs, no renormalising, no shared
    expert."""
    return moe.expert_layer(
        lp, x, valid, topk=c.moe_topk, scale=c.routed_scaling_factor,
        score="softmax", renormalise=False, held=c.held,
        n_routed=c.n_routed_experts)


def _forward(params, tokens, pool, tables, lengths, positions, blk, off,
             valid, c: LongCatConfig, kernel: str, queries=None):
    """tokens [S, T] at absolute ``positions`` [S, T]; rows go to pool cells
    (``blk``, ``off``); ``valid`` [S, T] marks the tokens whose output is
    read; ``queries``: a prefill's count of real rows, the attention
    kernel's. Returns (logits [S, T, V] float32, pool, the expert layers'
    pick counts summed over layers)."""
    dt = c.dtype
    eps = c.rms_norm_eps
    x = jnp.take(params["tok_embed"], tokens, axis=0).astype(dt)
    counts = jnp.zeros((moe.PICK_COUNTS,), jnp.int32)
    for l, lp in enumerate(params["layers"]):
        o, pool = _mla(lp["attn"][0], rms_norm(x, lp["norm_attn"][0], eps),
                       pool, 2 * l, blk, off, tables, lengths, positions, c,
                       kernel, queries)
        a = x + o
        hn = rms_norm(a, lp["norm_ffn"][0], eps)
        m, cnt = expert_layer(lp, hn, valid, c)
        counts = counts + cnt
        b = a + _ffn(lp["ffn"][0], hn, dt)
        o, pool = _mla(lp["attn"][1], rms_norm(b, lp["norm_attn"][1], eps),
                       pool, 2 * l + 1, blk, off, tables, lengths, positions,
                       c, kernel, queries)
        cc = b + o
        d = cc + _ffn(lp["ffn"][1], rms_norm(cc, lp["norm_ffn"][1], eps), dt)
        x = d + m
    x = rms_norm(x, params["norm_f"], eps)
    logits = jnp.einsum("std,dv->stv", x, params["lm_head"],
                        preferred_element_type=jnp.float32)
    return logits, pool, counts


def forward_prefill_paged(params, tokens, pool, state, table, start_pos,
                          suffix_len, slot, config: LongCatConfig,
                          block_tokens: int, kernel: str = "gather"):
    """The family's ``prefill``: ``tokens`` [1, P] (a suffix bucket) at
    positions [start_pos, start_pos + P) through ``table`` [NB]; positions
    below ``start_pos`` are a prefix hit, read back from the pool. Pad writes
    go to trash block 0, pad tokens route to no expert. Same contract as
    ``generate._forward_prefill_paged``, plus the pick counts; the family
    keeps no slot state (``state`` is the empty tuple, handed back)."""
    (pool,) = pool
    positions, valid, blk, off = prefill_cells(
        table, start_pos, suffix_len, tokens.shape[1], block_tokens)
    lengths1 = jnp.reshape(start_pos, (1,)).astype(jnp.int32)
    logits, pool, counts = _forward(
        params, tokens, pool, table[None], lengths1, positions[None],
        blk[None], off[None], valid[None], config, kernel,
        queries=suffix_len)
    return logits, (pool,), state, expert_aux(counts)


def forward_decode_paged(params, tokens, pool, state, tables, lengths,
                         config: LongCatConfig, block_tokens: int,
                         kernel: str = "gather",
                         active: Optional[jax.Array] = None):
    """The family's ``decode``: ``tokens`` [S, T], slot s's token t at
    position ``lengths[s] + t`` (``generate.decode_cells``); slots not
    ``active`` route to no expert, so an idle slot's garbage reads no
    expert's weights and counts no pick."""
    (pool,) = pool
    S, T = tokens.shape
    positions, blk, off = decode_cells(tables, lengths, T, block_tokens)
    valid = jnp.ones((S, T), bool) if active is None else jnp.broadcast_to(
        active[:, None], (S, T))
    logits, pool, counts = _forward(
        params, tokens, pool, tables, lengths, positions, blk, off, valid,
        config, kernel)
    return logits, (pool,), state, expert_aux(counts)


PAGED_FAMILY = PagedFamily(
    init_pool=lambda c, num_blocks, block_tokens: (init_latent_pool(
        c.latent_spec(), c.attn_sublayers, num_blocks, block_tokens),),
    prefill=forward_prefill_paged,
    decode=forward_decode_paged,
    logits_dim=lambda params, config: params["lm_head"].shape[-1],
    aux_counts=EXPERT_AUX_COUNTS,
    walk_group_blocks=lambda c, pool: latent_group_blocks(
        pool[0], c.num_attention_heads),
)
