"""Kimi-K2's language model (``model_type: kimi_k2``, the DeepSeek-V3 layer)
on the paged serve path.

The third latent-attention stack of the zoo and the first whose layers are
not all alike, as one chip of an expert-parallel serving deployment holds it
(keys as in huggingface.co/moonshotai/Kimi-K2.5 ``config.json``)::

    h  = x + MLA(RMSNorm(x))
    x' = h + F_l(RMSNorm(h))

    F_l, l <  first_k_dense_replace:  a dense gated FFN (intermediate_size)
    F_l, l >= first_k_dense_replace:  sum_{i in P} w_i E_i(u) + E_shared(u)

The router scores ``n_routed_experts`` outputs with a SIGMOID, picks the
``num_experts_per_tok`` largest of ``s + e_score_correction_bias`` (with
``n_group = topk_group = 1`` the group limit is the identity) and weighs the
picks with the unbiased ``s`` renormalised over the picks and scaled by
``routed_scaling_factor``. ``held = (first, count)`` says which routed
experts' weights live here: the layer routes over all of them, normalises
over ALL of a token's picks (most lie on absent chips) and adds only what its
own experts give (``ops/moe.py:held_experts_ffn``). The shared expert is
whole on every chip and is computed for every live token; where the shares
of a deployment are summed it counts once.

Attention is the shared latent sublayer (``ops/mla.py``): no
``mla_scale_*`` factors, YaRN's blended rotary frequencies, a softmax scale
times ``mscale ** 2``, and the two absorbed projections stored heads-major,
as their products read them. The pool is ``ops/mla.py``'s: one ``[c_kv |
k_rope]`` row a token a layer, padded to 640 lanes.

Weights are created and stored in ``param_dtype`` (bfloat16), one array a
matrix and no stacking over layers; the serve programs read them as stored
(no ``working_params``).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.generate import (EXPERT_AUX_COUNTS, PagedFamily,
                                     decode_cells, expert_aux, prefill_cells)
from ray_tpu.ops import moe
from ray_tpu.ops.layers import gated_ffn, rms_norm, yarn_mscale
from ray_tpu.ops.mla import LatentSpec, init_latent_pool, latent_attention
from ray_tpu.ops.paged_attention import latent_group_blocks

# Kimi-K2.5's published ``rope_scaling`` (type yarn), as sorted pairs so that
# the config stays hashable; ``KimiK2Config`` takes a mapping too.
_YARN = (("beta_fast", 32.0), ("beta_slow", 1.0), ("factor", 64.0),
         ("mscale", 1.0), ("mscale_all_dim", 1.0),
         ("original_max_position_embeddings", 4096.0))


@dataclass(frozen=True)
class KimiK2Config:
    """Field names are the published ``config.json`` keys; ``held``,
    ``max_seq_len`` and the two dtypes are this program's."""
    vocab_size: int = 163840
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 1
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_rope_head_dim: int = 64
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    n_routed_experts: int = 384
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.827
    rms_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    rope_scaling: Tuple[Tuple[str, float], ...] = _YARN
    max_seq_len: int = 262144
    # Routed experts whose weights live on this chip: (first, count).
    held: Tuple[int, int] = (0, 384)
    dtype: Any = jnp.bfloat16          # activation/compute dtype
    param_dtype: Any = jnp.bfloat16    # storage dtype

    def __post_init__(self):
        # A configuration's file gives a mapping and a list: kept hashable.
        if isinstance(self.rope_scaling, dict):
            object.__setattr__(self, "rope_scaling", tuple(sorted(
                (k, float(v)) for k, v in self.rope_scaling.items()
                if k != "type")))
        object.__setattr__(self, "held", tuple(self.held))

    @property
    def latent_width(self) -> int:
        """Numbers a token a layer the cache must hold."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def pool_width(self) -> int:
        """The row as the pool stores it: whole 128-lane tiles."""
        return -(-self.latent_width // 128) * 128

    @property
    def attn_sublayers(self) -> int:
        return self.num_hidden_layers

    @property
    def expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def mscale(self) -> float:
        """YaRN's temperature over all dimensions; squared in the scores."""
        y = dict(self.rope_scaling)
        return yarn_mscale(y["factor"], y["mscale_all_dim"])

    def latent_spec(self) -> LatentSpec:
        """This family's latent attention (``ops/mla.py``). The rotary's cos
        and sin carry ``yarn_mscale(factor, mscale) / yarn_mscale(factor,
        mscale_all_dim)``, which is 1 for the published values and is not
        multiplied in."""
        return LatentSpec(
            nope=self.qk_nope_head_dim, rope=self.qk_rope_head_dim,
            rank=self.kv_lora_rank, pool_width=self.pool_width,
            eps=self.rms_norm_eps, dtype=self.dtype,
            softmax_scale=(self.qk_nope_head_dim + self.qk_rope_head_dim)
            ** -0.5 * self.mscale ** 2,
            rope_theta=self.rope_theta, rope_scaling=dict(self.rope_scaling),
            heads_major=True)

    def replace(self, **kw) -> "KimiK2Config":
        return replace(self, **kw)

    def paged_family(self) -> PagedFamily:
        return PAGED_FAMILY


def kimi_k2_share(*, num_hidden_layers: int = 7, held: Tuple[int, int] = (0, 12),
                  vocab_size: int = 20480, max_seq_len: int = 3072,
                  **kw) -> KimiK2Config:
    """Kimi-K2.5's language model at its published widths, cut to one chip of
    a deployment that shares each layer 32 ways: the dense first layer and
    six expert layers (the other 54 lie on further pipeline stages), 12 of
    384 experts held, an eighth of the vocabulary
    (``benchmark/configs/kimi-k2.5.json``)."""
    return KimiK2Config(num_hidden_layers=num_hidden_layers, held=held,
                        vocab_size=vocab_size, max_seq_len=max_seq_len, **kw)


def tiny(**kw) -> KimiK2Config:
    """Test-sized: one dense and two expert layers, width 64, 4 heads
    (16 + 8 | 16), 32 routed experts of which 4 held, top-4, one shared
    expert, YaRN over 16 original positions, float32."""
    defaults = dict(
        vocab_size=256, hidden_size=64, intermediate_size=160,
        moe_intermediate_size=32, num_hidden_layers=3,
        num_attention_heads=4, kv_lora_rank=32, q_lora_rank=48,
        qk_rope_head_dim=8, qk_nope_head_dim=16, v_head_dim=16,
        n_routed_experts=32, num_experts_per_tok=4, max_seq_len=64,
        held=(0, 4), dtype=jnp.float32, param_dtype=jnp.float32,
        rope_theta=100.0,
        rope_scaling=dict(_YARN, original_max_position_embeddings=16.0,
                          factor=8.0, beta_fast=2.0, beta_slow=0.5))
    defaults.update(kw)
    return KimiK2Config(**defaults)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_params(config: KimiK2Config, key: jax.Array) -> Dict:
    """Seeded weights, made in ``param_dtype``: every matrix normal with
    standard deviation ``1/sqrt(fan_in)``, so that each projection keeps its
    input's scale. ``W_qb`` counts ``mscale ** 4`` into its fan-in: the
    published softmax scale carries ``mscale ** 2`` (2.0), which a trained
    model has learned to live with and random weights have not, and with it
    counted attention's scores come out at a standard deviation near one
    (at ~7 LongCat's softmax was one-hot and its logits turned on bfloat16
    rounding, PR 27). A routed expert's ``w_down`` counts
    ``routed_scaling_factor ** 2`` into its fan-in for the same reason: a
    token's eight picks weigh 2.827 / 8 = 0.35 each, and where a token's
    eighth and ninth scores lie within bfloat16's rounding of the router's
    input a pick changes hands between this program and a float32 reference;
    at 0.35 of a unit-scale expert that one pick moved the token's logits by
    up to 1.03 (PR 33, on the chip), as far as int8 products move them. With
    the factor counted the picks' weights sum to one, the routed part is a
    weighted mean of the picked experts, and the same event moves a logit by
    0.15. Norms at one. ``e_score_correction_bias`` is a seeded
    NON-zero float32 buffer of standard deviation 0.02, a tenth of the
    spread of a sigmoid score: the biased top-k differs from the unbiased
    one at the margin and no expert's popularity turns on its bias.
    ``W_kb`` [H, nope, rank] and ``W_vb`` [H, rank, v] are the two halves of
    the published ``W_kvb`` laid heads-major, as the absorbed products read
    them."""
    c = config
    dt = c.param_dtype
    D, H, R = c.hidden_size, c.num_attention_heads, c.kv_lora_rank
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
                         c.qk_nope_head_dim + c.qk_rope_head_dim),
                        c.q_lora_rank * c.mscale ** 4),
            "w_kva": nrm((D, c.latent_width), D),
            "kv_norm": jnp.ones((R,), dt),
            "w_kb": nrm((H, c.qk_nope_head_dim, R), R),
            "w_vb": nrm((H, R, c.v_head_dim), R),
            "w_o": nrm((H, c.v_head_dim, D), H * c.v_head_dim),
        }

    def ffn(width):
        return {"w_gate": nrm((D, width), D), "w_up": nrm((D, width), D),
                "w_down": nrm((width, D), width)}

    def layer(l):
        lp = {"attn": mla(), "norm_attn": jnp.ones((D,), dt),
              "norm_ffn": jnp.ones((D,), dt)}
        if l < c.first_k_dense_replace:
            lp["ffn"] = ffn(c.intermediate_size)
            return lp
        F = c.moe_intermediate_size
        kb = jax.random.fold_in(key, next(counter))
        lp.update(
            router=nrm((D, c.n_routed_experts), D),
            router_bias=jax.random.normal(
                kb, (c.n_routed_experts,), jnp.float32) * 0.02,
            experts={"w_gate_up": nrm((n_held, D, 2 * F), D),
                     "w_down": nrm((n_held, F, D),
                                   F * c.routed_scaling_factor ** 2)},
            shared=ffn(c.n_shared_experts * F))
        return lp

    return {
        "tok_embed": nrm((c.vocab_size, D), 1),
        "layers": [layer(l) for l in range(c.num_hidden_layers)],
        "norm_f": jnp.ones((D,), dt),
        "lm_head": nrm((D, c.vocab_size), D),
    }


def describe(config: KimiK2Config) -> Dict[str, int]:
    """What the stack is made of, by count."""
    c = config
    return {"expert_layers": c.expert_layers,
            "dense_layers": c.first_k_dense_replace,
            "shared_expert_params": (3 * c.hidden_size * c.n_shared_experts
                                     * c.moe_intermediate_size)}


# ---------------------------------------------------------------------------
# Forward over the paged latent pool
# ---------------------------------------------------------------------------

def expert_layer(lp, x, valid, c: KimiK2Config):
    """``moe.expert_layer`` under this family's names: sigmoid scores
    renormalised over the picks, a gated shared expert."""
    return moe.expert_layer(
        lp, x, valid, topk=c.num_experts_per_tok,
        scale=c.routed_scaling_factor, score="sigmoid", renormalise=True,
        held=c.held, n_routed=c.n_routed_experts,
        shared=lambda fp, rows: gated_ffn(fp, rows, c.dtype))


def _forward(params, tokens, pool, tables, lengths, positions, blk, off,
             valid, c: KimiK2Config, kernel: str, last_row=None,
             queries=None):
    """tokens [S, T] at absolute ``positions`` [S, T]; rows go to pool cells
    (``blk``, ``off``); ``valid`` [S, T] marks the tokens whose output is
    read. ``last_row``: hand the head that one position alone. ``queries``:
    a prefill's count of real rows, the attention kernel's. Returns
    (logits float32, pool, the expert layers' pick counts summed over
    layers)."""
    dt = c.dtype
    eps = c.rms_norm_eps
    spec = c.latent_spec()
    x = jnp.take(params["tok_embed"], tokens, axis=0).astype(dt)
    counts = jnp.zeros((moe.PICK_COUNTS,), jnp.int32)
    for l, lp in enumerate(params["layers"]):
        o, pool = latent_attention(
            lp["attn"], rms_norm(x, lp["norm_attn"], eps), pool, l, blk, off,
            tables, lengths, positions, spec, kernel, queries=queries)
        h = x + o
        u = rms_norm(h, lp["norm_ffn"], eps)
        if "ffn" in lp:                  # l < first_k_dense_replace
            with jax.named_scope("dense_ffn"):
                f = gated_ffn(lp["ffn"], u, dt)
        else:
            f, cnt = expert_layer(lp, u, valid, c)
            counts = counts + cnt
        x = h + f
    if last_row is not None:
        x = jax.lax.dynamic_slice_in_dim(x, last_row, 1, axis=1)
    x = rms_norm(x, params["norm_f"], eps)
    logits = jnp.einsum("std,dv->stv", x, params["lm_head"],
                        preferred_element_type=jnp.float32)
    return logits, pool, counts


def forward_prefill_paged(params, tokens, pool, state, table, start_pos,
                          suffix_len, slot, config: KimiK2Config,
                          block_tokens: int, kernel: str = "gather"):
    """The family's ``prefill``: ``tokens`` [1, P] (a suffix bucket) at
    positions [start_pos, start_pos + P) through ``table`` [NB]; positions
    below ``start_pos`` are a prefix hit, read back from the pool. Pad writes
    go to trash block 0, pad tokens route to no expert. The head sees ONE
    row, the last real position (logits [1, 1, V]): at the 3,072 bucket the
    whole bucket's float32 logits would be a quarter of a gigabyte that no
    one reads. The family keeps no slot state (``state`` is the empty tuple,
    handed back)."""
    (pool,) = pool
    positions, valid, blk, off = prefill_cells(
        table, start_pos, suffix_len, tokens.shape[1], block_tokens)
    lengths1 = jnp.reshape(start_pos, (1,)).astype(jnp.int32)
    logits, pool, counts = _forward(
        params, tokens, pool, table[None], lengths1, positions[None],
        blk[None], off[None], valid[None], config, kernel,
        last_row=suffix_len - 1, queries=suffix_len)
    return logits, (pool,), state, expert_aux(counts)


def forward_decode_paged(params, tokens, pool, state, tables, lengths,
                         config: KimiK2Config, block_tokens: int,
                         kernel: str = "gather",
                         active: Optional[jax.Array] = None):
    """The family's ``decode``: ``tokens`` [S, T], slot s's token t at
    position ``lengths[s] + t`` (``generate.decode_cells``); slots not
    ``active`` route to no expert, so an idle slot's garbage reads no routed
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
    describe=describe,
    walk_group_blocks=lambda c, pool: latent_group_blocks(
        pool[0], c.num_attention_heads),
)
