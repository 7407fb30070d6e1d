"""GLM-5's language model (``model_type: glm_moe_dsa``) on the paged serve
path: latent attention behind a LEARNED SPARSE SELECTION.

The fourth latent-attention stack of the zoo and the first whose attention
does not read a prefix (or a window) of a slot's rows: in every layer a small
INDEXER scores each cached token for each query and attention reads the
``index_topk`` highest-scored alone (DeepSeek Sparse Attention; keys as in
huggingface.co/zai-org/GLM-5 ``config.json``)::

    a  = RMSNorm(x)
    c_q = RMSNorm(a W_qa)                          the query latent, q_lora_rank
    q^I = c_q W^I_qb        index_n_heads x index_head_dim, rotary on the first
    k^I = LayerNorm(a W^I_k)                qk_rope_head_dim of both, one k^I
    w   = a W^I_w * index_n_heads^-0.5 * index_head_dim^-0.5   for all heads
    I_ij = sum_h w_ih ReLU(q^I_ih . k^I_j),  j <= i
    S_i  = the min(index_topk, i + 1) largest I_ij, ties to the lower position
    h  = x + MLA(a) with the softmax over j in S_i
    x' = h + F_l(RMSNorm(h))      F_l as Kimi-K2's: a dense gated FFN below
                                  first_k_dense_replace, else sigmoid top-k
                                  routed experts (held share) + a shared one

The pool is TWO arrays, both ``[layers, num_blocks, block_tokens, .]`` and
both written in the same step at the same block and offset: the latent rows
``[c_kv | k_rope]`` padded to whole lane tiles (LongCat's and Kimi-K2's
row) and the indexer's keys ``k^I`` after their rotary. The generator's
block copy copies both (blocks are dimension 1 of every pool array); the
block manager and the engine know block ids alone.

Attention is the shared latent sublayer (``ops/mla.py``) with the selection
handed in as keep bits (``ops/sparse_select.py``): no YaRN (``rope_type``
default), softmax scale ``(qk_nope + qk_rope)^-0.5``. The expert layer is
Kimi-K2's in form (sigmoid scores renormalised over the picks, a gated shared
expert), bound to ``ops/moe.py:expert_layer`` under the same key names.

The family refuses the prefix cache: a hit at position p is a prefill that
starts at p, whose queries score CACHED index keys of blocks other slots
share. ``select`` gathers a slot's keys through its table by absolute
position, so the arithmetic is there; no test covers a start past zero, and
chunked prefill (ROADMAP R3, R10) has the same shape and brings both.

Weights are created and stored in ``param_dtype`` (bfloat16), one array a
matrix and no stacking over layers; the serve programs read them as stored.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.generate import (EXPERT_AUX_COUNTS, AuxCount,
                                     PagedFamily, decode_cells, expert_aux,
                                     prefill_cells)
from ray_tpu.ops import moe
from ray_tpu.ops.layers import gated_ffn, layer_norm, mm, rms_norm, rope
from ray_tpu.ops.mla import LatentSpec, init_latent_pool, latent_attention
from ray_tpu.ops.paged_attention import latent_group_blocks
from ray_tpu.ops.sparse_select import keep_bits

# What seeded weights must bring that a trained model has: attention logits
# WIDE enough that which tokens were chosen moves the output. At a standard
# deviation of one the softmax over 2,048 seeded keys is near flat, a wrong
# selection averages 2,048 other rows to nearly the same vector and no check
# sees it. The wider, the more the PROGRAM's own selection shows too: a
# 2,048th and 2,049th index score change hands between its bfloat16 and a
# float32 reference some ten times a row, now and then on a row that carries
# a head. Worst logit gap over 8,192 positions on the chip, program / int8
# control / selection ignored (PERF.md 6, PR 53): at 3.0 (ISSUE 53's value,
# 4,096 positions) 1.15 / 1.33 / 2.6, no limit between the first two; at 2.0
# 0.37 / 0.62 / 1.34; at 1.5 0.18 / 0.44 / 0.67.
ATTN_LOGIT_STD = 1.5
INDEX_NORM_EPS = 1e-6        # the indexer's LayerNorm (nn.LayerNorm's own is 1e-5;
                             # the published DSA code passes 1e-6)


@dataclass(frozen=True)
class GlmDsaConfig:
    """Field names are the published ``config.json`` keys; ``held``,
    ``max_seq_len`` and the two dtypes are this program's."""
    vocab_size: int = 154880
    hidden_size: int = 6144
    intermediate_size: int = 12288
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 78
    first_k_dense_replace: int = 3
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    q_lora_rank: int = 2048
    qk_rope_head_dim: int = 64
    qk_nope_head_dim: int = 192
    v_head_dim: int = 256
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    max_seq_len: int = 202752
    # Routed experts whose weights live on this chip: (first, count).
    held: Tuple[int, int] = (0, 256)
    dtype: Any = jnp.bfloat16          # activation/compute dtype
    param_dtype: Any = jnp.bfloat16    # storage dtype

    def __post_init__(self):
        object.__setattr__(self, "held", tuple(self.held))

    @property
    def latent_width(self) -> int:
        """Numbers a token a layer the latent pool must hold."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def pool_width(self) -> int:
        """The latent row as the pool stores it: whole 128-lane tiles."""
        return -(-self.latent_width // 128) * 128

    @property
    def attn_sublayers(self) -> int:
        return self.num_hidden_layers

    @property
    def expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    def latent_spec(self) -> LatentSpec:
        return LatentSpec(
            nope=self.qk_nope_head_dim, rope=self.qk_rope_head_dim,
            rank=self.kv_lora_rank, pool_width=self.pool_width,
            eps=self.rms_norm_eps, dtype=self.dtype,
            softmax_scale=(self.qk_nope_head_dim + self.qk_rope_head_dim)
            ** -0.5,
            rope_theta=self.rope_theta, heads_major=True)

    def replace(self, **kw) -> "GlmDsaConfig":
        return replace(self, **kw)

    def paged_family(self) -> PagedFamily:
        return PAGED_FAMILY


def glm_5_share(*, num_hidden_layers: int = 5, first_k_dense_replace: int = 1,
                held: Tuple[int, int] = (0, 8), vocab_size: int = 19456,
                max_seq_len: int = 8192, **kw) -> GlmDsaConfig:
    """GLM-5's language model at its published widths, cut to one chip of a
    deployment that shares each layer 32 ways: the leading dense layers once
    and four expert layers behind it (the other 71 lie on further pipeline
    stages), 8 of 256 experts held, an eighth of the vocabulary in whole lane
    tiles (``benchmark/configs/glm-5.json``)."""
    return GlmDsaConfig(
        num_hidden_layers=num_hidden_layers,
        first_k_dense_replace=first_k_dense_replace, held=held,
        vocab_size=vocab_size, max_seq_len=max_seq_len, **kw)


def tiny(**kw) -> GlmDsaConfig:
    """Test-sized: one dense and two expert layers, width 64, 4 heads
    (16 + 8 | 16), an indexer of 4 heads x 16 (8 of them rotated) that keeps
    12 tokens a query, 32 routed experts of which 4 held, top-4, one shared
    expert, float32."""
    defaults = dict(
        vocab_size=256, hidden_size=64, intermediate_size=160,
        moe_intermediate_size=32, num_hidden_layers=3,
        first_k_dense_replace=1, num_attention_heads=4, kv_lora_rank=32,
        q_lora_rank=48, qk_rope_head_dim=8, qk_nope_head_dim=16,
        v_head_dim=16, index_n_heads=4, index_head_dim=16, index_topk=12,
        n_routed_experts=32, num_experts_per_tok=4, max_seq_len=64,
        held=(0, 4), dtype=jnp.float32, param_dtype=jnp.float32,
        rope_theta=100.0)
    defaults.update(kw)
    return GlmDsaConfig(**defaults)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_params(config: GlmDsaConfig, key: jax.Array) -> Dict:
    """Seeded weights, made in ``param_dtype``: every matrix normal with
    standard deviation ``1/sqrt(fan_in)``, as Kimi-K2's, with two factors
    counted into a fan-in. ``W_qb`` counts ``ATTN_LOGIT_STD ** -2``:
    attention's scores come out at a standard deviation of 1.5, not one
    (see ``ATTN_LOGIT_STD``: what makes a wrong selection visible). The two
    norm gains of a layer are LOG-NORMAL, ``exp(n - 1)`` (unit mean square, a
    few channels several times the rest, as a trained model's are), and a
    routed expert's ``w_down`` counts ``4 routed_scaling_factor ** 2``
    (``mimo_v2.init_params``' two lessons, PR 49: a bfloat16 program is
    indifferent to a channel's scale and an int8 one, with one scale a
    tensor, is not; a token's picks weigh one in sum and an expert's output
    half a unit, so a pick that changes hands on bfloat16 rounding moves a
    logit by hundredths: with unit gains and unit experts this program's
    worst logit gap read 0.06-0.24 on the chip beside the int8 control's
    0.38, PERF.md 6, PR 53). The other norms at one; the indexer's
    LayerNorm has a seeded weight (1 + 0.1 n) and bias (0.1 n), and
    ``e_score_correction_bias`` is a seeded float32 buffer of standard
    deviation 0.02: what a trained model brings and ``config.json`` does not.
    ``W_kb`` [H, nope, rank] and ``W_vb`` [H, rank, v] are the two halves of
    the published ``W_kvb`` laid heads-major."""
    c = config
    dt = c.param_dtype
    D, H, R = c.hidden_size, c.num_attention_heads, c.kv_lora_rank
    Hi, Di = c.index_n_heads, c.index_head_dim
    n_held = c.held[1]
    counter = iter(range(1 << 30))

    def nrm(shape, fan_in, dtype=dt):
        k = jax.random.fold_in(key, next(counter))
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    def gain(n):
        # log-normal with unit mean square: exp(sigma n - sigma^2), sigma 1
        k = jax.random.fold_in(key, next(counter))
        return jnp.exp(jax.random.normal(k, (n,), jnp.float32) - 1.0).astype(dt)

    def mla():
        return {
            "w_qa": nrm((D, c.q_lora_rank), D),
            "q_norm": jnp.ones((c.q_lora_rank,), dt),
            "w_qb": nrm((c.q_lora_rank, H,
                         c.qk_nope_head_dim + c.qk_rope_head_dim),
                        c.q_lora_rank * ATTN_LOGIT_STD ** -2),
            "w_kva": nrm((D, c.latent_width), D),
            "kv_norm": jnp.ones((R,), dt),
            "w_kb": nrm((H, c.qk_nope_head_dim, R), R),
            "w_vb": nrm((H, R, c.v_head_dim), R),
            "w_o": nrm((H, c.v_head_dim, D), H * c.v_head_dim),
        }

    def indexer():
        return {
            # [r, heads x head_dim], a plain matrix: stored [r, heads,
            # head_dim] the decode program re-laid it on every step
            "w_q": nrm((c.q_lora_rank, Hi * Di), c.q_lora_rank),
            "w_k": nrm((D, Di), D),
            "k_norm": (1.0 + nrm((Di,), 100.0, jnp.float32)).astype(dt),
            "k_bias": nrm((Di,), 100.0),
            "w_w": nrm((D, Hi), D),
        }

    def ffn(width):
        return {"w_gate": nrm((D, width), D), "w_up": nrm((D, width), D),
                "w_down": nrm((width, D), width)}

    def layer(l):
        lp = {"attn": mla(), "indexer": indexer(),
              "norm_attn": gain(D), "norm_ffn": gain(D)}
        if l < c.first_k_dense_replace:
            lp["ffn"] = ffn(c.intermediate_size)
            return lp
        F = c.moe_intermediate_size
        lp.update(
            router=nrm((D, c.n_routed_experts), D),
            router_bias=nrm((c.n_routed_experts,), 2500.0, jnp.float32),
            experts={"w_gate_up": nrm((n_held, D, 2 * F), D),
                     "w_down": nrm((n_held, F, D),
                                   4 * F * c.routed_scaling_factor ** 2)},
            shared=ffn(c.n_shared_experts * F))
        return lp

    return {
        "tok_embed": nrm((c.vocab_size, D), 1),
        "layers": [layer(l) for l in range(c.num_hidden_layers)],
        "norm_f": jnp.ones((D,), dt),
        "lm_head": nrm((D, c.vocab_size), D),
    }


def describe(config: GlmDsaConfig) -> Dict[str, int]:
    """What the stack is made of, by count."""
    c = config
    return {"expert_layers": c.expert_layers,
            "dense_layers": c.first_k_dense_replace,
            "index_heads": c.index_n_heads,
            "index_topk": c.index_topk,
            "index_key_bytes_per_token": (
                c.index_head_dim * jnp.dtype(c.dtype).itemsize),
            "shared_expert_params": (3 * c.hidden_size * c.n_shared_experts
                                     * c.moe_intermediate_size)}


def init_pool(config: GlmDsaConfig, num_blocks: int,
              block_tokens: int) -> Tuple[jax.Array, jax.Array]:
    """``(latent rows [layers, num_blocks, block_tokens, pool_width], index
    keys [layers, num_blocks, block_tokens, index_head_dim])``: block 0 of
    both is the trash block, blocks are dimension 1 of both."""
    c = config
    return (init_latent_pool(c.latent_spec(), c.attn_sublayers, num_blocks,
                             block_tokens),
            jnp.zeros((c.attn_sublayers, num_blocks, block_tokens,
                       c.index_head_dim), c.dtype))


# ---------------------------------------------------------------------------
# Forward over the two paged pools
# ---------------------------------------------------------------------------

def _rope_first(x, positions, c: GlmDsaConfig):
    """Rotary on the first ``qk_rope_head_dim`` of the last axis of ``x``
    [S, T, heads, index_head_dim], the rest as it is."""
    r = c.qk_rope_head_dim
    return jnp.concatenate(
        [rope(x[..., :r], positions, base=c.rope_theta), x[..., r:]], axis=-1)


def index_keys(ip, a, positions, c: GlmDsaConfig):
    """``k^I`` [S, T, index_head_dim] of the normed stream ``a``: one key a
    token for all index heads, LayerNorm (weight and bias), rotary."""
    k = layer_norm(mm("std,di->sti", a, ip["w_k"], c.dtype), ip["k_norm"],
                   ip["k_bias"], INDEX_NORM_EPS)
    return _rope_first(k[:, :, None], positions, c)[:, :, 0]


def select(cq, *, ip, a, index, sub, tables, positions, c: GlmDsaConfig,
           kernel: str):
    """The keep bits [S, T, NB * bt] of one sublayer: the indexer's queries
    from the query latent ``cq`` (what ``latent_attention`` hands its
    ``select``), its head weights from ``a``, its keys from the index pool
    through ``tables`` (this step's own among them)."""
    S, T = positions.shape
    q = mm("str,rf->stf", cq, ip["w_q"], c.dtype).reshape(
        S, T, c.index_n_heads, c.index_head_dim)
    q = _rope_first(q, positions, c)
    w = jnp.einsum("std,dh->sth", a, ip["w_w"],
                   preferred_element_type=jnp.float32) * (
        c.index_n_heads ** -0.5 * c.index_head_dim ** -0.5)
    with jax.named_scope("dsa_gather"):
        keys = index[sub, tables].reshape(S, -1, c.index_head_dim)
    return keep_bits(q, w, keys, positions, k=c.index_topk, kernel=kernel,
                     dtype=c.dtype)


def expert_layer(lp, x, valid, c: GlmDsaConfig):
    """``moe.expert_layer`` under this family's names: sigmoid scores
    renormalised over the picks, a gated shared expert."""
    return moe.expert_layer(
        lp, x, valid, topk=c.num_experts_per_tok,
        scale=c.routed_scaling_factor, score="sigmoid", renormalise=True,
        held=c.held, n_routed=c.n_routed_experts,
        shared=lambda fp, rows: gated_ffn(fp, rows, c.dtype))


def _forward(params, tokens, pool, tables, lengths, positions, blk, off,
             valid, c: GlmDsaConfig, kernel: str, last_row=None,
             queries=None):
    """tokens [S, T] at absolute ``positions`` [S, T]; rows go to cells
    (``blk``, ``off``) of both pools; ``valid`` [S, T] marks the tokens whose
    output is read. ``last_row``: hand the head that one position alone.
    ``queries``: a prefill's count of real rows, the attention kernel's.
    Returns (logits float32, pool, pick counts, kept): the expert layers'
    counts summed over layers (``moe.PICK_COUNT_NAMES``' order), and the keep
    bits set for each slot [S], a sublayer's mean."""
    latent, index = pool
    dt = c.dtype
    eps = c.rms_norm_eps
    spec = c.latent_spec()
    x = jnp.take(params["tok_embed"], tokens, axis=0).astype(dt)
    counts = jnp.zeros((moe.PICK_COUNTS,), jnp.int32)
    kept = []

    def counted(cq, **kw):
        keep = select(cq, **kw)
        kept.append(jnp.sum(keep, axis=(1, 2), dtype=jnp.int32))
        return keep

    for l, lp in enumerate(params["layers"]):
        a = rms_norm(x, lp["norm_attn"], eps)
        with jax.named_scope("index_pool_write"):
            index = index.at[l, blk, off].set(
                index_keys(lp["indexer"], a, positions, c))
        o, latent = latent_attention(
            lp["attn"], a, latent, l, blk, off, tables, lengths, positions,
            spec, kernel, queries=queries,
            select=functools.partial(
                counted, ip=lp["indexer"], a=a, index=index, sub=l,
                tables=tables, positions=positions, c=c, kernel=kernel))
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
    return logits, (latent, index), counts, sum(kept) // len(kept)


def _aux(counts, live, contexts, kept, c: GlmDsaConfig):
    """``AUX_COUNTS``' order: the expert layers' counts, then the selection's
    over the ``live`` slots at ``contexts`` (rows visible to this step's
    query, its own among them): rows chosen (``kept`` [S], the keep bits
    that were SET, so that the count witnesses the selection and not the
    traffic), rows visible, slot-steps whose context was past
    ``index_topk``, slot-steps."""
    ctx = jnp.where(live, contexts, 0)
    return expert_aux(counts, jnp.stack(
        [jnp.sum(jnp.where(live, kept, 0)), jnp.sum(ctx),
         jnp.sum(ctx > c.index_topk), jnp.sum(live)]))


def forward_prefill_paged(params, tokens, pool, state, table, start_pos,
                          suffix_len, slot, config: GlmDsaConfig,
                          block_tokens: int, kernel: str = "gather"):
    """The family's ``prefill``: ``tokens`` [1, P] (a bucket) at positions
    [start_pos, start_pos + P) through ``table`` [NB]. Pad writes go to trash
    block 0 of both pools, pad tokens route to no expert. The head sees ONE
    row, the last real position (logits [1, 1, V]). The family keeps no slot
    state (``state`` is the empty tuple, handed back). A prefill's selection
    is not counted (``AUX_COUNTS``: the decode steps' alone)."""
    positions, valid, blk, off = prefill_cells(
        table, start_pos, suffix_len, tokens.shape[1], block_tokens)
    lengths1 = jnp.reshape(start_pos, (1,)).astype(jnp.int32)
    logits, pool, counts, _ = _forward(
        params, tokens, pool, table[None], lengths1, positions[None],
        blk[None], off[None], valid[None], config, kernel,
        last_row=suffix_len - 1, queries=suffix_len)
    return logits, pool, state, _aux(counts, jnp.zeros((1,), bool), lengths1,
                                     jnp.zeros((1,), jnp.int32), config)


def forward_decode_paged(params, tokens, pool, state, tables, lengths,
                         config: GlmDsaConfig, block_tokens: int,
                         kernel: str = "gather",
                         active: Optional[jax.Array] = None):
    """The family's ``decode``: ``tokens`` [S, T], slot s's token t at
    position ``lengths[s] + t`` (``generate.decode_cells``, the same cells
    of both pools); slots not ``active`` route to no expert and count no
    selection."""
    S, T = tokens.shape
    positions, blk, off = decode_cells(tables, lengths, T, block_tokens)
    live = jnp.ones((S,), bool) if active is None else active
    logits, pool, counts, kept = _forward(
        params, tokens, pool, tables, lengths, positions, blk, off,
        jnp.broadcast_to(live[:, None], (S, T)), config, kernel)
    return logits, pool, state, _aux(counts, live, lengths + T, kept, config)


# The expert layers' counts, then the selection's four; ``dsa_rows`` rides the
# ``llm.step`` span.
AUX_COUNTS = EXPERT_AUX_COUNTS + (
    AuxCount("dsa_selected_rows_total", None, "dsa_rows"),
    AuxCount("dsa_context_rows_total"),
    AuxCount("dsa_capped_slot_steps_total"),
    AuxCount("dsa_slot_steps_total"))

PAGED_FAMILY = PagedFamily(
    init_pool=init_pool,
    prefill=forward_prefill_paged,
    decode=forward_decode_paged,
    logits_dim=lambda params, config: params["lm_head"].shape[-1],
    unsupported=("prefix_cache",),
    aux_counts=AUX_COUNTS,
    describe=describe,
    walk_group_blocks=lambda c, pool: latent_group_blocks(
        pool[0], c.num_attention_heads),
)
