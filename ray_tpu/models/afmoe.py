"""Trinity's language model (``model_type: afmoe``) on the paged serve path.

The sixth model family of the zoo and the first with two kinds of ATTENTION
layer in one stack: ``layer_types`` names each layer ``sliding_attention``
(rotary, a window of ``sliding_window`` positions) or ``full_attention`` (no
rotation, the whole context), three to one. The two kinds keep their K/V in
two kinds of memory: a full layer's rows lie in the paged pool (a row a token
for as long as the request lives: ``PagedFamily.init_pool``, the block
manager's), a window layer's in a RING a slot (``PagedFamily.init_slot_state``:
position ``p`` writes ring row ``p mod ring``), so that what a window layer
pins for a slot is bounded by the window whatever the context. Keys as in
huggingface.co/arcee-ai/Trinity-Large-Preview ``config.json``; the layer, with
``rms`` an RMSNorm at ``rms_norm_eps`` (what the keys do not say is marked †
and listed under ``assumed`` in ``benchmark/configs/trinity-large-preview.json``)::

    h0      = E[tokens] * sqrt(hidden_size)                  (mup_enabled †)
    a       = rms(h; g_in)
    q, k, v = a Wq, a Wk, a Wv;   gate = a Wg †
    q, k    = rms(q; g_q), rms(k; g_k)  over head_dim †
    sliding layer: q, k rotated (rope_theta, half-split pairs, no scaling);
                   key j visible to query i  iff  j <= i and i - j < window
    full layer:    NO rotation †;  j <= i
    o       = softmax(q k^T / sqrt(head_dim)) v,  query head h on KV head h // R
    h       = h + rms((o * sigmoid(gate)) Wo; g_post_attn) †
    m       = rms(h; g_pre_mlp)
    dense (layers < num_dense_layers):  f = Wdown(silu(Wgate m) * Wup m)
    expert layer:  s = sigmoid(m Wr) in float32;  picks = top-k of (s + bias);
                   w = s[picks] / (sum s[picks] + 1e-20) * route_scale
                   f = shared(m)  +  sum_e w_e * expert_e(m)
    h       = h + rms(f; g_post_mlp) †
    logits  = rms(h_last; g_f) W_head                        (untied)

``held = (first, count)`` says which routed experts' weights live here, as
``kimi_k2``: the layer routes over all ``num_experts``, normalises over ALL
of a token's picks and adds only what its own experts give
(``ops/moe.py:held_experts_ffn``); the shared expert is whole on every chip.

The window layers' attention is ``ops/paged_attention.py:paged_attention``
with ``window=``: in decode over the slot's ring through a table that is the
slot's own blocks, read modulo the ring; in prefill over the prompt's FRESH
K/V viewed as blocks (a ring shorter than the prompt cannot be attended
through), after which the last ``ring`` rows go into the ring. A parked
slot's ring is left bit for bit (its write is dropped).

The prefix cache is not supported (``PagedFamily.unsupported``): a hit at
position p would need every window layer's ring at p, which nothing keeps.
So ``start_pos`` is always 0 and a prefill writes its slot's rings from
position 0. Weights are one array a matrix, stored in ``param_dtype``, read
as stored.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.generate import (EXPERT_AUX_COUNTS, AuxCount,
                                     PagedFamily, _paged_attend,
                                     decode_cells, expert_aux,
                                     init_block_pool, prefill_cells)
from ray_tpu.ops import moe, window_ring
from ray_tpu.ops.layers import (gated_ffn, mm as _mm, rms_norm, rope,
                                rope_frequencies)
from ray_tpu.ops.paged_attention import (paged_attention,
                                         paged_attention_reference)

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclass(frozen=True)
class AfmoeConfig:
    """Field names are the published ``config.json`` keys (Trinity-Large-
    Preview's values); ``held``, ``window_block_tokens``, ``max_seq_len`` and
    the two dtypes are this program's."""
    vocab_size: int = 200192
    hidden_size: int = 3072
    intermediate_size: int = 12288
    moe_intermediate_size: int = 3072
    num_hidden_layers: int = 60
    num_dense_layers: int = 6
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 4096
    global_attn_every_n_layers: int = 4
    # Empty: every ``global_attn_every_n_layers``-th layer is a full one.
    layer_types: Tuple[str, ...] = ()
    num_experts: int = 256
    num_experts_per_tok: int = 4
    num_shared_experts: int = 1
    route_norm: bool = True
    route_scale: float = 2.448
    score_func: str = "sigmoid"
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    mup_enabled: bool = True
    max_seq_len: int = 262144
    # Routed experts whose weights live on this chip: (first, count).
    held: Tuple[int, int] = (0, 256)
    # Rows of one block of a window layer's ring: what one copy of the
    # decode kernel brings (128 rows of 2 KB: a 256 KB copy).
    window_block_tokens: int = 128
    dtype: Any = jnp.bfloat16          # activation/compute dtype
    param_dtype: Any = jnp.bfloat16    # storage dtype

    def __post_init__(self):
        n = self.global_attn_every_n_layers
        types = tuple(self.layer_types) or tuple(
            FULL if (l + 1) % n == 0 else SLIDING
            for l in range(self.num_hidden_layers))
        object.__setattr__(self, "layer_types", types)
        object.__setattr__(self, "held", tuple(self.held))
        if len(types) != self.num_hidden_layers or set(types) - {SLIDING, FULL}:
            raise ValueError(
                f"layer_types names {len(types)} layers of kinds "
                f"{sorted(set(types))}: want {self.num_hidden_layers} of "
                f"{SLIDING!r} / {FULL!r}")
        if FULL not in types:
            raise ValueError("a stack with no full layer has no paged pool")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads divide into KV heads in whole runs")
        if self.score_func != "sigmoid":
            raise ValueError(f"no scoring rule {self.score_func!r} here")

    # What the generator and the pool read: the pool is the FULL layers'.
    @property
    def n_heads(self) -> int:
        return self.num_attention_heads

    @property
    def n_kv_heads(self) -> int:
        return self.num_key_value_heads

    @property
    def n_layers(self) -> int:
        """Layers whose K/V rows lie in the paged pool: the full ones."""
        return self.layer_types.count(FULL)

    @property
    def window_layers(self) -> int:
        return self.layer_types.count(SLIDING)

    @property
    def expert_layers(self) -> int:
        return self.num_hidden_layers - self.num_dense_layers

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
        return (self.window_layers * 2 * self.ring_rows
                * self.num_key_value_heads * self.head_dim
                * jnp.dtype(self.dtype).itemsize)

    def kind_index(self, layer: int) -> int:
        """Layer ``layer``'s index among the layers of its own kind: which
        layer of the pool, or of the rings, is its."""
        return self.layer_types[:layer].count(self.layer_types[layer])

    def replace(self, **kw) -> "AfmoeConfig":
        return replace(self, **kw)

    def paged_family(self) -> PagedFamily:
        return PAGED_FAMILY


def trinity_large_share(*, num_hidden_layers: int = 5, num_dense_layers: int = 1,
                        held: Tuple[int, int] = (0, 16),
                        vocab_size: int = 25088, max_seq_len: int = 8192,
                        **kw) -> AfmoeConfig:
    """Trinity-Large-Preview at its published widths, cut to one chip of a
    deployment that shares each layer 16 ways: one leading dense layer and
    four expert layers, ``layer_types`` the first five published entries
    (sliding, sliding, sliding, full, sliding: a whole period), 16 of 256
    experts held, an eighth of the vocabulary (rounded up to whole 128-lane
    tiles) (``benchmark/configs/trinity-large-preview.json``)."""
    return AfmoeConfig(num_hidden_layers=num_hidden_layers,
                       num_dense_layers=num_dense_layers, held=held,
                       vocab_size=vocab_size, max_seq_len=max_seq_len, **kw)


def tiny(**kw) -> AfmoeConfig:
    """Test-sized: one dense and four expert layers (sliding, sliding,
    sliding, full, sliding), width 64, 4 query heads over 2 KV heads of 64 (a
    128-lane row), a window of 16 in ring blocks of 8, 32 routed experts of
    which 4 held, top-4, one shared expert, float32."""
    defaults = dict(
        vocab_size=256, hidden_size=64, intermediate_size=160,
        moe_intermediate_size=32, num_hidden_layers=5, num_dense_layers=1,
        num_attention_heads=4, num_key_value_heads=2, head_dim=64,
        sliding_window=16, window_block_tokens=8, num_experts=32,
        num_experts_per_tok=4, held=(0, 4), rope_theta=100.0, max_seq_len=64,
        dtype=jnp.float32, param_dtype=jnp.float32)
    defaults.update(kw)
    return AfmoeConfig(**defaults)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_params(config: AfmoeConfig, key: jax.Array) -> Dict:
    """Seeded weights, made in ``param_dtype``: every matrix normal with
    standard deviation ``1/sqrt(fan_in)``; the embedding's rows ``1/sqrt(
    hidden_size)`` where ``mup_enabled``, so that ``E[token] * sqrt(
    hidden_size)`` has unit mean square. "Depth-scaled" names an
    initialisation of the norm gains, no equation: the gains here are seeded
    near one (``1 + 0.1 n``, so that a norm left out, or its gain, is seen),
    those of q and k near ``sqrt(2)``: scores then have a standard deviation
    of 2 (over a window of thousands of keys a softmax of unit scores is
    nearly a mean: ``falcon_h1.init_params``). A routed expert's ``w_down``
    counts ``route_scale ** 2`` into its fan-in, ``kimi_k2.init_params``'s
    lesson: a token's picks then weigh one in sum, and a pick that changes
    hands between this program and a float32 reference on bfloat16 rounding
    of the router's input moves a logit by a fraction, not by a whole unit.
    ``router_bias`` (the selection bias) is a seeded NON-zero float32 buffer
    of standard deviation 0.02, a tenth of the spread of a sigmoid score."""
    c = config
    dt = c.param_dtype
    D, H, KV, hd = (c.hidden_size, c.num_attention_heads,
                    c.num_key_value_heads, c.head_dim)
    n_held = c.held[1]
    counter = iter(range(1 << 30))
    sub = lambda: jax.random.fold_in(key, next(counter))  # noqa: E731

    def nrm(shape, fan_in):
        return (jax.random.normal(sub(), shape, jnp.float32)
                * fan_in ** -0.5).astype(dt)

    def gain(n, mean=1.0, spread=0.1):
        return (mean * (1.0 + spread * jax.random.normal(
            sub(), (n,), jnp.float32))).astype(dt)

    def ffn(width):
        return {"w_gate": nrm((D, width), D), "w_up": nrm((D, width), D),
                "w_down": nrm((width, D), width)}

    def layer(l):
        lp = {
            "norm_in": gain(D), "norm_post_attn": gain(D),
            "norm_pre_mlp": gain(D), "norm_post_mlp": gain(D),
            "q_norm": gain(hd, 2 ** 0.5, 0.05),
            "k_norm": gain(hd, 2 ** 0.5, 0.05),
            # q a head first: [heads, D, head_dim]; K's heads, then V's, a
            # head first: [2 KV heads, D, head_dim]: the forms the decode
            # program's products read where they lie (as [D, heads,
            # head_dim] XLA re-laid w_q on every call: compile-only for a
            # v5e).
            "w_q": nrm((H, D, hd), D),
            "w_kv": nrm((2 * KV, D, hd), D),
            "w_g": nrm((D, H * hd), D),
            "w_o": nrm((H * hd, D), H * hd)}
        if l < c.num_dense_layers:
            lp["ffn"] = ffn(c.intermediate_size)
            return lp
        F = c.moe_intermediate_size
        lp.update(
            router=nrm((D, c.num_experts), D),
            router_bias=jax.random.normal(
                sub(), (c.num_experts,), jnp.float32) * 0.02,
            experts={"w_gate_up": nrm((n_held, D, 2 * F), D),
                     "w_down": nrm((n_held, F, D), F * c.route_scale ** 2)},
            shared=ffn(c.num_shared_experts * F))
        return lp

    return {
        "tok_embed": nrm((c.vocab_size, D), D if c.mup_enabled else 1),
        "layers": [layer(l) for l in range(c.num_hidden_layers)],
        "norm_f": gain(D),
        "lm_head": nrm((D, c.vocab_size), D),
    }


# ---------------------------------------------------------------------------
# The window layers' memory: a ring a slot
# ---------------------------------------------------------------------------

def init_slot_state(config: AfmoeConfig, slots: int) -> Tuple[jax.Array, jax.Array]:
    """``(K rings, V rings)`` as ``ops/window_ring.py`` lays them, each
    ``[window layers, slots, ring blocks, window_block_tokens, KV heads *
    head_dim]``."""
    c = config
    shape = (c.window_layers, slots, c.ring_blocks, c.window_block_tokens,
             c.num_key_value_heads * c.head_dim)
    return jnp.zeros(shape, c.dtype), jnp.zeros(shape, c.dtype)


def _window_attend(q, k, v, rings, wl: int, ctx, c: AfmoeConfig, kernel: str):
    """A sliding layer's attention and its ring's update. Decode: the new
    row goes into the ring first, then the walk reads the ring's live rows
    through a table that is each slot's own blocks. Prefill: the walk attends
    the prompt's FRESH rows, viewed as blocks behind an identity table, and
    the prompt's last ``ring`` rows go into the ring."""
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
    kw = dict(scale=c.head_dim ** -0.5, window=c.sliding_window)
    with jax.named_scope("attn_window"):
        if kernel in ("pallas", "interpret"):
            # a prefill's walk follows its real rows alone
            o = paged_attention(q, *operands, interpret=kernel == "interpret",
                                queries=ctx.get("suffix_len"), **kw)
        else:       # the gather path: [S, T, window] rows, a CPU's sizes
            o = paged_attention_reference(q, *operands, **kw)
    return o, rings


def _rotates(c: AfmoeConfig, layer: int) -> bool:
    """Whether ``layer``'s queries and keys are rotated: the sliding layers'
    are, the full layers' carry no position at all."""
    return c.layer_types[layer] == SLIDING


def _attention(lw, a, pool, rings, layer: int, ctx, c: AfmoeConfig, kernel: str):
    """One layer's attention sublayer before its output norm: ``a`` [S, T, D]
    the normed input. Returns (``(o * sigmoid(gate)) Wo``, pool, rings)."""
    dt = c.dtype
    S, T, _ = a.shape
    KV = c.num_key_value_heads
    q = _mm("std,hdk->sthk", a, lw["w_q"], dt)
    kv = _mm("std,hdk->sthk", a, lw["w_kv"], dt)
    gate = jnp.einsum("std,de->ste", a, lw["w_g"],
                      preferred_element_type=jnp.float32)
    q = rms_norm(q, lw["q_norm"], c.rms_norm_eps)
    k = rms_norm(kv[:, :, :KV], lw["k_norm"], c.rms_norm_eps)
    v = kv[:, :, KV:]
    idx = c.kind_index(layer)
    if _rotates(c, layer):
        freqs = rope_frequencies(c.rope_theta, c.head_dim)
        q = rope(q, ctx["positions"], freqs=freqs)
        k = rope(k, ctx["positions"], freqs=freqs)
    if c.layer_types[layer] == SLIDING:
        o, rings = _window_attend(q, k, v, rings, idx, ctx, c, kernel)
    else:
        k_pool, v_pool = pool
        with jax.named_scope("kv_pool_write"):
            k_pool = k_pool.at[idx, ctx["blk"], ctx["off"]].set(
                k.reshape(S, T, -1))
            v_pool = v_pool.at[idx, ctx["blk"], ctx["off"]].set(
                v.reshape(S, T, -1))
        with jax.named_scope("attn_full"):
            o = _paged_attend(q, k_pool, v_pool, ctx["tables"], ctx["lengths"],
                              idx, scale=c.head_dim ** -0.5, kernel=kernel,
                              queries=ctx.get("suffix_len"))
        pool = (k_pool, v_pool)
    with jax.named_scope("attn_gate"):
        o = (o.reshape(S, T, -1) * jax.nn.sigmoid(gate)).astype(dt)
    return _mm("ste,ed->std", o, lw["w_o"], dt), pool, rings


def expert_layer(lp, x, valid, c: AfmoeConfig):
    """``moe.expert_layer`` under this family's names, with a gated shared
    expert."""
    return moe.expert_layer(
        lp, x, valid, topk=c.num_experts_per_tok, scale=c.route_scale,
        score=c.score_func, renormalise=c.route_norm, held=c.held,
        n_routed=c.num_experts,
        shared=lambda fp, rows: gated_ffn(fp, rows, c.dtype))


def _forward(params, tokens, pool, rings, ctx, c: AfmoeConfig, kernel: str,
             last_row=None):
    """tokens [S, T]; ``ctx`` holds the positions, the pool's cells and
    tables, and what the mode's window layers need. Returns (logits float32,
    pool, rings, the expert layers' pick counts summed over layers)."""
    dt, eps = c.dtype, c.rms_norm_eps
    x = jnp.take(params["tok_embed"], tokens, axis=0).astype(dt)
    if c.mup_enabled:       # in float32: sqrt(3072) is no bfloat16 number
        x = (x.astype(jnp.float32) * c.hidden_size ** 0.5).astype(dt)
    counts = jnp.zeros((moe.PICK_COUNTS,), jnp.int32)
    for l, lp in enumerate(params["layers"]):
        o, pool, rings = _attention(
            lp, rms_norm(x, lp["norm_in"], eps), pool, rings, l, ctx, c, kernel)
        h = x + rms_norm(o, lp["norm_post_attn"], eps)
        m = rms_norm(h, lp["norm_pre_mlp"], eps)
        if "ffn" in lp:                  # l < num_dense_layers
            with jax.named_scope("dense_ffn"):
                f = gated_ffn(lp["ffn"], m, dt)
        else:
            f, cnt = expert_layer(lp, m, ctx["valid"], c)
            counts = counts + cnt
        x = h + rms_norm(f, lp["norm_post_mlp"], eps)
    if last_row is not None:
        x = jax.lax.dynamic_slice_in_dim(x, last_row, 1, axis=1)
    x = rms_norm(x, params["norm_f"], eps)
    logits = jnp.einsum("std,dv->stv", x, params["lm_head"],
                        preferred_element_type=jnp.float32)
    return logits, pool, rings, counts


def forward_prefill_paged(params, tokens, pool, state, table, start_pos,
                          suffix_len, slot, config: AfmoeConfig,
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
                         config: AfmoeConfig, block_tokens: int,
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


def describe(config: AfmoeConfig) -> Dict[str, int]:
    """What the stack is made of, for ``engine.describe()``."""
    c = config
    return {"window_layers": c.window_layers, "full_layers": c.n_layers,
            "window_tokens": c.sliding_window,
            "window_ring_bytes_per_slot": c.ring_bytes_per_slot,
            "expert_layers": c.expert_layers,
            "dense_layers": c.num_dense_layers,
            "kv_heads": c.num_key_value_heads}


# The expert layers' counts, then the active slot-steps whose context was past
# the window (beside the engine's ``state_slot_steps_total``).
AUX_COUNTS = EXPERT_AUX_COUNTS + (
    AuxCount("window_capped_slot_steps_total"),)

PAGED_FAMILY = PagedFamily(
    # The pool is the FULL layers' alone (``config.n_layers``): the block
    # manager, the reservation at admission and serve_kv_pool_blocks count
    # rows that live as long as the request. The window layers' rows are a
    # state a slot.
    init_pool=init_block_pool,
    prefill=forward_prefill_paged,
    decode=forward_decode_paged,
    logits_dim=lambda params, config: params["lm_head"].shape[-1],
    init_slot_state=init_slot_state,
    # As the other families with a state a slot: a hit at position p would
    # need every ring at p.
    unsupported=("prefix_cache",),
    aux_counts=AUX_COUNTS,
    describe=describe,
)
