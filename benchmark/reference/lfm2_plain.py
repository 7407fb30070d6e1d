"""LFM2's expert model (``model_type: lfm2_moe``) in plain jax.numpy, float32.

The benchmark's own statement of what the configuration
``configs/lfm2-8b-a1b.json`` computes (keys as in
huggingface.co/LiquidAI/LFM2-8B-A1B ``config.json``). No cache, no tail, no
kernels, no grouped or batched product, no import from the program: the
convolution is three shifted copies of a whole sequence, attention a full
causal softmax a query head with its KV head repeated, the experts a loop
over the ones ``held`` names. Every matrix product is a ``jnp.einsum`` /
``jnp.matmul`` by name at ``highest`` precision. Sizes and constants come
from the configuration's dict.

``x`` [T, hidden_size]; no bias anywhere; ``RMSNorm`` at ``norm_eps`` with a
learned weight. Layer ``l``::

    h       = x_l + Mixer_l(RMSNorm_op(x_l))
    x_(l+1) = h + FFN_l(RMSNorm_ffn(h))
    logits  = RMSNorm_f(x_L) E^T

``RMSNorm_f`` is the norm the source names ``embedding_norm``: it is applied
to the OUTPUT of the stack. ``E`` [vocab_size, hidden_size] is the embedding
table: it is tied (``tie_word_embeddings``, assumed).

*conv* (``layer_types[l] == "conv"``), the gated short convolution: ``[B | C
| u] = a W_in`` (``W_in`` [hidden, 3 hidden], three chunks in that order);
``p_t = B_t * u_t`` elementwise; ``c_t = sum_{j < K} w[j] * p_(t - (K-1) +
j)`` a channel (``w`` [K, hidden] with ``K = conv_L_cache``: depthwise,
causal, zeros before the sequence's start, no bias, NO activation);
``Mixer(a)_t = (C_t * c_t) W_out``.

*full_attention*: ``q = a W_q`` as ``num_attention_heads`` heads of
``head_dim = hidden_size / num_attention_heads``, ``k = a W_k``, ``v = a
W_v`` as ``num_key_value_heads`` heads; every query head and every key head
RMS-normed over its own ``head_dim`` numbers with ONE learned weight the
kind (``q_norm``, ``k_norm``), BEFORE the rotation; rotary over the whole
head, half-split pairs (number ``i`` turns with number ``i + head_dim / 2``),
base ``rope_theta``, no scaling, absolute positions; causal softmax of ``q_h
. k_g / sqrt(head_dim)`` with query head ``h`` reading KV head ``h //
(heads / kv heads)``; ``Mixer(a) = concat_h(o_h) W_o``.

*FFN*, ``l < num_dense_layers``: ``W_2 (silu(W_1 f) * W_3 f)`` at
``intermediate_size``. Else the expert layer: ``s = sigmoid(W_r f)`` over
``held.of`` outputs; the ``num_experts_per_tok`` largest of ``s +
expert_bias`` are picked (the bias selects and never weighs); weights ``s_i
/ (sum of the picked s + 1e-20)`` x ``routed_scaling_factor`` (with
``norm_topk_prob``); ``FFN(f) = sum_i w_i W_2^i (silu(W_1^i f) * W_3^i f)``
at ``moe_intermediate_size`` over the picks that land on experts
``held.first .. held.first + held.count - 1`` (``count == of``: the uncut
layer). No shared expert.

Departures from the source, each noted: (1) the normaliser adds 1e-20 where
the source adds 1e-6 (``ops/moe.py:route_topk``'s constant: at 4 picks of a
sigmoid, each over 0.02 where it matters, the sum is 1e4 times 1e-6 and the
difference is under float32's last place of a logit); (2) the rotary pairs
are half-split, the source's own ``rotate_half`` convention; (3) everything
is float32 here (the program keeps the router float32 and the rest
bfloat16). ``weights`` keeps the program's arrays as they are stored, and
``forward`` upcasts one matrix (one expert's, one block of the table's rows)
where it uses it: no float32 copy of the 9.3 GB tree ever exists beside the
engine's.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
# The head (65,536 rows of the tied table) goes in blocks, one upcast at a
# time.
_HEAD_BLOCKS = 32


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(g)


def short_conv(lw, a, c):
    """a [T, D] -> [T, D]: the gated short convolution."""
    T, D = a.shape
    K = int(c["conv_L_cache"])
    p = jnp.matmul(a, _f32(lw["w_in"]), precision=_HI)
    B, C, u = p[:, :D], p[:, D:2 * D], p[:, 2 * D:]
    padded = jnp.pad(B * u, ((K - 1, 0), (0, 0)))
    w = _f32(lw["conv"])
    conv = sum(padded[j:j + T] * w[j] for j in range(K))
    return jnp.matmul(C * conv, _f32(lw["w_out"]), precision=_HI)


def rotate(x, theta):
    """x [H, T, d] at positions 0..T-1: half-split rotary over the whole
    head."""
    T, d = x.shape[1], x.shape[2]
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(lw, a, c):
    """a [T, D] -> [T, D]: causal softmax attention, a query head at a time,
    its KV head repeated; heads normed, then rotated."""
    T, D = a.shape
    Hq, Hkv = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    hd = D // Hq
    eps, theta = float(c["norm_eps"]), float(c["rope_theta"])
    heads = lambda w, n: jnp.matmul(  # noqa: E731
        a, _f32(w), precision=_HI).reshape(T, n, hd).transpose(1, 0, 2)
    q = rotate(_rms(heads(lw["w_q"], Hq), lw["q_norm"], eps), theta)
    k = rotate(_rms(heads(lw["w_k"], Hkv), lw["k_norm"], eps), theta)
    k, v = (jnp.repeat(x, Hq // Hkv, axis=0)
            for x in (k, heads(lw["w_v"], Hkv)))
    causal = jnp.tril(jnp.ones((T, T), bool))

    def head(qkv):
        q_h, k_h, v_h = qkv
        s = jnp.matmul(q_h, k_h.T, precision=_HI) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.matmul(p, v_h, precision=_HI)

    o = jax.lax.map(head, (q, k, v))                           # [Hq, T, hd]
    return jnp.matmul(o.transpose(1, 0, 2).reshape(T, Hq * hd),
                      _f32(lw["w_o"]), precision=_HI)


MIXERS = {"conv": short_conv, "full_attention": attention}


def _gated(w_1, w_3, w_2, f):
    """``W_2 (silu(W_1 f) * W_3 f)``."""
    g = jnp.matmul(f, _f32(w_1), precision=_HI)
    u = jnp.matmul(f, _f32(w_3), precision=_HI)
    return jnp.matmul(jax.nn.silu(g) * u, _f32(w_2), precision=_HI)


def dense_ffn(lw, f, c):
    return _gated(lw["w_1"], lw["w_3"], lw["w_2"], f)


def router(lw, f, c):
    """(picks [T, k] int32, weights [T, k]) of one expert layer."""
    s = jax.nn.sigmoid(jnp.matmul(f, _f32(lw["router"]), precision=_HI))
    _, idx = jax.lax.top_k(s + _f32(lw["expert_bias"]),
                           int(c["num_experts_per_tok"]))
    picked = jnp.take_along_axis(s, idx, axis=-1)
    if c.get("norm_topk_prob", True):
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return idx, float(c["routed_scaling_factor"]) * picked


def experts(lw, f, c):
    """``sum w_i E_i(f)`` over the picks that land on the experts
    ``config["held"]`` names, one expert at a time (each upcast where it is
    used)."""
    first, count = int(c["held"]["first"]), int(c["held"]["count"])
    idx, w = router(lw, f, c)

    F = int(c["moe_intermediate_size"])

    def one(e, out):
        w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        w_13 = lw["w_13"][e]                 # [D, 2F]: W_1 | W_3
        return out + w_e[:, None] * _gated(w_13[:, :F], w_13[:, F:],
                                           lw["w_2"][e], f)

    return jax.lax.fori_loop(0, count, one, jnp.zeros_like(f))


def _head(x, table):
    """x [T, D] @ table^T [D, V] in blocks of the table's rows, each upcast
    where used."""
    V = table.shape[0]
    nb = _HEAD_BLOCKS if V % _HEAD_BLOCKS == 0 else 1
    B = V // nb

    def block(i, out):
        rows = jax.lax.dynamic_slice_in_dim(table, i * B, B, axis=0)
        return jax.lax.dynamic_update_slice_in_dim(
            out, jnp.matmul(x, _f32(rows).T, precision=_HI), i * B, axis=1)

    return jax.lax.fori_loop(0, nb, block,
                             jnp.zeros((x.shape[0], V), jnp.float32))


def forward(w: Dict, tokens: jax.Array, config: Dict) -> jax.Array:
    """tokens [B, T] int32 -> logits [B, T, vocab_size] float32."""
    c = config
    kinds = list(c["layer_types"])
    if not int(c["num_hidden_layers"]) == len(kinds) == len(w["layers"]):
        raise ValueError(f"{c['num_hidden_layers']} layers stated, "
                         f"{len(kinds)} named, {len(w['layers'])} layers of "
                         "weights")
    eps, dense = float(c["norm_eps"]), int(c["num_dense_layers"])

    def one(seq):
        x = _f32(w["tok_embed"][seq])
        for l, (kind, lw) in enumerate(zip(kinds, w["layers"])):
            h = x + MIXERS[kind](lw["mixer"], _rms(x, lw["norm_op"], eps), c)
            f = _rms(h, lw["norm_ffn"], eps)
            x = h + (dense_ffn if l < dense else experts)(lw["ffn"], f, c)
        return _head(_rms(x, w["norm_f"], eps), w["tok_embed"])

    return jnp.stack([one(seq) for seq in tokens])


def weights(p: Dict) -> Dict:
    """ray_tpu.models.lfm2's tree -> this file's: the same arrays under this
    file's names, in the dtype the program stores (nothing is copied to
    float32 here). The only place that knows the program's layout: a list of
    one dict a layer holding its two norms, its mixer's matrices under
    ``mixer`` (an attention layer's ``w_q`` [H, D, d] is stored a head
    first and turned into [D, H d]; its ``w_kv`` [D, 2 KV d] holds K's
    heads, then V's: cut into ``w_k`` and ``w_v``) and its feed-forward's: ``ffn``
    with ``w_gate``, ``w_up``, ``w_down`` (here ``w_1``, ``w_3``, ``w_2``),
    or ``router``, ``router_bias`` (here ``expert_bias``) and ``experts``
    with ``w_gate_up`` [E, D, 2F] (gate | up: here ``w_13``, kept WHOLE and
    cut an expert at a time where it is used: cut here, the two halves would
    be a second copy of every expert beside the engine's, 5.6 GB at the
    cell's sizes) and ``w_down``. The table is tied: there is no head to
    map."""
    def mixer(mw):
        if "w_kv" not in mw:
            return dict(mw)
        half = mw["w_kv"].shape[1] // 2
        H, D, d = mw["w_q"].shape
        return {"w_q": jnp.transpose(mw["w_q"], (1, 0, 2)).reshape(D, H * d),
                "w_k": mw["w_kv"][:, :half],
                "w_v": mw["w_kv"][:, half:], "w_o": mw["w_o"],
                "q_norm": mw["q_norm"], "k_norm": mw["k_norm"]}

    def ffn(lp):
        if "ffn" in lp:
            f = lp["ffn"]
            return {"w_1": f["w_gate"], "w_3": f["w_up"], "w_2": f["w_down"]}
        return {"router": lp["router"], "expert_bias": lp["router_bias"],
                "w_13": lp["experts"]["w_gate_up"],
                "w_2": lp["experts"]["w_down"]}

    return {"tok_embed": p["tok_embed"], "norm_f": p["norm_f"],
            "layers": [{"norm_op": lp["norm_op"], "norm_ffn": lp["norm_ffn"],
                        "mixer": mixer(lp["mixer"]), "ffn": ffn(lp)}
                       for lp in p["layers"]]}
