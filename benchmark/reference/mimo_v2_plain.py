"""MiMo-V2-Flash's language model (``model_type: mimo_v2_flash``) in plain
jax.numpy, float32.

The benchmark's own statement of what the configuration
``configs/mimo-v2-flash.json`` computes (keys as in
huggingface.co/XiaomiMiMo/MiMo-V2-Flash ``config.json``). No cache, no ring,
no kernels, no import from the program: attention is a dense masked softmax
taken ``_QUERY_BLOCK`` queries at a time, the expert layer a loop over
experts. Every matrix product is a ``jnp.einsum`` / ``jnp.matmul`` by name at
``highest`` precision.

Layer ``l`` on the stream ``x`` [T, hidden_size] (``rms`` an RMSNorm at
``layernorm_epsilon``; no biases; what the published keys do not say is
listed under ``assumed`` in the configuration's file)::

    a    = rms(x; g_attn)
    q    = a Wq  as num_attention_heads heads of head_dim (192)
    k    = a Wk  as KV_l heads of head_dim
    v    = attention_value_scale * (a Wv)  as KV_l heads of v_head_dim (128)
           KV_l = num_key_value_heads where hybrid_layer_pattern[l] == 0
           (full), swa_num_key_value_heads where it is 1 (window); query head
           h reads KV head h // (num_attention_heads / KV_l)
    q, k : the FIRST int(partial_rotary_factor * head_dim) // 2 * 2 = 64
           dimensions of a head rotated at absolute positions, pair i with i +
           32 inside those 64, base rope_theta (full) or swa_rope_theta
           (window); the other 128 dimensions pass unrotated
    s_ij = q_i . k_j / sqrt(head_dim);  key j visible to query i iff j <= i
           (full) or j <= i and i - j < sliding_window (window)
    full:   p = softmax_j(s_ij)             (add_full_attention_sink_bias false)
    window: p_ij = exp(s_ij) / (exp(b_h) + sum_j' exp(s_ij'))
            b_h a learned scalar a query head (add_swa_attention_sink_bias):
            the sink takes probability and adds nothing
    x    = x + concat_h(sum_j p_ij v_j) Wo          (Wo [heads * 128, hidden])
    f    = rms(x; g_ffn)
    moe_layer_freq[l] == 0:  x = x + Wdown(silu(Wgate f) * Wup f)  at intermediate_size
    else:  s = sigmoid(f Wr) in float32 over held.of outputs;
           picks = the num_experts_per_tok largest of (s + bias)  (noaux_tc,
           n_group = topk_group = 1: no groups);
           w = s[picks] / sum s[picks] (norm_topk_prob) * routed_scaling_factor
           (null = 1.0);
           x = x + sum over the picked experts HELD here of w_e expert_e(f),
           an expert the same gated form at moe_intermediate_size
    logits = rms(x; g_f) W_head                                   (untied)

There is no shared expert, no gate on the attention's output, no norm on q or
k, and one norm a sublayer (before it).

The share: ``config["held"] = {"first", "count", "of"}`` says which routed
experts' weights are here. The layer routes over all ``of`` and adds only the
held experts' part; with ``count == of`` it is the uncut layer. The depth is
the weights' own; ``hybrid_layer_pattern`` and ``moe_layer_freq`` are the
configuration's.

Weights are stored in bfloat16: ``weights`` keeps the program's arrays as
they are and ``forward`` upcasts one matrix, one expert or one block of
columns where it uses it (bfloat16 -> float32 is exact; no float32 copy of
the tree exists beside the engine's). Wide products run in blocks of
``_BLOCK`` columns and attention in blocks of queries: the order of a
float32 sum, not what is summed.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
_BLOCK = 4096          # columns of a wide matrix upcast at a time
_QUERY_BLOCK = 128     # queries whose scores exist at a time


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(g)


def rotary_dim(c: Dict) -> int:
    return int(float(c["partial_rotary_factor"]) * int(c["head_dim"])) // 2 * 2


def _rotary(x, positions, theta: float, n: int):
    """x [T, heads, d] at ``positions`` [T]: the first ``n`` dimensions
    rotated, pair i with i + n/2; the rest as they are."""
    half = n // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs          # [T, half]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:n]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., n:]], axis=-1)


def _softmax_attention(q, k, v, window, sink):
    """q [T, H, d], k [T, KV, d], v [T, KV, dv] -> [T, H, dv]: dense causal
    softmax, ``window`` positions wide where it is a number, with ``sink``
    [H] in the denominator where it is an array, ``_QUERY_BLOCK`` queries at
    a time; query head h reads KV head h // (H // KV)."""
    T, H, d = q.shape
    KV, dv = k.shape[1], v.shape[2]
    qb = min(T, _QUERY_BLOCK)
    assert T % qb == 0, (T, qb)
    kv_pos = jnp.arange(T)[None, :]
    qg = q.reshape(T // qb, qb, KV, H // KV, d)

    def block(args):
        b, qs = args                                  # [qb, KV, R, d]
        i = (b * qb + jnp.arange(qb))[:, None]
        seen = kv_pos <= i
        if window is not None:
            seen = seen & (i - kv_pos < window)
        s = jnp.einsum("qgrd,kgd->grqk", qs, k, precision=_HI) * d ** -0.5
        s = jnp.where(seen, s, -jnp.inf)
        top = jnp.max(s, axis=-1, keepdims=True)
        rest = 0.0
        if sink is not None:
            b_h = _f32(sink).reshape(KV, H // KV, 1, 1)
            top = jnp.maximum(top, b_h)
            rest = jnp.exp(b_h - top)
        e = jnp.exp(s - top)
        p = e / (jnp.sum(e, axis=-1, keepdims=True) + rest)
        return jnp.einsum("grqk,kgd->qgrd", p, v, precision=_HI)

    o = jax.lax.map(block, (jnp.arange(T // qb), qg))
    return o.reshape(T, H, dv)


def attention(lw, a, window_layer: bool, c: Dict):
    """One sequence: ``a`` [T, D] the normed input -> ``concat_h(o) Wo``."""
    H, d, dv = (int(c["num_attention_heads"]), int(c["head_dim"]),
                int(c["v_head_dim"]))
    KV = int(c["swa_num_key_value_heads"] if window_layer
             else c["num_key_value_heads"])
    theta = float(c["swa_rope_theta"] if window_layer else c["rope_theta"])
    T = a.shape[0]
    q = jnp.matmul(a, _f32(lw["w_q"]), precision=_HI).reshape(T, H, d)
    k = jnp.matmul(a, _f32(lw["w_k"]), precision=_HI).reshape(T, KV, d)
    v = jnp.matmul(a, _f32(lw["w_v"]), precision=_HI).reshape(T, KV, dv)
    v = float(c["attention_value_scale"]) * v
    pos, n = jnp.arange(T), rotary_dim(c)
    q, k = _rotary(q, pos, theta, n), _rotary(k, pos, theta, n)
    sinks = bool(c["add_swa_attention_sink_bias"] if window_layer
                 else c["add_full_attention_sink_bias"])
    assert sinks == ("sink" in lw), (sinks, sorted(lw))
    o = _softmax_attention(
        q, k, v, int(c["sliding_window"]) if window_layer else None,
        lw["sink"] if sinks else None)
    return jnp.matmul(o.reshape(T, H * dv), _f32(lw["w_o"]), precision=_HI)


def _ffn(w_gate, w_up, w_down, h):
    """``W_down(silu(W_gate h) * W_up h)``, ``_BLOCK`` columns at a time."""
    out = jnp.zeros_like(h)
    for a in range(0, w_gate.shape[-1], _BLOCK):
        g = jnp.matmul(h, _f32(w_gate[:, a:a + _BLOCK]), precision=_HI)
        u = jnp.matmul(h, _f32(w_up[:, a:a + _BLOCK]), precision=_HI)
        out = out + jnp.matmul(jax.nn.silu(g) * u,
                               _f32(w_down[a:a + _BLOCK]), precision=_HI)
    return out


def router(lw, h, c):
    """(picks [..., k] int32, weights [..., k]) of one expert layer."""
    s = jax.nn.sigmoid(jnp.matmul(h, _f32(lw["router"]), precision=_HI))
    _, idx = jax.lax.top_k(s + _f32(lw["router_bias"]),
                           int(c["num_experts_per_tok"]))
    picked = jnp.take_along_axis(s, idx, axis=-1)
    if c.get("norm_topk_prob", True):
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    scale = c.get("routed_scaling_factor")
    return idx, (1.0 if scale is None else float(scale)) * picked


def routed_part(lw, h, c):
    """What the experts ``config["held"]`` names add: ``sum w_i E_i(h)`` over
    the picks that land on them."""
    held = c["held"]
    first, count = int(held["first"]), int(held["count"])
    F = int(c["moe_intermediate_size"])
    idx, w = router(lw, h, c)
    out = jnp.zeros_like(h)
    for e in range(count):                       # a loop over the experts here
        w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        gu = lw["w_gate_up"][e]
        out = out + w_e[..., None] * _ffn(gu[:, :F], gu[:, F:],
                                          lw["w_down"][e], h)
    return out


def block(lw, x, window_layer: bool, c: Dict):
    """One layer on one sequence's stream ``x`` [T, D]."""
    eps = float(c["layernorm_epsilon"])
    x = x + attention(lw, _rms(x, lw["norm_attn"], eps), window_layer, c)
    f = _rms(x, lw["norm_ffn"], eps)
    if "ffn" in lw:                              # moe_layer_freq[l] == 0
        d = lw["ffn"]
        return x + _ffn(d["w_gate"], d["w_up"], d["w_down"], f)
    return x + routed_part(lw, f, c)


def forward(w: Dict, tokens: jax.Array, config: Dict) -> jax.Array:
    """tokens [B, T] int32 -> logits [B, T, vocab rows held] float32."""
    c = config
    kinds, ffn = list(c["hybrid_layer_pattern"]), list(c["moe_layer_freq"])
    assert len(kinds) == len(ffn) == len(w["layers"]), (
        len(kinds), len(ffn), len(w["layers"]))
    out = []
    for b in range(tokens.shape[0]):
        x = _f32(w["tok_embed"][tokens[b]])
        for l, lw in enumerate(w["layers"]):
            assert ("ffn" in lw) == (ffn[l] == 0), l
            x = block(lw, x, kinds[l] == 1, c)
        x = _rms(x, w["norm_f"], float(c["layernorm_epsilon"]))
        head = w["lm_head"]
        out.append(jnp.concatenate(
            [jnp.matmul(x, _f32(head[:, a:a + _BLOCK]), precision=_HI)
             for a in range(0, head.shape[-1], _BLOCK)], axis=-1))
    return jnp.stack(out)


def weights(p: Dict) -> Dict:
    """ray_tpu.models.mimo_v2's tree -> this file's: the same arrays under
    this file's names, in the dtype the program stores (nothing is copied to
    float32 here). The only place that knows the program's layout: every
    matrix is stored ``[in, out]`` with the heads folded into the columns,
    which is this file's form too, so nothing is cut or turned."""
    def layer(lp):
        lw = {k: lp[k] for k in ("norm_attn", "norm_ffn", "w_q", "w_k", "w_v",
                                 "w_o")}
        if "sink" in lp:
            lw["sink"] = lp["sink"]
        if "ffn" in lp:
            lw["ffn"] = dict(lp["ffn"])
        else:
            lw.update(router=lp["router"], router_bias=lp["router_bias"],
                      w_gate_up=lp["experts"]["w_gate_up"],
                      w_down=lp["experts"]["w_down"])
        return lw
    return {"tok_embed": p["tok_embed"], "norm_f": p["norm_f"],
            "lm_head": p["lm_head"],
            "layers": [layer(lp) for lp in p["layers"]]}
