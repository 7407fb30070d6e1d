"""LongCat-Flash's language model in plain jax.numpy, float32.

The benchmark's own statement of what the configuration
``configs/longcat-flash-omni.json`` computes (the layer of the LongCat-Flash
technical report, arXiv:2509.01322; keys as in
huggingface.co/meituan-longcat/LongCat-Flash-Omni ``config.json``). No cache,
no kernels, no batching tricks, no import from the program; attention is NOT
absorbed (``W_kvb`` up-projects every cached row to per-head keys and values)
and the expert layer is a loop over experts. Every matrix product is a
``jnp.einsum`` / ``jnp.matmul`` by name at ``highest`` precision.

One block ``l`` on the stream ``x`` (RMSNorm with ``rms_norm_eps``)::

    a = x + MLA_0(RMSNorm(x))
    m = MoE(RMSNorm'(a))            # the shortcut: taken here, added at the end
    b = a + FFN_0(RMSNorm'(a))      # the same normed input as the expert layer
    c = b + MLA_1(RMSNorm(b))
    d = c + FFN_1(RMSNorm(c))
    x_next = d + m

``FFN(h) = W_down(silu(W_gate h) * W_up h)``; experts the same at
``expert_ffn_hidden_size``. Final RMSNorm, untied head.

MLA: ``c_q = RMSNorm(W_qa h)``; ``q = W_qb c_q`` (heads of ``qk_nope_head_dim
+ qk_rope_head_dim``) times ``sqrt(hidden_size / q_lora_rank)``
(``mla_scale_q_lora``); ``[c_kv, k_r] = W_kva h``; ``c_kv = RMSNorm(c_kv) *
sqrt(hidden_size / kv_lora_rank)`` (``mla_scale_kv_lora``); ``[k_nope, v] =
W_kvb c_kv`` per head; rotary (``rope_theta``, no scaling) on ``q_rope`` and
on the one ``k_r`` all heads share; causal softmax of ``q.k / sqrt(qk_nope +
qk_rope)``; ``W_o``.

Router: ``s = softmax(W_r h)`` over ``held.of + zero_expert_num`` outputs,
float32; the ``moe_topk`` largest of ``s + b`` (``b`` the selection bias);
weights ``routed_scaling_factor * s`` at the picks, not renormalised. A pick
``i < held.of`` adds ``w_i * Expert_i(h)``, a pick ``i >= held.of`` is a
zero-compute (identity) expert and adds ``w_i * h``. Nothing is dropped.

The share: ``config["held"] = {"first", "count", "of"}`` says which routed
experts' weights are here. The layer routes over all ``of`` and adds only the
held experts' part and the zero-compute part; with ``count == of`` it is the
uncut layer.

Departures and assumptions (also under ``assumed`` in the configuration's
file): SiLU as the gate's activation; the rotary pairs are (i, i + half); no
router bias in ``W_r``; no renormalisation of the top-k weights; weights
stored in bfloat16 (``weights`` keeps the program's arrays as they are, and
``forward`` upcasts one sublayer's or one expert's matrices where it uses
them: bfloat16 -> float32 is exact, and no float32 copy of the whole tree
ever exists beside the engine's).
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(g)


def _rotary(x, positions, theta):
    """x [..., T, n, d] at ``positions`` [T]: pair i with i + d/2."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs          # [T, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _mla(aw, h, c):
    B, T, D = h.shape
    nope, rp = int(c["qk_nope_head_dim"]), int(c["qk_rope_head_dim"])
    R, eps = int(c["kv_lora_rank"]), float(c["rms_norm_eps"])
    theta = float(c["rope_theta"])
    pos = jnp.arange(T)
    c_q = _rms(jnp.matmul(h, _f32(aw["w_qa"]), precision=_HI), aw["q_norm"], eps)
    q = jnp.einsum("btr,rhk->bthk", c_q, _f32(aw["w_qb"]), precision=_HI)
    q = q * math.sqrt(D / int(c["q_lora_rank"]))
    kva = jnp.matmul(h, _f32(aw["w_kva"]), precision=_HI)
    c_kv = _rms(kva[..., :R], aw["kv_norm"], eps) * math.sqrt(D / R)
    k_r = _rotary(kva[..., None, R:], pos, theta)                 # [B, T, 1, rp]
    w_kvb = jnp.concatenate([_f32(aw["w_kb"]), _f32(aw["w_vb"])], axis=-1)
    kv = jnp.einsum("btr,rhk->bthk", c_kv, w_kvb, precision=_HI)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_nope, q_rope = q[..., :nope], _rotary(q[..., nope:], pos, theta)
    s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope, precision=_HI)
         + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_r[:, :, 0], precision=_HI))
    s = s / math.sqrt(nope + rp)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=_HI)
    return jnp.einsum("bqhd,hde->bqe", o, _f32(aw["w_o"]), precision=_HI)


def _ffn(w_gate, w_up, w_down, h):
    g = jnp.matmul(h, _f32(w_gate), precision=_HI)
    u = jnp.matmul(h, _f32(w_up), precision=_HI)
    return jnp.matmul(jax.nn.silu(g) * u, _f32(w_down), precision=_HI)


def router(lw, h, c):
    """(picks [B, T, k] int32, weights [B, T, k]) of one layer."""
    s = jax.nn.softmax(
        jnp.matmul(h, _f32(lw["router"]), precision=_HI), axis=-1)
    _, idx = jax.lax.top_k(s + _f32(lw["router_bias"]), int(c["moe_topk"]))
    w = float(c["routed_scaling_factor"]) * jnp.take_along_axis(s, idx, axis=-1)
    return idx, w


def moe(lw, h, c, zero_part: bool = True):
    """The expert layer's result from the experts ``config["held"]`` names,
    plus (``zero_part``) what the zero-compute experts add."""
    held = c["held"]
    first, count, of = int(held["first"]), int(held["count"]), int(held["of"])
    F = int(c["expert_ffn_hidden_size"])
    idx, w = router(lw, h, c)
    out = jnp.zeros_like(h)
    for e in range(count):                       # a loop over the experts here
        w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        gu = lw["w_gate_up"][e]
        out = out + w_e[..., None] * _ffn(gu[:, :F], gu[:, F:],
                                          lw["w_down"][e], h)
    if zero_part:
        w_z = jnp.sum(jnp.where(idx >= of, w, 0.0), axis=-1)
        out = out + w_z[..., None] * h
    return out


def block(lw, x, c):
    eps = float(c["rms_norm_eps"])
    a = x + _mla(lw["attn"][0], _rms(x, lw["norm_attn"][0], eps), c)
    hn = _rms(a, lw["norm_ffn"][0], eps)
    m = moe(lw, hn, c)
    f = lw["ffn"][0]
    b = a + _ffn(f["w_gate"], f["w_up"], f["w_down"], hn)
    cc = b + _mla(lw["attn"][1], _rms(b, lw["norm_attn"][1], eps), c)
    f = lw["ffn"][1]
    d = cc + _ffn(f["w_gate"], f["w_up"], f["w_down"],
                  _rms(cc, lw["norm_ffn"][1], eps))
    return d + m


def forward(w: Dict, tokens: jax.Array, config: Dict) -> jax.Array:
    """tokens [B, T] int32 -> logits [B, T, vocab rows held] float32."""
    x = _f32(w["tok_embed"][tokens])
    for lw in w["layers"]:
        x = block(lw, x, config)
    x = _rms(x, w["norm_f"], float(config["rms_norm_eps"]))
    return jnp.matmul(x, _f32(w["lm_head"]), precision=_HI)


def weights(p: Dict) -> Dict:
    """ray_tpu.models.longcat's tree -> this file's: the same arrays under
    this file's names, in the dtype the program stores (nothing is copied to
    float32 here). The only place that knows the program's layout."""
    def layer(lp):
        return {"attn": [dict(a) for a in lp["attn"]],
                "ffn": [dict(f) for f in lp["ffn"]],
                "norm_attn": list(lp["norm_attn"]),
                "norm_ffn": list(lp["norm_ffn"]),
                "router": lp["router"], "router_bias": lp["router_bias"],
                "w_gate_up": lp["experts"]["w_gate_up"],
                "w_down": lp["experts"]["w_down"]}
    return {"tok_embed": p["tok_embed"], "norm_f": p["norm_f"],
            "lm_head": p["lm_head"],
            "layers": [layer(lp) for lp in p["layers"]]}
