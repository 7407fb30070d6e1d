"""Kimi-K2's language model (``model_type: kimi_k2``, the DeepSeek-V3 layer)
in plain jax.numpy, float32.

The benchmark's own statement of what the configuration
``configs/kimi-k2.5.json`` computes (keys as in
huggingface.co/moonshotai/Kimi-K2.5 ``config.json``). No cache, no kernels,
no import from the program; attention is NOT absorbed (``W_kvb`` up-projects
every row to per-head keys and values, a full causal softmax a head) and the
expert layer is a loop over experts. Every matrix product is a
``jnp.einsum`` / ``jnp.matmul`` by name at ``highest`` precision.

Layer ``l`` on the stream ``x`` (RMSNorm with ``rms_norm_eps``)::

    h  = x + MLA(RMSNorm(x))
    x' = h + F_l(RMSNorm(h))
    F_l, l <  first_k_dense_replace:  E(u) at intermediate_size
    F_l, l >= first_k_dense_replace:  sum_{i in P} w_i E_i(u) + E_shared(u)
    E(u) = W_down(silu(W_gate u) * W_up u)   (experts: moe_intermediate_size;
                                              shared: n_shared_experts x that)

Router: ``s = sigmoid(W_g u)`` over ``held.of`` outputs, float32
(``scoring_func`` sigmoid); ``P`` the ``num_experts_per_tok`` largest of
``s + b`` (``topk_method`` noaux_tc; with ``n_group = topk_group = 1`` the
group limit is the identity); ``w_i = routed_scaling_factor * s_i /
(sum_{j in P} s_j + 1e-20)`` (``norm_topk_prob``): the bias selects and never
weighs, the sum runs over all of a token's picks.

MLA: ``c_q = RMSNorm(W_qa x)``; ``q_h = W_qb c_q = [q_nope | q_pe]``;
``[c_kv | k_pe] = W_kva x``; ``c_kv = RMSNorm(c_kv)``; ``[k_nope_h | v_h] =
W_kvb c_kv``; ``score = (q_nope.k_nope + rot(q_pe).rot(k_pe)) * (qk_nope +
qk_rope)^-0.5 * m^2`` with ``m = 0.1 * mscale_all_dim * ln(factor) + 1``;
causal softmax; ``W_o``. ``rot`` is YaRN's: ``theta_i = rope_theta^(-2i/d)``,
``inv_freq_i = theta_i / factor * (1 - g_i) + theta_i * g_i``, ``g`` one
below the lower correction dimension (of ``beta_fast`` turns over
``original_max_position_embeddings``), zero above the upper one (of
``beta_slow`` turns), linear between; cos and sin times
``yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)``.
Final RMSNorm, untied head.

The share: ``config["held"] = {"first", "count", "of"}`` says which routed
experts' weights are here. The layer routes over all ``of`` and adds only the
held experts' part, plus the shared expert; with ``count == of`` it is the
uncut layer. The depth is the weights' own.

Departures and assumptions (also under ``assumed`` in the configuration's
file): the rotary pairs are (i, i + d/2) of the stored columns (the published
code de-interleaves pairs (2i, 2i+1) first; with seeded weights that is a
permutation of columns); ``b`` is seeded; weights are stored in bfloat16
(``weights`` keeps the program's arrays as they are, and ``forward`` upcasts
one matrix, one expert, one head or one block of columns where it uses it:
bfloat16 -> float32 is exact, and no float32 copy of the whole tree ever
exists beside the engine's). Wide products run in blocks of ``_BLOCK``
columns and attention a head at a time: the order of a float32 sum, not what
is summed.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
_BLOCK = 4096       # columns of a wide matrix upcast at a time


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(g)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(c: Dict) -> jax.Array:
    """The ``qk_rope_head_dim / 2`` blended frequencies."""
    y, d = c["rope_scaling"], int(c["qk_rope_head_dim"])
    base, span = float(c["rope_theta"]), float(y["original_max_position_embeddings"])
    theta = base ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d)

    def correction_dim(turns):
        return d * math.log(span / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(y["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(y["beta_slow"]))), d - 1)
    if low == high:
        high += 0.001
    g = 1.0 - jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                       / (high - low), 0.0, 1.0)
    return theta / float(y["factor"]) * (1.0 - g) + theta * g


def _rotary(x, positions, c):
    """x [T, d] at ``positions`` [T]: pair i with i + d/2."""
    y = c["rope_scaling"]
    half = x.shape[-1] // 2
    ang = positions.astype(jnp.float32)[:, None] * yarn_inv_freq(c)   # [T, half]
    m = (yarn_mscale(float(y["factor"]), float(y["mscale"]))
         / yarn_mscale(float(y["factor"]), float(y["mscale_all_dim"])))
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def softmax_scale(c: Dict) -> float:
    y = c["rope_scaling"]
    m = yarn_mscale(float(y["factor"]), float(y["mscale_all_dim"]))
    return (int(c["qk_nope_head_dim"]) + int(c["qk_rope_head_dim"])) ** -0.5 * m * m


def _mla(aw, h, c):
    """h [B, T, D] -> [B, T, D]; one sequence at a time, one head at a time."""
    nope, R = int(c["qk_nope_head_dim"]), int(c["kv_lora_rank"])
    eps, scale = float(c["rms_norm_eps"]), softmax_scale(c)

    def one(x):                                              # [T, D]
        T = x.shape[0]
        pos = jnp.arange(T)
        c_q = _rms(jnp.matmul(x, _f32(aw["w_qa"]), precision=_HI),
                   aw["q_norm"], eps)
        kva = jnp.matmul(x, _f32(aw["w_kva"]), precision=_HI)
        c_kv = _rms(kva[:, :R], aw["kv_norm"], eps)
        k_pe = _rotary(kva[:, R:], pos, c)                   # shared by heads
        causal = jnp.tril(jnp.ones((T, T), bool))

        def head(hw):
            w_q, w_k, w_v = hw                   # [r, nope+rope] [nope, R] [R, v]
            q = jnp.matmul(c_q, _f32(w_q), precision=_HI)
            k_nope = jnp.einsum("tr,nr->tn", c_kv, _f32(w_k), precision=_HI)
            v = jnp.matmul(c_kv, _f32(w_v), precision=_HI)
            s = (jnp.einsum("qn,kn->qk", q[:, :nope], k_nope, precision=_HI)
                 + jnp.einsum("qn,kn->qk", _rotary(q[:, nope:], pos, c), k_pe,
                              precision=_HI)) * scale
            p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            return jnp.matmul(p, v, precision=_HI)           # [T, v]

        o = jax.lax.map(head, (jnp.swapaxes(aw["w_qb"], 0, 1), aw["w_kb"],
                               aw["w_vb"]))                  # [H, T, v]
        return jnp.einsum("htv,hvd->td", o, _f32(aw["w_o"]), precision=_HI)

    return jnp.stack([one(h[b]) for b in range(h.shape[0])])


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
    """(picks [B, T, k] int32, weights [B, T, k]) of one expert layer."""
    s = jax.nn.sigmoid(jnp.matmul(h, _f32(lw["router"]), precision=_HI))
    _, idx = jax.lax.top_k(s + _f32(lw["router_bias"]),
                           int(c["num_experts_per_tok"]))
    picked = jnp.take_along_axis(s, idx, axis=-1)
    picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return idx, float(c["routed_scaling_factor"]) * picked


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


def shared_part(lw, h):
    s = lw["shared"]
    return _ffn(s["w_gate"], s["w_up"], s["w_down"], h)


def block(lw, x, c):
    eps = float(c["rms_norm_eps"])
    h = x + _mla(lw["attn"], _rms(x, lw["norm_attn"], eps), c)
    u = _rms(h, lw["norm_ffn"], eps)
    if "ffn" in lw:                              # l < first_k_dense_replace
        f = lw["ffn"]
        return h + _ffn(f["w_gate"], f["w_up"], f["w_down"], u)
    return h + routed_part(lw, u, c) + shared_part(lw, u)


def forward(w: Dict, tokens: jax.Array, config: Dict) -> jax.Array:
    """tokens [B, T] int32 -> logits [B, T, vocab rows held] float32."""
    x = _f32(w["tok_embed"][tokens])
    dense = int(config["first_k_dense_replace"])
    for l, lw in enumerate(w["layers"]):
        assert ("ffn" in lw) == (l < dense), l
        x = block(lw, x, config)
    x = _rms(x, w["norm_f"], float(config["rms_norm_eps"]))
    head = w["lm_head"]
    return jnp.concatenate(
        [jnp.matmul(x, _f32(head[:, a:a + _BLOCK]), precision=_HI)
         for a in range(0, head.shape[-1], _BLOCK)], axis=-1)


def weights(p: Dict) -> Dict:
    """ray_tpu.models.kimi_k2's tree -> this file's: the same arrays under
    this file's names, in the dtype the program stores (nothing is copied to
    float32 here). The only place that knows the program's layout: ``w_kb``
    [H, nope, R] and ``w_vb`` [H, R, v] are the two halves of ``W_kvb``, a
    head at a time."""
    def layer(lp):
        lw = {"attn": dict(lp["attn"]), "norm_attn": lp["norm_attn"],
              "norm_ffn": lp["norm_ffn"]}
        if "ffn" in lp:
            lw["ffn"] = dict(lp["ffn"])
        else:
            lw.update(router=lp["router"], router_bias=lp["router_bias"],
                      w_gate_up=lp["experts"]["w_gate_up"],
                      w_down=lp["experts"]["w_down"],
                      shared=dict(lp["shared"]))
        return lw
    return {"tok_embed": p["tok_embed"], "norm_f": p["norm_f"],
            "lm_head": p["lm_head"],
            "layers": [layer(lp) for lp in p["layers"]]}
