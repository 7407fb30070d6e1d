"""Trinity's language model (``model_type: afmoe``) in plain jax.numpy,
float32.

The benchmark's own statement of what the configuration
``configs/trinity-large-preview.json`` computes (keys as in
huggingface.co/arcee-ai/Trinity-Large-Preview ``config.json``). No cache, no
ring, no kernels, no import from the program: attention is a dense masked
softmax taken ``_QUERY_BLOCK`` queries at a time (48 heads x 8,192 x 8,192
float32 scores would be 12.9 GB whole), the expert layer a loop over
experts. Every matrix product is a ``jnp.einsum`` / ``jnp.matmul`` by name at
``highest`` precision.

Layer ``l`` on the stream ``h`` (``rms`` an RMSNorm at ``rms_norm_eps``;
what the published keys do not say is marked † and listed under ``assumed``
in the configuration's file)::

    h0      = E[tokens] * sqrt(hidden_size)                  (mup_enabled †)
    a       = rms(h; g_in)
    q, k, v = a Wq, a Wk, a Wv;   gate = a Wg †
    q, k    = rms(q; g_q), rms(k; g_k)  over head_dim †
    layer_types[l] sliding_attention: q, k rotated (rope_theta, pair i with
                   i + head_dim/2, no scaling) at absolute positions;
                   key j visible to query i  iff  j <= i and i - j < sliding_window
    layer_types[l] full_attention:    NO rotation †;  j <= i
    o       = softmax(q k^T / sqrt(head_dim)) v,  query head h on KV head h // R
    h       = h + rms((o * sigmoid(gate)) Wo; g_post_attn) †
    m       = rms(h; g_pre_mlp)
    l <  num_dense_layers:  f = Wdown(silu(Wgate m) * Wup m)   at intermediate_size
    l >= num_dense_layers:  s = sigmoid(m Wr) in float32 over held.of outputs;
                   picks = the num_experts_per_tok largest of (s + bias);
                   w = s[picks] / (sum s[picks] + 1e-20) * route_scale  (route_norm)
                   f = shared(m) at moe_intermediate_size x num_shared_experts
                     + sum_e w_e * expert_e(m) at moe_intermediate_size
    h       = h + rms(f; g_post_mlp) †
    logits  = rms(h_last; g_f) W_head                        (untied)

The share: ``config["held"] = {"first", "count", "of"}`` says which routed
experts' weights are here. The layer routes over all ``of`` and adds only the
held experts' part, plus the shared expert; with ``count == of`` it is the
uncut layer. The depth is the weights' own; ``layer_types`` and
``num_dense_layers`` are the configuration's.

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


def _rotary(x, positions, theta: float):
    """x [T, heads, d] at ``positions`` [T]: pair i with i + d/2."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs          # [T, half]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _softmax_attention(q, k, v, window):
    """q [T, H, d], k / v [T, KV, d] -> [T, H, d]: dense causal softmax,
    ``window`` positions wide where it is a number, ``_QUERY_BLOCK`` queries
    at a time; query head h reads KV head h // (H // KV)."""
    T, H, d = q.shape
    KV = k.shape[1]
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
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", p, v, precision=_HI)

    o = jax.lax.map(block, (jnp.arange(T // qb), qg))
    return o.reshape(T, H, d)


def attention(lw, a, kind: str, c: Dict):
    """One sequence: ``a`` [T, D] the normed input -> ``(o * sigmoid(gate))
    Wo`` [T, D], before the output norm."""
    H, KV, d = (int(c["num_attention_heads"]), int(c["num_key_value_heads"]),
                int(c["head_dim"]))
    eps = float(c["rms_norm_eps"])
    T = a.shape[0]
    q = jnp.matmul(a, _f32(lw["w_q"]), precision=_HI).reshape(T, H, d)
    k = jnp.matmul(a, _f32(lw["w_k"]), precision=_HI).reshape(T, KV, d)
    v = jnp.matmul(a, _f32(lw["w_v"]), precision=_HI).reshape(T, KV, d)
    gate = jnp.matmul(a, _f32(lw["w_g"]), precision=_HI)
    q, k = _rms(q, lw["q_norm"], eps), _rms(k, lw["k_norm"], eps)
    window = None
    if kind == "sliding_attention":
        pos = jnp.arange(T)
        q = _rotary(q, pos, float(c["rope_theta"]))
        k = _rotary(k, pos, float(c["rope_theta"]))
        window = int(c["sliding_window"])
    else:
        assert kind == "full_attention", kind
    o = _softmax_attention(q, k, v, window).reshape(T, H * d)
    return jnp.matmul(o * jax.nn.sigmoid(gate), _f32(lw["w_o"]), precision=_HI)


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
    if c.get("route_norm", True):
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return idx, float(c["route_scale"]) * picked


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


def block(lw, x, kind: str, c: Dict):
    """One layer on one sequence's stream ``x`` [T, D]."""
    eps = float(c["rms_norm_eps"])
    o = attention(lw, _rms(x, lw["norm_in"], eps), kind, c)
    h = x + _rms(o, lw["norm_post_attn"], eps)
    m = _rms(h, lw["norm_pre_mlp"], eps)
    if "ffn" in lw:                              # l < num_dense_layers
        f = lw["ffn"]
        f = _ffn(f["w_gate"], f["w_up"], f["w_down"], m)
    else:
        f = routed_part(lw, m, c) + shared_part(lw, m)
    return h + _rms(f, lw["norm_post_mlp"], eps)


def forward(w: Dict, tokens: jax.Array, config: Dict) -> jax.Array:
    """tokens [B, T] int32 -> logits [B, T, vocab rows held] float32."""
    c = config
    dense, kinds = int(c["num_dense_layers"]), list(c["layer_types"])
    assert len(kinds) == len(w["layers"]), (len(kinds), len(w["layers"]))
    out = []
    for b in range(tokens.shape[0]):
        x = _f32(w["tok_embed"][tokens[b]])
        if c.get("mup_enabled", True):
            x = x * float(c["hidden_size"]) ** 0.5
        for l, lw in enumerate(w["layers"]):
            assert ("ffn" in lw) == (l < dense), l
            x = block(lw, x, kinds[l], c)
        x = _rms(x, w["norm_f"], float(c["rms_norm_eps"]))
        head = w["lm_head"]
        out.append(jnp.concatenate(
            [jnp.matmul(x, _f32(head[:, a:a + _BLOCK]), precision=_HI)
             for a in range(0, head.shape[-1], _BLOCK)], axis=-1))
    return jnp.stack(out)


def weights(p: Dict) -> Dict:
    """ray_tpu.models.afmoe's tree -> this file's: the same arrays under this
    file's names, in the dtype the program stores (nothing is copied to
    float32 here). The only place that knows the program's layout: ``w_q``
    [H, D, d] and ``w_kv`` [2 KV, D, d] (K's heads, then V's) are stored a
    head first, and are cut and turned into ``w_q`` [D, H*d], ``w_k``,
    ``w_v`` [D, KV*d]."""
    def layer(lp):
        kv = lp["w_kv"]
        n, D, d = kv.shape
        flat = lambda a: jnp.transpose(a, (1, 0, 2)).reshape(D, -1)  # noqa: E731
        lw = {k: lp[k] for k in ("norm_in", "norm_post_attn", "norm_pre_mlp",
                                 "norm_post_mlp", "q_norm", "k_norm", "w_g",
                                 "w_o")}
        lw.update(w_q=flat(lp["w_q"]), w_k=flat(kv[:n // 2]),
                  w_v=flat(kv[n // 2:]))
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
