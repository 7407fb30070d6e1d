"""GLM-5's language model (``model_type: glm_moe_dsa``: the DeepSeek-V3 layer
behind DeepSeek Sparse Attention's selection) in plain jax.numpy, float32.

The benchmark's own statement of what the configuration
``configs/glm-5.json`` computes (keys as in huggingface.co/zai-org/GLM-5
``config.json``). No cache, no kernels, no batching, no import from the
program; attention is NOT absorbed (``W_kvb`` up-projects every row to
per-head keys and values, a masked softmax a head) and the expert layer is a
loop over experts. Every matrix product is a ``jnp.einsum`` / ``jnp.matmul``
by name at ``highest`` precision.

Layer ``l`` on the stream ``x`` (RMSNorm with ``rms_norm_eps``, no biases)::

    a = RMSNorm(x)
    c_q = RMSNorm(a W_qa);  q_h = c_q W_qb = [q_nope (qk_nope_head_dim) | q_pe]
    [c | k_pe] = a W_kva;   c_kv = RMSNorm(c);  [k_nope_h | v_h] = c_kv W_kvb
    s_ij = (q_nope_i . k_nope_j + rot(q_pe_i) . rot(k_pe_j))
           * (qk_nope_head_dim + qk_rope_head_dim)^-0.5

    the indexer:  q^I_ih = c_q W^I_qb  (index_n_heads x index_head_dim)
                  k^I_j  = LayerNorm(a_j W^I_k)      one for all heads
                  rot on the first qk_rope_head_dim numbers of both
                  w_ih   = a_i W^I_w * index_n_heads^-0.5 * index_head_dim^-0.5
                  I_ij   = sum_h w_ih ReLU(q^I_ih . k^I_j),   j <= i
                  S_i    = the min(index_topk, i + 1) largest I_ij over j <= i,
                           ties to the lower position (jax.lax.top_k's order)

    p_ij = softmax over j in S_i of s_ij;  o_i = sum_j p_ij v_j
    h  = x + concat_h(o) W_o
    x' = h + F_l(RMSNorm(h))
    F_l, l <  first_k_dense_replace:  E(u) at intermediate_size
    F_l, l >= first_k_dense_replace:  sum_{i in P} w_i E_i(u) + E_shared(u)
    E(u) = W_down(silu(W_gate u) * W_up u)

``rot`` is the plain rotary (``rope_type`` default) at ``rope_theta`` over
absolute positions, pairs (i, i + d/2) of the stored columns. Router: ``s =
sigmoid(W_r u)`` over ``held.of`` outputs in float32; ``P`` the
``num_experts_per_tok`` largest of ``s + b`` (``noaux_tc``, ``n_group =
topk_group = 1``: no group limit); ``w_i = routed_scaling_factor * s_i /
sum_{j in P} s_j`` (``norm_topk_prob``): the bias selects and never weighs,
the sum runs over ALL of a token's picks. Final RMSNorm, untied head.

The share: ``config["held"] = {"first", "count", "of"}`` says which routed
experts' weights are here. The layer routes over all ``of`` and adds only the
held experts' part, plus the shared expert; with ``count == of`` it is the
uncut layer. The depth is the weights' own.

Departures and assumptions (also under ``assumed`` in the configuration's
file): the indexer's key norm is a LayerNorm with weight and bias at eps
1e-6 and its queries come from ``c_q``; the rotated part of an indexer head
is its FIRST ``qk_rope_head_dim`` numbers; the rotary pairs are (i, i + d/2)
(the published code pairs (2i, 2i+1): with seeded weights a permutation of
columns); no Hadamard rotation and no fp8 of the indexer's operands; ``b``
and the LayerNorm are seeded; the multi-token-prediction layer is left out;
weights are stored in bfloat16 (``weights`` keeps the program's arrays as
they are, and ``forward`` upcasts one matrix, one expert, one head or one
block of columns where it uses it: bfloat16 -> float32 is exact). Wide
products run in blocks of ``_BLOCK`` columns, attention a head and
``_QUERY_BLOCK`` queries at a time and the index scores ``_QUERY_BLOCK``
queries at a time: the order of a float32 sum, not what is summed.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
_BLOCK = 4096           # columns of a wide matrix upcast at a time
_QUERY_BLOCK = 128      # queries whose scores over every position exist at once
_INDEX_NORM_EPS = 1e-6


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(g)


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * _f32(g) + _f32(b)


def _rotary(x, positions, theta: float):
    """x [T, d] at ``positions`` [T]: pair i with i + d/2."""
    d = x.shape[-1]
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    ang = positions.astype(jnp.float32)[:, None] * inv              # [T, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _blocks(T: int) -> int:
    qb = min(T, _QUERY_BLOCK)
    assert T % qb == 0, (T, qb)
    return qb


def selection(iw, a, c_q, c) -> jax.Array:
    """``[T, T]`` bool: row i holds ``S_i``. One sequence; the index scores of
    ``_QUERY_BLOCK`` queries at a time."""
    T = a.shape[0]
    Hi, Di = int(c["index_n_heads"]), int(c["index_head_dim"])
    r, k = int(c["qk_rope_head_dim"]), min(int(c["index_topk"]), T)
    theta = float(c["rope_parameters"]["rope_theta"])
    pos = jnp.arange(T)

    def rot_first(x):                    # [T, Di]: the first r numbers rotate
        return jnp.concatenate([_rotary(x[:, :r], pos, theta), x[:, r:]], -1)

    q = jnp.matmul(c_q, _f32(iw["w_q"]), precision=_HI).reshape(T, Hi, Di)
    q = jax.vmap(rot_first, in_axes=1, out_axes=1)(q)
    keys = rot_first(_layer_norm(
        jnp.matmul(a, _f32(iw["w_k"]), precision=_HI), iw["k_norm"],
        iw["k_bias"], _INDEX_NORM_EPS))
    w = jnp.matmul(a, _f32(iw["w_w"]), precision=_HI) * (Hi ** -0.5 * Di ** -0.5)

    def block(args):
        qb, wb, pb = args                            # [qb, Hi, Di] [qb, Hi] [qb]
        dots = jnp.einsum("qhd,kd->qhk", qb, keys, precision=_HI)
        score = jnp.sum(jax.nn.relu(dots) * wb[:, :, None], axis=1)  # [qb, T]
        seen = pos[None, :] <= pb[:, None]
        score = jnp.where(seen, score, -jnp.inf)
        kth = jax.lax.top_k(score, k)[0][:, -1:]     # the k-th largest score
        above, tie = score > kth, score == kth
        need = k - jnp.sum(above, axis=-1, keepdims=True)
        # top_k takes ties in the order of their positions.
        chosen = above | (tie & (jnp.cumsum(tie, axis=-1) <= need))
        return chosen & seen

    qb = _blocks(T)
    split = lambda x: x.reshape(T // qb, qb, *x.shape[1:])       # noqa: E731
    return jax.lax.map(block, (split(q), split(w), split(pos))).reshape(T, T)


def _mla(aw, iw, h, c):
    """h [B, T, D] -> [B, T, D]; one sequence at a time, one head at a time."""
    nope, R = int(c["qk_nope_head_dim"]), int(c["kv_lora_rank"])
    eps = float(c["rms_norm_eps"])
    scale = (nope + int(c["qk_rope_head_dim"])) ** -0.5
    theta = float(c["rope_parameters"]["rope_theta"])

    def one(x):                                              # [T, D]
        T = x.shape[0]
        pos = jnp.arange(T)
        c_q = _rms(jnp.matmul(x, _f32(aw["w_qa"]), precision=_HI),
                   aw["q_norm"], eps)
        kva = jnp.matmul(x, _f32(aw["w_kva"]), precision=_HI)
        c_kv = _rms(kva[:, :R], aw["kv_norm"], eps)
        k_pe = _rotary(kva[:, R:], pos, theta)               # shared by heads
        chosen = selection(iw, x, c_q, c)                    # [T, T]
        qb = _blocks(T)

        def head(hw):
            w_q, w_k, w_v = hw                   # [r, nope+rope] [nope, R] [R, v]
            q = jnp.matmul(c_q, _f32(w_q), precision=_HI)
            k_nope = jnp.einsum("tr,nr->tn", c_kv, _f32(w_k), precision=_HI)
            v = jnp.matmul(c_kv, _f32(w_v), precision=_HI)
            q_pe = _rotary(q[:, nope:], pos, theta)

            def rows(args):
                qn, qp, keep = args                          # a block of queries
                s = (jnp.einsum("qn,kn->qk", qn, k_nope, precision=_HI)
                     + jnp.einsum("qn,kn->qk", qp, k_pe, precision=_HI)) * scale
                p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
                return jnp.matmul(p, v, precision=_HI)

            split = lambda a: a.reshape(T // qb, qb, a.shape[-1])  # noqa: E731
            return jax.lax.map(rows, (split(q[:, :nope]), split(q_pe),
                                      split(chosen))).reshape(T, -1)

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
    h = x + _mla(lw["attn"], lw["indexer"], _rms(x, lw["norm_attn"], eps), c)
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
    """ray_tpu.models.glm_dsa's tree -> this file's: the same arrays under
    this file's names, in the dtype the program stores (nothing is copied to
    float32 here). The only place that knows the program's layout: ``w_kb``
    [H, nope, R] and ``w_vb`` [H, R, v] are the two halves of ``W_kvb``, a
    head at a time; the indexer's ``w_q`` is [q_lora_rank, heads x head_dim]."""
    def layer(lp):
        lw = {"attn": dict(lp["attn"]), "indexer": dict(lp["indexer"]),
              "norm_attn": lp["norm_attn"], "norm_ffn": lp["norm_ffn"]}
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
