"""Olmo-Hybrid's language model in plain jax.numpy, float32.

The benchmark's own statement of what the configuration
``configs/olmo-hybrid-7b.json`` computes (keys as in
huggingface.co/allenai/Olmo-Hybrid-7B ``config.json``; the linear-attention
layer is Gated DeltaNet, arXiv:2412.06464). No cache, no kernels, no chunks,
no import from the program: the recurrence is a token-by-token ``lax.scan``,
attention is a full causal softmax a head. Every matrix product is a
``jnp.einsum`` / ``jnp.matmul`` by name at ``highest`` precision. Sizes and
``layer_types`` come from the configuration's dict.

One layer on the stream ``x`` (RMSNorm at ``rms_norm_eps``, no biases)::

    h = x + RMSNorm(mixer(x))
    y = h + RMSNorm(ffn(h)),   ffn(h) = W_down (silu(W_gate h) * W_up h)

After the last layer one RMSNorm, then the untied head (computed in column
blocks, so that no float32 copy of the head exists).

``linear_attention`` mixer. ``[q~ | k~ | v~] = W_qkv x`` (``Hk dk``, ``Hk
dk``, ``Hv dv`` channels). Each channel passes a causal depthwise
convolution over time of width ``linear_conv_kernel_dim`` (zeros before the
sequence's start) and then SiLU. Per head h: ``q_h``, ``k_h`` L2-normalised,
``q_h`` scaled by ``dk^-1/2``. ``beta_h = 2 sigmoid(w_b,h . x)`` (the factor
2 is ``linear_allow_neg_eigval``). ``g_h = -exp(A_log,h) softplus(w_a,h . x +
dt_bias_h)``, ``alpha_h = exp(g_h)``. The state ``S_h`` in R^(dv x dk), zero
at the sequence's start::

    S_t = alpha_t S_(t-1) (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t

Output ``W_o [RMSNorm_dv(o_h) * silu((W_g x)_h)]_h``.

``full_attention`` mixer. ``q, k, v = W_q x, W_k x, W_v x``; RMSNorm with a
learned weight over the whole width of ``q`` and of ``k``; heads of
``hidden_size / num_attention_heads``; scores scaled by ``head_dim^-1/2``;
causal softmax; no positional rotation; ``W_o``.

Departures and assumptions (also under ``assumed`` in the configuration's
file): the residual form above (the Olmo 2/3 family's reordered norm) and
the QK-norm are the family's, not keys of ``config.json``; no rotary
embedding because ``rope_parameters.rope_theta`` is null there; the state is
float32 (here everything is); weights stored in
bfloat16 (``weights`` keeps the program's arrays as they are, and ``forward``
upcasts one matrix of one layer where it uses it: no float32 copy of the
tree ever exists beside the engine's).
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
_HEAD_BLOCKS = 8


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(g)


def _ffn(fw, h):
    g = jnp.matmul(h, _f32(fw["w_gate"]), precision=_HI)
    u = jnp.matmul(h, _f32(fw["w_up"]), precision=_HI)
    return jnp.matmul(jax.nn.silu(g) * u, _f32(fw["w_down"]), precision=_HI)


def delta_rule(q, k, v, alpha, beta):
    """The recurrence, token by token. ``q``, ``k`` [T, H, dk], ``v`` [T, H,
    dv], ``alpha``, ``beta`` [T, H] -> o [T, H, dv]. ``S`` [H, dv, dk]."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(S, x):
        q_t, k_t, v_t, a_t, b_t = x
        Sk = jnp.einsum("hvk,hk->hv", S, k_t, precision=_HI)
        S = (a_t[:, None, None] * (S - b_t[:, None, None] * jnp.einsum(
            "hv,hk->hvk", Sk, k_t, precision=_HI))
            + b_t[:, None, None] * jnp.einsum("hv,hk->hvk", v_t, k_t,
                                              precision=_HI))
        return S, jnp.einsum("hvk,hk->hv", S, q_t, precision=_HI)

    _, o = jax.lax.scan(step, jnp.zeros((H, dv, dk), jnp.float32),
                        (q, k, v, alpha, beta))
    return o


def linear_mixer(lw, x, c):
    """x [T, D] -> [T, D]."""
    H, dk, dv = (int(c["linear_num_value_heads"]),
                 int(c["linear_key_head_dim"]), int(c["linear_value_head_dim"]))
    K, eps = int(c["linear_conv_kernel_dim"]), float(c["rms_norm_eps"])
    T = x.shape[0]
    pre = jnp.matmul(x, _f32(lw["w_qkv"]), precision=_HI)
    padded = jnp.pad(pre, ((K - 1, 0), (0, 0)))
    w = _f32(lw["conv"])
    y = jax.nn.silu(sum(padded[j:j + T] * w[j] for j in range(K)))
    q = y[:, :H * dk].reshape(T, H, dk)
    k = y[:, H * dk:2 * H * dk].reshape(T, H, dk)
    v = y[:, 2 * H * dk:].reshape(T, H, dv)
    unit = lambda a: a / jnp.sqrt(  # noqa: E731
        jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    q, k = unit(q) * dk ** -0.5, unit(k)
    beta = 2.0 * jax.nn.sigmoid(jnp.matmul(x, _f32(lw["w_b"]), precision=_HI))
    g = -jnp.exp(_f32(lw["A_log"])) * jax.nn.softplus(
        jnp.matmul(x, _f32(lw["w_a"]), precision=_HI) + _f32(lw["dt_bias"]))
    o = _rms(delta_rule(q, k, v, jnp.exp(g), beta), lw["o_norm"], eps)
    gate = jnp.matmul(x, _f32(lw["w_g"]), precision=_HI)
    return jnp.matmul(o.reshape(T, H * dv) * jax.nn.silu(gate),
                      _f32(lw["w_o"]), precision=_HI)


def full_mixer(lw, x, c):
    """x [T, D] -> [T, D]: causal softmax attention, a head at a time."""
    T, D = x.shape
    H, eps = int(c["num_attention_heads"]), float(c["rms_norm_eps"])
    hd = D // H
    q = _rms(jnp.matmul(x, _f32(lw["w_q"]), precision=_HI), lw["q_norm"], eps)
    k = _rms(jnp.matmul(x, _f32(lw["w_k"]), precision=_HI), lw["k_norm"], eps)
    v = jnp.matmul(x, _f32(lw["w_v"]), precision=_HI)
    heads = lambda a: a.reshape(T, H, hd).transpose(1, 0, 2)  # noqa: E731
    causal = jnp.tril(jnp.ones((T, T), bool))

    def head(qkv):
        q_h, k_h, v_h = qkv
        s = jnp.matmul(q_h, k_h.T, precision=_HI) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.matmul(p, v_h, precision=_HI)

    o = jax.lax.map(head, (heads(q), heads(k), heads(v)))      # [H, T, hd]
    return jnp.matmul(o.transpose(1, 0, 2).reshape(T, D), _f32(lw["w_o"]),
                      precision=_HI)


def layer(lw, x, kind, c):
    eps = float(c["rms_norm_eps"])
    o = (linear_mixer(lw, x, c) if kind == "linear_attention"
         else full_mixer(lw, x, c))
    h = x + _rms(o, lw["norm_mixer"], eps)
    return h + _rms(_ffn(lw["ffn"], h), lw["norm_ffn"], eps)


def _head(x, lm_head):
    """x [T, D] @ lm_head [D, V] in column blocks, each upcast where used."""
    V = lm_head.shape[1]
    nb = _HEAD_BLOCKS if V % _HEAD_BLOCKS == 0 else 1
    B = V // nb

    def block(i, out):
        cols = jax.lax.dynamic_slice_in_dim(lm_head, i * B, B, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(
            out, jnp.matmul(x, _f32(cols), precision=_HI), i * B, axis=1)

    return jax.lax.fori_loop(0, nb, block,
                             jnp.zeros((x.shape[0], V), jnp.float32))


def forward(w: Dict, tokens: jax.Array, config: Dict) -> jax.Array:
    """tokens [B, T] int32 -> logits [B, T, vocab] float32."""
    kinds = list(config["layer_types"])
    if len(kinds) != len(w["layers"]):
        raise ValueError(f"{len(kinds)} layer_types for {len(w['layers'])} "
                         "layers of weights")

    def one(seq):
        x = _f32(w["tok_embed"][seq])
        for lw, kind in zip(w["layers"], kinds):
            x = layer(lw, x, kind, config)
        x = _rms(x, w["norm_f"], float(config["rms_norm_eps"]))
        return _head(x, w["lm_head"])

    return jnp.stack([one(seq) for seq in tokens])


def weights(p: Dict) -> Dict:
    """ray_tpu.models.olmo_hybrid's tree (a list of periods, each a list of
    its layers) -> this file's (a flat list of layers): the same arrays, in
    the dtype the program stores. The only place that knows the program's
    layout."""
    return {"tok_embed": p["tok_embed"], "norm_f": p["norm_f"],
            "lm_head": p["lm_head"],
            "layers": [dict(lw, ffn=dict(lw["ffn"]))
                       for period in p["periods"] for lw in period]}
