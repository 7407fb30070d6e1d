"""Falcon-H1's language model in plain jax.numpy, float32.

The benchmark's own statement of what the configuration
``configs/falcon-h1-34b.json`` computes (keys as in
huggingface.co/tiiuae/Falcon-H1-34B-Instruct ``config.json``; the layer as
``modeling_falcon_h1.py`` states it; the mixer is Mamba-2, arXiv:2405.21060).
No cache, no kernels, no chunks, no import from the program: the recurrence
is a token-by-token ``lax.scan``, attention is a full causal softmax a query
head with its KV head repeated. Every matrix product is a ``jnp.einsum`` /
``jnp.matmul`` by name at ``highest`` precision. Sizes and every multiplier
come from the configuration's dict; none is dropped or folded into a weight.

One layer on the stream ``x`` (RMSNorm at ``rms_norm_eps``, no biases but the
convolution's)::

    u  = RMSNorm_in(x)
    x' = x + ssm_out_multiplier * Mixer(u)
           + attention_out_multiplier * Attn(attention_in_multiplier * u)
    y  = x' + FFN(RMSNorm_ff(x'))
    FFN(h) = mlp_multipliers[1] * W_down(W_up h * silu(mlp_multipliers[0] * W_gate h))

``embedding_multiplier * E[token]`` goes in; after the last layer one
RMSNorm, then ``lm_head_multiplier *`` the untied head (computed in column
blocks, so that no float32 copy of the head exists).

*Attn*. ``q, k, v = W_q a, key_multiplier * W_k a, W_v a``;
``num_attention_heads`` query heads, ``num_key_value_heads`` KV heads of
``head_dim``, query head ``i`` reading KV head ``i // (heads / kv heads)``;
rotary over the whole head at ``rope_theta`` with the halves paired
(``rotate_half``: number ``j`` turns with number ``j + head_dim / 2``);
scores x ``head_dim^-1/2``; causal softmax; ``W_o``.

*Mixer*. ``[z | xBC | dt] = mup * (W_in (ssm_in_multiplier * u))``, ``mup``
carrying ``ssm_multipliers[0..4]`` over the z (``mamba_d_ssm``), x
(``mamba_d_ssm``), B and C (``mamba_n_groups * mamba_d_state`` each) and dt
(``mamba_n_heads``) channels. ``xBC`` passes a causal depthwise convolution
over time of width ``mamba_d_conv`` with a bias (zeros before the sequence's
start), then SiLU. ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``. Per
head ``h`` of group ``g``, with ``S`` in R^(mamba_d_head x mamba_d_state)
zero at the sequence's start::

    S_t = exp(dt_t A) S_(t-1) + dt_t x_t B_(g,t)^T
    y_t = S_t C_(g,t) + D x_t

then ``RMSNorm_group(y * silu(z))`` (``mamba_rms_norm`` true,
``mamba_norm_before_gate`` false: the gate first, the mean square over each
group's ``mamba_d_ssm / mamba_n_groups`` channels, one learned weight of
``mamba_d_ssm``) and ``W_out``.

Departures and assumptions (also under ``assumed`` in the configuration's
file): everything is float32 here (the program keeps the state float32 and
the rest bfloat16); ``mamba_use_mlp`` true means every layer has its
feed-forward; dt is not clamped above; weights are stored in bfloat16
(``weights`` keeps the program's arrays as they are, and ``forward`` upcasts
one matrix of one layer where it uses it: no float32 copy of a 10.5 GB tree
ever exists beside the engine's).
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
# The head (261,120 columns) and the feed-forward (21,504) go in column
# blocks, one upcast at a time: the check runs beside a 13 GB engine.
_HEAD_BLOCKS = 32
_FFN_BLOCKS = 8


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(g)


def ffn(fw, h, c):
    """``down_m * W_down(W_up h * silu(gate_m * W_gate h))``, the
    intermediate channels in blocks (a sum over them: the same number)."""
    gate_m, down_m = (float(m) for m in c["mlp_multipliers"])
    F = fw["w_gate"].shape[1]
    nb = _FFN_BLOCKS if F % _FFN_BLOCKS == 0 else 1
    B = F // nb

    def block(i, out):
        cols = lambda w: _f32(jax.lax.dynamic_slice_in_dim(  # noqa: E731
            w, i * B, B, axis=1))
        g = jnp.matmul(h, cols(fw["w_gate"]), precision=_HI) * gate_m
        u = jnp.matmul(h, cols(fw["w_up"]), precision=_HI)
        rows = _f32(jax.lax.dynamic_slice_in_dim(fw["w_down"], i * B, B))
        return out + jnp.matmul(u * jax.nn.silu(g), rows, precision=_HI)

    return jax.lax.fori_loop(0, nb, block, jnp.zeros_like(h)) * down_m


def state_space(x, dt, A, B, C, D):
    """The recurrence, token by token. ``x`` [T, H, P], ``dt`` [T, H], ``A``,
    ``D`` [H], ``B``, ``C`` [T, H, N] (a group's vectors already given to
    each of its heads) -> y [T, H, P]. ``S`` [H, P, N]."""
    H, P, N = x.shape[1], x.shape[2], B.shape[2]

    def step(S, t):
        x_t, dt_t, B_t, C_t = t
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + jnp.einsum("hp,hn->hpn", dt_t[:, None] * x_t, B_t,
                          precision=_HI))
        return S, jnp.einsum("hpn,hn->hp", S, C_t, precision=_HI) + D[:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (x, dt, B, C))
    return y


def mixer(lw, u, c):
    """u [T, D] -> [T, D]."""
    E, H, N, G, K = (int(c[k]) for k in (
        "mamba_d_ssm", "mamba_n_heads", "mamba_d_state", "mamba_n_groups",
        "mamba_d_conv"))
    T, P = u.shape[0], E // H
    mult = [float(m) for m in c["ssm_multipliers"]]
    mup = jnp.concatenate([jnp.full((w,), m, jnp.float32) for w, m in zip(
        (E, E, G * N, G * N, H), mult)])
    p = jnp.matmul(u * float(c["ssm_in_multiplier"]), _f32(lw["w_in"]),
                   precision=_HI) * mup
    z, xBC, dt = p[:, :E], p[:, E:2 * E + 2 * G * N], p[:, 2 * E + 2 * G * N:]
    padded = jnp.pad(xBC, ((K - 1, 0), (0, 0)))
    w = _f32(lw["conv"])
    xBC = jax.nn.silu(sum(padded[j:j + T] * w[j] for j in range(K))
                      + _f32(lw["conv_bias"]))
    x = xBC[:, :E].reshape(T, H, P)
    per_head = lambda a: jnp.repeat(  # noqa: E731
        a.reshape(T, G, N), H // G, axis=1)
    B, C = per_head(xBC[:, E:E + G * N]), per_head(xBC[:, E + G * N:])
    dt = jax.nn.softplus(dt + _f32(lw["dt_bias"]))
    y = state_space(x, dt, -jnp.exp(_f32(lw["A_log"])), B, C, _f32(lw["D"]))
    y = (y.reshape(T, E) * jax.nn.silu(z)).reshape(T, G, E // G)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                     + float(c["rms_norm_eps"]))
    return jnp.matmul(y.reshape(T, E) * _f32(lw["ssm_norm"]),
                      _f32(lw["w_out"]), precision=_HI)


def _rotate(x, theta):
    """x [T, heads, hd] at positions 0..T-1: number j of a head turns with
    number j + hd/2 by the angle ``t * theta^(-2j/hd)``."""
    T, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(lw, a, c):
    """a [T, D] -> [T, D]: causal softmax attention, a query head at a time,
    its KV head repeated."""
    T = a.shape[0]
    Hq, Hkv, hd = (int(c[k]) for k in (
        "num_attention_heads", "num_key_value_heads", "head_dim"))
    theta = float(c["rope_theta"])
    q = jnp.einsum("td,dhk->thk", a, _f32(lw["w_q"]), precision=_HI)
    kv = jnp.einsum("td,hdk->thk", a, _f32(lw["w_kv"]), precision=_HI)
    k, v = kv[:, :Hkv] * float(c["key_multiplier"]), kv[:, Hkv:]
    q, k = _rotate(q, theta), _rotate(k, theta)
    k, v = (jnp.repeat(x, Hq // Hkv, axis=1) for x in (k, v))
    heads = lambda x: x.transpose(1, 0, 2)  # noqa: E731
    causal = jnp.tril(jnp.ones((T, T), bool))

    def head(qkv):
        q_h, k_h, v_h = qkv
        s = jnp.matmul(q_h, k_h.T, precision=_HI) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.matmul(p, v_h, precision=_HI)

    o = jax.lax.map(head, (heads(q), heads(k), heads(v)))      # [Hq, T, hd]
    return jnp.matmul(o.transpose(1, 0, 2).reshape(T, Hq * hd),
                      _f32(lw["w_o"]), precision=_HI)


def layer(lw, x, c):
    eps = float(c["rms_norm_eps"])
    u = _rms(x, lw["norm_in"], eps)
    x = (x + float(c["ssm_out_multiplier"]) * mixer(lw, u, c)
         + float(c["attention_out_multiplier"]) * attention(
             lw, u * float(c["attention_in_multiplier"]), c))
    return x + ffn(lw["ffn"], _rms(x, lw["norm_ff"], eps), c)


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
    if int(config["num_hidden_layers"]) != len(w["layers"]):
        raise ValueError(f"{config['num_hidden_layers']} layers stated for "
                         f"{len(w['layers'])} layers of weights")

    def one(seq):
        x = _f32(w["tok_embed"][seq]) * float(config["embedding_multiplier"])
        for lw in w["layers"]:
            x = layer(lw, x, config)
        x = _rms(x, w["norm_f"], float(config["rms_norm_eps"]))
        return _head(x, w["lm_head"]) * float(config["lm_head_multiplier"])

    return jnp.stack([one(seq) for seq in tokens])


def weights(p: Dict) -> Dict:
    """ray_tpu.models.falcon_h1's tree -> this file's: the same arrays, in
    the dtype the program stores. The only place that knows the program's
    layout (here the two agree: a list of one dict a layer; ``w_q`` is
    ``[hidden, heads, head_dim]``, ``w_kv`` ``[2 KV heads, hidden,
    head_dim]`` with K's heads first, every other matrix ``[in, out]``)."""
    return {"tok_embed": p["tok_embed"], "norm_f": p["norm_f"],
            "lm_head": p["lm_head"],
            "layers": [dict(lw, ffn=dict(lw["ffn"])) for lw in p["layers"]]}
