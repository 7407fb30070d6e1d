"""Nemotron-H's language model in plain jax.numpy, float32.

The benchmark's own statement of what the configuration
``configs/nemotron-3-nano-30b-a3b.json`` computes (keys as in
huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 ``config.json``;
the family's paper is Nemotron-H, arXiv:2504.03624, its mixer Mamba-2,
arXiv:2405.21060). No cache, no kernels, no chunks, no grouped product, no
import from the program: the recurrence is a token-by-token ``lax.scan``,
attention a full causal softmax a query head with its KV head repeated, the
experts a loop over the ones ``held`` names. Every matrix product is a
``jnp.einsum`` / ``jnp.matmul`` by name at ``highest`` precision. Sizes and
constants come from the configuration's dict.

Character ``i`` of ``hybrid_override_pattern`` gives layer ``i``'s ONE
sublayer (RMSNorm at ``layer_norm_epsilon``, no bias but the convolution's)::

    x_(i+1) = x_i + f_i(RMSNorm_i(x_i))      f_i = mixer ('M'), experts ('E') or attention ('*')
    logits  = W_head RMSNorm_f(x_L)          E and W_head untied

*mixer*. ``d_inner = mamba_num_heads x mamba_head_dim``. ``[z | xBC | dt] =
W_in u`` (``d_inner``, ``d_inner + 2 n_groups ssm_state_size``,
``mamba_num_heads`` columns); ``xBC`` passes a causal depthwise convolution
over time of width ``conv_kernel`` with a bias (zeros before the sequence's
start), then SiLU; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``. Per
head ``h`` of group ``g``, ``S`` in R^(mamba_head_dim x ssm_state_size) zero
at the sequence's start::

    S_t = exp(dt_t A) S_(t-1) + dt_t x_t B_(g,t)^T
    y_t = S_t C_(g,t) + D x_t

then ``RMSNorm(y * silu(z))`` with the mean square over each of the
``n_groups`` groups' ``d_inner / n_groups`` channels and one learned weight
of ``d_inner``, and ``W_out``.

*experts*. ``s = sigmoid(W_r u)`` over ``held.of`` outputs; the
``num_experts_per_tok`` largest of ``s + e_score_correction_bias`` are picked
(``n_group`` = ``topk_group`` = 1: no group limit); a pick's weight is
``routed_scaling_factor * s_i``, with ``norm_topk_prob`` the picked ``s``
first divided by their sum (over ALL the picks, wherever their experts
live); ``Expert_e(u) = W_down,e act(W_up,e u)`` with ``act(h) = relu(h)^2``
(``mlp_hidden_act: relu2``: two matrices, no gate), summed over the picks
that land on experts ``held.first .. held.first + held.count - 1`` (with
``count == of`` the uncut layer); plus, for every token, the shared expert
of the same form.

*attention*. ``num_attention_heads`` query heads over ``num_key_value_heads``
KV heads of ``head_dim``, query head ``i`` reading KV head ``i // (heads / kv
heads)``; NO rotation (the Nemotron-H paper: no position embeddings; the
file's ``rope_theta`` is unused); scores x ``head_dim^-1/2``; causal
softmax; ``W_o``.

Everything is float32 here (the program keeps the state and the router
float32 and the rest bfloat16). ``weights`` keeps the program's arrays as
they are stored, and ``forward`` upcasts one matrix (one expert's, one block
of the head's columns) where it uses it: no float32 copy of the 7.85 GB tree
ever exists beside the engine's.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
# The head (65,536 columns here) goes in column blocks, one upcast at a time.
_HEAD_BLOCKS = 32
ACTIVATIONS = {"relu2": lambda h: jnp.square(jax.nn.relu(h)),
               "relu": jax.nn.relu}


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(g)


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
    H, P, N, G, K = (int(c[k]) for k in (
        "mamba_num_heads", "mamba_head_dim", "ssm_state_size", "n_groups",
        "conv_kernel"))
    T, E = u.shape[0], H * P
    p = jnp.matmul(u, _f32(lw["w_in"]), precision=_HI)
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
                     + float(c["layer_norm_epsilon"]))
    return jnp.matmul(y.reshape(T, E) * _f32(lw["ssm_norm"]),
                      _f32(lw["w_out"]), precision=_HI)


def attention(lw, a, c):
    """a [T, D] -> [T, D]: causal softmax attention, a query head at a time,
    its KV head repeated; nothing is rotated."""
    T = a.shape[0]
    Hq, Hkv, hd = (int(c[k]) for k in (
        "num_attention_heads", "num_key_value_heads", "head_dim"))
    heads = lambda w, n: jnp.matmul(  # noqa: E731
        a, _f32(w), precision=_HI).reshape(T, n, hd).transpose(1, 0, 2)
    q = heads(lw["w_q"], Hq)
    k, v = (jnp.repeat(heads(lw[n], Hkv), Hq // Hkv, axis=0)
            for n in ("w_k", "w_v"))
    causal = jnp.tril(jnp.ones((T, T), bool))

    def head(qkv):
        q_h, k_h, v_h = qkv
        s = jnp.matmul(q_h, k_h.T, precision=_HI) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.matmul(p, v_h, precision=_HI)

    o = jax.lax.map(head, (q, k, v))                           # [Hq, T, hd]
    return jnp.matmul(o.transpose(1, 0, 2).reshape(T, Hq * hd),
                      _f32(lw["w_o"]), precision=_HI)


def _ffn(w_up, w_down, h, c):
    """``W_down act(W_up h)``: the family's two-matrix feed-forward."""
    act = ACTIVATIONS[c.get("mlp_hidden_act", "relu2")]
    return jnp.matmul(act(jnp.matmul(h, _f32(w_up), precision=_HI)),
                      _f32(w_down), precision=_HI)


def router(lw, h, c):
    """(picks [T, k] int32, weights [T, k]) of one expert layer."""
    s = jax.nn.sigmoid(jnp.matmul(h, _f32(lw["router"]), precision=_HI))
    _, idx = jax.lax.top_k(s + _f32(lw["router_bias"]),
                           int(c["num_experts_per_tok"]))
    picked = jnp.take_along_axis(s, idx, axis=-1)
    if c.get("norm_topk_prob", True):
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return idx, float(c["routed_scaling_factor"]) * picked


def routed_part(lw, h, c):
    """What the experts ``config["held"]`` names add: ``sum w_i E_i(h)`` over
    the picks that land on them, one expert at a time (each upcast where it
    is used)."""
    first, count = int(c["held"]["first"]), int(c["held"]["count"])
    F = int(c["moe_intermediate_size"])
    idx, w = router(lw, h, c)

    def one(e, out):
        w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        # the published width: whatever the program stores past it is not read
        return out + w_e[:, None] * _ffn(lw["w_up"][e][:, :F],
                                         lw["w_down"][e][:F], h, c)

    return jax.lax.fori_loop(0, count, one, jnp.zeros_like(h))


def shared_part(lw, h, c):
    return _ffn(lw["shared"]["w_up"], lw["shared"]["w_down"], h, c)


def experts(lw, h, c):
    return routed_part(lw, h, c) + shared_part(lw, h, c)


SUBLAYERS = {"M": mixer, "E": experts, "*": attention}


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
    """tokens [B, T] int32 -> logits [B, T, vocab rows held] float32."""
    c = config
    kinds = str(c["hybrid_override_pattern"])
    if not int(c["num_hidden_layers"]) == len(kinds) == len(w["layers"]):
        raise ValueError(f"{c['num_hidden_layers']} layers stated, {kinds!r} "
                         f"named, {len(w['layers'])} layers of weights")
    eps = float(c["layer_norm_epsilon"])

    def one(seq):
        x = _f32(w["tok_embed"][seq])
        for kind, lw in zip(kinds, w["layers"]):
            x = x + SUBLAYERS[kind](lw, _rms(x, lw["norm"], eps), c)
        return _head(_rms(x, w["norm_f"], eps), w["lm_head"])

    return jnp.stack([one(seq) for seq in tokens])


def weights(p: Dict) -> Dict:
    """ray_tpu.models.nemotron_h's tree -> this file's: the same arrays under
    this file's names, in the dtype the program stores (nothing is copied to
    float32 here). The only place that knows the program's layout: a list of
    one dict a layer holding its norm and its kind's matrices; an attention
    layer's ``w_q`` [H, D, d] and ``w_kv`` [2 KV, d, D] (K's heads, then
    V's, each transposed) are stored a head first, and are cut and turned
    into ``w_q`` [D, H*d], ``w_k``, ``w_v`` [D, KV*d]; an expert layer's ``experts`` dict is
    flattened (its matrices may be stored wider than
    ``moe_intermediate_size``: ``routed_part`` reads the published width)."""
    def layer(lp):
        if "w_kv" in lp:
            kv = jnp.transpose(lp["w_kv"], (0, 2, 1))           # [2 KV, D, d]
            n, D, _ = kv.shape
            flat = lambda a: jnp.transpose(a, (1, 0, 2)).reshape(D, -1)  # noqa: E731
            return {"norm": lp["norm"], "w_o": lp["w_o"], "w_q": flat(lp["w_q"]),
                    "w_k": flat(kv[:n // 2]), "w_v": flat(kv[n // 2:])}
        if "experts" in lp:
            return {"norm": lp["norm"], "router": lp["router"],
                    "router_bias": lp["router_bias"],
                    "w_up": lp["experts"]["w_up"],
                    "w_down": lp["experts"]["w_down"],
                    "shared": dict(lp["shared"])}
        return dict(lp)
    return {"tok_embed": p["tok_embed"], "norm_f": p["norm_f"],
            "lm_head": p["lm_head"],
            "layers": [layer(lp) for lp in p["layers"]]}
