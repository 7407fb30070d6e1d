"""GPT-2's forward pass and loss in plain jax.numpy, float32.

No kernels, no cache, no batching tricks, and no import from the program:
this file is the benchmark's own statement of what the model computes
(Radford et al. 2019; the layout of openai-community/gpt2 ``config.json``):
learned positions, pre-layer-norm blocks, causal softmax attention scaled by
1/sqrt(head_dim), GELU (tanh approximation, ``gelu_new``), tied output head.
Every matrix multiplication runs at ``highest`` precision, because a TPU
computes a float32 matmul in bf16 passes unless told otherwise.

Weights come as a flat dict of float32 arrays in this file's own names;
``from_program_params`` maps the program's parameter tree onto them and is
the only place that knows the program's layout.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def _ln(x, g, b, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def forward(w: Dict[str, jax.Array], tokens: jax.Array, n_head: int) -> jax.Array:
    """tokens [B, T] int32 -> logits [B, T, V_rows] float32."""
    B, T = tokens.shape
    d = w["wte"].shape[1]
    hd = d // n_head
    h = w["wte"][tokens] + w["wpe"][:T][None]
    mask = jnp.tril(jnp.ones((T, T), bool))
    def block(h, lw):
        x = _ln(h, lw["ln1_g"], lw["ln1_b"])
        qkv = jnp.einsum("btd,de->bte", x, lw["w_qkv"], precision=_HI) + lw["b_qkv"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, T, n_head, hd)
        k = k.reshape(B, T, n_head, hd)
        v = v.reshape(B, T, n_head, hd)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=_HI) / math.sqrt(hd)
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        a = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=_HI).reshape(B, T, d)
        h = h + jnp.einsum("btd,de->bte", a, lw["w_o"], precision=_HI) + lw["b_o"]
        x = _ln(h, lw["ln2_g"], lw["ln2_b"])
        u = _gelu_new(jnp.einsum("btd,df->btf", x, lw["w_up"], precision=_HI) + lw["b_up"])
        return h + jnp.einsum("btf,fd->btd", u, lw["w_down"], precision=_HI) + lw["b_down"], None

    # One layer's equations, applied to the stacked per-layer weights in turn
    # (a scan only so that 48 layers compile as one).
    layers = {k: v for k, v in w.items() if k not in ("wte", "wpe", "lnf_g", "lnf_b")}
    h, _ = jax.lax.scan(block, h, layers)
    h = _ln(h, w["lnf_g"], w["lnf_b"])
    return jnp.einsum("btd,vd->btv", h, w["wte"], precision=_HI)


def loss(w: Dict[str, jax.Array], tokens: jax.Array, n_head: int) -> jax.Array:
    """Mean next-token cross-entropy over all B x (T-1) positions.

    Rows the program pads the table with take part in the softmax exactly
    as the program's do, so the two losses compare.
    """
    logits = forward(w, tokens, n_head)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(picked)


def from_program_params(p: Dict) -> Dict[str, jax.Array]:
    """ray_tpu.models.transformer's tree -> this file's flat float32 dict."""
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    b = p["blocks"]
    L, d = b["wq"].shape[0], b["wq"].shape[1]
    w_qkv = jnp.concatenate([f32(b[n]).reshape(L, d, d) for n in ("wq", "wk", "wv")], axis=-1)
    b_qkv = jnp.concatenate([f32(b[n]).reshape(L, d) for n in ("bq", "bk", "bv")], axis=-1)
    return {
        "wte": f32(p["tok_embed"]), "wpe": f32(p["pos_embed"]),
        "ln1_g": f32(b["ln1_g"]), "ln1_b": f32(b["ln1_b"]),
        "w_qkv": w_qkv, "b_qkv": b_qkv,
        "w_o": f32(b["wo"]).reshape(L, d, d), "b_o": f32(b["bo"]),
        "ln2_g": f32(b["ln2_g"]), "ln2_b": f32(b["ln2_b"]),
        "w_up": f32(b["w_up"]), "b_up": f32(b["b_up"]),
        "w_down": f32(b["w_down"]), "b_down": f32(b["b_down"]),
        "lnf_g": f32(p["lnf_g"]), "lnf_b": f32(p["lnf_b"]),
    }
