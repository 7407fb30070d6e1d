"""The causal depthwise convolution in front of a recurrent mixer, with the
tail a slot carries between tokens.

A channel's output at position ``t`` is ``sum_j w[j] x[t - (K - 1) + j]``
over the ``K`` latest inputs (zeros before the sequence's start), plus a
bias where the family has one; the activation is the caller's. Between
tokens a slot keeps the last ``K - 1`` PRE-convolution rows of every
channel: ``tail [layers, K - 1, slots, channels]``, the slots beside the
channels so that its two minor dimensions are whole tiles (three rows a slot
would pad to a tile of sixteen). Products and sums are float32, the tail is
the inputs' dtype. Three families call it (``models/olmo_hybrid.py``,
``models/falcon_h1.py``, ``models/nemotron_h.py``).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def prefill(pre, w, bias, tail, layer, slot, suffix_len):
    """One sequence from its start. ``pre`` [P, C] (a bucket, the first
    ``suffix_len`` rows real), ``w`` [K, C], ``bias`` [C] or None. Returns
    (y [P, C] float32, tail) with slot ``slot``'s tail of ``layer`` set to
    rows ``suffix_len - (K - 1) .. suffix_len - 1`` of the input."""
    K, P = w.shape[0], pre.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, pre.shape[1]), pre.dtype), pre])
    w = w.astype(jnp.float32)
    y = sum(padded[j:j + P].astype(jnp.float32) * w[j] for j in range(K))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    new_tail = lax.dynamic_slice_in_dim(padded, suffix_len, K - 1, axis=0)
    tail = lax.dynamic_update_slice(tail, new_tail[None, :, None],
                                    (layer, 0, slot, 0))
    return y, tail


def decode(pre, w, bias, tail, layer, active):
    """One token a slot. ``pre`` [S, C]; ``active`` [S] bool. Returns (y [S,
    C] float32, tail): an active slot's tail of ``layer`` takes the new row,
    a parked slot's stays bit for bit."""
    old = lax.dynamic_index_in_dim(tail, layer, axis=0, keepdims=False)
    window = jnp.concatenate([old, pre[None]], axis=0)          # [K, S, C]
    y = jnp.sum(window.astype(jnp.float32)
                * w.astype(jnp.float32)[:, None], axis=0)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    new_tail = jnp.where(active[None, :, None], window[1:], old)
    return y, lax.dynamic_update_slice(tail, new_tail[None], (layer, 0, 0, 0))
