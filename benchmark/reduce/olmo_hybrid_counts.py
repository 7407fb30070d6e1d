"""What Olmo-Hybrid's work weighs, from the configuration's dict alone
(``configs/olmo-hybrid-7b.json``: the published keys, with
``num_hidden_layers``, ``layer_types`` and ``max_position_embeddings`` as
cut). Sequence memory is of two kinds here: rows a token in the K/V pool of
the ``full_attention`` layers, and one state a slot for every
``linear_attention`` layer."""

from __future__ import annotations

from typing import Dict

LINEAR, FULL = "linear_attention", "full_attention"
STORED_BYTES = 2        # weights, activations, K/V and the tail: bfloat16
STATE_BYTES = 4         # the recurrent state: float32


def conv_channels(c: Dict) -> int:
    """Channels the depthwise convolution runs over: q, k and v."""
    return (2 * c["linear_num_key_heads"] * c["linear_key_head_dim"]
            + c["linear_num_value_heads"] * c["linear_value_head_dim"])


def ffn_params(c: Dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def linear_mixer_params(c: Dict) -> int:
    """W_qkv, W_g, W_o, the two gate projections, the convolution, A_log and
    dt_bias, the output norm."""
    d, h = c["hidden_size"], c["linear_num_value_heads"]
    v = h * c["linear_value_head_dim"]
    return (d * conv_channels(c) + 2 * d * v + 2 * d * h
            + c["linear_conv_kernel_dim"] * conv_channels(c) + 2 * h
            + c["linear_value_head_dim"])


def full_mixer_params(c: Dict) -> int:
    """W_q, W_k, W_v, W_o and the two QK-norm weights."""
    d = c["hidden_size"]
    return 4 * d * d + 2 * d


def layer_params(c: Dict, kind: str) -> int:
    """A layer whole: its mixer, its feed-forward, its two output norms."""
    mixer = linear_mixer_params(c) if kind == LINEAR else full_mixer_params(c)
    return mixer + ffn_params(c) + 2 * c["hidden_size"]


def param_count(c: Dict) -> int:
    """Every parameter the chip holds: the layers, the embedding, the final
    norm and the untied head."""
    d = c["hidden_size"]
    return (sum(layer_params(c, kind) for kind in c["layer_types"])
            + 2 * c["vocab_size"] * d + d)


def params_per_token(c: Dict) -> int:
    """``counts.params_per_token``: a dense model multiplies a token by every
    parameter but the embedding's, which is a lookup."""
    return param_count(c) - c["vocab_size"] * c["hidden_size"]


def kv_bytes_per_context_token(c: Dict) -> int:
    """``counts.kv_bytes_per_context_token``: a K and a V row of
    ``hidden_size`` (as many KV heads as query heads) in bf16 for each
    ``full_attention`` layer: what one decode step reads for one token of
    context. The ``linear_attention`` layers read none."""
    return (c["layer_types"].count(FULL) * 2 * c["num_key_value_heads"]
            * (c["hidden_size"] // c["num_attention_heads"]) * STORED_BYTES)


def recurrent_bytes_per_slot(c: Dict) -> int:
    """``counts.recurrent_bytes_per_slot``: the float32 state ``S`` of every
    ``linear_attention`` layer, heads x value x key: what the decode state
    kernel reads, and writes, for one active slot and token step."""
    return (c["layer_types"].count(LINEAR) * c["linear_num_value_heads"]
            * c["linear_value_head_dim"] * c["linear_key_head_dim"]
            * STATE_BYTES)


def state_bytes_per_slot(c: Dict) -> int:
    """``counts.state_bytes_per_slot``: everything a slot carries between
    tokens beside its K/V rows: ``S`` and the convolution's tail (the last
    ``linear_conv_kernel_dim - 1`` inputs of every channel, bf16), every
    ``linear_attention`` layer."""
    tail = ((c["linear_conv_kernel_dim"] - 1) * conv_channels(c)
            * STORED_BYTES)
    return recurrent_bytes_per_slot(c) + c["layer_types"].count(LINEAR) * tail
