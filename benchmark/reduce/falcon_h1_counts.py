"""What Falcon-H1's work weighs, from the configuration's dict alone
(``configs/falcon-h1-34b.json``: the published keys, with
``num_hidden_layers`` and ``max_position_embeddings`` as cut). EVERY layer
keeps both kinds of sequence memory: a K and a V row a token for its
``num_key_value_heads`` (not its query heads: the pool's row is the heads
that are stored), and one state-space state a slot for its Mamba-2 mixer."""

from __future__ import annotations

from typing import Dict

STORED_BYTES = 2        # weights, activations, K/V and the tail: bfloat16
STATE_BYTES = 4         # the state-space state: float32


def conv_channels(c: Dict) -> int:
    """Channels the depthwise convolution runs over: x, then B and C of
    every group."""
    return c["mamba_d_ssm"] + 2 * c["mamba_n_groups"] * c["mamba_d_state"]


def mixer_params(c: Dict) -> int:
    """W_in (z | xBC | dt), the convolution and its bias, W_out, the gated
    norm's weight, A_log, D and dt_bias."""
    d, e, h = c["hidden_size"], c["mamba_d_ssm"], c["mamba_n_heads"]
    return (d * (e + conv_channels(c) + h)
            + (c["mamba_d_conv"] + 1) * conv_channels(c)
            + e * d + e + 3 * h)


def attention_params(c: Dict) -> int:
    """W_q and W_o over the query heads, W_k and W_v over the KV heads."""
    d, hd = c["hidden_size"], c["head_dim"]
    return (2 * d * c["num_attention_heads"] * hd
            + 2 * d * c["num_key_value_heads"] * hd)


def ffn_params(c: Dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def layer_params(c: Dict) -> int:
    """A layer whole: mixer, attention, feed-forward, its two norms."""
    return (mixer_params(c) + attention_params(c) + ffn_params(c)
            + 2 * c["hidden_size"])


def param_count(c: Dict) -> int:
    """Every parameter the chip holds: the layers, the embedding, the final
    norm and the untied head."""
    d = c["hidden_size"]
    return (c["num_hidden_layers"] * layer_params(c)
            + 2 * c["vocab_size"] * d + d)


def params_per_token(c: Dict) -> int:
    """``counts.params_per_token``: a dense model multiplies a token by every
    parameter but the embedding's, which is a lookup."""
    return param_count(c) - c["vocab_size"] * c["hidden_size"]


def kv_bytes_per_context_token(c: Dict) -> int:
    """``counts.kv_bytes_per_context_token``: a K and a V row of
    ``num_key_value_heads * head_dim`` in bf16 for every layer: what one
    decode step reads for one token of context, once for all the query
    heads that share a KV head."""
    return (c["num_hidden_layers"] * 2 * c["num_key_value_heads"]
            * c["head_dim"] * STORED_BYTES)


def recurrent_bytes_per_slot(c: Dict) -> int:
    """``counts.recurrent_bytes_per_slot``: the float32 state of every
    layer's mixer, heads x channels x state size: what the decode state
    kernel reads, and writes, for one active slot and token step."""
    return (c["num_hidden_layers"] * c["mamba_d_ssm"] * c["mamba_d_state"]
            * STATE_BYTES)


def state_bytes_per_slot(c: Dict) -> int:
    """``counts.state_bytes_per_slot``: everything a slot carries between
    tokens beside its K/V rows: the state and the convolution's tail (the
    last ``mamba_d_conv - 1`` inputs of every channel, bf16), every layer."""
    tail = (c["mamba_d_conv"] - 1) * conv_channels(c) * STORED_BYTES
    return recurrent_bytes_per_slot(c) + c["num_hidden_layers"] * tail
