"""What LFM2's work weighs, from the configuration's dict alone
(``configs/lfm2-8b-a1b.json``: the published keys, with
``num_hidden_layers``, ``layer_types`` and ``max_position_embeddings`` as
cut; ``n_routed_experts`` the experts held here and ``held.of`` the
router's outputs, both the published ``num_experts``).

A layer is a mixer and a feed-forward, each of two kinds, and every count is
read off ``layer_types`` and ``num_dense_layers``. A convolution layer
(``conv``) keeps two rows a SLOT (the tail: the last ``conv_L_cache - 1``
inputs of its depthwise convolution) and no row a token; an attention layer
(``full_attention``) a K and a V row a TOKEN for its
``num_key_value_heads`` and nothing a slot. There is no recurrent state: no
function here counts one. The first ``num_dense_layers`` layers have a
dense gated feed-forward, the others a router and experts of the same gated
form (three matrices an expert), no shared expert. The table is tied: it is
counted once."""

from __future__ import annotations

from typing import Dict

STORED_BYTES = 2        # weights, activations, K/V and the tail: bfloat16


def conv_layers(c: Dict) -> int:
    return list(c["layer_types"]).count("conv")


def attention_layers(c: Dict) -> int:
    return list(c["layer_types"]).count("full_attention")


def expert_layers(c: Dict) -> int:
    """``counts.expert_layers``: the layers that have a router and experts."""
    return c["num_hidden_layers"] - c["num_dense_layers"]


def head_dim(c: Dict) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


def short_conv_params(c: Dict) -> int:
    """W_in (B | C | u), the taps (no bias) and W_out."""
    d = c["hidden_size"]
    return d * 3 * d + c["conv_L_cache"] * d + d * d


def attention_params(c: Dict) -> int:
    """W_q and W_o over the query heads, W_k and W_v over the KV heads, and
    the two head norms' weights."""
    d, hd = c["hidden_size"], head_dim(c)
    return (2 * d * c["num_attention_heads"] * hd
            + 2 * d * c["num_key_value_heads"] * hd + 2 * hd)


def dense_ffn_params(c: Dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c: Dict) -> int:
    """One expert: gate, up and down."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c: Dict) -> int:
    """The router over all ``held.of`` outputs, and its selection bias."""
    return c["hidden_size"] * c["held"]["of"] + c["held"]["of"]


def expert_ffn_params(c: Dict) -> int:
    """An expert layer's feed-forward as this chip holds it:
    ``n_routed_experts`` (held) experts and the router whole."""
    return c["n_routed_experts"] * expert_params(c) + router_params(c)


def _mixers(c: Dict, conv: int, attention: int) -> int:
    return conv_layers(c) * conv + attention_layers(c) * attention


def param_count(c: Dict) -> int:
    """Every parameter this chip holds: each layer's mixer, feed-forward and
    two norms, the tied table once and the final norm."""
    d = c["hidden_size"]
    return (_mixers(c, short_conv_params(c), attention_params(c))
            + c["num_dense_layers"] * dense_ffn_params(c)
            + expert_layers(c) * expert_ffn_params(c)
            + c["num_hidden_layers"] * 2 * d + c["vocab_size"] * d + d)


def params_per_token(c: Dict) -> float:
    """``counts.params_per_token``: the matrices one token's forward pass
    multiplies by HERE (as the other expert configurations count it): the
    mixers' projections whole, the dense feed-forwards whole, an expert
    layer's router and, of its experts, the picks that land on a held one at
    uniform routing (``num_experts_per_tok`` picks, ``n_routed_experts`` of
    ``held.of`` of them here: all of them where every expert is held); the
    table as the head (the embedding is a lookup; the taps, the norms and
    the bias multiply no matrix)."""
    d, hd = c["hidden_size"], head_dim(c)
    attention = (2 * d * c["num_attention_heads"] * hd
                 + 2 * d * c["num_key_value_heads"] * hd)
    picks_here = (c["num_experts_per_tok"] * c["n_routed_experts"]
                  / c["held"]["of"])
    return (_mixers(c, 4 * d * d, attention)
            + c["num_dense_layers"] * dense_ffn_params(c)
            + expert_layers(c) * (d * c["held"]["of"]
                                  + picks_here * expert_params(c))
            + c["vocab_size"] * d)


def kv_bytes_per_context_token(c: Dict) -> int:
    """``counts.kv_bytes_per_context_token``: a K and a V row of
    ``num_key_value_heads * head_dim`` in bf16 for every ATTENTION layer:
    what the paged pool holds, and what one decode step reads, for one token
    of context, once for all the query heads that share a KV head."""
    return (attention_layers(c) * 2 * c["num_key_value_heads"] * head_dim(c)
            * STORED_BYTES)


def state_bytes_per_slot(c: Dict) -> int:
    """``counts.state_bytes_per_slot``: everything a slot carries between
    tokens beside its K/V rows: the convolution's tail (the last
    ``conv_L_cache - 1`` rows ``B * u``, bf16), every convolution layer."""
    return (conv_layers(c) * (c["conv_L_cache"] - 1) * c["hidden_size"]
            * STORED_BYTES)


def expert_weight_bytes(c: Dict) -> int:
    """``counts.expert_weight_bytes``: one expert's three matrices as stored
    (bf16): what a decode step reads for each held expert that got a token."""
    return expert_params(c) * STORED_BYTES


def short_conv_weight_bytes(c: Dict) -> int:
    """``counts.short_conv_weight_bytes``: ONE convolution layer's mixer as
    stored (bf16), W_in, the taps and W_out: what a decode step has to read
    for it whatever the batch."""
    return short_conv_params(c) * STORED_BYTES


def short_conv_matched_bytes_per_step(c: Dict) -> int:
    """``counts.short_conv_matched_bytes_per_step``: W_in and the taps of
    EVERY convolution layer as stored: what the fusions that
    ``short_conv_roofline`` can tell by shape have to read a token step
    (W_out's product has the residual's shape: not among them)."""
    d = c["hidden_size"]
    return conv_layers(c) * (short_conv_weight_bytes(c)
                             - d * d * STORED_BYTES)
