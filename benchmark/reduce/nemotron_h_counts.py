"""What Nemotron-H's work weighs, from the configuration's dict alone
(``configs/nemotron-3-nano-30b-a3b.json``: the published keys, with
``num_hidden_layers``, ``hybrid_override_pattern``, ``n_routed_experts``
(held here), ``vocab_size`` and ``max_position_embeddings`` as cut, and
``held.of`` the published expert count).

Nothing is uniform over the stack: a layer is ONE thing, named by its
character of ``hybrid_override_pattern``, and every count is read off the
pattern. A mixer layer (``M``) keeps a float32 state a SLOT and no row a
token; an attention layer (``*``) a K and a V row a TOKEN for its
``num_key_value_heads`` and nothing a slot; an expert layer (``E``) keeps no
per-sequence memory at all. So ``kv_bytes_per_context_token`` is the
attention layers' alone, ``state_bytes_per_slot`` the mixers' alone, and
what is counted a layer with experts is counted over ``expert_layers``. An
expert is TWO matrices (``relu2``: up and down, no gate)."""

from __future__ import annotations

from typing import Dict

STORED_BYTES = 2        # weights, activations, K/V and the tail: bfloat16
STATE_BYTES = 4         # the state-space state: float32


def mixer_layers(c: Dict) -> int:
    return str(c["hybrid_override_pattern"]).count("M")


def expert_layers(c: Dict) -> int:
    """``counts.expert_layers``: the layers that have a router and experts."""
    return str(c["hybrid_override_pattern"]).count("E")


def attention_layers(c: Dict) -> int:
    return str(c["hybrid_override_pattern"]).count("*")


def d_inner(c: Dict) -> int:
    """The mixer's channels: heads x channels a head, NOT ``expand x
    hidden_size``."""
    return c["mamba_num_heads"] * c["mamba_head_dim"]


def conv_channels(c: Dict) -> int:
    """Channels the depthwise convolution runs over: x, then B and C of
    every group."""
    return d_inner(c) + 2 * c["n_groups"] * c["ssm_state_size"]


def mixer_params(c: Dict) -> int:
    """W_in (z | xBC | dt), the convolution and its bias, W_out, the gated
    norm's weight, A_log, D and dt_bias."""
    d, e, h = c["hidden_size"], d_inner(c), c["mamba_num_heads"]
    return (d * (e + conv_channels(c) + h)
            + (c["conv_kernel"] + 1) * conv_channels(c)
            + e * d + e + 3 * h)


def attention_params(c: Dict) -> int:
    """W_q and W_o over the query heads, W_k and W_v over the KV heads."""
    d, hd = c["hidden_size"], c["head_dim"]
    return (2 * d * c["num_attention_heads"] * hd
            + 2 * d * c["num_key_value_heads"] * hd)


def expert_params(c: Dict) -> int:
    """One routed expert: up and down, two matrices."""
    return 2 * c["hidden_size"] * c["moe_intermediate_size"]


def shared_expert_params(c: Dict) -> int:
    return (c["n_shared_experts"] * 2 * c["hidden_size"]
            * c["moe_shared_expert_intermediate_size"])


def router_params(c: Dict) -> int:
    """The router over all ``held.of`` outputs, and its selection bias."""
    return c["hidden_size"] * c["held"]["of"] + c["held"]["of"]


def expert_layer_params(c: Dict) -> int:
    """An expert layer as this chip holds it: ``n_routed_experts`` (held)
    routed experts, the shared expert whole, the router whole."""
    return (c["n_routed_experts"] * expert_params(c) + shared_expert_params(c)
            + router_params(c))


def param_count(c: Dict) -> int:
    """Every parameter this chip holds: each layer's sublayer and its norm,
    the embedding, the final norm and the untied head."""
    d = c["hidden_size"]
    layers = (mixer_layers(c) * mixer_params(c)
              + attention_layers(c) * attention_params(c)
              + expert_layers(c) * expert_layer_params(c)
              + c["num_hidden_layers"] * d)
    return layers + 2 * c["vocab_size"] * d + d


def params_per_token(c: Dict) -> float:
    """``counts.params_per_token``: the matrices one token's forward pass
    multiplies by HERE (as the other expert configurations count it). The
    mixers' and the attention layers' projections whole; an expert layer's
    router and shared expert whole, and of the routed experts the picks that
    land on a held one at uniform routing (``num_experts_per_tok`` picks,
    ``n_routed_experts`` (held) of ``held.of`` of them here: 3 of a token's
    6 on this chip, the other 3 on the chip that shares the layer); the
    head's slice (the embedding is a lookup, the convolution, the norms and
    the per-head scalars multiply no matrix)."""
    d, e = c["hidden_size"], d_inner(c)
    mixer = d * (e + conv_channels(c) + c["mamba_num_heads"]) + e * d
    picks_here = (c["num_experts_per_tok"] * c["n_routed_experts"]
                  / c["held"]["of"])
    experts = (d * c["held"]["of"] + shared_expert_params(c)
               + picks_here * expert_params(c))
    return (mixer_layers(c) * mixer + attention_layers(c) * attention_params(c)
            + expert_layers(c) * experts + c["vocab_size"] * d)


def kv_bytes_per_context_token(c: Dict) -> int:
    """``counts.kv_bytes_per_context_token``: a K and a V row of
    ``num_key_value_heads * head_dim`` in bf16 for every ATTENTION layer:
    what the paged pool holds, and what one decode step reads, for one token
    of context, once for all the query heads that share a KV head."""
    return (attention_layers(c) * 2 * c["num_key_value_heads"]
            * c["head_dim"] * STORED_BYTES)


def recurrent_bytes_per_slot(c: Dict) -> int:
    """``counts.recurrent_bytes_per_slot``: the float32 state of every MIXER
    layer, heads x channels x state size: what the decode state kernel
    reads, and writes, for one active slot and token step."""
    return mixer_layers(c) * d_inner(c) * c["ssm_state_size"] * STATE_BYTES


def state_bytes_per_slot(c: Dict) -> int:
    """``counts.state_bytes_per_slot``: everything a slot carries between
    tokens beside its K/V rows: the state and the convolution's tail (the
    last ``conv_kernel - 1`` inputs of every channel, bf16), every mixer
    layer."""
    tail = (c["conv_kernel"] - 1) * conv_channels(c) * STORED_BYTES
    return recurrent_bytes_per_slot(c) + mixer_layers(c) * tail


def expert_weight_bytes(c: Dict) -> int:
    """``counts.expert_weight_bytes``: one expert's two matrices as stored
    (bf16): what a decode step reads for each held expert that got a token."""
    return expert_params(c) * STORED_BYTES
