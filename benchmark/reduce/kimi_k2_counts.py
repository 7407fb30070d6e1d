"""What Kimi-K2's work weighs, from the configuration's dict alone
(``configs/kimi-k2.5.json``: the published keys, with ``num_hidden_layers``,
``n_routed_experts`` (held here) and ``vocab_size`` as cut, and ``held.of``
the published expert count). Matrices only: norm gains and the router's
selection bias multiply nothing. The stack's layers are not all alike: the
first ``first_k_dense_replace`` have a dense FFN and no experts, so whatever
is counted a layer with experts is counted over ``expert_layers``, not over
``num_hidden_layers``."""

from __future__ import annotations

from typing import Dict


def mla_params(c: Dict) -> int:
    """One latent-attention sublayer: W_qa, W_qb, W_kva, W_kvb, W_o."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (d * c["q_lora_rank"] + c["q_lora_rank"] * h * qk
            + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            + c["kv_lora_rank"] * h * (c["qk_nope_head_dim"] + c["v_head_dim"])
            + h * c["v_head_dim"] * d)


def expert_params(c: Dict) -> int:
    """One routed expert's gated FFN: gate, up, down."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def shared_expert_params(c: Dict) -> int:
    return c["n_shared_experts"] * expert_params(c)


def expert_layers(c: Dict) -> int:
    """``counts.expert_layers``: the layers that have a router and experts."""
    return c["num_hidden_layers"] - c["first_k_dense_replace"]


def dense_layer_params(c: Dict) -> int:
    return mla_params(c) + 3 * c["hidden_size"] * c["intermediate_size"]


def expert_layer_params_outside_routed(c: Dict) -> int:
    """MLA, the router over all ``held.of`` outputs, the shared expert."""
    return (mla_params(c) + c["hidden_size"] * c["held"]["of"]
            + shared_expert_params(c))


def param_count(c: Dict) -> int:
    """Every matrix this chip holds: what the program's tree must weigh."""
    return (c["first_k_dense_replace"] * dense_layer_params(c)
            + expert_layers(c) * (expert_layer_params_outside_routed(c)
                                  + c["n_routed_experts"] * expert_params(c))
            + 2 * c["vocab_size"] * c["hidden_size"])


def params_per_token(c: Dict) -> float:
    """``counts.params_per_token``: what one token's forward pass multiplies
    by HERE. The dense layers whole; an expert layer's attention, router and
    shared expert whole, and of the routed experts the picks that land on a
    held one at uniform routing (``num_experts_per_tok`` picks,
    ``n_routed_experts`` (held) of ``held.of`` of them here); the head's
    slice (the embedding is a lookup)."""
    picks_here = (c["num_experts_per_tok"] * c["n_routed_experts"]
                  / c["held"]["of"])
    return (c["first_k_dense_replace"] * dense_layer_params(c)
            + expert_layers(c) * (expert_layer_params_outside_routed(c)
                                  + picks_here * expert_params(c))
            + c["vocab_size"] * c["hidden_size"])


def kv_bytes_per_context_token(c: Dict) -> int:
    """``counts.kv_bytes_per_context_token``: one latent row (c_kv and the
    shared rotary key) in bf16 for the one attention sublayer of every layer,
    dense and expert alike: what one decode step reads for one token of
    context."""
    return (c["num_hidden_layers"]
            * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * 2)


def expert_weight_bytes(c: Dict) -> int:
    """``counts.expert_weight_bytes``: one expert's three matrices as stored
    (bf16): what a decode step reads for each held expert that got a token."""
    return expert_params(c) * 2
