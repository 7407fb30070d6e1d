"""What LongCat-Flash's work weighs, from the configuration's dict alone
(``configs/longcat-flash-omni.json``: the published keys, with
``num_layers``, ``n_routed_experts`` (held here) and ``vocab_size`` as cut,
and ``held.of`` the published expert count). Matrices only: norm gains and
the router's selection bias multiply nothing."""

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
    return 3 * c["hidden_size"] * c["expert_ffn_hidden_size"]


def layer_params_outside_experts(c: Dict) -> int:
    """Two MLA sublayers, two dense gated FFNs, the router."""
    d = c["hidden_size"]
    router = d * (c["held"]["of"] + c["zero_expert_num"])
    return 2 * mla_params(c) + 2 * 3 * d * c["ffn_hidden_size"] + router


def params_per_token(c: Dict) -> float:
    """``counts.params_per_token``: what one token's forward pass multiplies
    by HERE. A layer's dense part whole; of the experts, the picks that land
    on a held one at uniform routing: ``moe_topk`` picks, ``held.of`` of the
    router's outputs routed (the rest zero-compute, no product), and
    ``n_routed_experts`` (held) of ``held.of`` of those here; the head's
    slice (the embedding is a lookup)."""
    of = c["held"]["of"]
    picks_here = (c["moe_topk"] * of / (of + c["zero_expert_num"])
                  * c["n_routed_experts"] / of)
    per_layer = layer_params_outside_experts(c) + picks_here * expert_params(c)
    return c["num_layers"] * per_layer + c["vocab_size"] * c["hidden_size"]


def kv_bytes_per_context_token(c: Dict) -> int:
    """``counts.kv_bytes_per_context_token``: one latent row (c_kv and the
    shared rotary key) in bf16 for each of the two attention sublayers of
    every layer: what one decode step reads for one token of context."""
    return (2 * c["num_layers"] * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            * 2)


def expert_weight_bytes(c: Dict) -> int:
    """``counts.expert_weight_bytes``: one expert's three matrices as stored
    (bf16): what a decode step reads for each held expert that got a token."""
    return expert_params(c) * 2
