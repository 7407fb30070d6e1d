"""What GLM-5's work weighs, from the configuration's dict alone
(``configs/glm-5.json``: the published keys, with ``num_hidden_layers``,
``first_k_dense_replace``, ``n_routed_experts`` (held here) and
``vocab_size`` as cut, and ``held.of`` the published expert count). Matrices
only: norm gains, the indexer's LayerNorm and the router's selection bias
multiply nothing. The first ``first_k_dense_replace`` layers have a dense FFN
and no experts, so whatever is counted a layer with experts is counted over
``expert_layers``; EVERY layer has attention and an indexer."""

from __future__ import annotations

from typing import Dict, Iterable

STORED_BYTES = 2        # bfloat16, weights and both pools


def mla_params(c: Dict) -> int:
    """One latent-attention sublayer: W_qa, W_qb, W_kva, W_kvb, W_o."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (d * c["q_lora_rank"] + c["q_lora_rank"] * h * qk
            + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            + c["kv_lora_rank"] * h * (c["qk_nope_head_dim"] + c["v_head_dim"])
            + h * c["v_head_dim"] * d)


def indexer_params(c: Dict) -> int:
    """One sublayer's indexer: W^I_qb (from the query latent), W^I_k, W^I_w."""
    hi, di = c["index_n_heads"], c["index_head_dim"]
    return (c["q_lora_rank"] * hi * di + c["hidden_size"] * di
            + c["hidden_size"] * hi)


def attention_params(c: Dict) -> int:
    return mla_params(c) + indexer_params(c)


def expert_params(c: Dict) -> int:
    """One routed expert's gated FFN: gate, up, down."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def shared_expert_params(c: Dict) -> int:
    return c["n_shared_experts"] * expert_params(c)


def router_params(c: Dict) -> int:
    """The router over all ``held.of`` outputs."""
    return c["hidden_size"] * _of(c)


def _of(c: Dict) -> int:
    return c.get("held", {}).get("of", c["n_routed_experts"])


def _held(c: Dict) -> int:
    return c.get("held", {}).get("count", c["n_routed_experts"])


def expert_layers(c: Dict) -> int:
    """``counts.expert_layers``: the layers that have a router and experts."""
    return c["num_hidden_layers"] - c["first_k_dense_replace"]


def dense_layer_params(c: Dict) -> int:
    return attention_params(c) + 3 * c["hidden_size"] * c["intermediate_size"]


def expert_layer_params_outside_routed(c: Dict) -> int:
    """Attention with its indexer, the router, the shared expert."""
    return attention_params(c) + router_params(c) + shared_expert_params(c)


def _stack(c: Dict, experts_a_layer: float, table_rows: int) -> float:
    return (c["first_k_dense_replace"] * dense_layer_params(c)
            + expert_layers(c) * (expert_layer_params_outside_routed(c)
                                  + experts_a_layer * expert_params(c))
            + table_rows * c["hidden_size"])


def param_count(c: Dict) -> int:
    """Every matrix this chip holds (the experts HELD, the embedding and the
    untied head); on the published dict (``configs/glm-5.json``'s
    ``published``: no ``held``, so all 256 experts) the whole model's 743.9B
    without its multi-token-prediction layer."""
    return int(_stack(c, _held(c), 2 * c["vocab_size"]))


def params_per_token(c: Dict) -> float:
    """``counts.params_per_token``: what one token's forward pass multiplies
    by HERE. The dense layers whole; an expert layer's attention, indexer,
    router and shared expert whole, and of the routed experts the picks that
    land on a held one at uniform routing (``num_experts_per_tok`` picks,
    ``held.count`` of ``held.of`` of them here); the head's slice (the
    embedding is a lookup). On the published dict: the 40.8B a token uses."""
    picks_here = c["num_experts_per_tok"] * _held(c) / _of(c)
    return _stack(c, picks_here, c["vocab_size"])


def latent_row_bytes(c: Dict) -> int:
    """One latent row's numbers (``c_kv`` and the shared rotary key), as
    used; the pool pads it to whole lane tiles."""
    return (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * STORED_BYTES


def index_key_bytes(c: Dict) -> int:
    return c["index_head_dim"] * STORED_BYTES


def kv_bytes_per_context_token(c: Dict) -> int:
    """``counts.kv_bytes_per_context_token``: what the two pools HOLD for one
    token of context: a latent row and an index key for every layer (read by
    the cache-share readers). NOT what a decode step reads a context token:
    past ``index_topk`` a step reads every index key and ``index_topk``
    latent rows (``attention_bytes_read``)."""
    return c["num_hidden_layers"] * (latent_row_bytes(c) + index_key_bytes(c))


def attention_bytes_read(c: Dict, contexts: Iterable[int]) -> int:
    """``counts.attention_bytes_read``: the cache bytes the decode steps
    behind tokens at these contexts (rows visible, the token's own included)
    HAD to read, whatever implements the selection: a layer and a context
    ``min(context, index_topk)`` latent rows and, where the context is past
    ``index_topk`` (else every row is chosen and no score decides anything),
    ``context`` index keys. A walk of every latent row with the unchosen
    masked, or a compact copy written and read again, reads more than this
    and reads LOW against it, never high."""
    k, layers = c["index_topk"], c["num_hidden_layers"]
    row, key = latent_row_bytes(c), index_key_bytes(c)
    return layers * sum(min(n, k) * row + (n * key if n > k else 0)
                        for n in contexts)


def expert_weight_bytes(c: Dict) -> int:
    """``counts.expert_weight_bytes``: one expert's three matrices as stored
    (bf16): what a decode step reads for each held expert that got a token."""
    return expert_params(c) * STORED_BYTES
