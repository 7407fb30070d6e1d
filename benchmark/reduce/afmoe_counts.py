"""What Trinity's (``model_type: afmoe``) work weighs, from the
configuration's dict alone (``configs/trinity-large-preview.json``: the
published keys, with ``num_hidden_layers``, ``num_dense_layers``,
``layer_types``, ``num_experts`` (held here) and ``vocab_size`` as cut, and
``held.of`` the published expert count). Matrices only: norm gains and the
router's selection bias multiply nothing.

Two things are not uniform over the stack. The first ``num_dense_layers``
layers have a dense FFN and no experts, so whatever is counted a layer with
experts is counted over ``expert_layers``. And ``layer_types`` names two
kinds of attention layer that keep their K/V in two kinds of memory: a
``full_attention`` layer a row a token of context in the paged pool, a
``sliding_attention`` layer at most ``sliding_window`` rows in a ring a slot,
whatever the context. So nothing here is "bytes a context token" for the
whole stack: ``kv_bytes_per_context_token`` is the FULL layers' alone (what
the pool holds and what a pinned block weighs), the rings are
``state_bytes_per_slot``, and what a decode step has to read at a given
context is ``attention_bytes_read``, which is not linear in it."""

from __future__ import annotations

from typing import Dict, Iterable

STORED_BYTES = 2        # weights, activations, K and V: bfloat16


def window_layers(c: Dict) -> int:
    return list(c["layer_types"]).count("sliding_attention")


def full_layers(c: Dict) -> int:
    return list(c["layer_types"]).count("full_attention")


def kv_row_bytes(c: Dict) -> int:
    """A K and a V row of one layer: ``num_key_value_heads * head_dim`` each
    (the heads that are STORED; their query heads share the read)."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * STORED_BYTES


def attention_params(c: Dict) -> int:
    """W_q, W_gate and W_o over the query heads, W_k and W_v over the KV
    heads."""
    d, hd = c["hidden_size"], c["head_dim"]
    return (3 * d * c["num_attention_heads"] * hd
            + 2 * d * c["num_key_value_heads"] * hd)


def expert_params(c: Dict) -> int:
    """One routed expert's gated FFN: gate, up, down."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def shared_expert_params(c: Dict) -> int:
    return c["num_shared_experts"] * expert_params(c)


def expert_layers(c: Dict) -> int:
    """``counts.expert_layers``: the layers that have a router and experts."""
    return c["num_hidden_layers"] - c["num_dense_layers"]


def dense_layer_params(c: Dict) -> int:
    return attention_params(c) + 3 * c["hidden_size"] * c["intermediate_size"]


def expert_layer_params_outside_routed(c: Dict) -> int:
    """Attention, the router over all ``held.of`` outputs, the shared
    expert."""
    return (attention_params(c) + c["hidden_size"] * c["held"]["of"]
            + shared_expert_params(c))


def param_count(c: Dict) -> int:
    """Every matrix this chip holds: what the program's tree must weigh
    beside its norm gains and selection biases."""
    return (c["num_dense_layers"] * dense_layer_params(c)
            + expert_layers(c) * (expert_layer_params_outside_routed(c)
                                  + c["num_experts"] * expert_params(c))
            + 2 * c["vocab_size"] * c["hidden_size"])


def params_per_token(c: Dict) -> float:
    """``counts.params_per_token``: what one token's forward pass multiplies
    by HERE. The dense layers whole; an expert layer's attention, router and
    shared expert whole, and of the routed experts the picks that land on a
    held one at uniform routing (``num_experts_per_tok`` picks,
    ``num_experts`` (held) of ``held.of`` of them here); the head's slice
    (the embedding is a lookup)."""
    picks_here = c["num_experts_per_tok"] * c["num_experts"] / c["held"]["of"]
    return (c["num_dense_layers"] * dense_layer_params(c)
            + expert_layers(c) * (expert_layer_params_outside_routed(c)
                                  + picks_here * expert_params(c))
            + c["vocab_size"] * c["hidden_size"])


def kv_bytes_per_context_token(c: Dict) -> int:
    """``counts.kv_bytes_per_context_token``: a K and a V row in bf16 for
    every FULL layer: what the paged pool holds for one token of context
    (``state_cache_share``'s pool side). NOT what a decode step reads a
    context token: the window layers' share of that stops growing at the
    window (``attention_bytes_read``)."""
    return full_layers(c) * kv_row_bytes(c)


def ring_rows(c: Dict) -> int:
    """Rows of one window layer's ring a slot: the window, rounded up to the
    ring's blocks (``window_block_tokens``), and a block more."""
    rb = c["window_block_tokens"]
    return (-(-c["sliding_window"] // rb) + 1) * rb


def state_bytes_per_slot(c: Dict) -> int:
    """``counts.state_bytes_per_slot``: what a slot carries beside its rows
    in the pool: the K and V rings of every window layer."""
    return window_layers(c) * ring_rows(c) * kv_row_bytes(c)


def attention_bytes_read(c: Dict, contexts: Iterable[int]) -> int:
    """``counts.attention_bytes_read``: the K and V bytes the decode steps
    behind tokens at these contexts (rows attended, the token's own
    included) HAD to read: a window layer ``min(context, sliding_window)``
    rows, a full layer ``context`` rows."""
    w, n_win, n_full = c["sliding_window"], window_layers(c), full_layers(c)
    rows = sum(n_win * min(n, w) + n_full * n for n in contexts)
    return rows * kv_row_bytes(c)


def expert_weight_bytes(c: Dict) -> int:
    """``counts.expert_weight_bytes``: one expert's three matrices as stored
    (bf16): what a decode step reads for each held expert that got a token."""
    return expert_params(c) * STORED_BYTES
