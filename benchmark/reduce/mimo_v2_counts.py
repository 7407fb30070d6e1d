"""What MiMo-V2-Flash's (``model_type: mimo_v2_flash``) work weighs, from the
configuration's dict alone (``configs/mimo-v2-flash.json``: the published
keys, with ``num_hidden_layers``, ``hybrid_layer_pattern``,
``moe_layer_freq``, ``n_routed_experts`` (held here) and ``vocab_size`` as
cut, and ``held.of`` the published expert count). Matrices only: norm gains,
the sinks and the router's selection bias multiply nothing.

Three things are not uniform over the stack. ``moe_layer_freq`` says which
layers have a dense feed-forward (0) and which experts (1), so whatever is
counted a layer with experts is counted over ``expert_layers``.
``hybrid_layer_pattern`` names two kinds of attention layer, full (0) and
window (1), that keep their K/V in two kinds of memory: a full layer a row a
token of context in the paged pool, a window layer at most ``sliding_window``
rows in a ring a slot, whatever the context. And the two kinds differ in
their KV heads (``num_key_value_heads`` full, ``swa_num_key_value_heads``
window), a head of K being ``head_dim`` wide and a head of V ``v_head_dim``,
so a row of one kind is not a row of the other. So nothing here is "bytes a
context token" for the whole stack: ``kv_bytes_per_context_token`` is the
FULL layers' alone (what the pool holds and what a pinned block weighs), the
rings are ``state_bytes_per_slot``, and what a decode step has to read at a
given context is ``attention_bytes_read``, which is not linear in it."""

from __future__ import annotations

from typing import Dict, Iterable

STORED_BYTES = 2        # weights, activations, K and V: bfloat16


def window_layers(c: Dict) -> int:
    return list(c["hybrid_layer_pattern"]).count(1)


def full_layers(c: Dict) -> int:
    return list(c["hybrid_layer_pattern"]).count(0)


def kv_heads(c: Dict, window: bool) -> int:
    return c["swa_num_key_value_heads" if window else "num_key_value_heads"]


def kv_row_bytes(c: Dict, window: bool) -> int:
    """A K and a V row of one layer of the kind: its KV heads (the heads that
    are STORED; their query heads share the read) x (``head_dim`` +
    ``v_head_dim``)."""
    return (kv_heads(c, window) * (c["head_dim"] + c["v_head_dim"])
            * STORED_BYTES)


def attention_params(c: Dict, window: bool) -> int:
    """W_q over the query heads of ``head_dim``, W_o over the query heads of
    ``v_head_dim``, W_k and W_v over the kind's KV heads."""
    d, H = c["hidden_size"], c["num_attention_heads"]
    return d * (H * (c["head_dim"] + c["v_head_dim"])
                + kv_heads(c, window) * (c["head_dim"] + c["v_head_dim"]))


def dense_ffn_params(c: Dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c: Dict) -> int:
    """One routed expert's gated FFN: gate, up, down."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c: Dict) -> int:
    """The router over all ``held.of`` outputs."""
    return c["hidden_size"] * c["held"]["of"]


def expert_layers(c: Dict) -> int:
    """``counts.expert_layers``: the layers that have a router and experts."""
    return sum(c["moe_layer_freq"])


def _layers(c: Dict):
    """(window layer?, expert layer?) of every layer held."""
    kinds, ffn = list(c["hybrid_layer_pattern"]), list(c["moe_layer_freq"])
    assert len(kinds) == len(ffn) == c["num_hidden_layers"], (kinds, ffn)
    return [(k == 1, f == 1) for k, f in zip(kinds, ffn)]


def _stack_params(c: Dict, experts_a_layer: float, table_rows: int) -> float:
    total = table_rows * c["hidden_size"]
    for window, experts in _layers(c):
        total += attention_params(c, window)
        total += (router_params(c) + experts_a_layer * expert_params(c)
                  if experts else dense_ffn_params(c))
    return total


def param_count(c: Dict) -> int:
    """Every matrix this chip holds (``n_routed_experts`` as cut is the
    experts HELD); on the published dict (``configs/...json``'s ``published``
    with ``held.of`` = its 256) the whole model's 308.8B."""
    held = c.get("held", {}).get("count", c["n_routed_experts"])
    return int(_stack_params(c, held, 2 * c["vocab_size"]))


def params_per_token(c: Dict) -> float:
    """``counts.params_per_token``: what one token's forward pass multiplies
    by HERE. Attention and the dense layer whole; an expert layer's router
    whole and of the routed experts the picks that land on a held one at
    uniform routing (``num_experts_per_tok`` picks, ``held.count`` of
    ``held.of`` of them here); the head's slice (the embedding is a
    lookup)."""
    picks_here = (c["num_experts_per_tok"] * c["held"]["count"]
                  / c["held"]["of"])
    return _stack_params(c, picks_here, c["vocab_size"])


def kv_bytes_per_context_token(c: Dict) -> int:
    """``counts.kv_bytes_per_context_token``: a K and a V row in bf16 for
    every FULL layer: what the paged pool holds for one token of context
    (``state_cache_share``'s pool side). NOT what a decode step reads a
    context token: the window layers' share of that stops growing at the
    window (``attention_bytes_read``)."""
    return full_layers(c) * kv_row_bytes(c, window=False)


def ring_rows(c: Dict) -> int:
    """Rows of one window layer's ring a slot: the window, rounded up to the
    ring's blocks (``window_block_tokens``), and a block more."""
    rb = c["window_block_tokens"]
    return (-(-c["sliding_window"] // rb) + 1) * rb


def state_bytes_per_slot(c: Dict) -> int:
    """``counts.state_bytes_per_slot``: what a slot carries beside its rows
    in the pool: the K and V rings of every window layer."""
    return window_layers(c) * ring_rows(c) * kv_row_bytes(c, window=True)


def attention_bytes_read(c: Dict, contexts: Iterable[int]) -> int:
    """``counts.attention_bytes_read``: the K and V bytes the decode steps
    behind tokens at these contexts (rows attended, the token's own
    included) HAD to read: a window layer ``min(context, sliding_window)``
    rows of its kind's width, a full layer ``context`` rows of its own."""
    w = c["sliding_window"]
    win = window_layers(c) * kv_row_bytes(c, window=True)
    full = full_layers(c) * kv_row_bytes(c, window=False)
    return sum(win * min(n, w) + full * n for n in contexts)


def attention_prefill_flops(c: Dict, prompt: int) -> int:
    """``counts.attention_prefill_flops``: the multiply-adds x 2 the two
    attention products (q k^T over ``head_dim``, p v over ``v_head_dim``) of
    a prompt of ``prompt`` tokens HAVE to make, every query head, every
    layer: a full layer's query ``i`` meets ``i + 1`` keys (causal), a window
    layer's ``min(i + 1, sliding_window)`` (banded). A walk that computes
    masked products besides (whole blocks, a chunk of heads' zero lanes)
    does more than this and reads LOW against it, never high."""
    w = c["sliding_window"]
    causal = prompt * (prompt + 1) // 2
    m = min(prompt, w)
    banded = m * (m + 1) // 2 + (prompt - m) * w
    pairs = full_layers(c) * causal + window_layers(c) * banded
    return (2 * pairs * c["num_attention_heads"]
            * (c["head_dim"] + c["v_head_dim"]))


def expert_weight_bytes(c: Dict) -> int:
    """``counts.expert_weight_bytes``: one expert's three matrices as stored
    (bf16): what a decode step reads for each held expert that got a token."""
    return expert_params(c) * STORED_BYTES
