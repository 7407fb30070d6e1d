"""Operations and bytes that the algorithm needs, from shapes alone.

Kept with the benchmark so that no PR that claims a gain can change the
yardstick. Every function counts what the mathematics requires and nothing
the implementation adds (recomputation, padding, copies).
"""

from __future__ import annotations

import json
import os
from typing import Dict

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def gpt2_param_count(n_layer: int, n_embd: int, n_inner: int, vocab: int,
                     n_positions: int) -> int:
    """Parameters of GPT-2 with tied embeddings, biases and layer norms in.

    Per block: qkv 3d^2+3d, out d^2+d, up d*f+f, down f*d+d, two layer norms
    4d. Outside: token table V*d, positions T*d, final layer norm 2d.
    """
    d, f = n_embd, n_inner
    block = 4 * d * d + 2 * d * f + 5 * d + f + 4 * d
    return n_layer * block + vocab * d + n_positions * d + 2 * d


def train_flops_per_token(n_params: int, n_layer: int, seq: int,
                          n_embd: int) -> float:
    """6*N for the matrix multiplications (forward 2N, backward 4N) plus the
    attention scores and weighted sums, 12*L*T*d with the full T x T square
    as the usual MFU convention counts it. Recomputation is not counted."""
    return 6.0 * n_params + 12.0 * n_layer * seq * n_embd


def flash_causal_flops(batch: int, heads: int, seq: int, head_dim: int,
                       backward: bool = True) -> float:
    """Causal attention for one layer: only the lower triangle is needed.

    Forward: QK^T and PV, 2 matmuls of 2*T*T*D flops per head, halved by
    causality -> 2*T^2*D. Backward needs dV, dP, dQ, dK (4 matmuls; the
    recomputed QK^T is the implementation's, not the algorithm's) -> 4*T^2*D.
    """
    fwd = 2.0 * seq * seq * head_dim
    total = fwd + (2.0 * fwd if backward else 0.0)
    return batch * heads * total


def paged_decode_kv_bytes(context_tokens: float, heads: int, head_dim: int,
                          n_layer: int, dtype_bytes: int = 2) -> float:
    """K and V bytes one decode step must read for ``context_tokens`` summed
    over the active slots: tokens x heads x head_dim x (K and V) x bytes x
    layers. Queries, outputs and block tables are left out (they are small)."""
    return context_tokens * heads * head_dim * 2 * dtype_bytes * n_layer
