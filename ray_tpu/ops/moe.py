"""Mixture-of-Experts FFN — expert parallelism over the ``expert`` mesh axis.

Absent from the reference entirely (SURVEY §2.4: EP "Absent from Ray
itself"); TPU-native it is a mesh axis: experts shard onto ``expert``, tokens
route to their expert via ``all_to_all`` over ICI, compute locally, and route
back. Static shapes throughout (XLA requirement): per-expert capacity is
fixed and overflow tokens drop (standard Switch-style capacity factor).

Layout: tokens [B, S, D] → top-1 router → dispatch [E, C, D] (E experts,
C capacity) → expert FFN → combine back to [B, S, D] weighted by router
probability. Under ``shard_map`` the E axis is sharded on ``expert`` so each
device runs only its local experts; the dispatch/combine einsums become
all_to_all-style collectives compiled by XLA.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.layers import gelu


@dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int
    num_experts: int
    capacity_factor: float = 1.25
    dtype: Any = jnp.float32

    def capacity(self, tokens_per_batch: int) -> int:
        c = int(self.capacity_factor * tokens_per_batch / self.num_experts)
        return max(4, ((c + 3) // 4) * 4)  # pad to a friendly multiple


def init_params(cfg: MoEConfig, key: jax.Array) -> Dict:
    k1, k2, k3 = jax.random.split(key, 3)
    E, D, F = cfg.num_experts, cfg.d_model, cfg.d_ff
    return {
        "router": (jax.random.normal(k1, (D, E)) * 0.02).astype(cfg.dtype),
        "w_up": (jax.random.normal(k2, (E, D, F)) * (2.0 / D) ** 0.5).astype(cfg.dtype),
        "w_down": (jax.random.normal(k3, (E, F, D)) * (2.0 / F) ** 0.5).astype(cfg.dtype),
    }


def logical_axes(cfg: MoEConfig) -> Dict:
    return {
        "router": (None, None),
        "w_up": ("experts", "embed", "mlp"),
        "w_down": ("experts", "mlp", "embed"),
    }


def moe_ffn(params: Dict, x: jax.Array, cfg: MoEConfig) -> Tuple[jax.Array, Dict]:
    """Top-1 (Switch) MoE FFN. x: [B, S, D] → ([B, S, D], aux metrics).

    Pure function of static shapes — safe inside jit/shard_map; the caller
    shards ``w_up``/``w_down`` on the ``expert`` axis via logical rules.
    """
    B, S, D = x.shape
    E = cfg.num_experts
    T = B * S
    C = cfg.capacity(T)
    flat = x.reshape(T, D)

    logits = jnp.einsum("td,de->te", flat.astype(jnp.float32), params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)          # [T, E]
    expert_idx = jnp.argmax(probs, axis=-1)          # [T]
    gate = jnp.max(probs, axis=-1)                   # [T]

    # position of each token within its expert's queue
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32)       # [T, E]
    pos_in_expert = (jnp.cumsum(onehot, axis=0) - 1) * onehot      # [T, E]
    pos = jnp.sum(pos_in_expert, axis=-1)                          # [T]
    keep = pos < C                                                  # overflow drops

    # dispatch tensor [T, E, C] — one-hot of (expert, slot); overflow tokens
    # map to slot C which is sliced away
    slot_onehot = jax.nn.one_hot(
        jnp.where(keep, pos, C), C + 1, dtype=jnp.float32
    )[:, :C]  # [T, C]
    dispatch = onehot.astype(jnp.float32)[:, :, None] * slot_onehot[:, None, :]  # [T, E, C]
    expert_in = jnp.einsum("tec,td->ecd", dispatch, flat.astype(jnp.float32))  # [E, C, D]

    h = jnp.einsum("ecd,edf->ecf", expert_in, params["w_up"].astype(jnp.float32))
    h = gelu(h)
    out = jnp.einsum("ecf,efd->ecd", h, params["w_down"].astype(jnp.float32))  # [E, C, D]

    combine = dispatch * (gate * keep)[:, None, None]               # [T, E, C]
    y = jnp.einsum("tec,ecd->td", combine, out).astype(x.dtype)     # [T, D]

    # load-balancing auxiliary loss (Switch: E * sum_e f_e * P_e)
    frac_tokens = jnp.mean(onehot.astype(jnp.float32), axis=0)      # [E]
    frac_probs = jnp.mean(probs, axis=0)                            # [E]
    aux_loss = E * jnp.sum(frac_tokens * frac_probs)
    metrics = {
        "aux_loss": aux_loss,
        "dropped_fraction": 1.0 - jnp.mean(keep.astype(jnp.float32)),
    }
    return y.reshape(B, S, D), metrics


# ---------------------------------------------------------------------------
# Top-k dropless routing over the experts held here (serve path)
# ---------------------------------------------------------------------------
# The Switch layer above holds every expert and drops overflow. The layer
# below is what an expert-parallel SERVING deployment asks of one chip: it is
# told which experts it holds, routes over ALL of them (and over the
# zero-compute experts, which need no weights), and computes the part of the
# result that its own experts give. What the absent experts would have added
# is left out; no code stands in for the other chips or for their exchange.

# Per-call pick counts, in the order of the array ``held_experts_ffn`` returns
# (a model family names its serve counters after them): picks, zero-compute
# picks, picks on held experts, the busiest held expert's pairs, held experts
# with at least one pair; then 1 for a call that walked its pairs (the bounded
# row buffer's windows or the capacity form's passes, below) and the windows or
# passes it walked beyond the first (or, for a small call of the capacity form,
# which walks nothing, 1 if an expert's rows overflowed it into the all-rows
# product).
PICK_COUNT_NAMES = ("picks", "picks_zero", "picks_held", "held_pairs_max",
                    "experts_hit", "bounded_calls", "extra_windows")
PICK_COUNTS = len(PICK_COUNT_NAMES)

# The row buffer of the grouped products. ``N * k`` rows is the most that can
# land on this chip; what does land is its share of the picks, ``N * k *
# count / n_routed`` under an even router: 3% of the worst case where 12 of
# 384 experts are held. So the buffer is bounded by twice that share, rounded
# up to the grouped product's row tile, and the pairs, sorted by expert, are
# walked in windows of that many rows until none is left: one window under an
# even load, as many as the load asks otherwise. No pick is dropped. Where
# the bound is not clearly under ``N * k`` (past a quarter of it: every decode
# program, the smallest prefill buckets) the buffer keeps its ``N * k`` rows
# and there is no walk. The bound is read off the static shapes alone.
_ROW_TILE = 512
_SHARE_FACTOR = 2


def held_row_bound(n_tokens: int, topk: int, held: Tuple[int, int],
                   n_routed: int) -> Optional[int]:
    """Rows of ``held_experts_ffn``'s buffer where it is bounded by the
    chip's share of the picks (a multiple of the row tile), None where it
    keeps all ``n_tokens * topk`` rows."""
    pairs = n_tokens * topk
    tiles = -(-_SHARE_FACTOR * pairs * held[1] // (n_routed * _ROW_TILE))
    rows = max(tiles, 1) * _ROW_TILE
    return rows if 4 * rows <= pairs else None


# Where MANY experts are held (a chip that holds 64 of 128), the grouped
# product is the wrong tool: it visits every group with a whole row tile, so
# its work grows with the groups, not the pairs (on the chip, 64 groups over
# 768 rows ran at 11% of its bytes' time, 6.4 ms a product where the weights
# stream in 0.8, and a layer took 15-17 ms over 1,536, 3,072 or 6,144 rows
# alike; at 12-16 groups the same product reads 60%). There the pairs are laid
# out ``cap`` rows an expert and multiplied by a plain batched product, which
# streams each expert's matrices once. A small call (a decode step, the
# smallest buckets: a mean of at most ``_CAPACITY_MAX_MEAN`` pairs an expert)
# takes ``_CAPACITY`` rows, eight times the mean and more, and a call in which
# some expert got more rows than that takes the all-rows grouped product
# instead (both are in the program, a ``lax.cond`` picks). A larger call (a
# prefill bucket) WALKS: pass ``i`` multiplies each expert's pairs ``[i * cap,
# (i + 1) * cap)``, until the busiest expert has none left, and there is no
# grouped product in the program. A pass streams every held expert's matrices
# again (2.2-2.5 ms at 64 experts of 2,688 x 1,920 up to 128 rows an expert;
# 3.0-3.8 ms at 256), so ``cap`` is ``_CAPACITY_FACTOR`` times the even
# router's mean, rounded up to a power of two: the busiest expert of a seeded
# router gets ~4 times the mean (one maximum among 64 spreads wider than the
# sum over the chip's share, which ``_SHARE_FACTOR`` bounds), and twice the
# mean walked a second pass in 41-62 of 100 calls where four times walks one
# in 8-10. Past ``_CAPACITY_MAX`` rows a pass is bound by its products, not by
# the weights it streams, and two passes cost what one of twice the rows
# would: ``cap`` stops there. Either way no pick is dropped.
_CAPACITY = 64
_CAPACITY_MIN_EXPERTS = 32
_CAPACITY_MAX_MEAN = 8
_CAPACITY_FACTOR = 4
_CAPACITY_MAX = 256


def _one_product_call(n_tokens: int, topk: int, n_routed: int) -> bool:
    """A call small enough for ONE product of ``_CAPACITY`` rows an expert
    (a decode step, the smallest buckets); a larger one walks in passes."""
    return n_tokens * topk <= _CAPACITY_MAX_MEAN * n_routed


def held_capacity(n_tokens: int, topk: int, held: Tuple[int, int],
                  n_routed: int) -> Optional[int]:
    """Rows an expert of the capacity form, where ``held_experts_ffn`` takes
    it: at least ``_CAPACITY_MIN_EXPERTS`` experts held; None elsewhere. Up to
    a mean of ``_CAPACITY_MAX_MEAN`` pairs an expert under an even router,
    ``_CAPACITY`` rows; past it ``_CAPACITY_FACTOR`` times the mean, rounded
    up to a power of two, from ``_CAPACITY`` to ``_CAPACITY_MAX``. Read off
    the static shapes alone."""
    if held[1] < _CAPACITY_MIN_EXPERTS:
        return None
    if _one_product_call(n_tokens, topk, n_routed):
        return _CAPACITY
    rows = -(-_CAPACITY_FACTOR * n_tokens * topk // n_routed)
    return min(max(_CAPACITY, 1 << (rows - 1).bit_length()), _CAPACITY_MAX)


def route_topk(h: jax.Array, w_router: jax.Array, bias: jax.Array, *,
               topk: int, scale: float, score: str = "softmax",
               renormalise: bool = False) -> Tuple[jax.Array, jax.Array]:
    """h [N, D] -> (idx [N, topk] int32, weights [N, topk] float32).

    ``s = score(W_r h)`` in float32 over every router output (routed and
    zero-compute experts alike): a ``"softmax"`` over the outputs, or each
    output's own ``"sigmoid"``. The ``topk`` largest of ``s + bias`` are
    picked (the bias selects, it never weighs), and a pick's weight is
    ``scale * s`` there; with ``renormalise`` the picked ``s`` are first
    divided by their sum, so a token's weights sum to ``scale`` wherever its
    picks lie: the sum runs over ALL its picks, those on experts that are
    held elsewhere too, which is what lets the shares of an expert-parallel
    deployment add up to the whole layer."""
    if score not in ("softmax", "sigmoid"):
        raise ValueError(f"route_topk: no scoring rule {score!r}")
    logits = jnp.einsum("nd,de->ne", h.astype(jnp.float32),
                        w_router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    s = (jax.nn.softmax(logits, axis=-1) if score == "softmax"
         else jax.nn.sigmoid(logits))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), topk)
    picked = jnp.take_along_axis(s, idx, axis=-1)
    if renormalise:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), scale * picked


# What one expert computes, and so what its first matrix holds.
EXPERT_FORMS = ("silu_gate", "relu2")


def _expert_hidden(p, F: int, form: str):
    """The first product's rows ``p`` -> what ``w_down`` multiplies, float32:
    ``silu(gate) * up`` of ``[rows, 2F]`` (gate | up), or ``relu(up)^2`` of
    ``[rows, F]`` (two matrices an expert, no gate)."""
    if form == "silu_gate":
        return jax.nn.silu(p[:, :F]) * p[:, F:]
    return jnp.square(jax.nn.relu(p))


def _add_rows_by_token(out, y, tok, in_group, seg_max: int):
    """``out[tok[r]] += y[r]`` over the rows ``in_group``, in float32 and
    with no scatter of rows (on the chip ``out.at[tok].add(y)`` of 1,024
    rows of 7,168 took 1.56 ms, a layer 1.4 ms more than with this): the
    window's rows are sorted by token, each token's rows (``seg_max`` at
    most) are summed onto its first by doubling strides, and every token
    that has a row gathers its first."""
    N, R = out.shape[0], y.shape[0]
    key = jnp.where(in_group, tok, N)
    perm = jnp.argsort(key, stable=True)
    ts, ys = key[perm], y[perm]
    stride = 1
    while stride < seg_max:
        same = jnp.concatenate(
            [ts[stride:] == ts[:-stride], jnp.zeros((stride,), bool)])
        ahead = jnp.concatenate(
            [ys[stride:], jnp.zeros((stride, ys.shape[1]), ys.dtype)])
        ys = ys + jnp.where(same[:, None], ahead, 0.0)
        stride *= 2
    rows_of = jnp.zeros((N + 1,), jnp.int32).at[key].add(1)[:N]
    head = jnp.minimum(jnp.cumsum(rows_of) - rows_of, R - 1)
    return out + jnp.where((rows_of > 0)[:, None], ys[head], 0.0)


def _walk_held_pairs(h, weights, w_in, w_down, order, sizes, rows: int,
                     form: str):
    """The held pairs' weighted sum [N, D] float32 through a buffer of
    ``rows`` rows: window ``i`` takes sorted pairs ``[i * rows, (i + 1) *
    rows)``, each group's size clipped to it (a group that straddles an edge
    is multiplied part by part), and adds each row, weighed by its pick's
    weight, to its token's. Returns (sum, windows walked)."""
    N, D = h.shape
    k = weights.shape[1]
    F = w_down.shape[1]
    held_pairs = jnp.sum(sizes)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    order = jnp.pad(order, (0, -(N * k) % rows))     # whole windows to slice
    w_flat = weights.reshape(-1)
    seg_max = min(k, sizes.shape[0])     # a token's pairs: k distinct experts

    def window(carry):
        i, out = carry
        lo = i * rows
        pair = jax.lax.dynamic_slice(order, (lo,), (rows,))
        tok = pair // k
        in_group = lo + jnp.arange(rows, dtype=jnp.int32) < held_pairs
        cut = jnp.clip(jnp.minimum(ends, lo + rows) - jnp.maximum(starts, lo),
                       0)
        with jax.named_scope("moe_experts"):
            gu = jax.lax.ragged_dot(h[tok], w_in, cut,
                                    preferred_element_type=jnp.float32)
            a = _expert_hidden(gu, F, form).astype(h.dtype)
            y = jax.lax.ragged_dot(a, w_down, cut,
                                   preferred_element_type=jnp.float32)
        # Rows past the last pair belong to no group: their product is not
        # defined, so they are cut out before the weights touch them.
        y = jnp.where(in_group[:, None], y * w_flat[pair][:, None], 0.0)
        return i + 1, _add_rows_by_token(out, y, tok, in_group, seg_max)

    windows, out = jax.lax.while_loop(
        lambda carry: carry[0] * rows < held_pairs, window,
        (jnp.int32(0), jnp.zeros((N, D), jnp.float32)))
    return out, windows


def _capacity_held_pairs(h, w_held, key, w_in, w_down, order, sizes,
                         cap: int, form: str, lo=None):
    """The held pairs' weighted sum [N, D] float32 with the pairs laid out
    ``cap`` rows an expert: expert ``e``'s rows are sorted pairs ``[start_e,
    start_e + size_e)``, the rest of its ``cap`` rows zeros. Two batched
    products ``[E, cap, D] x [E, D, F]`` and ``[E, cap, F] x [E, F, D]``;
    then every pair gathers its row. With no ``lo`` the caller has checked
    ``max(sizes) <= cap``; with ``lo`` (a pass of :func:`_walk_capacity`) the
    rows are each expert's pairs ``[lo, lo + cap)`` and a pair outside them
    weighs 0."""
    N, D = h.shape
    k = w_held.shape[1]
    E, F = sizes.shape[0], w_down.shape[1]
    starts = jnp.cumsum(sizes) - sizes
    if lo is not None:
        starts, sizes = starts + lo, sizes - lo
    slot = jnp.arange(cap, dtype=jnp.int32)
    pos = jnp.minimum(starts[:, None] + slot[None, :], N * k - 1)
    live = slot[None, :] < sizes[:, None]                        # [E, cap]
    x = jnp.where(live[..., None], h[order[pos] // k], 0)        # [E, cap, D]
    with jax.named_scope("moe_experts"):
        p = jnp.einsum("ecd,edf->ecf", x, w_in,
                       preferred_element_type=jnp.float32)
        a = _expert_hidden(p.reshape(E * cap, -1), F, form).astype(h.dtype)
        y = jnp.einsum("ecf,efd->ecd", a.reshape(E, cap, F), w_down,
                       preferred_element_type=jnp.float32)
    # Pair (n, j) on held expert e lies at row ``its place in the sorted
    # order - start_e`` of e's block; a pair on no held expert weighs 0.
    back = jnp.zeros((N * k,), jnp.int32).at[order].set(
        jnp.arange(N * k, dtype=jnp.int32))
    e = jnp.minimum(key, E - 1)
    block = e * cap
    place = back - starts[e]
    if lo is not None:
        w_held = jnp.where(((place >= 0) & (place < cap)).reshape(N, k),
                           w_held, 0.0)
    row = block + jnp.clip(place, 0, cap - 1)
    rows = y.reshape(E * cap, D)
    if lo is None:
        return jnp.einsum("nk,nkd->nd", w_held, rows[row].reshape(N, k, D))
    # A pass gathers one pick of every token at a time, so no [N, k, D]
    # array is written (at 2,176 tokens and 256 rows 3.7 against 4.4 ms and
    # 80 MB less; the form above is the decode program's and stays as it is).
    row = row.reshape(N, k)
    return sum(w_held[:, j, None] * rows[row[:, j]] for j in range(k))


def _walk_capacity(h, w_held, key, w_in, w_down, order, sizes, cap: int,
                   form: str):
    """The held pairs' weighted sum [N, D] float32 in passes of the capacity
    form: pass ``i`` gives expert ``e`` its sorted pairs ``[start_e + i *
    cap, start_e + min((i + 1) * cap, size_e))`` and each pair gathers its
    row from the pass it fell in. Returns (sum, passes walked)."""
    def one_pass(carry):
        i, out = carry
        return i + 1, out + _capacity_held_pairs(
            h, w_held, key, w_in, w_down, order, sizes, cap, form,
            lo=i * cap)

    passes, out = jax.lax.while_loop(
        lambda carry: carry[0] * cap < jnp.max(sizes), one_pass,
        (jnp.int32(0), jnp.zeros(h.shape, jnp.float32)))
    return out, passes


def held_experts_ffn(h: jax.Array, idx: jax.Array, weights: jax.Array,
                     w_in: jax.Array, w_down: jax.Array, *,
                     held: Tuple[int, int], n_routed: int,
                     valid: Optional[jax.Array] = None,
                     form: str = "silu_gate",
                     ) -> Tuple[jax.Array, jax.Array]:
    """This chip's part of a top-k expert layer, dropless.

    ``h`` [N, D]; ``idx`` / ``weights`` [N, k] from :func:`route_topk`;
    ``w_in`` and ``w_down`` [E_held, F, D] are the FFNs of experts ``held =
    (first, count)`` of ``n_routed``, of the ``form`` (``EXPERT_FORMS``)
    ``"silu_gate"``: ``w_in`` [E_held, D, 2F] (gate | up), ``W_down(silu(gate)
    * up)``; or ``"relu2"``: ``w_in`` [E_held, D, F], ``W_down relu(W_in
    h)^2``, two matrices an expert and no gate.
    A pick ``i >= n_routed`` is a zero-compute (identity) expert and adds
    ``w_i * h`` with no matrix product; a pick on a held expert adds
    ``w_i * Expert_i(h)``; a pick on an absent expert adds nothing here.
    ``valid`` [N] masks tokens whose output is dead (pad positions, idle
    slots): they route nowhere, so they make no expert be read.

    Only routed pairs are multiplied: the (token, expert) pairs that land on
    held experts are sorted by expert and go through ``jax.lax.ragged_dot``
    (a grouped matrix product; on TPU a Mosaic kernel that visits only the
    row tiles that hold pairs). The row buffer is bounded by the chip's
    share of the picks (:func:`held_row_bound`: twice ``N * k * count /
    n_routed``, a prefill bucket's 1,024 rows where ``N * k`` is 16,384) and
    the sorted pairs are walked in windows of that many rows until none is
    left; where that bound is past a quarter of ``N * k`` (a decode step,
    a small bucket) the buffer is ``N * k`` long, the most that can land
    here, and is filled once. Either way no pick is ever dropped however
    uneven the load: an uneven load walks more windows. The products and
    their precision are the same in both forms (bfloat16 operands, float32
    accumulation, float32 weights and sum, one cast at the end); only the
    order in which a token's picks are summed differs. Where many experts
    are held (:func:`held_capacity`) no grouped product fits: the pairs are
    laid out a capacity of rows an expert and multiplied by a batched
    product. A small call (a decode step) takes one such product, unless
    some expert got more rows than the capacity: the all-rows grouped
    product then takes the call, which counts one ``extra_windows`` and no
    ``bounded_calls``. A larger call (a prefill bucket) walks the capacity
    form in passes until the busiest expert has no pair left and holds no
    grouped product: it counts one ``bounded_calls`` and the passes beyond
    the first as ``extra_windows``. Dropless either way.

    Returns (out [N, D] in ``h.dtype``, counts int32 [PICK_COUNTS], one a
    name of ``PICK_COUNT_NAMES``)."""
    N, D = h.shape
    k = idx.shape[1]
    first, count = held
    F = w_down.shape[1]
    if form not in EXPERT_FORMS or w_in.shape[2] != (
            2 * F if form == "silu_gate" else F):
        raise ValueError(f"held_experts_ffn: w_in {w_in.shape} is no first "
                         f"matrix of a {form!r} expert of {F} channels")
    live = jnp.ones((N,), bool) if valid is None else valid
    live_k = live[:, None]

    local = idx - first
    on_held = (local >= 0) & (local < count) & live_k            # [N, k]
    key = jnp.where(on_held, local, count).reshape(-1)           # [N*k]
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((count + 1,), jnp.int32).at[key].add(1)[:count]
    rows = held_row_bound(N, k, held, n_routed)
    if rows is not None:
        out, windows = _walk_held_pairs(h, weights, w_in, w_down, order,
                                        sizes, rows, form)
        bounded = {"bounded_calls": 1,
                   "extra_windows": jnp.maximum(windows - 1, 0)}
    else:
        def all_rows():
            tok = (jnp.arange(N * k, dtype=jnp.int32) // k)[order]
            x = h[tok]                                           # [N*k, D]
            with jax.named_scope("moe_experts"):
                gu = jax.lax.ragged_dot(x, w_in, sizes,
                                        preferred_element_type=jnp.float32)
                a = _expert_hidden(gu, F, form).astype(h.dtype)
                y = jax.lax.ragged_dot(a, w_down, sizes,
                                       preferred_element_type=jnp.float32)
            # Rows past the last pair belong to no group: their product is
            # not defined, so they are cut out before the weights touch them.
            in_group = jnp.arange(N * k) < jnp.sum(sizes)
            y = jnp.where(in_group[:, None], y, 0.0)
            back = jnp.zeros((N * k,), jnp.int32).at[order].set(
                jnp.arange(N * k, dtype=jnp.int32))
            w_held = jnp.where(on_held, weights, 0.0)            # [N, k]
            return jnp.einsum("nk,nkd->nd", w_held, y[back].reshape(N, k, D))

        cap = held_capacity(N, k, held, n_routed)
        if cap is None:
            out = all_rows()
            bounded = {"bounded_calls": 0, "extra_windows": 0}
        elif _one_product_call(N, k, n_routed):
            fits = jnp.max(sizes) <= cap
            out = jax.lax.cond(
                fits, lambda: _capacity_held_pairs(
                    h, jnp.where(on_held, weights, 0.0), key, w_in, w_down,
                    order, sizes, cap, form), all_rows)
            bounded = {"bounded_calls": 0,
                       "extra_windows": 1 - fits.astype(jnp.int32)}
        else:
            out, passes = _walk_capacity(
                h, jnp.where(on_held, weights, 0.0), key, w_in, w_down,
                order, sizes, cap, form)
            bounded = {"bounded_calls": 1,
                       "extra_windows": jnp.maximum(passes - 1, 0)}

    is_zero = (idx >= n_routed) & live_k
    w_zero = jnp.sum(jnp.where(is_zero, weights, 0.0), axis=-1)  # [N]
    out = out + w_zero[:, None] * h.astype(jnp.float32)

    counts = {"picks": jnp.sum(live) * k, "picks_zero": jnp.sum(is_zero),
              "picks_held": jnp.sum(on_held), "held_pairs_max": jnp.max(sizes),
              "experts_hit": jnp.sum(sizes > 0), **bounded}
    return out.astype(h.dtype), jnp.stack(
        [counts[n] for n in PICK_COUNT_NAMES]).astype(jnp.int32)


def expert_layer(lp: Dict, x: jax.Array, valid: jax.Array, *, topk: int,
                 scale: float, score: str, renormalise: bool,
                 held: Tuple[int, int], n_routed: int,
                 form: str = "silu_gate", w_in: str = "w_gate_up",
                 shared: Optional[Callable] = None,
                 ) -> Tuple[jax.Array, jax.Array]:
    """One chip's part of a routed expert layer on ``x`` [S, T, D], the one
    every expert family binds its config's names to: ``sum_{i in picks,
    held} w_i E_i(x)`` (:func:`route_topk` over all the router's outputs,
    :func:`held_experts_ffn` over the experts ``held`` of ``n_routed``, of
    the ``form``), plus ``E_shared(x)`` where the family has a shared expert.
    Returns (out [S, T, D], counts int32 [PICK_COUNTS]).

    ``lp`` holds ``router`` [D, outputs], ``router_bias`` [outputs],
    ``experts`` (its first matrix under the name ``w_in``, and ``w_down``)
    and, with ``shared``, the shared expert's weights ``lp["shared"]``:
    ``shared(lp["shared"], rows)`` is the family's own feed-forward on the
    FLAT rows [S * T, D]; it is whole on every chip and runs over every row
    (a dead row's result is dead). Tokens not ``valid`` [S, T] (pad
    positions, parked slots) route to no expert, read no expert's weights
    and count no pick.

    The layer's scopes, the names a profile's operations carry:
    ``moe_router`` holds the router's product, the scores and the top-k;
    ``moe_experts`` the expert products alone (inside
    :func:`held_experts_ffn`: the sort, the gathers and the weighted sum
    around them carry no scope of the three); ``moe_shared`` the shared
    expert."""
    S, T, D = x.shape
    flat = x.reshape(S * T, D)
    with jax.named_scope("moe_router"):
        idx, w = route_topk(flat, lp["router"], lp["router_bias"], topk=topk,
                            scale=scale, score=score, renormalise=renormalise)
    out, counts = held_experts_ffn(
        flat, idx, w, lp["experts"][w_in], lp["experts"]["w_down"], held=held,
        n_routed=n_routed, valid=valid.reshape(S * T), form=form)
    if shared is not None:
        with jax.named_scope("moe_shared"):
            out = out + shared(lp["shared"], flat)
    return out.reshape(S, T, D), counts
