"""A learned sparse selection in front of attention (DeepSeek Sparse
Attention, ``model_type: glm_moe_dsa``): which of a slot's cached tokens a
query may attend is chosen a query by a small INDEXER with a cache of its own
keys.

Three operations, each one form:

- :func:`index_scores`: ``I_ij = sum_h w_ih ReLU(q^I_ih . k^I_j)`` of a block
  of queries over a slot's index keys as they lie behind its block table;
- :func:`select_keep`: the EXACT ``min(k, visible)`` largest of a row of
  scores as keep bits, ties to the lower position: the set
  ``jax.lax.top_k`` gives (``approx_max_k`` gives another). Found without a
  sort, by bisection on the scores' bits: 32 counts of a row fix the k-th
  largest value, ``log2(N)`` more fix how far into the ties at that value
  the set reaches. A row of 8,192 scores is 8 vector registers a sublane, so
  the counts run out of VMEM (the Pallas kernel ``dsa_select``) where a sort
  of every row of a prompt would stream it through HBM some ninety times;
- attention over the kept rows alone is the latent kernel with one more
  operand (``ops/paged_attention.py:latent_paged_attention(keep=)``): the
  walk fetches every live block and masks what was not kept.

:func:`keep_bits` strings the first two together for one attention sublayer,
a block of queries at a time, so that neither a prompt's ``[T, heads, T]``
products nor its ``[T, T]`` scores ever exist whole.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_INT_MIN = -(1 << 31)
_SELECT_ROWS = 32        # rows of scores a step of ``dsa_select`` holds
_QUERY_BLOCK = 256       # queries whose [heads, positions] products exist at once


def index_scores(q, w, keys):
    """``q`` [B, Q, Hi, Di] index queries, ``w`` [B, Q, Hi] float32 head
    weights, ``keys`` [B, N, Di] index keys -> ``[B, Q, N]`` float32:
    ``sum_h w_h ReLU(q_h . k)``. The positive factor ``Hi^-0.5 Di^-0.5`` of
    the published form changes no choice and is kept: the caller multiplies
    it into ``w``."""
    with jax.named_scope("dsa_index_scores"):
        s = jnp.einsum("bqhd,bnd->bqhn", q, keys,
                       preferred_element_type=jnp.float32)
        return jnp.sum(jnp.maximum(s, 0.0) * w[..., None], axis=2)


def _sortable(scores):
    """float32 -> int32 that orders as the floats do (-0.0 as +0.0)."""
    bits = jax.lax.bitcast_convert_type(scores + 0.0, jnp.int32)
    return jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)


def _count(mask):
    return jnp.sum(mask.astype(jnp.int32), axis=-1, keepdims=True)


def _keep_of_rows(scores, visible, k: int):
    """``scores`` [R, N] float32, ``visible`` [R, 1] int32 (positions below
    it may be chosen) -> [R, N] bool: the ``min(k, visible)`` largest of a
    row's visible scores, ties to the lower position. Compares, counts along
    a row and integer bit operations alone: the body of the Pallas kernel and
    of the plain path."""
    R, N = scores.shape
    pos = jax.lax.broadcasted_iota(jnp.int32, (R, N), 1)
    key = jnp.where(pos < visible, _sortable(scores), _INT_MIN)
    want = jnp.clip(visible, 1, k)                                # [R, 1]

    # The want-th largest key, bit by bit from the top: the largest t with
    # count(key >= t) >= want. INT_MIN | bits rises with the bits.
    t = jnp.where(_count(key >= 0) >= want, 0, _INT_MIN)

    def value_bit(i, t):
        cand = t | jax.lax.shift_left(jnp.int32(1), 30 - i)
        return jnp.where(_count(key >= cand) >= want, cand, t)

    t = jax.lax.fori_loop(0, 31, value_bit, t)
    above, tie = key > t, key == t
    need = want - _count(above)             # ties at t the set still takes, >= 1

    # The smallest position p with count(tie & pos <= p) >= need, likewise.
    bits = max(1, (N - 1).bit_length())

    def position_bit(i, p):
        cand = p - jax.lax.shift_left(jnp.int32(1), bits - 1 - i)
        return jnp.where(
            _count(jnp.logical_and(tie, pos <= cand)) >= need, cand, p)

    p = jax.lax.fori_loop(0, bits, position_bit,
                          jnp.full((R, 1), (1 << bits) - 1, jnp.int32))
    return jnp.logical_or(above, jnp.logical_and(tie, pos <= p))


def _select_kernel(scores_ref, visible_ref, keep_ref, *, k: int):
    keep_ref[...] = _keep_of_rows(scores_ref[...], visible_ref[...],
                                  k).astype(keep_ref.dtype)


@functools.partial(jax.jit, static_argnames=("k", "kernel", "dtype", "name"))
def select_keep(scores, visible, *, k: int, kernel: str = "gather",
                dtype=jnp.bfloat16, name: str = "dsa_select"):
    """``scores`` [R, N] float32, ``visible`` [R] -> keep bits [R, N] as 1 /
    0 in ``dtype``: row r's ``min(k, visible[r])`` largest scores among its
    first ``visible[r]`` positions, ties to the lower position (the set
    ``jax.lax.top_k`` gives over the visible scores). ``kernel``: ``pallas``
    / ``interpret`` run the Pallas kernel ``name``, a tile of rows in VMEM a
    step; anything else the same body as plain array operations."""
    R, N = scores.shape
    visible = visible.astype(jnp.int32).reshape(R, 1)
    with jax.named_scope("dsa_select"):
        if kernel not in ("pallas", "interpret"):
            return _keep_of_rows(scores, visible, k).astype(dtype)
        tr = min(_SELECT_ROWS, R)
        pad_r, pad_n = -R % tr, -N % 128
        if pad_r or pad_n:      # pad rows see nothing, pad positions are never visible
            scores = jnp.pad(scores, ((0, pad_r), (0, pad_n)))
            visible = jnp.pad(visible, ((0, pad_r), (0, 0)))
        Rp, Np = scores.shape
        keep = pl.pallas_call(
            functools.partial(_select_kernel, k=k),
            grid=(Rp // tr,),
            in_specs=[pl.BlockSpec((tr, Np), lambda r: (r, 0)),
                      pl.BlockSpec((tr, 1), lambda r: (r, 0))],
            out_specs=pl.BlockSpec((tr, Np), lambda r: (r, 0)),
            out_shape=jax.ShapeDtypeStruct((Rp, Np), dtype),
            interpret=kernel == "interpret",
            name=name,
        )(scores, visible)
        return keep[:R, :N]


def keep_bits(q, w, keys, q_pos, *, k: int, kernel: str = "gather",
              dtype=jnp.bfloat16):
    """One sublayer's selection: ``q`` [B, Q, Hi, Di], ``w`` [B, Q, Hi]
    float32, ``keys`` [B, N, Di] each slot's index keys by position (the
    queries' own among them), ``q_pos`` [B, Q] the queries' positions ->
    keep bits [B, Q, N]: a query at position i keeps the ``min(k, i + 1)``
    highest-scored positions ``<= i``. ``_QUERY_BLOCK`` queries at a time:
    their ``[Hi, N]`` products are 268 MB in float32 at 8,192 positions, a
    whole prompt's 8.6 GB. The Pallas kernel is ``dsa_select`` under a decode
    step's one query a slot, ``dsa_select_prefill`` under a prompt's."""
    B, Q = q.shape[:2]
    N = keys.shape[1]
    name = "dsa_select" if Q == 1 else "dsa_select_prefill"

    def block(args):
        qb, wb, pb = args                       # [B, qb, ...]
        rows = index_scores(qb, wb, keys).reshape(-1, N)
        return select_keep(rows, pb.reshape(-1) + 1, k=k, kernel=kernel,
                           dtype=dtype, name=name).reshape(B, -1, N)

    qb = min(Q, _QUERY_BLOCK)
    if Q % qb:
        raise ValueError(f"{Q} queries are not whole blocks of {qb}")
    if Q == qb:
        return block((q, w, q_pos))
    split = lambda a: jnp.moveaxis(                          # noqa: E731
        a.reshape(B, Q // qb, qb, *a.shape[2:]), 1, 0)
    out = jax.lax.map(block, (split(q), split(w), split(q_pos)))
    return jnp.moveaxis(out, 0, 1).reshape(B, Q, N)
