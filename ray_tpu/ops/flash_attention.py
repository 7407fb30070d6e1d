"""Flash attention — Pallas TPU kernels, forward AND backward.

The hot op of the transformer stack. The reference delegates attention math to
torch/framework kernels; TPU-native it is a Pallas kernel: grid over
(batch*heads, q-blocks, kv-blocks) with the kv axis innermost (sequential on
TPU), online-softmax accumulators (m, l, acc) held in VMEM scratch across the
kv sweep, causal blocks fully skipped via ``pl.when``, and the MXU fed
(block_q × d) @ (d × block_k) tiles in f32 accumulation.

Training integrates via ``jax.custom_vjp``. The forward kernel additionally
emits the row log-sum-exp; the backward is TWO Pallas kernels in the standard
flash-attention-2 decomposition — O(L) memory, no materialized L×L
probability matrix:

- dQ kernel: fix a q block, sweep kv blocks; p is recomputed from (q, k,
  lse), ``ds = p * (dO·Vᵀ - delta)``, ``dq += ds @ k``.
- dK/dV kernel: fix a kv block, sweep q blocks; ``dv += pᵀ @ dO``,
  ``dk += dsᵀ @ q``.

``delta = rowsum(dO * O)`` is a cheap elementwise reduce left to XLA fusion.
Sequence lengths must divide the block size: a ragged length raises (there is
no padded kernel); ``models.transformer``'s ``attn_impl="auto"`` picks the
dense path for those. Numerics are validated against
``parallel.ring_attention.reference_attention`` in interpret mode on CPU.

The kernels see per-device arrays only — Mosaic calls cannot be partitioned
by GSPMD. Under a mesh the caller wraps ``flash_attention`` in
``jax.shard_map`` (``models.transformer._make_attention``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref,  # [1, block_q, d], [1, block_k, d]
    o_ref,                # [1, block_q, d]
    lse_ref,              # [1, block_q, 1]
    m_scr, l_scr, acc_scr,  # VMEM scratch: [block_q, 1], [block_q, 1], [block_q, d]
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    kv_blocks: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    # Causal: a kv block strictly after the q block contributes nothing.
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1

    @pl.when(run if causal else True)
    def _body():
        q = q_ref[0].astype(jnp.float32)          # [bq, d]
        k = k_ref[0].astype(jnp.float32)          # [bk, d]
        v = v_ref[0].astype(jnp.float32)          # [bk, d]
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                  # [bq, bk]
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + q_start
            cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) + k_start
            scores = jnp.where(rows >= cols, scores, _NEG_INF)

        m_prev = m_scr[:]                          # [bq, 1]
        m_cur = jnp.max(scores, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)            # rescale of old accumulators
        p = jnp.exp(scores - m_new)                # [bq, bk]
        l_new = alpha * l_scr[:] + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = m_new
        l_scr[:] = l_new

    @pl.when(ki == kv_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / denom).astype(o_ref.dtype)
        lse_ref[0] = (m_scr[:] + jnp.log(denom)).astype(lse_ref.dtype)


def _flash_forward(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *, scale: float, causal: bool, block_q: int, block_k: int,
    interpret: bool,
):
    """q/k/v: [BH, L, D] (batch*heads flattened). Returns (o, lse):
    o [BH, L, D], lse [BH, L, 1] (row log-sum-exp of scaled scores)."""
    bh, lq, d = q.shape
    lk = k.shape[1]
    q_blocks = lq // block_q
    kv_blocks = lk // block_k

    kernel = functools.partial(
        _flash_kernel,
        scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, kv_blocks=kv_blocks,
    )
    return pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=(bh, q_blocks, kv_blocks),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, lq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, lq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


def _dq_kernel(
    q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,  # blocks (see specs)
    dq_ref,                                           # [1, block_q, d]
    dq_scr,                                           # VMEM [block_q, d] f32
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    kv_blocks: int,
):
    """Fix a q block, sweep kv blocks (innermost): accumulate dq."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1

    @pl.when(run if causal else True)
    def _body():
        q = q_ref[0].astype(jnp.float32)            # [bq, d]
        k = k_ref[0].astype(jnp.float32)            # [bk, d]
        v = v_ref[0].astype(jnp.float32)            # [bk, d]
        g = g_ref[0].astype(jnp.float32)            # [bq, d]
        lse = lse_ref[0]                            # [bq, 1] f32
        delta = delta_ref[0]                        # [bq, 1] f32
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                    # [bq, bk]
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + q_start
            cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) + k_start
            scores = jnp.where(rows >= cols, scores, _NEG_INF)
        p = jnp.exp(scores - lse)                    # [bq, bk]
        dp = jax.lax.dot_general(
            g, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                            # [bq, bk]
        ds = p * (dp - delta) * scale                # [bq, bk]
        dq_scr[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(ki == kv_blocks - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
    dk_ref, dv_ref,                                  # [1, block_k, d]
    dk_scr, dv_scr,                                  # VMEM [block_k, d] f32
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    q_blocks: int,
):
    """Fix a kv block, sweep q blocks (innermost): accumulate dk, dv."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    run = True
    if causal:
        # A q block strictly before the kv block sees none of it.
        run = q_start + block_q - 1 >= k_start

    @pl.when(run if causal else True)
    def _body():
        q = q_ref[0].astype(jnp.float32)            # [bq, d]
        k = k_ref[0].astype(jnp.float32)            # [bk, d]
        v = v_ref[0].astype(jnp.float32)            # [bk, d]
        g = g_ref[0].astype(jnp.float32)            # [bq, d]
        lse = lse_ref[0]                            # [bq, 1]
        delta = delta_ref[0]                        # [bq, 1]
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                    # [bq, bk]
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + q_start
            cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) + k_start
            scores = jnp.where(rows >= cols, scores, _NEG_INF)
        p = jnp.exp(scores - lse)                    # [bq, bk]
        # dv += pᵀ @ g
        dv_scr[:] += jax.lax.dot_general(
            p, g, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )                                            # [bk, d]
        dp = jax.lax.dot_general(
            g, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                            # [bq, bk]
        ds = p * (dp - delta) * scale                # [bq, bk]
        # dk += dsᵀ @ q
        dk_scr[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )                                            # [bk, d]

    @pl.when(qi == q_blocks - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_backward(
    q, k, v, g, o, lse,
    *, scale: float, causal: bool, block_q: int, block_k: int,
    interpret: bool,
):
    """All inputs [BH, L, D] (lse [BH, L, 1]); returns (dq, dk, dv)."""
    bh, lq, d = q.shape
    lk = k.shape[1]
    q_blocks = lq // block_q
    kv_blocks = lk // block_k
    # delta_i = Σ_d dO_id · O_id — cheap rowwise reduce; XLA fuses it.
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)          # [BH, L, 1]

    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    kv_spec_for_dq = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0))
    row_spec = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))

    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, kv_blocks=kv_blocks,
        ),
        name="flash_bwd_dq",
        grid=(bh, q_blocks, kv_blocks),
        in_specs=[q_spec, kv_spec_for_dq, kv_spec_for_dq, q_spec,
                  row_spec, row_spec],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, lq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, g, lse, delta)

    # dk/dv: transposed sweep — kv block outer, q block inner.
    q_spec_t = pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0))
    kv_spec_t = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0))
    row_spec_t = pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, q_blocks=q_blocks,
        ),
        name="flash_bwd_dkv",
        grid=(bh, kv_blocks, q_blocks),
        in_specs=[q_spec_t, kv_spec_t, kv_spec_t, q_spec_t,
                  row_spec_t, row_spec_t],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, lk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, lk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, g, lse, delta)
    return dq, dk, dv


def _dense_reference(q, k, v, *, scale, causal):
    scores = jnp.einsum("blhd,bkhd->bhlk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if causal:
        l, kk = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((l, kk), bool))
        scores = jnp.where(mask[None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhlk,bkhd->blhd", probs, v.astype(jnp.float32)).astype(q.dtype)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7)
)
def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Multi-head attention, [B, L, H, D] layout (matches
    ``models.transformer``). Heads fold into the grid's batch dim."""
    return _fa_fwd(q, k, v, causal, scale, block_q, block_k, interpret)[0]


def _fold(x):
    b, l, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, l, d)


def _unfold(x, b, h):
    bh, l, d = x.shape
    return x.reshape(b, h, l, d).transpose(0, 2, 1, 3)


def _fa_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    b, l, h, d = q.shape
    s = scale if scale is not None else 1.0 / d**0.5
    bq = min(block_q, l)
    bk = min(block_k, k.shape[1])
    if l % bq != 0 or k.shape[1] % bk != 0:
        raise ValueError(
            f"flash_attention: sequence lengths ({l}, {k.shape[1]}) must be "
            f"multiples of the blocks ({bq}, {bk}); use the dense path for "
            f"ragged lengths")
    qf, kf, vf = _fold(q), _fold(k), _fold(v)
    of, lse = _flash_forward(
        qf, kf, vf,
        scale=s, causal=causal, block_q=bq, block_k=bk, interpret=interpret,
    )
    return _unfold(of, b, h), (q, k, v, of, lse)


def _fa_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, of, lse = res
    b, l, h, d = q.shape
    s = scale if scale is not None else 1.0 / d**0.5
    bq = min(block_q, l)
    bk = min(block_k, k.shape[1])
    dqf, dkf, dvf = _flash_backward(
        _fold(q), _fold(k), _fold(v), _fold(g), of, lse,
        scale=s, causal=causal, block_q=bq, block_k=bk, interpret=interpret,
    )
    return _unfold(dqf, b, h), _unfold(dkf, b, h), _unfold(dvf, b, h)


flash_attention.defvjp(_fa_fwd, _fa_bwd)
