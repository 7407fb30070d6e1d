"""Flash attention — Pallas TPU kernels, forward AND backward.

The hot op of the transformer stack. The reference delegates attention math to
torch/framework kernels; TPU-native it is two Pallas kernels, ``flash_fwd`` and
``flash_bwd``, integrated via ``jax.custom_vjp``. O(L) memory: no L×L
probability matrix is ever materialized.

**The kernels take the array where the projections leave it.** The interface
is ``[B, L, H, D]``; the kernels' operands and results are ``[B, H*D, L]``:
a head is ``D`` whole sublanes, positions lie on the lanes. That is the order
in which XLA lays what a projection writes on the chip (the compiled train
step holds ``bf16[B,L,H,D]{1,3,2,0}``: L minor, then D, then H), so ``_lay``
and ``_unlay``, a reshape and a transpose in the program, move nothing there,
and neither does the way back into the output projection and the gradients'
products. A kernel that asks for ``[B*H, L, D]`` or for ``[B, L, H*D]``
row-major makes XLA turn every operand and result, 32 MB each at the train
step's shape. One head is one grid step, a block ``(1, D, L)``
cut out of the ``H*D`` axis by its ``BlockSpec``: any number of heads, any
``D`` that is whole sublane tiles (16 rows in bfloat16). No padded lanes in
HBM either: a ``[.., L, 64]`` array is laid 128 lanes wide.

**A score tile is ``[kv chunk, q block]``**: keys on the sublanes, queries on
the lanes. Every per-query statistic (the running max ``m``, the sum ``l``,
``lse``, ``delta``) is then a lane-dense ``[1, block_q]`` row of a few vector
registers, where the query-major tile made it a ``[block_q, 1]`` column that
costs as many registers as a 128-wide tile and is touched ten times a tile;
reductions over keys are elementwise across registers, and ``p`` and ``ds``
enter the products that follow them as they lie. With positions on the lanes
every accumulator (``o`` and ``dq`` ``[D, block_q]``, ``dk`` and ``dv``
``[D, span]``) is lane-dense and is written out as it lies; the one operand
turned is the ``[D, chunk]`` of ``k`` (and of ``v`` in the backward) that a
score tile contracts over its sublanes, by the product itself (turning a
head's ``k`` once a grid step into scratch measured 3% slower).

**Operands meet in the dtype they arrive in** (bfloat16 in the train step:
exact products summed in float32 by ``preferred_element_type``); ``p`` and
``ds`` are rounded to that dtype as operands, as every other product of the
block rounds its operands. ``m``, ``l``, ``lse``, ``delta``, the ``exp`` and
the accumulators stay float32. ``scale`` rides the ``[D, block_q]`` q operand
when that is exact (a power of two: 1/8 at D = 64), else the float32 scores.

**Causality is walked, not masked**: a head's K and V sit in VMEM whole, and
the kv sweep is a loop inside the kernel whose bound is read from the q
block's position. Chunks wholly under the diagonal take the unmasked body (a
``fori_loop``); the ``block_q // block_k`` chunks the diagonal crosses are
straight-line code, each against only the queries at or past its first key
and masked by one static lower-triangle compare. With one q block a head
(L ≤ 1,024) nothing is left to loop over: the kernel is ``L // block_k``
tiles, the scores computed 1 + block_k / L of what causality needs.

**The backward is one kernel** in place of flash-attention-2's two: scores,
``p``, ``dp`` and ``ds`` once a tile, five products (``dv += dO·pᵀ``,
``dk += q·dsᵀ``, ``dq += k·ds`` and the two that make ``p`` and ``dp``) where
a dQ and a dK/dV kernel make seven and two ``exp``. ``dk`` and ``dv`` of the
head accumulate in float32 VMEM over the q blocks.
``delta = rowsum(dO * O)`` is a cheap elementwise reduce left to XLA fusion,
over ``O`` as the forward returned it: the saved residual is that array.

**Which lengths take which grid.** Up to ``_RESIDENT_ROWS`` (2,048) keys the
grid is ``(batch, heads, 1, 1)``: one step a head. A key then costs VMEM
128 B an operand or result block (``D`` = 64 bfloat16 rows of one lane),
twice for the pipeline's second buffer, and 256 B a float32 accumulator:
~1.3 KB a key in the forward, ~2.3 KB in the backward, with the
``[chunk, block_q]`` float32 tiles beside them: 5.8 and 5.5 MB at 2,048 keys
(compile-only; 2.8 and 2.6 MB at the train step's 1,024) of the 16 MB of scoped
VMEM. Beyond that the kv axis goes back onto the grid in spans of
``_RESIDENT_ROWS`` keys: ``flash_fwd`` carries ``m``, ``l`` and the
accumulator across a q block's spans (innermost, sequential), ``flash_bwd``
runs the q blocks inside a span and hands back one float32 ``dq`` a span for
XLA to add. Same kernels, same walk: chosen from the shape alone.

Sequence lengths must divide the blocks: a ragged length raises (there is
no padded kernel); ``models.transformer``'s ``attn_impl="auto"`` picks the
dense path for those. Numerics are validated against
``parallel.ring_attention.reference_attention`` in interpret mode on CPU.

The kernels see per-device arrays only — Mosaic calls cannot be partitioned
by GSPMD. Under a mesh the caller wraps ``flash_attention`` in
``jax.shard_map`` (``models.transformer._make_attention``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

# Block rule (sweeps of the kernels alone on a v5e: at bf16[256, 1024, 64],
# PERF.md §6, PR 37; again at bf16[16, 1024, 1024], positions on the lanes,
# PR 41): constants of the shape, upper-bounded by the caller's ``block_q`` /
# ``block_k``.
# Queries a tile (lanes): the widest that divides the sequence. A tile's fixed
# costs (loop step, accumulator read-modify-write, the MXU's weight loads) are
# paid once however wide it is, and one q block a head leaves no loop at all.
_BLOCK_Q = 1024
# Keys a chunk (sublanes). The diagonal wastes block_k / 2 scores a query and a
# smaller chunk pays a tile's fixed costs more often. The forward's fixed cost
# is large (the [D, block_q] accumulator rescaled and rewritten every tile)
# and a wasted score costs it two products: 512 (0.63 ms a call against 0.72
# at 256 and 0.78 at 1,024). The backward has no running statistic to carry
# and a wasted score costs it five products: 256 (0.88 ms against 0.93 at 128
# and 0.99 at 512).
_BLOCK_K_FWD = 512
_BLOCK_K_BWD = 256
# Keys a head may hold in VMEM whole (see the module docstring).
_RESIDENT_ROWS = 2048

_A_BT = (((1,), (1,)), ((), ()))   # a @ b.T
_A_B = (((1,), (0,)), ((), ()))    # a @ b
_AT_B = (((0,), (0,)), ((), ()))   # a.T @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _split_scale(scale: float):
    """``(pre, post)``: ``pre`` multiplies the q operand, exactly (a power of
    two shifts the exponent in any float type); ``post`` multiplies the
    float32 scores. One of them is 1."""
    if math.frexp(scale)[0] == 0.5:
        return scale, 1.0
    return 1.0, scale


def _aligned(x, multiple: int):
    return x if isinstance(x, int) else pl.multiple_of(x, multiple)


def _lanes(row0, lane0: int, lanes: int, block_q: int):
    """Positions ``[row0 + lane0, + lanes)`` of a q block that starts at
    ``row0``, a multiple of ``block_q``; ``lane0`` is static."""
    return pl.ds(_aligned(row0 + lane0, math.gcd(block_q, lane0)), lanes)


def _when(cond, fn):
    """``pl.when`` that folds a condition known while tracing."""
    if isinstance(cond, bool):
        if cond:
            fn()
    else:
        pl.when(cond)(fn)


def _walk(tile, q_start, span_index, *, causal, block_q, block_k, span,
          n_spans):
    """Run ``tile(lane0, lanes, k_local, masked)`` over what causality leaves
    of the q block at ``q_start`` against kv span ``span_index``: whole
    chunks under the diagonal in a loop, then the chunks the diagonal crosses,
    each against the queries ``[lane0, block_q)`` of the block that see it."""
    chunks = span // block_k
    if not causal:
        under = chunks
    else:
        under = q_start // block_k - span_index * chunks
        if isinstance(under, int):
            under = max(0, min(under, chunks))
        else:
            under = jnp.clip(under, 0, chunks)

    def whole(j, carry):
        tile(0, block_q, _aligned(j * block_k, block_k), False)
        return carry
    jax.lax.fori_loop(0, under, whole, 0)
    if not causal:
        return

    def crossed():
        local = q_start - span_index * span
        for c in range(block_q // block_k):
            tile(c * block_k, block_q - c * block_k,
                 _aligned(local + c * block_k, block_k), True)
    # ``span`` is a multiple of ``block_q``: a q block's diagonal lies in
    # one span.
    _when(True if n_spans == 1 else q_start // span == span_index, crossed)


def _scores(k, q, post, masked):
    """``[kv chunk, queries]`` float32 scores of ``k`` ``[D, chunk]`` and
    ``q`` ``[D, queries]``; ``masked``: the chunk's first key is the queries'
    first position, so key j is seen by query i >= j."""
    s = _dot(k, q, _AT_B)
    if post != 1.0:
        s = s * post
    if masked:
        j = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        i = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(j <= i, s, _NEG_INF)
    return s


def _flash_kernel(
    q_ref, k_ref, v_ref,    # [1, d, nq * block_q], [1, d, span] x 2
    o_ref,                  # [1, d, nq * block_q]
    lse_ref,                # [1, 1, 1, nq * block_q]
    m_scr, l_scr, acc_scr,  # VMEM f32: [1, block_q] x 2, [d, block_q]
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    nq: int,
    span: int,
    n_spans: int,
):
    group = pl.program_id(2)
    span_index = 0 if n_spans == 1 else pl.program_id(3)
    pre, post = _split_scale(scale)
    first = True if n_spans == 1 else span_index == 0
    last = True if n_spans == 1 else span_index == n_spans - 1

    def q_block(t, carry):
        row0 = _aligned(t * block_q, block_q)
        q_start = row0 if n_spans == 1 else group * block_q

        def init():
            m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc_scr[:] = jnp.zeros_like(acc_scr)
        _when(first, init)

        def tile(lane0, lanes, k_local, masked):
            q = q_ref[0, :, _lanes(row0, lane0, lanes, block_q)]
            if pre != 1.0:
                q = q * pre
            keys = pl.ds(k_local, block_k)
            s = _scores(k_ref[0, :, keys], q, post, masked)  # [bk, lanes]
            cols = slice(lane0, lane0 + lanes)
            m_prev = m_scr[:, cols]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)    # rescale of old accumulators
            p = jnp.exp(s - m_new)
            l_scr[:, cols] = (alpha * l_scr[:, cols]
                              + jnp.sum(p, axis=0, keepdims=True))
            acc_scr[:, cols] = (
                acc_scr[:, cols] * alpha
                + _dot(v_ref[0, :, keys], p.astype(v_ref.dtype), _A_B))
            m_scr[:, cols] = m_new

        _walk(tile, q_start, span_index, causal=causal, block_q=block_q,
              block_k=block_k, span=span, n_spans=n_spans)

        def finalize():
            denom = jnp.maximum(l_scr[:], 1e-30)
            at = pl.ds(row0, block_q)
            o_ref[0, :, at] = (acc_scr[:] / denom).astype(o_ref.dtype)
            lse_ref[0, 0, :, at] = m_scr[:] + jnp.log(denom)
        _when(last, finalize)
        return carry

    if nq == 1:
        q_block(0, 0)
    else:
        jax.lax.fori_loop(0, nq, q_block, 0)


def _tile(length: int, bound: int) -> int:
    """The largest multiple of 128 up to ``bound`` that divides ``length``;
    where there is none (a test's small blocks), the largest divisor."""
    for size in range(bound - bound % 128, 0, -128):
        if length % size == 0:
            return size
    return next(size for size in range(bound, 0, -1) if length % size == 0)


def _span(lk: int) -> int:
    """Keys a kv span: a head's all while they fit VMEM."""
    return lk if lk <= _RESIDENT_ROWS else _tile(lk, _RESIDENT_ROWS)


def _grid(lq: int, lk: int, block_q: int):
    """``(span, n_spans, nq)``: keys a span, spans a head, q blocks a grid
    step. One span: a head's q blocks are a loop inside its one step.
    Several: one q block a step."""
    span = _span(lk)
    n_spans = lk // span
    return span, n_spans, lq // block_q if n_spans == 1 else 1


def _flash_forward(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *, heads: int, scale: float, causal: bool, block_q: int, block_k: int,
    interpret: bool,
):
    """q/k/v: [B, H*D, L]. Returns (o, lse): o [B, H*D, L], lse [B, H, 1, L]
    (row log-sum-exp of scaled scores, a lane-dense row a head)."""
    b, hd, lq = q.shape
    lk = k.shape[2]
    d = hd // heads
    span, n_spans, nq = _grid(lq, lk, block_q)

    def kv_block(b, h, i, s):
        if causal and n_spans > 1:
            # A step past its q block's diagonal walks nothing: it re-reads
            # the span it has, which is no copy.
            s = jnp.minimum(s, (i * block_q + block_q - 1) // span)
        return b, h, s

    kernel = functools.partial(
        _flash_kernel,
        scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        nq=nq, span=span, n_spans=n_spans,
    )
    q_spec = pl.BlockSpec((1, d, nq * block_q), lambda b, h, i, s: (b, h, i))
    kv_spec = pl.BlockSpec((1, d, span), kv_block)
    return pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=(b, heads, lq // (nq * block_q), n_spans),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[
            q_spec,
            pl.BlockSpec((1, 1, 1, nq * block_q),
                         lambda b, h, i, s: (b, h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hd, lq), q.dtype),
            jax.ShapeDtypeStruct((b, heads, 1, lq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, block_q), jnp.float32),
            pltpu.VMEM((1, block_q), jnp.float32),
            pltpu.VMEM((d, block_q), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


def _bwd_kernel(
    q_ref, k_ref, v_ref, g_ref,   # [1, d, nq * block_q] (q, g), [1, d, span]
    lse_ref, delta_ref,           # [1, 1, 1, nq * block_q] f32
    dk_ref, dv_ref,               # [1, d, span]
    dq_ref,                       # [1, d, nq * block_q]
    dk_scr, dv_scr,               # VMEM f32 [d, span]
    dq_scr,                       # VMEM f32 [d, block_q]
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    nq: int,
    span: int,
    n_spans: int,
    n_groups: int,
):
    """One kv span against the q blocks of one group: ``dk`` and ``dv``
    accumulate over the groups (innermost on the grid), ``dq`` of a q block
    over the span's chunks."""
    span_index = 0 if n_spans == 1 else pl.program_id(2)
    group = pl.program_id(3)
    pre, post = _split_scale(scale)

    def init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)
    _when(True if n_groups == 1 else group == 0, init)

    def q_block(t, carry):
        row0 = _aligned(t * block_q, block_q)
        q_start = row0 if n_spans == 1 else group * block_q
        dq_scr[:] = jnp.zeros_like(dq_scr)

        def tile(lane0, lanes, k_local, masked):
            at = _lanes(row0, lane0, lanes, block_q)
            keys = pl.ds(k_local, block_k)
            q = q_ref[0, :, at]
            if pre != 1.0:
                q = q * pre
            g = g_ref[0, :, at]
            s = _scores(k_ref[0, :, keys], q, post, masked)  # [bk, lanes]
            cols = slice(lane0, lane0 + lanes)
            p = jnp.exp(s - lse_ref[0, 0, :, at])
            dp = _dot(v_ref[0, :, keys], g, _AT_B)           # [bk, lanes]
            ds = (p * (dp - delta_ref[0, 0, :, at])).astype(q.dtype)
            dv_scr[:, keys] += _dot(g, p.astype(g.dtype), _A_BT)  # [d, bk]
            dk_scr[:, keys] += _dot(q, ds, _A_BT)    # q carries ``pre``
            dq_scr[:, cols] += _dot(k_ref[0, :, keys], ds, _A_B)

        _walk(tile, q_start, span_index, causal=causal, block_q=block_q,
              block_k=block_k, span=span, n_spans=n_spans)
        dq_ref[0, :, pl.ds(row0, block_q)] = (
            dq_scr[:] * scale).astype(dq_ref.dtype)
        return carry

    if nq == 1:
        q_block(0, 0)
    else:
        jax.lax.fori_loop(0, nq, q_block, 0)

    def finalize():
        dk = dk_scr[:]
        if post != 1.0:
            dk = dk * post
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)
    _when(True if n_groups == 1 else group == n_groups - 1, finalize)


def _flash_backward(
    q, k, v, g, lse, delta,
    *, heads: int, scale: float, causal: bool, block_q: int, block_k: int,
    interpret: bool,
):
    """q, k, v, g: [B, H*D, L]; lse as ``_flash_forward`` returns it and
    ``delta = rowsum(dO * O)`` in its shape; returns (dq, dk, dv)."""
    batch, hd, lq = q.shape
    lk = k.shape[2]
    d = hd // heads
    span, n_spans, nq = _grid(lq, lk, block_q)
    n_groups = lq // (nq * block_q)

    q_spec = pl.BlockSpec((1, d, nq * block_q), lambda b, h, s, i: (b, h, i))
    kv_spec = pl.BlockSpec((1, d, span), lambda b, h, s, i: (b, h, s))
    row_spec = pl.BlockSpec((1, 1, 1, nq * block_q),
                            lambda b, h, s, i: (b, h, 0, i))
    dk, dv, dq = pl.pallas_call(
        functools.partial(
            _bwd_kernel, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, nq=nq, span=span, n_spans=n_spans,
            n_groups=n_groups,
        ),
        name="flash_bwd",
        grid=(batch, heads, n_spans, n_groups),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[
            kv_spec, kv_spec,
            # A span's share of dq: one array a span, added below.
            pl.BlockSpec((1, d, nq * block_q),
                         lambda b, h, s, i: (s * batch + b, h, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, hd, lk), k.dtype),
            jax.ShapeDtypeStruct((batch, hd, lk), v.dtype),
            jax.ShapeDtypeStruct(
                (n_spans * batch, hd, lq),
                q.dtype if n_spans == 1 else jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((d, span), jnp.float32),
            pltpu.VMEM((d, span), jnp.float32),
            pltpu.VMEM((d, block_q), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, g, lse, delta)
    if n_spans > 1:
        dq = dq.reshape(n_spans, batch, hd, lq).sum(axis=0).astype(q.dtype)
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
    block_q: int = 1024,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Multi-head attention, [B, L, H, D] layout (matches
    ``models.transformer``). A head is a grid step; the kernels read and
    write ``[B, H*D, L]`` (``_lay``). ``block_q`` / ``block_k`` are upper
    bounds: the blocks run are the block rule's (``_blocks``)."""
    return _fa_fwd(q, k, v, causal, scale, block_q, block_k, interpret)[0]


def _lay(x):
    """``[B, L, H, D]`` as the kernels take it, ``[B, H*D, L]``: a head is
    ``D`` whole sublanes, positions lie on the lanes. On the chip no data
    moves: it is how XLA lays the array a projection writes (see the module
    docstring)."""
    b, l, h, d = x.shape
    return x.reshape(b, l, h * d).transpose(0, 2, 1)


def _unlay(x, shape):
    """``_lay``'s inverse, to ``shape`` = ``(B, L, H, D)``."""
    return x.transpose(0, 2, 1).reshape(shape)


def _blocks(lq: int, lk: int, block_q: int, block_k: int, causal: bool):
    """(queries a tile, keys a chunk of the forward, of the backward): the
    block rule under the caller's bounds. A span holds whole q blocks; causal:
    the chunk divides the q block, so that the diagonal crosses whole
    chunks."""
    bq, bk = min(block_q, lq), min(block_k, lk)
    if lq % bq != 0 or lk % bk != 0:
        raise ValueError(
            f"flash_attention: sequence lengths ({lq}, {lk}) must be "
            f"multiples of the blocks ({bq}, {bk}); use the dense path for "
            f"ragged lengths")
    if causal and lq != lk:
        raise ValueError(
            f"flash_attention: causal attention needs queries and keys of "
            f"one length, got ({lq}, {lk})")
    span = _span(lk)
    bq = _tile(lq, min(bq, _BLOCK_Q))
    if causal:
        bq = math.gcd(bq, span)
    chunks = [_tile(span, min(bk, rule))
              for rule in (_BLOCK_K_FWD, _BLOCK_K_BWD)]
    if causal:
        chunks = [math.gcd(bq, chunk) for chunk in chunks]
    return (bq, *chunks)


def _fa_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    heads, d = q.shape[2:]
    s = scale if scale is not None else 1.0 / d**0.5
    bq, bk, _ = _blocks(q.shape[1], k.shape[1], block_q, block_k, causal)
    o, lse = _flash_forward(
        _lay(q), _lay(k), _lay(v), heads=heads,
        scale=s, causal=causal, block_q=bq, block_k=bk, interpret=interpret,
    )
    o = _unlay(o, q.shape)
    return o, (q, k, v, o, lse)


def _fa_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, o, lse = res
    heads, d = q.shape[2:]
    s = scale if scale is not None else 1.0 / d**0.5
    bq, _, bk = _blocks(q.shape[1], k.shape[1], block_q, block_k, causal)
    # delta_i = Σ_d dO_id · O_id, a row a head as ``lse`` has them: a cheap
    # reduce left to XLA, over ``o`` as it was returned.
    delta = jnp.einsum("blhd,blhd->bhl", g.astype(jnp.float32),
                       o.astype(jnp.float32))[:, :, None, :]
    dq, dk, dv = _flash_backward(
        _lay(q), _lay(k), _lay(v), _lay(g), lse, delta, heads=heads,
        scale=s, causal=causal, block_q=bq, block_k=bk, interpret=interpret,
    )
    return _unlay(dq, q.shape), _unlay(dk, k.shape), _unlay(dv, v.shape)


flash_attention.defvjp(_fa_fwd, _fa_bwd)
