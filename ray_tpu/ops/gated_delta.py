"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464) in its two forms.

A linear-attention head keeps, instead of rows a token, ONE matrix a
sequence: ``S`` in R^(dk x dv) (keys down, values across; the paper's ``S``
transposed), zero at the sequence's start and moved by every token::

    S_t = alpha_t (I - beta_t k_t k_t^T) S_(t-1) + beta_t k_t v_t^T
    o_t = S_t^T q_t

with ``alpha_t = exp(g_t)`` in (0, 1) the gate and ``beta_t`` in (0, 2) the
writing strength (above 1 the transition has a negative eigenvalue). Three
functions compute it:

- :func:`recurrence`: token by token, the oracle the other two are tested
  against (``tests/test_gated_delta.py``);
- :func:`chunked`: prefill. Chunks of ``chunk`` tokens (64); inside a chunk
  the WY form of the paper's section 3: with ``G`` the chunk's cumulative log
  gates, ``A = strict_lower(diag(beta) K K^T * exp(G_i - G_j))`` and
  ``T = (I + A)^-1``, the chunk's pseudo-values ``T (beta V) - T (beta K
  exp(G)) S`` stand where a token's ``beta (v - S^T k)`` stands in the
  recurrence, and one carry a chunk moves ``S`` on. A position with ``g = 0``
  and ``beta = 0`` leaves ``S`` as it stands: a bucket's padded tail is given
  those, so the state after the bucket is the state after the real tokens.
  Plain ``jnp`` in float32 with every product at ``highest`` precision
  (their operations are a few hundredths of the projections');
- :func:`gdn_decode`: one token for every slot, a Pallas kernel that reads
  and writes the slots' states IN PLACE (``input_output_aliases``): ``u =
  S^T k``, ``S' = alpha S + k (beta (v - alpha u))^T``, ``o = S'^T q``, one
  read and one write of ``S``.

The kernel's state layout is ``[layers, slots, dk, H * dv]`` float32: heads
folded into the lanes beside their value channels, so a slot's state of one
layer is one dense ``[dk, H * dv]`` tile (``30 x 192 = 5760 = 45 x 128``
lanes at the published sizes; a trailing ``[.., 192, 96]`` would pad every
row to 128 lanes, a third more bytes to move). The grid is ``(slots, lane
groups)``; a group is ``Hg`` heads' lanes. Inside a step everything is
elementwise on ``[dk, Hg * dv]`` with two sums over the sublanes; a head's
``q``, ``k``, ``alpha`` and ``beta`` reach its ``dv`` lanes through one small
product with a 0/1 matrix (``_expander``). That product is exact in ONE
bfloat16 pass: each float32 operand comes in as three bfloat16 parts (``x =
hi + mid + lo``, ``ops/layers.py:split3``) laid side by side along the
contraction, which the MXU pads to 128 anyway, against the 0/1 matrix stacked
three times. (As a float32 product at ``highest`` precision, six passes, it
was the kernel's bound: 4.1 us a grid step against 1.8 us of DMA; my chip
run, PR 31.)
The whole state array goes in, with ``layer`` a scalar-prefetch operand, as
the pool does in ``ops/paged_attention.py``: a Mosaic call cannot read
through an XLA slice. A slot that is not ``active`` gets its state back bit
for bit (and its block still moves: see PERF.md, open questions).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.layers import SPLIT_PARTS, split3

_HI = jax.lax.Precision.HIGHEST
CHUNK = 64
# What one state block [dk, Hg * dv] float32 may take: in and out, each
# double-buffered, plus the body's temporaries of the same shape.
_BLOCK_BYTES = 1 << 20


def recurrence(q, k, v, g, beta, state=None):
    """Token by token. ``q``, ``k`` [T, H, dk], ``v`` [T, H, dv], ``g`` (log
    gate) and ``beta`` [T, H]; ``state`` [H, dk, dv] or None (zero). Returns
    (o [T, H, dv], state)."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    if state is None:
        state = jnp.zeros((H, dk, dv), jnp.float32)

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = S * jnp.exp(g_t)[:, None, None]
        u = jnp.einsum("hkv,hk->hv", S, k_t, precision=_HI)
        S = S + jnp.einsum("hk,hv->hkv", k_t, b_t[:, None] * (v_t - u),
                           precision=_HI)
        return S, jnp.einsum("hkv,hk->hv", S, q_t, precision=_HI)

    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    state, o = jax.lax.scan(step, f32(state),
                            (f32(q), f32(k), f32(v), f32(g), f32(beta)))
    return o, state


def chunked(q, k, v, g, beta, state=None, *, chunk: int = CHUNK):
    """The same, chunk-wise (see the module's docstring). Shapes as
    :func:`recurrence`; ``T`` need not be a multiple of ``chunk`` (the tail
    is padded with ``g = 0``, ``beta = 0``)."""
    T, H, dk = q.shape
    dv = v.shape[2]
    pad = -T % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                            for a in (q, k, v, g, beta))
    N, C = (T + pad) // chunk, chunk

    def chunks(a):          # [T, H, ...] -> [N, H, C, ...], float32
        a = a.astype(jnp.float32).reshape((N, C) + a.shape[1:])
        return jnp.moveaxis(a, 2, 1)

    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=-1)                              # [N, H, C]
    row = jnp.arange(C)[:, None]
    col = jnp.arange(C)[None, :]
    # exp(G_i - G_j) for i >= j, 0 above: masked BEFORE the exponential,
    # whose argument is positive (and may overflow) above the diagonal.
    decay = jnp.exp(jnp.where(row >= col, G[..., :, None] - G[..., None, :],
                              -jnp.inf))
    kb, vb = k * beta[..., None], v * beta[..., None]
    A = jnp.where(row > col,
                  jnp.einsum("nhik,nhjk->nhij", kb, k, precision=_HI) * decay,
                  0.0)
    # (I + A) X = [beta K exp(G) | beta V]: unit lower triangular.
    rhs = jnp.concatenate([kb * jnp.exp(G)[..., None], vb], axis=-1)
    X = _solve_unit_lower(A, rhs)
    k_cum, v_own = X[..., :dk], X[..., dk:]
    qk = jnp.einsum("nhik,nhjk->nhij", q, k, precision=_HI) * decay
    q_in = q * jnp.exp(G)[..., None]
    k_out = k * jnp.exp(G[..., -1:] - G)[..., None]
    g_end = jnp.exp(G[..., -1])                             # [N, H]

    def step(S, x):
        k_cum_n, v_own_n, qk_n, q_in_n, k_out_n, g_end_n = x
        v_n = v_own_n - jnp.einsum("hck,hkv->hcv", k_cum_n, S, precision=_HI)
        o = (jnp.einsum("hck,hkv->hcv", q_in_n, S, precision=_HI)
             + jnp.einsum("hij,hjv->hiv", qk_n, v_n, precision=_HI))
        S = (S * g_end_n[:, None, None]
             + jnp.einsum("hck,hcv->hkv", k_out_n, v_n, precision=_HI))
        return S, o

    if state is None:
        state = jnp.zeros((H, dk, dv), jnp.float32)
    state, o = jax.lax.scan(step, state.astype(jnp.float32),
                            (k_cum, v_own, qk, q_in, k_out, g_end))
    o = jnp.moveaxis(o, 1, 2).reshape(N * C, H, dv)         # [T + pad, H, dv]
    return o[:T], state


_SOLVE_BLOCK = 16


def _solve_unit_lower(A, rhs):
    """``(I + A)^-1 rhs`` for ``A`` [..., C, C] strictly lower triangular,
    ``rhs`` [..., C, n] (the TPU's own triangular solve took 4 ms a layer of
    a 1,024-token prefill, 12% of the first cell's device time; my chip run,
    PR 31). Blocks of 16 rows. The diagonal blocks' inverses come by forward
    substitution, a row a step, all blocks at once on ``[16, 16]`` operands:
    stable where a power series is not (keys that repeat under ``beta`` near
    2 put entries near 2 all over ``A``; the series' terms then reach 1e7 and
    cancel; ``tests/test_gated_delta.py``). The blocks then go by block
    forward substitution, in products alone."""
    C = A.shape[-1]
    b = _SOLVE_BLOCK if C % _SOLVE_BLOCK == 0 else C
    nb = C // b
    mm = lambda x, y: jnp.einsum("...ij,...jk->...ik", x, y, precision=_HI)  # noqa: E731
    block = lambda i, j: A[..., i * b:(i + 1) * b, j * b:(j + 1) * b]  # noqa: E731
    D = jnp.stack([block(i, i) for i in range(nb)], axis=-3)  # [..., nb, b, b]

    def row(i, T):
        # Row i of (I + D)^-1 is e_i - D[i, :i] T[:i]; D[i, i:] is zero, so
        # the rows of T not yet written do not matter.
        d_i = jax.lax.dynamic_slice_in_dim(D, i, 1, axis=-2)
        e_i = (jnp.arange(b) == i).astype(A.dtype)
        return jax.lax.dynamic_update_slice_in_dim(T, e_i - mm(d_i, T), i,
                                                   axis=-2)

    T = jax.lax.fori_loop(0, b, row, jnp.zeros_like(D))
    done = []
    for i in range(nb):
        r = rhs[..., i * b:(i + 1) * b, :]
        for j, x_j in enumerate(done):
            r = r - mm(block(i, j), x_j)
        done.append(mm(T[..., i, :, :], r))
    return jnp.concatenate(done, axis=-2)


def fold_state(state):
    """[H, dk, dv] (as :func:`chunked` gives it) -> the kernel's
    ``[dk, H * dv]``."""
    H, dk, dv = state.shape
    return state.transpose(1, 0, 2).reshape(dk, H * dv)


def unfold_state(folded, heads: int):
    dk = folded.shape[0]
    return folded.reshape(dk, heads, -1).transpose(1, 0, 2)


def _heads_per_group(heads: int, dk: int, dv: int) -> int:
    """Heads a grid step takes: the most whose ``[dk, Hg * dv]`` float32
    block stays under ``_BLOCK_BYTES`` with a lane count that is whole
    128-lane tiles; all of them where no such split exists (tiny sizes)."""
    fits = [hg for hg in range(1, heads) if heads % hg == 0
            and (hg * dv) % 128 == 0 and dk * hg * dv * 4 <= _BLOCK_BYTES]
    return max(fits) if fits else heads


def _expander(hg: int, dv: int):
    """[3 * Hg, Hg * dv] of 0 and 1, bfloat16: row h (of each of the three
    parts) is one on head h's lanes."""
    return jnp.tile(jnp.repeat(jnp.eye(hg, dtype=jnp.bfloat16), dv, axis=1),
                    (SPLIT_PARTS, 1))


def _gdn_kernel(layer_ref, active_ref,      # scalar prefetch: [1], [S] int32
                s_ref,                      # [1, 1, dk, W] block of the state
                q_ref, k_ref,               # [1, 1, dk, 3 Hg] blocks, bf16 parts
                v_ref,                      # [1, 1, W] block
                ab_ref,                     # [1, 1, 16, 3 Hg]: alpha, beta, zeros
                e_ref,                      # [3 Hg, W], whole
                out_ref,                    # the state's block, aliased
                o_ref):                     # [1, 1, W] block
    del layer_ref                           # read by the index maps
    S = s_ref[0, 0]
    E = e_ref[...]
    expand = lambda a: jax.lax.dot_general(  # noqa: E731
        a, E, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    kx, qx, ab = expand(k_ref[0, 0]), expand(q_ref[0, 0]), expand(ab_ref[0, 0])
    Sd = S * ab[0:1]                                        # alpha S
    u = jnp.sum(Sd * kx, axis=0, keepdims=True)             # alpha S^T k
    Sn = Sd + kx * (ab[1:2] * (v_ref[0] - u))
    o_ref[0] = jnp.sum(Sn * qx, axis=0, keepdims=True)
    out_ref[0, 0] = jnp.where(active_ref[pl.program_id(0)] > 0, Sn, S)


def gdn_decode(state, q, k, v, alpha, beta, active, layer, *,
               interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """One token of the recurrence for every slot, in place.

    ``state`` [layers, S, dk, H * dv] float32, the whole array (donated by
    the caller's program, aliased to the first result); ``q``, ``k`` [S, H,
    dk], ``v`` [S, H, dv], ``alpha``, ``beta`` [S, H]; ``active`` [S] bool;
    ``layer`` an int or int32 scalar: which layer's states. Returns (state,
    o [S, H, dv] float32). A slot that is not active gets its state back
    unchanged; its ``o`` is dead."""
    L, S, dk, lanes = state.shape
    H, dv = v.shape[1], v.shape[2]
    if state.dtype != jnp.float32 or lanes != H * dv or q.shape != (S, H, dk):
        raise ValueError(
            f"state {state.shape} {state.dtype} is not float32 "
            f"[layers, {S}, {dk}, {H}*{dv}] for q {q.shape}, v {v.shape}")
    return _gdn_decode(
        state, q, k, v, alpha, beta, active.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1), interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gdn_decode(state, q, k, v, alpha, beta, active, layer, *, interpret):
    _, S, dk, _ = state.shape
    H, dv = v.shape[1], v.shape[2]
    hg = _heads_per_group(H, dk, dv)
    G, W = H // hg, hg * dv
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    # [S, H, dk] -> [S, G, dk, 3 Hg]: a head a lane (three bfloat16 parts
    # side by side), keys down the sublanes.
    heads_last = lambda a: split3(  # noqa: E731
        f32(a).reshape(S, G, hg, dk).transpose(0, 1, 3, 2))
    ab = jnp.stack([f32(alpha), f32(beta)], axis=1).reshape(S, 2, G, hg)
    ab = split3(jnp.pad(ab.transpose(0, 2, 1, 3),
                        ((0, 0), (0, 0), (0, 14), (0, 0))))
    per_group = lambda width: pl.BlockSpec(  # noqa: E731
        (1, 1, width), lambda s, g, lyr, act: (s, 0, g))
    per_head = lambda rows: pl.BlockSpec(  # noqa: E731
        (1, 1, rows, SPLIT_PARTS * hg), lambda s, g, lyr, act: (s, g, 0, 0))
    state_spec = pl.BlockSpec(
        (1, 1, dk, W), lambda s, g, lyr, act: (lyr[0], s, 0, g))
    state, o = pl.pallas_call(
        _gdn_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S, G),
            in_specs=[state_spec, per_head(dk), per_head(dk), per_group(W),
                      per_head(16),
                      pl.BlockSpec((SPLIT_PARTS * hg, W),
                                   lambda s, g, lyr, act: (0, 0))],
            out_specs=[state_spec, per_group(W)]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, jnp.float32),
                   jax.ShapeDtypeStruct((S, 1, H * dv), jnp.float32)],
        # Operand 2 (after the two scalar-prefetch operands) is the state.
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="gdn_decode",
        interpret=interpret,
    )(layer, active, state, heads_last(q), heads_last(k),
      f32(v).reshape(S, 1, H * dv), ab, _expander(hg, dv))
    return state, o.reshape(S, H, dv)


def gdn_decode_reference(state, q, k, v, alpha, beta, active, layer):
    """:func:`gdn_decode` in plain ``jnp``: the kernel's oracle, and the
    ``gather`` mode's path (the CPU tier-1 default, where interpreting the
    kernel a token a slot would tax the tests)."""
    H = v.shape[1]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    S0 = jax.vmap(lambda s: unfold_state(s, H))(state[layer])  # [S,H,dk,dv]
    Sd = S0 * f32(alpha)[..., None, None]
    u = jnp.einsum("shkv,shk->shv", Sd, f32(k), precision=_HI)
    Sn = Sd + jnp.einsum("shk,shv->shkv", f32(k),
                         f32(beta)[..., None] * (f32(v) - u), precision=_HI)
    o = jnp.einsum("shkv,shk->shv", Sn, f32(q), precision=_HI)
    Sn = jnp.where(active[:, None, None, None], Sn, S0)
    return state.at[layer].set(jax.vmap(fold_state)(Sn)), o
