"""Mamba-2's state-space recurrence (SSD, arXiv:2405.21060) in its three forms.

A head of ``P`` channels keeps ONE matrix a sequence, ``S`` in R^(P x N)
(``N`` the state size), zero at the sequence's start. A token brings the
head's input ``x_t`` in R^P, a positive step ``dt_t`` (a scalar a head), and
the two vectors ``B_t``, ``C_t`` in R^N that all the heads of its GROUP
share (``G`` groups of ``H / G`` consecutive heads); ``A < 0`` and ``D`` are
learned scalars a head::

    S_t = exp(dt_t A) S_(t-1) + dt_t x_t B_t^T
    y_t = S_t C_t + D x_t

The decay is a scalar a head a token: no key is erased, no system is solved,
so the chunk-wise form is two levels of plain products (where
``ops/gated_delta.py``'s delta rule needs a triangular solve a chunk). Three
functions compute it:

- :func:`recurrence`: token by token, the oracle the other two are tested
  against (``tests/test_ssd.py``);
- :func:`chunked`: prefill. Chunks of ``chunk`` tokens (the published
  ``mamba_chunk_size``, 128). With ``a = dt A`` and ``cum`` its running sum
  inside a chunk: inside the chunk ``y_i += sum_(j<=i) (C_i . B_j) exp(cum_i -
  cum_j) dt_j x_j`` (a masked ``C B^T`` product, one a group), between
  chunks ``y_i += exp(cum_i) S_in C_i`` and ``S_out = exp(cum_last) S_in +
  sum_j exp(cum_last - cum_j) dt_j x_j B_j^T``, one carry a chunk. A
  position with ``dt = 0`` neither decays nor writes: a bucket's padded tail
  is given that, so the state after the bucket is the state after the real
  tokens. Plain ``jnp`` in float32, every product at ``highest`` precision
  (their operations are under a hundredth of the layer's projections');
- :func:`ssd_decode`: one token for every slot, a Pallas kernel that reads
  and writes the slots' states IN PLACE (``input_output_aliases``): one read
  and one write of ``S``, nothing else of its size.

The kernel's state layout is ``[layers, slots, N, H * P]`` float32: the
state size down the sublanes, heads and their channels folded into the
lanes (``32 x 128 = 4096`` lanes, 256 rows: 4 MiB a slot a layer at the
published sizes). So everything a HEAD brings (``exp(dt A)``, ``dt x``) is a
row vector over the lanes, broadcast down the sublanes for nothing, the sum
over ``N`` that gives ``y`` is a sum over sublanes (adds of whole registers),
and what a GROUP brings (``B``, ``C``) is a column that every lane of the
group's heads reads: it reaches the lanes through one small product with a
matrix of ones, exact in one bfloat16 pass because each float32 operand
comes in as three bfloat16 parts (``ops/layers.py:split3``, and for
``ops/gated_delta.py``'s reason: as a float32 product at ``highest``
precision the expansion was that kernel's bound). The grid is ``(slots, lane
blocks)``, a block ``_BLOCK_BYTES`` of one group's lanes. The whole state
array goes in, with ``layer`` a scalar-prefetch operand, as the pool does in
``ops/paged_attention.py``: a Mosaic call cannot read through an XLA slice.
A slot that is not ``active`` gets its state back bit for bit.
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
CHUNK = 128
# What one state block [N, W] float32 may take: in and out, each
# double-buffered, plus the body's temporaries.
_BLOCK_BYTES = 1 << 20


def _per_head(a, heads: int):
    """A group's vectors [..., G, N] -> a copy a head [..., H, N]."""
    return jnp.repeat(a, heads // a.shape[-2], axis=-2)


def recurrence(x, dt, A, B, C, D, state=None):
    """Token by token. ``x`` [T, H, P], ``dt`` [T, H] (after its softplus),
    ``A``, ``D`` [H], ``B``, ``C`` [T, G, N]; ``state`` [H, P, N] or None
    (zero). Returns (y [T, H, P], state), float32."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    x, dt, A, B, C, D = map(f32, (x, dt, A, B, C, D))
    H, P, N = x.shape[1], x.shape[2], B.shape[2]
    if state is None:
        state = jnp.zeros((H, P, N), jnp.float32)

    def step(S, t):
        x_t, dt_t, B_t, C_t = t
        S = (S * jnp.exp(dt_t * A)[:, None, None]
             + jnp.einsum("hp,hn->hpn", dt_t[:, None] * x_t,
                          _per_head(B_t, H), precision=_HI))
        y = jnp.einsum("hpn,hn->hp", S, _per_head(C_t, H), precision=_HI)
        return S, y + D[:, None] * x_t

    state, y = jax.lax.scan(step, f32(state), (x, dt, B, C))
    return y, state


def chunked(x, dt, A, B, C, D, state=None, *, chunk: int = CHUNK):
    """The same, chunk-wise (see the module's docstring). Shapes as
    :func:`recurrence`; ``T`` need not be a multiple of ``chunk`` (the tail
    is padded with ``dt = 0``)."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    x, dt, A, B, C, D = map(f32, (x, dt, A, B, C, D))
    T, H, P = x.shape
    G, N = B.shape[1], B.shape[2]
    pad = -T % chunk
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                       for a in (x, dt, B, C))
    n, L = (T + pad) // chunk, chunk
    xc = x.reshape(n, L, G, H // G, P)          # heads by their group
    dtc = dt.reshape(n, L, G, H // G)
    Bc, Cc = B.reshape(n, L, G, N), C.reshape(n, L, G, N)
    cum = jnp.cumsum(dtc * A.reshape(G, H // G), axis=1)        # [n, L, G, R]
    row, col = jnp.arange(L)[:, None], jnp.arange(L)[None, :]
    # exp(cum_i - cum_j) for i >= j, 0 above: masked BEFORE the exponential,
    # whose argument is positive (and may overflow) above the diagonal.
    decay = jnp.exp(jnp.where(
        (row >= col)[None, :, :, None, None],
        cum[:, :, None] - cum[:, None, :], -jnp.inf))          # [n, i, j, G, R]
    cb = jnp.einsum("nigs,njgs->nijg", Cc, Bc, precision=_HI)   # [n, i, j, G]
    xdt = xc * dtc[..., None]                                   # [n, L, G, R, P]
    y = jnp.einsum("nijgr,njgrp->nigrp", cb[..., None] * decay, xdt,
                   precision=_HI)
    # What a chunk adds to the state it is handed, and by how much it decays it.
    to_end = jnp.exp(cum[:, -1:] - cum)                         # [n, L, G, R]
    added = jnp.einsum("nlgrp,nlgs->ngrps", xdt * to_end[..., None], Bc,
                       precision=_HI)                           # [n, G, R, P, N]
    through = jnp.exp(cum[:, -1])                               # [n, G, R]

    def carry(S, c):
        added_c, through_c = c
        return S * through_c[..., None, None] + added_c, S      # S_in a chunk

    if state is None:
        state = jnp.zeros((H, P, N), jnp.float32)
    state, s_in = jax.lax.scan(carry, f32(state).reshape(G, H // G, P, N),
                               (added, through))
    y = y + jnp.einsum("ngrps,nlgs->nlgrp", s_in, Cc,
                       precision=_HI) * jnp.exp(cum)[..., None]
    y = y.reshape(n * L, H, P) + D[:, None] * x
    return y[:T], state.reshape(H, P, N)


def fold_state(state):
    """[H, P, N] (as :func:`chunked` gives it) -> the kernel's
    ``[N, H * P]``."""
    H, P, N = state.shape
    return state.transpose(2, 0, 1).reshape(N, H * P)


def unfold_state(folded, heads: int):
    N = folded.shape[0]
    return folded.reshape(N, heads, -1).transpose(1, 2, 0)


def _lanes_per_block(heads: int, groups: int, P: int, N: int) -> int:
    """Lanes a grid step takes: the widest run of whole heads inside ONE
    group (its ``B`` and ``C`` are the block's), the whole group included,
    whose ``[N, W]`` float32 block stays under ``_BLOCK_BYTES`` in whole
    128-lane tiles; a group's whole run where no such run exists (tiny
    sizes). Falcon-H1 (32 heads of 128 in 2 groups, N 256): 8 heads, 1,024
    lanes, 1 MiB, half a group. Nemotron-H (64 heads of 64 in 8 groups, N
    128): a whole group's 8 heads, 512 lanes, 256 KiB."""
    per_group = heads // groups
    fits = [h * P for h in range(1, per_group + 1) if per_group % h == 0
            and (h * P) % 128 == 0 and N * h * P * 4 <= _BLOCK_BYTES]
    return max(fits) if fits else per_group * P


def _ssd_kernel(layer_ref, active_ref,      # scalar prefetch: [1], [S] int32
                s_ref,                      # [1, 1, N, W] block of the state
                bc_ref,                     # [1, 1, N, 6]: B and C, bf16 parts
                row_ref,                    # [1, 2, W]: exp(dt A), dt x
                e_ref,                      # [2, 6, w]: ones for B, for C
                out_ref,                    # the state's block, aliased
                y_ref):                     # [1, 1, W] block
    del layer_ref                           # read by the index maps
    w = e_ref.shape[-1]
    bc = bc_ref[0, 0]
    expand = lambda e: jax.lax.dot_general(  # noqa: E731
        bc, e, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    Bx, Cx = expand(e_ref[0]), expand(e_ref[1])     # [N, w], every lane alike
    live = active_ref[pl.program_id(0)] > 0
    for c0 in range(0, s_ref.shape[-1], w):          # static: 128 lanes a turn
        lanes = slice(c0, c0 + w)
        S = s_ref[0, 0, :, lanes]
        Sn = S * row_ref[0, 0:1, lanes] + Bx * row_ref[0, 1:2, lanes]
        y_ref[0, :, lanes] = jnp.sum(Sn * Cx, axis=0, keepdims=True)
        out_ref[0, 0, :, lanes] = jnp.where(live, Sn, S)


def ssd_decode(state, x, dt, A, B, C, active, layer, *,
               interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """One token of the recurrence for every slot, in place.

    ``state`` [layers, S, N, H * P] float32, the whole array (donated by the
    caller's program, aliased to the first result); ``x`` [S, H, P], ``dt``
    [S, H] (after its softplus), ``A`` [H], ``B``, ``C`` [S, G, N];
    ``active`` [S] bool; ``layer`` an int or int32 scalar: which layer's
    states. Returns (state, y [S, H, P] float32) with ``y = S' C``: the
    caller adds ``D x``. A slot that is not active gets its state back
    unchanged; its ``y`` is dead."""
    L, S, N, lanes = state.shape
    H, P = x.shape[1], x.shape[2]
    G = B.shape[1]
    if (state.dtype != jnp.float32 or lanes != H * P or H % G
            or B.shape != (S, G, N) or C.shape != B.shape):
        raise ValueError(
            f"state {state.shape} {state.dtype} is not float32 "
            f"[layers, {S}, N, {H}*{P}] for x {x.shape}, B {B.shape}, "
            f"C {C.shape}")
    return _ssd_decode(
        state, x, dt, A, B, C, active.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1), interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssd_decode(state, x, dt, A, B, C, active, layer, *, interpret):
    _, S, N, lanes = state.shape
    H, P = x.shape[1], x.shape[2]
    G = B.shape[1]
    W = _lanes_per_block(H, G, P, N)
    per_group = lanes // G // W                 # blocks a group
    w = 128 if W % 128 == 0 else W
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    dt = f32(dt)
    rows = jnp.stack([
        jnp.repeat(jnp.exp(dt * f32(A)), P, axis=1),            # exp(dt A)
        (dt[..., None] * f32(x)).reshape(S, lanes)], axis=1)    # [S, 2, H P]
    # [S, G, N, 6]: B's three bfloat16 parts, then C's, N down the sublanes.
    bc = jnp.concatenate([split3(f32(B)[..., None]),
                          split3(f32(C)[..., None])], axis=-1)
    ones = jnp.ones((SPLIT_PARTS, w), jnp.bfloat16)
    e = jnp.stack([jnp.concatenate([ones, 0 * ones]),
                   jnp.concatenate([0 * ones, ones])])          # [2, 6, w]
    state_spec = pl.BlockSpec(
        (1, 1, N, W), lambda s, j, lyr, act: (lyr[0], s, 0, j))
    state, y = pl.pallas_call(
        _ssd_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S, lanes // W),
            in_specs=[state_spec,
                      pl.BlockSpec((1, 1, N, 2 * SPLIT_PARTS),
                                   lambda s, j, lyr, act: (s, j // per_group, 0, 0)),
                      pl.BlockSpec((1, 2, W), lambda s, j, lyr, act: (s, 0, j)),
                      pl.BlockSpec((2, 2 * SPLIT_PARTS, w),
                                   lambda s, j, lyr, act: (0, 0, 0))],
            out_specs=[state_spec,
                       pl.BlockSpec((1, 1, W), lambda s, j, lyr, act: (s, 0, j))]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, jnp.float32),
                   jax.ShapeDtypeStruct((S, 1, lanes), jnp.float32)],
        # Operand 2 (after the two scalar-prefetch operands) is the state.
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="ssd_decode",
        interpret=interpret,
    )(layer, active, state, bc, rows, e)
    return state, y.reshape(S, H, P)


def ssd_decode_reference(state, x, dt, A, B, C, active, layer):
    """:func:`ssd_decode` in plain ``jnp``: the kernel's oracle, and the
    ``gather`` mode's path (the CPU tier-1 default, where interpreting the
    kernel a token a slot would tax the tests)."""
    H = x.shape[1]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    S0 = jax.vmap(lambda s: unfold_state(s, H))(state[layer])   # [S, H, P, N]
    dt = f32(dt)
    Sn = (S0 * jnp.exp(dt * f32(A))[..., None, None]
          + jnp.einsum("shp,shn->shpn", dt[..., None] * f32(x),
                       _per_head(f32(B), H), precision=_HI))
    y = jnp.einsum("shpn,shn->shp", Sn, _per_head(f32(C), H), precision=_HI)
    Sn = jnp.where(active[:, None, None, None], Sn, S0)
    return state.at[layer].set(jax.vmap(fold_state)(Sn)), y
