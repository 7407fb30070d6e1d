"""``ops/moe.py:held_experts_ffn``'s bounded row buffer against a plain loop.

A prefill bucket's expert layer multiplies the pairs it holds through a
buffer of the chip's share of the picks, walked in windows; a decode step
keeps the ``N * k`` rows. Each case below is held to a per-token loop over
the picks in float64 (not to the unbounded form), and its counts exactly.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import moe

D, F = 32, 16


def _plain(h, idx, w, w_gate_up, w_down, held, n_routed, valid,
           form="silu_gate"):
    """out[n] = sum over n's picks, one at a time, in float64; the expert's
    first matrix is ``[D, 2F]`` (gate | up) or, for ``relu2``, ``[D, F]``."""
    h, w, w_gate_up, w_down = (np.asarray(a, np.float64)
                               for a in (h, w, w_gate_up, w_down))
    idx = np.asarray(idx)
    first, count = held
    out = np.zeros_like(h)
    sizes = np.zeros((count,), np.int64)
    picks = picks_zero = 0
    for n in range(h.shape[0]):
        if not valid[n]:
            continue
        for j in range(idx.shape[1]):
            e = int(idx[n, j])
            picks += 1
            if e >= n_routed:
                out[n] += w[n, j] * h[n]
                picks_zero += 1
            elif first <= e < first + count:
                gu = h[n] @ w_gate_up[e - first]
                gate, up = gu[:F], gu[F:]
                hidden = (np.maximum(gu, 0.0) ** 2 if form == "relu2"
                          else gate / (1 + np.exp(-gate)) * up)
                out[n] += w[n, j] * (hidden @ w_down[e - first])
                sizes[e - first] += 1
    return out, [picks, picks_zero, int(sizes.sum()), int(sizes.max()),
                 int((sizes > 0).sum())]


def _picks(load, key, N, k, held, n_routed, n_zero):
    """idx [N, k]: k DISTINCT router outputs a token, as top-k gives."""
    first, count = held
    absent = [e for e in range(n_routed) if not first <= e < first + count]
    if load == "even":
        scores = jax.random.uniform(key, (N, n_routed + n_zero))
        return jax.lax.top_k(scores, k)[1].astype(jnp.int32)
    if load == "all_held":          # min(k, count) held picks, the rest absent
        row = (list(range(first, first + count)) + absent)[:k]
        return jnp.tile(jnp.asarray([row], jnp.int32), (N, 1))
    if load == "none_held":
        return jnp.tile(jnp.asarray([absent[:k]], jnp.int32), (N, 1))
    raise ValueError(load)


# (N, k, held, n_routed, zero-compute outputs, load, dead rows, rows of the
# buffer or None for N * k, windows walked)
CASES = [
    # (a) a near-even router: ~75 of 2,400 pairs are held, one window
    pytest.param(600, 4, (8, 4), 128, 0, "even", 0, 512, 1,
                 id="even_one_window"),
    # (b) every token on the held experts alone: 4 groups of 600 rows, each
    # straddling an edge of the 512-row windows, the fifth window part full
    pytest.param(600, 4, (8, 4), 128, 0, "all_held", 0, 512, 5,
                 id="all_held_five_windows"),
    # k past the experts held: a token's pairs are min(k, count) = 4 of 8
    pytest.param(600, 8, (4, 4), 256, 0, "all_held", 0, 512, 5,
                 id="all_held_k_over_count"),
    # (c) dead rows route nowhere and count no pick
    pytest.param(600, 4, (8, 4), 128, 0, "even", 200, 512, 1,
                 id="dead_rows_even"),
    pytest.param(600, 4, (8, 4), 128, 0, "all_held", 215, 512, 4,
                 id="dead_rows_all_held"),
    # (d) zero-compute picks add w * h and no row
    pytest.param(600, 4, (8, 4), 128, 64, "even", 0, 512, 1,
                 id="zero_compute_picks"),
    # (e) no pair at all: no window is walked
    pytest.param(600, 4, (8, 4), 128, 0, "none_held", 0, 512, 0,
                 id="no_pair"),
    # a decode step's shape (Kimi's 96 slots x top-8, 12 of 384): N * k rows
    pytest.param(96, 8, (24, 12), 384, 0, "even", 0, None, None,
                 id="decode_shape"),
    pytest.param(96, 8, (24, 12), 384, 0, "all_held", 7, None, None,
                 id="decode_shape_all_held"),
]


@pytest.mark.parametrize(
    "N,k,held,n_routed,n_zero,load,dead,rows,windows", CASES)
def test_bounded_rows_give_the_plain_loops_sum_and_counts(
        N, k, held, n_routed, n_zero, load, dead, rows, windows):
    keys = jax.random.split(jax.random.key(N + k + n_zero + dead), 5)
    h = jax.random.normal(keys[0], (N, D))
    w_gate_up = jax.random.normal(keys[1], (held[1], D, 2 * F)) * 0.2
    w_down = jax.random.normal(keys[2], (held[1], F, D)) * 0.2
    idx = _picks(load, keys[3], N, k, held, n_routed, n_zero)
    w = jax.random.uniform(keys[4], (N, k)) + 0.1
    valid = np.ones((N,), bool)
    valid[N - dead:] = False
    valid = np.roll(valid, 17)          # dead rows in the middle of the bucket

    assert moe.held_row_bound(N, k, held, n_routed) == rows
    run = jax.jit(functools.partial(moe.held_experts_ffn, held=held,
                                    n_routed=n_routed))
    out, counts = run(h, idx, w, w_gate_up, w_down, valid=jnp.asarray(valid))
    want, want_counts = _plain(h, idx, w, w_gate_up, w_down, held, n_routed,
                               valid)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    assert not np.asarray(out)[~valid].any()
    bounded = [0, 0] if rows is None else [1, max(windows - 1, 0)]
    assert dict(zip(moe.PICK_COUNT_NAMES, map(int, counts))) == dict(
        zip(moe.PICK_COUNT_NAMES, want_counts + bounded))
    if windows is not None:             # the case walks what its name says
        assert -(-want_counts[2] // rows) == windows


# The two-matrix expert (``form="relu2"``: ``W_down relu(W_up h)^2``, the
# first matrix [E, D, F]) in the bounded AND the unbounded form.
RELU2_CASES = [
    pytest.param(600, 4, (8, 4), 128, "even", 0, 512, 1, id="relu2_even"),
    # 384 live tokens on the 4 held experts alone: 1,536 pairs, THREE
    # windows of 512 rows, every group straddling an edge; no pick dropped
    pytest.param(600, 4, (8, 4), 128, "all_held", 216, 512, 3,
                 id="relu2_three_windows"),
    # Nemotron-H's decode step: 128 slots x top-6, 64 of 128 held: the
    # CAPACITY form (64 rows an expert, a batched product), ~6 rows an expert
    pytest.param(128, 6, (0, 64), 128, "even", 5, None, None,
                 id="relu2_decode_shape"),
    # the same shape with every token on six experts alone: 128 rows an
    # expert overflow the capacity, the all-rows product takes the call
    pytest.param(128, 6, (0, 64), 128, "all_held", 0, None, "overflow",
                 id="relu2_capacity_overflows"),
    # a small prefill bucket of the same layer: capacity form, 0.75 rows a mean
    pytest.param(16, 6, (0, 64), 128, "even", 3, None, None,
                 id="relu2_small_bucket"),
]


@pytest.mark.parametrize("N,k,held,n_routed,load,dead,rows,windows",
                         RELU2_CASES)
def test_relu2_experts_give_the_plain_loops_sum_and_counts(
        N, k, held, n_routed, load, dead, rows, windows):
    keys = jax.random.split(jax.random.key(N + k + dead), 5)
    h = jax.random.normal(keys[0], (N, D))
    w_up = jax.random.normal(keys[1], (held[1], D, F)) * 0.2
    w_down = jax.random.normal(keys[2], (held[1], F, D)) * 0.2
    idx = _picks(load, keys[3], N, k, held, n_routed, 0)
    w = jax.random.uniform(keys[4], (N, k)) + 0.1
    valid = np.ones((N,), bool)
    valid[N - dead:] = False
    valid = np.roll(valid, 17)

    assert moe.held_row_bound(N, k, held, n_routed) == rows
    run = jax.jit(functools.partial(moe.held_experts_ffn, held=held,
                                    n_routed=n_routed, form="relu2"))
    out, counts = run(h, idx, w, w_up, w_down, valid=jnp.asarray(valid))
    want, want_counts = _plain(h, idx, w, w_up, w_down, held, n_routed, valid,
                               form="relu2")
    np.testing.assert_allclose(out, want, rtol=5e-5, atol=5e-5)
    assert not np.asarray(out)[~valid].any()
    # every held pick is in the sum: none dropped however many windows
    assert int(counts[2]) == want_counts[2] > 0
    if rows is None:        # the capacity form, or its overflow into all rows
        assert moe.held_capacity(N, k, held, n_routed) == 64
        assert (want_counts[3] > 64) == (windows == "overflow")
        bounded = [0, int(windows == "overflow")]
    else:
        bounded = [1, max(windows - 1, 0)]
    assert dict(zip(moe.PICK_COUNT_NAMES, map(int, counts))) == dict(
        zip(moe.PICK_COUNT_NAMES, want_counts + bounded))
    if rows is not None:
        assert -(-want_counts[2] // rows) == windows
    # the gated form's first matrix is refused for a relu2 expert, and back
    with pytest.raises(ValueError, match="first matrix"):
        moe.held_experts_ffn(h, idx, w, jnp.zeros((held[1], D, 2 * F)),
                             w_down, held=held, n_routed=n_routed,
                             form="relu2")
    with pytest.raises(ValueError, match="first matrix"):
        moe.held_experts_ffn(h, idx, w, w_up, w_down, held=held,
                             n_routed=n_routed)


@pytest.mark.parametrize("N,k,held,n_routed,rows", [
    # Kimi-K2.5: top-8, 12 of 384; buckets, then the 96-slot decode step
    (1024, 8, (0, 12), 384, 512), (2048, 8, (0, 12), 384, 1024),
    (3072, 8, (0, 12), 384, 1536), (96, 8, (0, 12), 384, None),
    (128, 8, (0, 12), 384, None), (256, 8, (0, 12), 384, 512),
    # LongCat-Flash: top-12, 16 of 512; the 128-slot decode step
    (256, 12, (0, 16), 512, 512), (512, 12, (0, 16), 512, 512),
    (128, 12, (0, 16), 512, None),
    # Trinity: top-4, 16 of 256; the 64-slot decode step
    (8192, 4, (0, 16), 256, 4096), (64, 4, (0, 16), 256, None),
    # Nemotron-H: top-6, 64 of 128: twice the share is the whole at every
    # shape (the 128-slot decode step, the largest bucket)
    (128, 6, (0, 64), 128, None), (2048, 6, (0, 64), 128, None),
    # every expert held: the share is the whole, nothing to bound
    (2048, 8, (0, 384), 384, None),
])
def test_row_bound_is_read_off_the_shapes(N, k, held, n_routed, rows):
    assert moe.held_row_bound(N, k, held, n_routed) == rows
    if rows is not None:
        assert rows % 512 == 0 and 4 * rows <= N * k
        assert rows >= 2 * N * k * held[1] / n_routed


@pytest.mark.parametrize("N,k,held,n_routed,cap", [
    # the three accepted expert cells' decode steps keep the grouped product
    (96, 8, (0, 12), 384, None), (128, 12, (0, 16), 512, None),
    (64, 4, (0, 16), 256, None),
    # Nemotron-H: the decode step and the buckets up to 128 tokens; past a
    # mean of 8 pairs an expert the grouped product again
    (128, 6, (0, 64), 128, 64), (16, 6, (0, 64), 128, 64),
    (256, 6, (0, 64), 128, None), (2048, 6, (0, 64), 128, None),
    (128, 6, (0, 16), 128, None),
])
def test_capacity_form_is_read_off_the_shapes(N, k, held, n_routed, cap):
    assert moe.held_capacity(N, k, held, n_routed) == cap


def test_the_capacity_form_serves_the_gated_expert_too():
    """32 gated experts held, 2 rows an expert: the capacity form against
    the plain loop (the form is an argument of one code path)."""
    N, k, held, n_routed = 32, 4, (32, 32), 64
    keys = jax.random.split(jax.random.key(11), 5)
    h = jax.random.normal(keys[0], (N, D))
    w_gate_up = jax.random.normal(keys[1], (32, D, 2 * F)) * 0.2
    w_down = jax.random.normal(keys[2], (32, F, D)) * 0.2
    idx = _picks("even", keys[3], N, k, held, n_routed, 0)
    w = jax.random.uniform(keys[4], (N, k)) + 0.1
    valid = np.ones((N,), bool)
    assert moe.held_capacity(N, k, held, n_routed) == 64
    out, counts = jax.jit(functools.partial(
        moe.held_experts_ffn, held=held, n_routed=n_routed))(
            h, idx, w, w_gate_up, w_down, valid=jnp.asarray(valid))
    want, want_counts = _plain(h, idx, w, w_gate_up, w_down, held, n_routed,
                               valid)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    assert list(map(int, counts)) == want_counts + [0, 0]


def test_the_bounded_program_holds_no_array_of_all_the_pairs():
    """Nothing ``N * k`` rows by ``D`` or ``2F`` is left in the traced
    program of a bounded call; the unbounded one has them (the scan sees)."""
    def widest(N, k, held, n_routed):
        args = (jnp.zeros((N, D)), jnp.zeros((N, k), jnp.int32),
                jnp.zeros((N, k)), jnp.zeros((held[1], D, 2 * F)),
                jnp.zeros((held[1], F, D)))
        jaxpr = jax.make_jaxpr(functools.partial(
            moe.held_experts_ffn, held=held, n_routed=n_routed))(*args)

        def shapes(j):
            for eqn in j.eqns:
                for v in eqn.outvars:
                    yield tuple(v.aval.shape)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from shapes(sub)
        return {s for s in shapes(jaxpr.jaxpr)
                if len(s) >= 2 and s[0] * (s[1] if len(s) == 3 else 1)
                >= N * k and s[-1] in (D, F, 2 * F)}

    assert widest(600, 4, (8, 4), 128) == set()
    assert widest(96, 8, (24, 12), 384) != set()
