"""``ops/moe.py:held_experts_ffn``'s bounded row buffer against a plain loop.

A prefill bucket's expert layer multiplies the pairs it holds through a
buffer of the chip's share of the picks, walked in windows; a decode step
keeps the ``N * k`` rows; where many experts are held the pairs are laid out
a capacity of rows an expert, a prefill bucket's walked in passes. Each case
below is held to a per-token loop over the picks in float64 (not to the
unbounded form), and its counts exactly.
"""

import functools
import hashlib

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
    # Nemotron-H: the decode step and the buckets up to 128 tokens take 64
    # rows; past a mean of 8 pairs an expert four times the mean, a power of
    # two from 64 to 256
    (128, 6, (0, 64), 128, 64), (16, 6, (0, 64), 128, 64),
    (256, 6, (0, 64), 128, 64), (2048, 6, (0, 64), 128, 256),
    (128, 6, (0, 16), 128, None),
    (512, 6, (0, 64), 128, 128), (1024, 6, (0, 64), 128, 256),
    (2176, 6, (0, 64), 128, 256), (8192, 6, (0, 64), 128, 256),
    # a prefill bucket of a chip that holds 12: the bounded row buffer's
    (2048, 8, (0, 12), 384, None),
])
def test_capacity_form_is_read_off_the_shapes(N, k, held, n_routed, cap):
    assert moe.held_capacity(N, k, held, n_routed) == cap
    if cap is not None:
        assert cap in (64, 128, 256)
        assert cap >= min(256, 4 * N * k / n_routed)


def _picks_with_one_busy_expert(key, N, k, held, n_routed, live, busy):
    """Even picks (k distinct outputs a token), then held expert ``first``
    picked by exactly the first ``busy`` of the ``live`` tokens and by no
    other: a token that has it and must not swaps it for an output it has
    not, one that lacks it and must have it swaps its first pick for it."""
    idx = np.array(_picks("even", key, N, k, held, n_routed, 0))
    first = held[0]
    must = np.zeros((N,), bool)
    must[np.flatnonzero(live)[:busy]] = True
    for n in range(N):
        has = idx[n] == first
        if has.any() and not must[n]:
            idx[n, has] = next(e for e in range(n_routed - 1, -1, -1)
                               if e != first and e not in idx[n])
        elif must[n] and not has.any():
            idx[n, 0] = first
    return jnp.asarray(idx, jnp.int32)


# The capacity form WALKED (a prefill bucket of a chip that holds many
# experts): 32 of 64 held, top-4, 2,048 tokens: a mean of 128 pairs an expert
# under an even router, 256 rows a pass (four times the mean is past the
# most a pass takes). (load, dead rows of the padded tail, pairs of the
# busiest expert or None for what the even load gives, passes)
WALK_CASES = [
    pytest.param("even", 64, None, 1, id="even_one_pass"),
    # one expert gets five times the mean: 256 + 256 + 128 rows
    pytest.param("busy", 64, 640, 3, id="one_expert_5x_three_passes"),
    # every live token picks one expert: ceil(1,984 / 256) passes
    pytest.param("busy", 64, 1984, 8, id="every_token_on_one_expert"),
    pytest.param("busy", 0, 2048, 8, id="every_token_no_dead_row"),
    pytest.param("none_held", 64, None, 0, id="no_pair_no_pass"),
]


@pytest.mark.parametrize("form", moe.EXPERT_FORMS)
@pytest.mark.parametrize("load,dead,busy,passes", WALK_CASES)
def test_capacity_passes_give_the_plain_loops_sum_and_counts(
        load, dead, busy, passes, form):
    N, k, held, n_routed, cap = 2048, 4, (16, 32), 64, 256
    keys = jax.random.split(jax.random.key(dead + (busy or 0)), 5)
    h = jax.random.normal(keys[0], (N, D))
    w_in = jax.random.normal(
        keys[1], (held[1], D, F if form == "relu2" else 2 * F)) * 0.2
    w_down = jax.random.normal(keys[2], (held[1], F, D)) * 0.2
    w = jax.random.uniform(keys[4], (N, k)) + 0.1
    valid = np.arange(N) < N - dead             # a bucket's padded tail
    idx = (_picks_with_one_busy_expert(keys[3], N, k, held, n_routed, valid,
                                       busy) if load == "busy"
           else _picks(load, keys[3], N, k, held, n_routed, 0))

    assert moe.held_row_bound(N, k, held, n_routed) is None
    assert moe.held_capacity(N, k, held, n_routed) == cap
    run = jax.jit(functools.partial(moe.held_experts_ffn, held=held,
                                    n_routed=n_routed, form=form))
    out, counts = run(h, idx, w, w_in, w_down, valid=jnp.asarray(valid))
    want, want_counts = _plain(h, idx, w, w_in, w_down, held, n_routed, valid,
                               form=form)
    np.testing.assert_allclose(out, want, rtol=5e-5, atol=5e-5)
    assert not np.asarray(out)[~valid].any()
    if busy is not None:
        assert want_counts[3] == busy
    assert -(-want_counts[3] // cap) == passes
    # every held pick is in the sum and in the counts: none dropped
    assert dict(zip(moe.PICK_COUNT_NAMES, map(int, counts))) == dict(
        zip(moe.PICK_COUNT_NAMES, want_counts + [1, max(passes - 1, 0)]))


def _traced(N, k, held, n_routed, form="silu_gate"):
    args = (jnp.zeros((N, D)), jnp.zeros((N, k), jnp.int32),
            jnp.zeros((N, k)),
            jnp.zeros((held[1], D, F if form == "relu2" else 2 * F)),
            jnp.zeros((held[1], F, D)))
    return str(jax.make_jaxpr(functools.partial(
        moe.held_experts_ffn, held=held, n_routed=n_routed, form=form))(
            *args, valid=jnp.ones((N,), bool)))


@pytest.mark.parametrize("N", [256, 512, 1024, 2176])
def test_a_walked_call_holds_no_grouped_product(N):
    """64 of 128 held, a bucket of 256 tokens or more: the traced call holds
    the batched products in a loop and no ``ragged_dot``."""
    text = _traced(N, 6, (0, 64), 128, "relu2")
    assert "ragged_dot" not in text and "while[" in text
    assert "cond[" not in text


# sha256 of ``str(jax.make_jaxpr(held_experts_ffn))`` at ``D, F = 32, 16``,
# under ``tests/conftest.py``'s configuration, taken on the commit BEFORE the
# walk in passes (PR 46's tree): the calls whose rule did not change trace to
# the program they were. A PR that changes one of these forms on purpose takes
# new digests.
UNCHANGED = [
    # Kimi-K2.5 (12 held): decode step, the bounded 2,048 bucket
    (96, 8, (0, 12), 384, "silu_gate", "f47db6413b891cbb"),
    (2048, 8, (0, 12), 384, "silu_gate", "b18a8aff4d299768"),
    # LongCat-Flash (16 held): decode step, the 512 bucket
    (128, 12, (0, 16), 512, "silu_gate", "fb048aa26ac8ee70"),
    (512, 12, (0, 16), 512, "silu_gate", "a1ca11c28abba609"),
    # Trinity (16 held): decode step, the 8,192 bucket
    (64, 4, (0, 16), 256, "silu_gate", "ca1ad4951d9508dd"),
    (8192, 4, (0, 16), 256, "silu_gate", "c47e42f5077afa7d"),
    # Nemotron-H (64 held): decode step and the 16 bucket: 64 rows, the cond
    (128, 6, (0, 64), 128, "relu2", "825d0f5c95ee8a76"),
    (16, 6, (0, 64), 128, "relu2", "274e1af567f74d0d"),
]


@pytest.mark.parametrize("N,k,held,n_routed,form,digest", UNCHANGED)
def test_the_other_calls_trace_to_the_program_they_were(
        N, k, held, n_routed, form, digest):
    text = _traced(N, k, held, n_routed, form)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest
    assert "ragged_dot" in text


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
