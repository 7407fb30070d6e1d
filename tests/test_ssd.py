"""Mamba-2's recurrence in its three forms against each other.

``ops/ssd.py``: the token-by-token recurrence is the oracle; the chunk-wise
prefill and the one-token decode kernel (interpreted here) must give its
outputs and its state. Everything is float32 (``conftest`` pins matmul
precision to ``highest``), so what differs is the order of summation: 5e-5 on
outputs of order one (the kernel's ``B`` and ``C`` pass one bfloat16 product
as three parts: 24 bits). A wrong decay mask, a transposed state, a head on
another group's ``B`` or a dropped ``dt`` moves them by 1e-2 and more.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import ssd

TOL = 5e-5
H, P, G, N = 4, 16, 2, 8
CHUNK = 16


# Nemotron-H's published mixer: 64 heads of 64 channels (half a 128-lane tile
# a head), 8 groups of 8 heads, state 128; a kernel block is a whole group.
NEMOTRON = (64, 64, 8, 128)


def inputs(T, seed=0, heads=H, groups=G, channels=P, state=N):
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (T, heads, channels))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (T, heads)) - 2.0)
    A = -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=0.0, maxval=2.7))
    B = jax.random.normal(ks[3], (T, groups, state))
    C = jax.random.normal(ks[4], (T, groups, state))
    D = jax.random.normal(ks[5], (heads,))
    return x, dt, A, B, C, D


@pytest.mark.parametrize("T", [1, 15, 16, 17, 40, 65])
def test_chunked_prefill_equals_the_recurrence(T):
    """Chunk boundaries at, before and after the sequence's end."""
    args = inputs(T, seed=T)
    y_r, s_r = ssd.recurrence(*args)
    y_c, s_c = ssd.chunked(*args, chunk=CHUNK)
    np.testing.assert_allclose(y_c, y_r, atol=TOL)
    np.testing.assert_allclose(s_c, s_r, atol=TOL)


def test_chunked_prefill_at_nemotrons_shape():
    """H=64, P=64, G=8, N=128 in the published chunks of 128, the sequence a
    chunk and two tokens: outputs are sums of 128 products of order one
    (magnitudes up to tens), so the order of summation is held relatively."""
    h, p, g, n = NEMOTRON
    args = inputs(130, seed=3, heads=h, groups=g, channels=p, state=n)
    y_r, s_r = ssd.recurrence(*args)
    y_c, s_c = ssd.chunked(*args, chunk=128)
    np.testing.assert_allclose(y_c, y_r, rtol=1e-4, atol=4 * TOL)
    np.testing.assert_allclose(s_c, s_r, rtol=1e-4, atol=4 * TOL)


def test_a_state_carried_in_is_carried_on():
    """Two halves, the first's state handed to the second, equal the whole:
    in the chunk-wise form, in the oracle, and across the two."""
    x, dt, A, B, C, D = inputs(70, seed=2)
    cut = 27
    head = lambda a: a[:cut]  # noqa: E731
    tail = lambda a: a[cut:]  # noqa: E731
    y_r, s_r = ssd.recurrence(x, dt, A, B, C, D)
    _, s_mid = ssd.chunked(head(x), head(dt), A, head(B), head(C), D,
                           chunk=CHUNK)
    y_2, s_2 = ssd.chunked(tail(x), tail(dt), A, tail(B), tail(C), D, s_mid,
                           chunk=CHUNK)
    np.testing.assert_allclose(y_2, y_r[cut:], atol=TOL)
    np.testing.assert_allclose(s_2, s_r, atol=TOL)


def test_strong_decay_does_not_overflow_the_mask():
    """dt A of -6 a token: exp(cum_i - cum_j) above the diagonal would be
    e^90 inside a chunk of 16."""
    x, dt, A, B, C, D = inputs(40, seed=5)
    dt, A = dt * 0 + 2.0, A * 0 - 3.0
    y_c, s_c = ssd.chunked(x, dt, A, B, C, D, chunk=CHUNK)
    y_r, s_r = ssd.recurrence(x, dt, A, B, C, D)
    assert np.isfinite(np.asarray(y_c)).all()
    np.testing.assert_allclose(y_c, y_r, atol=TOL)
    np.testing.assert_allclose(s_c, s_r, atol=TOL)


@pytest.mark.parametrize("real", [1, 15, 16, 37])
def test_a_padded_tail_leaves_the_state_as_after_the_real_tokens(real):
    """A bucket of 48 with ``real`` real tokens: pad positions carry
    whatever x, B, C the pad token gives, with dt = 0."""
    x, dt, A, B, C, D = inputs(48, seed=7)
    mask = (jnp.arange(48) < real)[:, None]
    y_c, s_c = ssd.chunked(x, jnp.where(mask, dt, 0.0), A, B, C, D,
                           chunk=CHUNK)
    y_r, s_r = ssd.recurrence(x[:real], dt[:real], A, B[:real], C[:real], D)
    np.testing.assert_allclose(y_c[:real], y_r, atol=TOL)
    np.testing.assert_allclose(s_c, s_r, atol=TOL)


def test_the_heads_of_a_group_share_its_b_and_c():
    """Two groups of two heads: the oracle on 4 heads with grouped B, C
    equals the oracle on 4 heads each handed its own copy; and swapping the
    groups' B moves every head's output (no head reads past its group)."""
    x, dt, A, B, C, D = inputs(20, seed=9)
    y_g, s_g = ssd.recurrence(x, dt, A, B, C, D)
    own = lambda a: jnp.repeat(a, H // G, axis=1)  # noqa: E731
    y_o, s_o = ssd.recurrence(x, dt, A, own(B), own(C), D)
    np.testing.assert_array_equal(np.asarray(y_g), np.asarray(y_o))
    y_c, _ = ssd.chunked(x, dt, A, B, C, D, chunk=CHUNK)
    y_s, _ = ssd.chunked(x, dt, A, B[:, ::-1], C, D, chunk=CHUNK)
    np.testing.assert_allclose(y_c, y_g, atol=TOL)
    moved = np.abs(np.asarray(y_s - y_c)).max(axis=(0, 2))      # a head
    assert (moved > 1e-2).all(), moved


def fold_all(states):
    return jnp.stack([ssd.fold_state(s) for s in states])


def test_fold_and_unfold_are_inverses():
    s = jax.random.normal(jax.random.key(0), (H, P, N))
    folded = ssd.fold_state(s)
    assert folded.shape == (N, H * P)
    np.testing.assert_array_equal(np.asarray(ssd.unfold_state(folded, H)),
                                  np.asarray(s))
    # head h's channel p is lane h * P + p, the state size down the rows
    assert float(folded[3, 2 * P + 5]) == float(s[2, 5, 3])


@pytest.mark.parametrize("decode,shape", [
    ("kernel", (H, P, G, N)), ("reference", (H, P, G, N)),
    pytest.param("kernel", NEMOTRON, id="kernel-nemotron"),
    pytest.param("reference", NEMOTRON, id="reference-nemotron")])
def test_decode_follows_the_recurrence_step_by_step(decode, shape):
    """Three slots, two layers of state, six token steps of layer 1: slot 1
    is parked throughout and slot 2 from step 3. Every step's output and
    state against the oracle advanced one token; the parked slots and the
    other layer bit for bit what they were. At the tiny shape and at
    Nemotron-H's (a grid of 3 slots x 8 whole groups)."""
    S, L, T = 3, 2, 6
    H, P, G, N = shape
    seqs = [inputs(T, seed=10 + s, heads=H, groups=G, channels=P, state=N)
            for s in range(S)]
    A, D = seqs[0][2], seqs[0][5]
    start = [jax.random.normal(jax.random.key(20 + s), (H, P, N))
             for s in range(S)]
    state = jnp.stack([jax.random.normal(jax.random.key(30), (S, N, H * P)),
                       fold_all(start)])
    other = np.asarray(state[0])
    want = list(start)
    step = (lambda *a: ssd.ssd_decode(*a, interpret=True)) if (
        decode == "kernel") else ssd.ssd_decode_reference
    for t in range(T):
        active = np.asarray([True, False, t < 3])
        x, dt, B, C = (jnp.stack([seq[i][t] for seq in seqs])
                       for i in (0, 1, 3, 4))
        before = np.asarray(state[1])
        state, y = step(state, x, dt, A, B, C, jnp.asarray(active), 1)
        for s in range(S):
            if not active[s]:
                np.testing.assert_array_equal(np.asarray(state[1, s]),
                                              before[s])
                continue
            y_r, want[s] = ssd.recurrence(
                x[s][None], dt[s][None], A, B[s][None], C[s][None],
                jnp.zeros_like(D), want[s])
            np.testing.assert_allclose(y[s], y_r[0], atol=TOL)
            np.testing.assert_allclose(state[1, s], ssd.fold_state(want[s]),
                                       atol=TOL)
        np.testing.assert_array_equal(np.asarray(state[0]), other)


def test_prefill_then_decode_is_the_whole_sequence():
    """The serve path's hand-off: the chunk-wise form over a prompt, folded,
    then the kernel a token at a time, against the oracle over all of it."""
    x, dt, A, B, C, D = inputs(45, seed=4)
    cut = 33
    y_r, s_r = ssd.recurrence(x, dt, A, B, C, D)
    _, s_p = ssd.chunked(x[:cut], dt[:cut], A, B[:cut], C[:cut], D,
                         chunk=CHUNK)
    state = ssd.fold_state(s_p)[None, None]
    for t in range(cut, 45):
        state, y = ssd.ssd_decode(state, x[t][None], dt[t][None], A,
                                  B[t][None], C[t][None],
                                  jnp.ones((1,), bool), 0, interpret=True)
        np.testing.assert_allclose(y[0] + D[:, None] * x[t], y_r[t],
                                   atol=TOL)
    np.testing.assert_allclose(state[0, 0], ssd.fold_state(s_r), atol=TOL)


def test_the_kernel_refuses_a_state_that_is_not_its_own():
    x, dt, A, B, C, D = inputs(2, seed=1)
    good = jnp.zeros((1, 2, N, H * P), jnp.float32)
    active = jnp.ones((2,), bool)
    with pytest.raises(ValueError, match="float32"):
        ssd.ssd_decode(good.astype(jnp.bfloat16), x, dt, A, B, C, active, 0)
    with pytest.raises(ValueError, match="float32"):
        ssd.ssd_decode(jnp.zeros((1, 2, H * P, N), jnp.float32), x, dt, A, B,
                       C, active, 0)


@pytest.mark.parametrize("heads,groups,P_,N_,want", [
    (32, 2, 128, 256, 1024),      # the published sizes: 8 heads, 1 MiB
    (4, 2, 16, 8, 32),            # tiny: a group's whole run
    (16, 1, 64, 128, 1024),       # one group, whole: 512 KiB, under the block's bytes
    (64, 8, 64, 128, 512),        # Nemotron-H: a WHOLE group's 8 heads, 256 KiB
    (32, 2, 128, 128, 2048),      # a whole group where it fits the block: 1 MiB
])
def test_a_block_is_whole_heads_of_one_group(heads, groups, P_, N_, want):
    W = ssd._lanes_per_block(heads, groups, P_, N_)
    assert W == want
    assert (heads // groups * P_) % W == 0 and W % P_ == 0
