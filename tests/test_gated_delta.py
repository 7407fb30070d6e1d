"""The gated delta rule's three forms against each other.

``ops/gated_delta.py``: the token-by-token recurrence is the oracle; the
chunk-wise prefill and the one-token decode kernel (interpreted here) must
give its outputs and its state. Everything is float32 (``conftest`` pins
matmul precision to ``highest``), so what differs is the order of summation
and the triangular solve: 5e-5 on outputs of order one. A wrong decay mask,
a transposed state or a dropped ``beta`` moves them by 1e-2 and more.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import gated_delta as gd

TOL = 5e-5
H, DK, DV = 4, 8, 16


def inputs(T, seed=0, strong_beta=False):
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (T, H, DK))) * DK ** -0.5
    k = unit(jax.random.normal(ks[1], (T, H, DK)))
    v = jax.random.normal(ks[2], (T, H, DV))
    g = -jax.random.uniform(ks[3], (T, H)) * 0.7
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))
    if strong_beta:          # every beta in (1, 2): negative eigenvalues
        beta = 1.0 + beta / 2.0
    return q, k, v, g, beta


@pytest.mark.parametrize("T", [1, 63, 64, 65, 150, 257])
def test_chunked_prefill_equals_the_recurrence(T):
    args = inputs(T, seed=T)
    o_r, s_r = gd.recurrence(*args)
    o_c, s_c = gd.chunked(*args)
    np.testing.assert_allclose(o_c, o_r, atol=TOL)
    np.testing.assert_allclose(s_c, s_r, atol=TOL)


def test_chunked_prefill_with_beta_above_one():
    args = inputs(150, seed=3, strong_beta=True)
    assert float(args[4].min()) > 1.0
    o_r, s_r = gd.recurrence(*args)
    o_c, s_c = gd.chunked(*args)
    np.testing.assert_allclose(o_c, o_r, atol=TOL)
    np.testing.assert_allclose(s_c, s_r, atol=TOL)


def test_strong_decay_does_not_overflow_the_mask():
    """g of -3 a token: exp(G_i - G_j) above the diagonal would be e^190."""
    q, k, v, g, beta = inputs(130, seed=5)
    o_c, s_c = gd.chunked(q, k, v, g * 0 - 3.0, beta)
    o_r, s_r = gd.recurrence(q, k, v, g * 0 - 3.0, beta)
    assert np.isfinite(np.asarray(o_c)).all()
    np.testing.assert_allclose(o_c, o_r, atol=TOL)
    np.testing.assert_allclose(s_c, s_r, atol=TOL)


@pytest.mark.parametrize("real", [1, 37, 64, 100])
def test_a_padded_tail_leaves_the_state_as_after_the_real_tokens(real):
    """A bucket of 128 with ``real`` real tokens: pad positions carry
    whatever q, k, v the pad token gives, with g = 0 and beta = 0."""
    q, k, v, g, beta = inputs(128, seed=7)
    mask = (jnp.arange(128) < real)[:, None]
    o_c, s_c = gd.chunked(q, k, v, jnp.where(mask, g, 0.0),
                          jnp.where(mask, beta, 0.0))
    o_r, s_r = gd.recurrence(q[:real], k[:real], v[:real], g[:real],
                             beta[:real])
    np.testing.assert_allclose(o_c[:real], o_r, atol=TOL)
    np.testing.assert_allclose(s_c, s_r, atol=TOL)


def fold_all(states):
    return jnp.stack([gd.fold_state(s) for s in states])


@pytest.mark.parametrize("impl", ["kernel", "reference"])
def test_decode_equals_the_recurrence_step_by_step_after_a_prefill(impl):
    """Three slots prefilled to different lengths, then six decode steps in
    one batch, slot 1 parked throughout: every step's output and the final
    states are the recurrence's over the whole sequence; the parked slot's
    state, and the other layer's, are bit for bit what they were."""
    lens, steps = [40, 70, 5], 6
    seqs = [inputs(n + steps, seed=10 + i) for i, n in enumerate(lens)]
    pre = [gd.chunked(*(a[:n] for a in seq)) for seq, n in zip(seqs, lens)]
    state = jnp.stack([jnp.ones((3, DK, H * DV)) * 7.0,
                       fold_all([s for _o, s in pre])])        # layer 1
    before = np.asarray(state)
    active = jnp.asarray([True, False, True])
    outs = []
    for t in range(steps):
        q, k, v, g, beta = (jnp.stack([seq[j][n + t] for seq, n in
                                       zip(seqs, lens)]) for j in range(5))
        fn = (lambda *a: gd.gdn_decode(*a, interpret=True)) \
            if impl == "kernel" else gd.gdn_decode_reference
        state, o = fn(state, q, k, v, jnp.exp(g), beta, active, 1)
        outs.append(o)
    for slot in (0, 2):
        o_r, s_r = gd.recurrence(*seqs[slot])
        got = jnp.stack([o[slot] for o in outs])
        np.testing.assert_allclose(got, o_r[lens[slot]:], atol=TOL)
        np.testing.assert_allclose(gd.unfold_state(state[1, slot], H), s_r,
                                   atol=TOL)
    np.testing.assert_array_equal(np.asarray(state[1, 1]), before[1, 1])
    np.testing.assert_array_equal(np.asarray(state[0]), before[0])


def test_kernel_equals_its_reference_on_a_state_that_is_not_zero():
    q, k, v, g, beta = inputs(5, seed=21)
    state = jax.random.normal(jax.random.key(22), (2, 5, DK, H * DV))
    active = jnp.asarray([True, True, False, True, False])
    s_k, o_k = gd.gdn_decode(state, q, k, v, jnp.exp(g), beta, active, 0,
                             interpret=True)
    s_r, o_r = gd.gdn_decode_reference(state, q, k, v, jnp.exp(g), beta,
                                       active, 0)
    np.testing.assert_allclose(s_k, s_r, atol=TOL)
    np.testing.assert_allclose(np.asarray(o_k)[np.asarray(active)],
                               np.asarray(o_r)[np.asarray(active)], atol=TOL)


def test_heads_per_group_keeps_whole_lane_tiles():
    """The published sizes: 30 heads of 96 x 192 split into three groups of
    ten heads (1,920 lanes = 15 tiles, 737 KB a block); a size with no such
    split takes all heads at once."""
    assert gd._heads_per_group(30, 96, 192) == 10
    assert gd._heads_per_group(4, 8, 16) == 4
    with pytest.raises(ValueError, match="float32"):
        gd.gdn_decode(jnp.zeros((1, 2, DK, H * DV), jnp.bfloat16),
                      jnp.zeros((2, H, DK)), jnp.zeros((2, H, DK)),
                      jnp.zeros((2, H, DV)), jnp.ones((2, H)),
                      jnp.ones((2, H)), jnp.ones((2,), bool), 0)


def test_chunked_prefill_with_keys_that_repeat():
    """The hard case for the chunk's triangular system: keys nearly equal
    (k_i . k_j near 1), beta near 2, hardly any decay, so ``A``'s entries
    are near 2 all over the lower triangle. The blocked solve
    (``_solve_unit_lower``) still gives the recurrence's answer; a 64-term
    power series in float32 would not."""
    q, k, v, g, beta = inputs(192, seed=11, strong_beta=True)
    base = k[:1]
    k = base + 0.05 * k
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    o_r, s_r = gd.recurrence(q, k, v, g * 0.02, beta)
    o_c, s_c = gd.chunked(q, k, v, g * 0.02, beta)
    scale = float(jnp.abs(o_r).max())
    np.testing.assert_allclose(o_c, o_r, atol=2e-4 * max(scale, 1.0))
    np.testing.assert_allclose(s_c, s_r, atol=2e-4 * float(jnp.abs(s_r).max()))


def test_solve_unit_lower_is_the_triangular_solve():
    A = jnp.tril(jax.random.normal(jax.random.key(0), (3, 2, 64, 64)), -1)
    rhs = jax.random.normal(jax.random.key(1), (3, 2, 64, 24))
    want = jax.scipy.linalg.solve_triangular(
        A * 0.3 + jnp.eye(64), rhs, lower=True, unit_diagonal=True)
    got = gd._solve_unit_lower(A * 0.3, rhs)
    np.testing.assert_allclose(got, want, rtol=2e-4,
                               atol=2e-4 * float(jnp.abs(want).max()))
