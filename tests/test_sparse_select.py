"""The exact selection of ``ops/sparse_select.py`` (a bisection on the
scores' bits, no sort) against ``jax.lax.top_k``: the same SET, ties to the
lower position, as plain array operations and as the interpreted Pallas
kernel; and ``keep_bits`` a block of queries at a time against all at once."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import sparse_select
from ray_tpu.ops.sparse_select import index_scores, keep_bits, select_keep


def select_keep_reference(scores, visible, k: int):
    """Oracle of ``select_keep`` by ``jax.lax.top_k`` over the visible scores
    (it breaks ties to the lower index): [R, N] bool."""
    R, N = scores.shape
    pos = jnp.arange(N)[None, :]
    seen = pos < visible.reshape(R, 1)
    _, idx = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), min(k, N))
    chosen = jnp.zeros((R, N), bool).at[jnp.arange(R)[:, None], idx].set(True)
    return jnp.logical_and(chosen, seen)


def _scores(R, N, seed):
    rng = np.random.default_rng(seed)
    sc = rng.standard_normal((R, N)).astype(np.float32)
    sc[:, ::7] = np.round(sc[:, ::7] * 2) / 2       # planted ties, many at a value
    sc[0, :50] = 0.0                                # a run of ties at the cut
    sc[1, 10:20] = -0.0                             # -0.0 ties with +0.0
    visible = rng.integers(1, N + 1, size=R)
    visible[0], visible[-1] = N, 1
    return jnp.asarray(sc), jnp.asarray(visible)


@pytest.mark.parametrize("kernel", ["gather", "interpret"])
@pytest.mark.parametrize("R,N,k", [(5, 300, 64), (40, 1000, 128),
                                   (3, 128, 200), (33, 257, 1),
                                   (8, 2048, 512)])
def test_the_selection_is_top_ks_set_with_planted_ties(R, N, k, kernel):
    scores, visible = _scores(R, N, seed=N + k)
    want = np.asarray(select_keep_reference(scores, visible, k))
    got = np.asarray(select_keep(scores, visible, k=k, kernel=kernel,
                                 dtype=jnp.float32))
    assert set(np.unique(got)) <= {0.0, 1.0}
    np.testing.assert_array_equal(got > 0, want)
    np.testing.assert_array_equal(want.sum(-1),
                                  np.minimum(np.asarray(visible), k))
    # nothing past what a row may see
    assert not (got > 0)[np.arange(N)[None] >= np.asarray(visible)[:, None]].any()


def test_ties_go_to_the_lower_position():
    scores = jnp.asarray([[1.0, 3.0, 1.0, 1.0, 3.0, 1.0, 0.5, 1.0]])
    keep = np.asarray(select_keep(scores, jnp.asarray([8]), k=4,
                                  dtype=jnp.float32))[0] > 0
    np.testing.assert_array_equal(
        keep, [True, True, True, False, True, False, False, False])
    # ... as jax.lax.top_k's own order has it
    np.testing.assert_array_equal(
        sorted(np.asarray(jax.lax.top_k(scores, 4)[1][0])), [0, 1, 2, 4])


def test_index_scores_by_hand():
    q = jnp.asarray([[[[1.0, 0.0], [0.0, 2.0]]]])            # [1, 1, 2 heads, 2]
    keys = jnp.asarray([[[1.0, 1.0], [-1.0, 1.0], [1.0, -1.0]]])
    w = jnp.asarray([[[0.5, -1.0]]])
    # head 0: relu(1, -1, 1) = (1, 0, 1); head 1: relu(2, 2, -2) = (2, 2, 0)
    np.testing.assert_allclose(np.asarray(index_scores(q, w, keys))[0, 0],
                               [0.5 - 2.0, -2.0, 0.5])


def test_keep_bits_a_block_of_queries_at_a_time(monkeypatch):
    """A prompt's selection in blocks of queries is the selection all at
    once; a decode step's (one query a slot, every slot its own keys) is
    each slot's alone."""
    k = jax.random.split(jax.random.key(0), 3)
    B, Q, H, D, N = 1, 64, 4, 16, 80
    q = jax.random.normal(k[0], (B, Q, H, D))
    w = jax.random.normal(k[1], (B, Q, H))
    keys = jax.random.normal(k[2], (B, N, D))
    pos = jnp.arange(Q)[None] + 8
    whole = np.asarray(keep_bits(q, w, keys, pos, k=9, dtype=jnp.float32))
    monkeypatch.setattr(sparse_select, "_QUERY_BLOCK", 16)
    for kernel in ("gather", "interpret"):
        blocks = np.asarray(keep_bits(q, w, keys, pos, k=9, kernel=kernel,
                                      dtype=jnp.float32))
        np.testing.assert_array_equal(blocks, whole)
    assert (whole.sum(-1) == 9).all() and whole.shape == (B, Q, N)
    with pytest.raises(ValueError, match="whole blocks"):
        keep_bits(q[:, :40], w[:, :40], keys, pos[:, :40], k=9)
    # decode: slots are the batch, each scored against its own keys
    qs, ws = jnp.moveaxis(q, 1, 0)[:8], jnp.moveaxis(w, 1, 0)[:8]
    ks = jax.random.normal(k[2], (8, N, D))
    ps = jnp.arange(8)[:, None] * 7 + 20
    both = np.asarray(keep_bits(qs, ws, ks, ps, k=9, dtype=jnp.float32))
    for s in (0, 5):
        alone = np.asarray(keep_bits(qs[s:s + 1], ws[s:s + 1], ks[s:s + 1],
                                     ps[s:s + 1], k=9, dtype=jnp.float32))
        np.testing.assert_array_equal(both[s], alone[0])
