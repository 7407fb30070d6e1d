"""The paged kernels handed a slot's count of REAL queries (``queries``: a
prefill's prompt in its bucket): the walk follows the real rows alone. Every
form of the walk (multi-head, grouped, off the lane grid, a ring under a
window, a sink and a narrow V, the latent rows with and without keep bits) at
counts on a tile's edge, inside a tile, 1 and all: real rows bit for bit the
call's without the operand and close to the oracle, pad rows exactly zero,
nothing behind the count dereferenced, and the next step's first group handed
on across skipped tiles and slots.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from paged_kernel_cases import BT, _assert_close, _setup
from ray_tpu.ops.paged_attention import (_LATENT_Q_TILE, _Q_TILE,
                                         latent_paged_attention,
                                         latent_paged_attention_reference,
                                         paged_attention,
                                         paged_attention_reference)
# (as modules: their test classes are not collected a second time here)
import test_paged_attention_latent as latent_cases
import test_paged_attention_window as window_cases

T = 2 * _Q_TILE + 44            # three query tiles, the last ragged
LT = 2 * _LATENT_Q_TILE + 8     # the latent kernel's: tiles of 16


def _plain(heads=8, kv_heads=8, dim=16, lengths=(3,)):
    """Multi-head or grouped over a pool, from a nonzero start."""
    q, k_pool, v_pool, *rest = _setup(list(lengths), T, heads=kv_heads,
                                      dim=dim, nb=-(-(T + 8) // BT),
                                      pool_blocks=len(lengths) * 42 + 2)
    if heads != kv_heads:
        q = jnp.asarray(np.random.default_rng(1).standard_normal(
            (len(lengths), T, heads, dim)).astype(np.float32))
    ops = (q, k_pool, v_pool, *rest)
    return (lambda **kw: paged_attention(*ops, interpret=True, **kw),
            paged_attention_reference(*ops), T)


def _windowed(lengths=(0,)):
    ops, want = window_cases._ring_setup(list(lengths), T, 64, 16, 24)
    return (lambda **kw: paged_attention(*ops, window=64, interpret=True,
                                         **kw), want, T)


def _sink_and_narrow_v(window):
    ops, sinks, want = window_cases._sink_setup(
        [0], T, window, 16, 20, heads=4, kv_heads=2, dim=192, v_dim=128,
        sink=True)
    return (lambda **kw: paged_attention(*ops, window=window, sinks=sinks,
                                         interpret=True, **kw), want, T)


def _latent(keep, lengths=(37,)):
    walk = latent_cases.TestLatentWalk
    q, clean, _poisoned, rest = walk._ops(list(lengths), LT, seed=4)
    kw = dict(value_lanes=128, scale=0.25)
    if keep:
        rng = np.random.default_rng(5)
        N = walk.LNB * walk.LBT
        bits = rng.random((len(lengths), LT, N)) < 0.3
        pos = np.asarray(lengths)[:, None] + np.arange(LT)[None]
        bits[np.arange(len(lengths))[:, None], np.arange(LT)[None], pos] = True
        kw["keep"] = jnp.asarray(bits)
    return (lambda **more: latent_paged_attention(q, clean, *rest, **kw,
                                                  interpret=True, **more),
            latent_paged_attention_reference(q, clean, *rest, **kw), LT)


FORMS = {
    "multi_head": _plain,
    "grouped": lambda: _plain(heads=8, kv_heads=2, dim=64),
    "off_the_lane_grid": lambda: _plain(heads=5, kv_heads=5, dim=16),
    "window": _windowed,
    "sink_narrow_v": lambda: _sink_and_narrow_v(None),
    "sink_narrow_v_window": lambda: _sink_and_narrow_v(64),
    "latent": lambda: _latent(False),
    "latent_keep": lambda: _latent(True),
}


@pytest.fixture(scope="module", params=sorted(FORMS))
def form(request):
    """(run(**kw), the oracle's output, T, the call's output without the
    operand, the tile): one trace of the plain call a form."""
    run, want, total = FORMS[request.param]()
    tile = _LATENT_Q_TILE if request.param.startswith("latent") else _Q_TILE
    return run, np.asarray(want), total, np.asarray(run()), tile


@pytest.mark.parametrize("count", ["a_tile", "inside_a_tile", "one", "all"])
def test_the_walk_follows_the_real_rows_alone(form, count):
    run, want, total, base, tile = form
    n = {"a_tile": tile, "inside_a_tile": tile + tile // 2 + 3, "one": 1,
         "all": total}[count]
    out = np.asarray(run(queries=jnp.asarray([n], jnp.int32)))
    assert out.shape == base.shape
    np.testing.assert_array_equal(out[:, :n], base[:, :n])
    _assert_close(out[:, :n], want[:, :n])
    assert not out[:, n:].any()           # zeros, and no NaN among them
    if n == total:
        np.testing.assert_array_equal(out, base)


def test_a_scalar_count_and_a_count_past_the_rows():
    run, _want, total = _plain()
    base = np.asarray(run())
    np.testing.assert_array_equal(np.asarray(run(queries=total + 500)), base)
    out = np.asarray(run(queries=jnp.int32(7)))
    np.testing.assert_array_equal(out[:, :7], base[:, :7])
    assert not out[:, 7:].any()
    with pytest.raises(ValueError, match="one count a slot"):
        run(queries=jnp.asarray([3, 4]))


@pytest.mark.parametrize("kind", ["pool", "latent"])
def test_nothing_behind_the_count_is_dereferenced(kind):
    """A prompt's table as the engine writes it: blocks for the prompt's own
    rows, every entry behind them dead. The dead entries' block AND the trash
    block hold NaN: the real rows are finite and the oracle's, the pad rows
    zero."""
    n, start = 150, 3
    if kind == "pool":
        q, k_pool, v_pool, tables, lengths, layer = _setup(
            [start], T, nb=-(-(T + 8) // BT), pool_blocks=44)
        live = -(-(start + n) // BT)
        clean = (k_pool, v_pool)
        tables = tables.at[:, live:].set(43)
        poison = lambda p: p.at[:, 0].set(jnp.nan).at[:, 43].set(  # noqa: E731
            jnp.nan)
        out = paged_attention(q, poison(k_pool), poison(v_pool), tables,
                              lengths, layer, interpret=True,
                              queries=jnp.asarray([n]))
        want = paged_attention_reference(q, *clean, tables, lengths, layer)
    else:
        walk = latent_cases.TestLatentWalk
        n, start, bt = 20, 37, walk.LBT
        q, clean, poisoned, (tables, lengths, layer) = walk._ops(
            [start], LT, seed=6)
        live = -(-(start + n) // bt)
        dead = int(tables[0, live])
        tables = tables.at[:, live:].set(dead)
        poisoned = poisoned.at[:, dead].set(jnp.nan)
        out = latent_paged_attention(q, poisoned, tables, lengths, layer,
                                     value_lanes=128, scale=0.25,
                                     interpret=True, queries=jnp.asarray([n]))
        want = latent_paged_attention_reference(
            q, clean, tables, lengths, layer, value_lanes=128, scale=0.25)
    out = np.asarray(out)
    assert np.isfinite(out).all()
    _assert_close(out[:, :n], np.asarray(want)[:, :n])
    assert not out[:, n:].any()


SLOTS = {
    "multi_head": lambda: _plain(lengths=(3, 9, 0)),
    "window": lambda: _windowed(lengths=(0, 7, 30)),
    "latent": lambda: _latent(False, lengths=(37, 500, 0)),
}


@pytest.mark.parametrize("counts", [
    ("inside", 0, "tile"),      # a fully padded slot between two live ones
    (0, "all", 0), ("one", "one", "inside"), (0, 0, 0)])
@pytest.mark.parametrize("kind", sorted(SLOTS))
def test_the_first_group_is_handed_on_across_skipped_tiles(kind, counts):
    """Three slots whose counts differ: every step after a skipped tile (the
    same slot's there is none of; the next slot's first) opens on a group
    that a skipped step started, in the half the walk expects."""
    run, want, total = SLOTS[kind]()
    tile = _LATENT_Q_TILE if kind == "latent" else _Q_TILE
    ns = [{"inside": tile + 5, "tile": tile, "one": 1, "all": total, 0: 0}[c]
          for c in counts]
    base = np.asarray(run())
    out = np.asarray(run(queries=jnp.asarray(ns, jnp.int32)))
    for s, n in enumerate(ns):
        np.testing.assert_array_equal(out[s, :n], base[s, :n])
        _assert_close(out[s, :n], np.asarray(want)[s, :n])
        assert not out[s, n:].any()
