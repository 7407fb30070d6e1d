"""The paged kernel's windowed walk (sliding-window layers over a ring), the
learned sink in its softmax and a value pool of another width than the key
pool's, each against a dense oracle written out here; and the traced bodies
of the unwindowed and the latent kernel held to the parent's by digest.
``test_paged_attention_kernel.py`` held these classes until PR 50; a file of
their own so that the test runner, whose unit is a file, shares the work.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paged_kernel_cases import _assert_close, _setup
from ray_tpu.ops.paged_attention import (latent_paged_attention,
                                         paged_attention,
                                         paged_attention_reference)


# -- a walk with a LOWER bound: sliding-window layers over a ring ---------------

def _dense_window(q, k, v, length, window):
    """The oracle, written out: q [T, H, D] at positions ``length + t`` over
    the sequence's own rows k / v [L, KV, D]; key j is seen by query i iff
    j <= i and i - j < window; query head h reads KV head h // (H // KV)."""
    T, heads, dim = q.shape
    ratio = heads // k.shape[1]
    out = np.zeros_like(q)
    for t in range(T):
        i = length + t
        lo = max(0, i - window + 1)
        for h in range(heads):
            s = k[lo:i + 1, h // ratio] @ q[t, h] / np.sqrt(dim)
            p = np.exp(s - s.max())
            out[t, h] = (p / p.sum()) @ v[lo:i + 1, h // ratio]
    return out


def _ring_setup(lengths, t_tokens, window, bt, ring, *, heads=4, kv_heads=2,
                dim=64, seed=0, poison=False, from_block_0=False):
    """Every slot's rows written into a RING of ``ring`` blocks of ``bt``
    (position p in entry (p // bt) mod ring of the slot's shuffled table,
    later positions over earlier ones), in layer 1 of a two-layer pool.
    ``poison``: every entry wholly behind the first query's window is NaN.
    ``from_block_0``: the tables a window layer's rings have
    (``models/afmoe.py``), slot ``s`` blocks ``s * ring + arange(ring)`` of a
    pool with no trash block, slot 0's first entry block 0.
    Returns (operands, the dense oracle's output)."""
    rng = np.random.default_rng(seed)
    S = len(lengths)
    q = rng.standard_normal((S, t_tokens, heads, dim)).astype(np.float32)
    shape = (2, S * ring + 1, bt, kv_heads * dim)
    k_pool = rng.standard_normal(shape).astype(np.float32)
    v_pool = rng.standard_normal(shape).astype(np.float32)
    tables = np.zeros((S, ring), np.int32)
    want = []
    for s, ln in enumerate(lengths):
        tables[s] = (s * ring + np.arange(ring) if from_block_0
                     else 1 + s * ring + rng.permutation(ring))
        total = ln + t_tokens
        k = rng.standard_normal((total, kv_heads, dim)).astype(np.float32)
        v = rng.standard_normal((total, kv_heads, dim)).astype(np.float32)
        for p in range(total):
            entry = tables[s, (p // bt) % ring]
            k_pool[1, entry, p % bt] = k[p].reshape(-1)
            v_pool[1, entry, p % bt] = v[p].reshape(-1)
        if poison:
            first, last = max(0, ln - window + 1) // bt, (total - 1) // bt
            live = {tables[s, b % ring] for b in range(first, last + 1)}
            for entry in set(tables[s]) - live:
                k_pool[1, entry] = v_pool[1, entry] = np.nan
        want.append(_dense_window(q[s], k, v, ln, window))
    ops = (jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
           jnp.asarray(tables), jnp.asarray(np.asarray(lengths, np.int32)), 1)
    return ops, np.stack(want)


class TestWindowedWalk:
    """``paged_attention(window=W)``: the walk's lower bound, the window's
    trailing edge masked inside a block, the table read modulo its width."""

    @pytest.mark.parametrize("lengths,t_tokens,window,bt,ring", [
        # decode: contexts under, at and over the window, across ring wraps
        ([0, 3, 15, 16, 17, 40, 100], 1, 16, 8, 3),
        ([0, 5, 31, 32, 33, 200], 1, 32, 16, 3),
        ([0, 9, 300], 1, 16, 128, 2),          # one block a group
        # prefill from position 0 over a table that covers the prompt
        ([0], 40, 16, 8, 7), ([0], 64, 16, 8, 8), ([0], 128, 16, 16, 8),
        ([0], 300, 64, 16, 19),                # three query tiles, the last ragged
        # T > 1 from a nonzero start, with and without a wrap
        ([7], 20, 16, 8, 5), ([100, 5], 3, 16, 8, 4),
    ])
    def test_against_a_dense_masked_oracle(self, lengths, t_tokens, window,
                                           bt, ring):
        ops, want = _ring_setup(lengths, t_tokens, window, bt, ring)
        out = paged_attention(*ops, window=window, interpret=True)
        _assert_close(out, want)
        _assert_close(paged_attention_reference(*ops, window=window), want)

    @pytest.mark.parametrize("lengths,t_tokens,window,bt,ring", [
        ([5, 0, 40, 100], 1, 16, 8, 3),        # decode, slot 0's ring at block 0
        ([0], 40, 16, 8, 7),                   # a prefill over arange(T // bt)
        ([0], 300, 64, 16, 19),                # three query tiles of it
    ])
    def test_a_ring_whose_first_entry_is_block_0_is_walked(
            self, lengths, t_tokens, window, bt, ring):
        """A window layer's rings are a pool with no trash block: under a
        window no slot is parked, whatever its table's first entry."""
        ops, want = _ring_setup(lengths, t_tokens, window, bt, ring,
                                from_block_0=True)
        assert int(ops[3][0, 0]) == 0
        out = paged_attention(*ops, window=window, interpret=True)
        _assert_close(out, want)
        assert np.asarray(out)[0].any()

    @pytest.mark.parametrize("lengths,t_tokens", [([17, 40, 100, 999], 1),
                                                   ([100], 3)])
    def test_no_block_wholly_behind_the_window_is_read(self, lengths, t_tokens):
        """Every ring entry wholly behind the window holds NaN: a copied
        block's rows, masked as keys, would still meet p = 0 as values, and
        0 x NaN is NaN."""
        ops, want = _ring_setup(lengths, t_tokens, 16, 8, 4, poison=True)
        out = paged_attention(*ops, window=16, interpret=True)
        assert np.isfinite(np.asarray(out)).all()
        _assert_close(out, want)

    def test_the_walks_bounds(self):
        """What one decode step of a window layer may copy, counted from the
        walk's own bounds: never more than the window's blocks and one, at
        any context, where the unwindowed walk's count grows with it."""
        from ray_tpu.ops.paged_attention import (_tile_first_block,
                                                 _tile_last_block)

        window, bt = 4096, 128
        lengths = jnp.asarray([0, 1, 4095, 4096, 4097, 5000, 8191, 100000])
        slots = jnp.arange(len(lengths))
        first = np.asarray(jax.vmap(lambda s: _tile_first_block(
            lengths, s, 0, 1, bt, window))(slots))
        last = np.asarray(jax.vmap(lambda s: _tile_last_block(
            lengths, s, 0, 1, 1, bt, 33, ring=True))(slots))
        assert list(first) == [0, 0, 0, 0, 0, 7, 32, 749]
        assert list(last) == [0, 0, 31, 32, 32, 39, 63, 781]
        assert (last - first + 1).max() == 33 == window // bt + 1
        clamped = np.asarray(jax.vmap(lambda s: _tile_last_block(
            lengths, s, 0, 1, 1, bt, 33))(slots))
        assert list(clamped) == [0, 0, 31, 32, 32, 32, 32, 32]

    def test_a_window_over_an_unaligned_row_is_refused(self):
        ops = _setup([5], 1, heads=5, dim=16)
        with pytest.raises(ValueError, match="128-lane"):
            paged_attention(*ops, window=8, interpret=True)

    def test_forty_eight_heads_take_half_a_query_tile(self):
        """A tile's float32 accumulators are (heads x queries) rows: past
        4,096 rows the tile is halved, below it the tile is what it was."""
        def grid(heads, kv_heads, t_tokens):
            q = jax.ShapeDtypeStruct((1, t_tokens, heads, 128), jnp.bfloat16)
            pool = jax.ShapeDtypeStruct((1, 9, 16, kv_heads * 128), jnp.bfloat16)
            jaxpr = jax.make_jaxpr(lambda q, k, v: paged_attention(
                q, k, v, jnp.zeros((1, 8), jnp.int32),
                jnp.zeros((1,), jnp.int32), 0, interpret=True))(q, pool, pool)
            found = []

            def walk(jp):
                for e in jp.eqns:
                    if e.primitive.name == "pallas_call":
                        found.append(tuple(e.params["grid_mapping"].grid))
                    for v in e.params.values():
                        if hasattr(v, "jaxpr"):
                            walk(v.jaxpr)
            walk(jaxpr.jaxpr)
            return found
        assert grid(48, 8, 256) == [(1, 4)]      # tiles of 64
        assert grid(30, 30, 256) == [(1, 2)]     # tiles of 128, as ever
        assert grid(20, 4, 256) == [(1, 2)]

    # sha256 of ``str(jax.make_jaxpr(...))`` of the kernel path (jax 0.9.0,
    # matmul precision "highest" as conftest pins it), last taken in PR 43
    # (the walk skips a parked slot; the form with the groups on the grid,
    # the fifth case, is still the one from BEFORE the window went in). A PR
    # that changes the unwindowed kernel on purpose takes new digests the
    # same way; one that means to leave it alone (eight cells run it) finds
    # out here.
    PARENT = {
        (3, 1, 8, 8, 16, 8, 6, "float32"):
            "063407d19317859699883c115decbc3de50c999a9ef097a0f76c75402c5d4f2d",
        (1, 40, 8, 8, 16, 8, 6, "float32"):
            "1e57193691f245b2f5bbe714cef3be1fa56b3960c272d965978b336b8632cc4a",
        (4, 1, 20, 4, 128, 16, 8, "bfloat16"):
            "aa1eb5ac6f2c01d34851b72a7b956fd3d9e6e5b7d3925b2d005c4b3de5301c08",
        (1, 256, 20, 4, 128, 16, 16, "bfloat16"):
            "d26270f32cc36b7da33194fd6b919a635ee043cf8ffce3d1b3407bcd5c1f95d7",
        (2, 1, 10, 5, 16, 8, 6, "float32"):
            "a4a15332394375403485789e27cbffb72f0d49f39fe03259137aa2ffa4389b29",
    }

    @pytest.mark.parametrize("case", sorted(PARENT))
    def test_without_a_window_the_traced_kernel_is_the_parents(self, case):
        """Byte for byte: multi-head and grouped, decode and prefill, the
        loop and the form with the groups on the grid."""
        import hashlib

        S, T, heads, kv_heads, dim, bt, nb, dtype = case
        q = jax.ShapeDtypeStruct((S, T, heads, dim), dtype)
        pool = jax.ShapeDtypeStruct((2, 40, bt, kv_heads * dim), dtype)
        with jax.default_matmul_precision("highest"):
            text = str(jax.make_jaxpr(lambda q, k, v, t, ln: paged_attention(
                q, k, v, t, ln, 1, interpret=True))(
                    q, pool, pool, jax.ShapeDtypeStruct((S, nb), jnp.int32),
                    jax.ShapeDtypeStruct((S,), jnp.int32)))
        assert hashlib.sha256(text.encode()).hexdigest() == self.PARENT[case]
        assert "window" not in text


    # The latent kernel runs the same walk (``_walk_live_groups``): its
    # traced body too is the parent's.
    PARENT_LATENT = {
        (3, 1, 8, 128, 8, 6, "float32"):
            "1c6d3def6db8084ab7fca29e4288c58bff57633c90fbcb60e6846b22ff292b6c",
        (1, 40, 8, 128, 8, 6, "float32"):
            "658322e26665e3b0d2eef7440aecd96cc40c294ee2b2ece41fca21898c2d4344",
        (4, 1, 64, 640, 16, 12, "bfloat16"):
            "1bcaed1a1e655c6cadad0e1bf0155b37c1679f8fcad4640f6089a70610572d39",
    }

    @pytest.mark.parametrize("case", sorted(PARENT_LATENT))
    def test_the_latent_kernels_traced_body_is_the_parents(self, case):
        import hashlib

        S, T, heads, width, bt, nb, dtype = case
        q = jax.ShapeDtypeStruct((S, T, heads, width), dtype)
        pool = jax.ShapeDtypeStruct((2, 40, bt, width), dtype)
        with jax.default_matmul_precision("highest"):
            text = str(jax.make_jaxpr(lambda q, p, t, ln: latent_paged_attention(
                q, p, t, ln, 1, value_lanes=width // 2, scale=0.1,
                interpret=True))(
                    q, pool, jax.ShapeDtypeStruct((S, nb), jnp.int32),
                    jax.ShapeDtypeStruct((S,), jnp.int32)))
        assert hashlib.sha256(text.encode()).hexdigest() == self.PARENT_LATENT[case]


def _dense_sink(q, k, v, length, window, sinks):
    """The oracle with a sink and a V head of its own width, written out: q
    [T, H, D] at positions ``length + t``, k [L, KV, D], v [L, KV, Dv]; key j
    is seen iff j <= i (and i - j < window where there is one); ``sinks[h]``
    (or nothing) joins the denominator and carries no value."""
    T, heads, _ = q.shape
    ratio = heads // k.shape[1]
    out = np.zeros((T, heads, v.shape[2]), q.dtype)
    for t in range(T):
        i = length + t
        lo = 0 if window is None else max(0, i - window + 1)
        for h in range(heads):
            s = k[lo:i + 1, h // ratio] @ q[t, h] / np.sqrt(q.shape[2])
            top = s.max() if sinks is None else max(s.max(), sinks[h])
            p = np.exp(s - top)
            rest = 0.0 if sinks is None else np.exp(sinks[h] - top)
            out[t, h] = (p / (p.sum() + rest)) @ v[lo:i + 1, h // ratio]
    return out


def _sink_setup(lengths, t_tokens, window, bt, nb, *, heads, kv_heads, dim,
                v_dim, sink, seed=0):
    """Every slot's rows in layer 1 of two-layer K and V pools whose rows are
    ``kv_heads * dim`` and ``kv_heads * v_dim`` lanes, through a shuffled
    table of ``nb`` entries read modulo its width under a window (a ring)
    and covering the context without one (block 0 the trash block). Returns
    (operands, sinks or None, the dense oracle's output)."""
    rng = np.random.default_rng(seed)
    S = len(lengths)
    q = rng.standard_normal((S, t_tokens, heads, dim)).astype(np.float32)
    k_pool = rng.standard_normal((2, S * nb + 1, bt, kv_heads * dim)).astype(np.float32)
    v_pool = rng.standard_normal((2, S * nb + 1, bt, kv_heads * v_dim)).astype(np.float32)
    sinks = (rng.standard_normal(heads) * 2 + 1).astype(np.float32) if sink else None
    tables = np.zeros((S, nb), np.int32)
    want = []
    for s, ln in enumerate(lengths):
        tables[s] = 1 + s * nb + rng.permutation(nb)
        total = ln + t_tokens
        assert window is not None or total <= nb * bt
        k = rng.standard_normal((total, kv_heads, dim)).astype(np.float32)
        v = rng.standard_normal((total, kv_heads, v_dim)).astype(np.float32)
        for p in range(total):
            entry = tables[s, (p // bt) % nb]
            k_pool[1, entry, p % bt] = k[p].reshape(-1)
            v_pool[1, entry, p % bt] = v[p].reshape(-1)
        want.append(_dense_sink(q[s], k, v, ln, window, sinks))
    ops = (jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
           jnp.asarray(tables), jnp.asarray(np.asarray(lengths, np.int32)), 1)
    return ops, None if sinks is None else jnp.asarray(sinks), np.stack(want)


class TestSinkAndValueWidth:
    """``paged_attention(sinks=)`` and a V pool whose heads are narrower
    than K's (192 / 128): each alone and both, windowed and not, a decode
    step and prefill tiles, against the dense oracle written out and the
    gather path."""

    @pytest.mark.parametrize("sink,dims", [
        (True, (64, 64)), (False, (192, 128)), (True, (192, 128))])
    @pytest.mark.parametrize("lengths,t_tokens,window,bt,nb", [
        ([0, 3, 15, 16, 17, 40, 100], 1, 16, 8, 3),     # decode over rings
        ([0, 9, 300], 1, 16, 128, 2),                   # one block a group
        ([0, 5, 17, 40], 1, None, 8, 6),                # decode over a pool
        ([0], 300, 64, 16, 19),                         # windowed prefill tiles
        ([0], 200, None, 16, 13),                       # full prefill tiles
        ([7], 20, 16, 8, 5),                            # T > 1 from a start
    ])
    def test_against_a_dense_oracle(self, lengths, t_tokens, window, bt, nb,
                                    sink, dims):
        dim, v_dim = dims
        ops, sinks, want = _sink_setup(
            lengths, t_tokens, window, bt, nb, heads=4, kv_heads=2, dim=dim,
            v_dim=v_dim, sink=sink)
        out = paged_attention(*ops, window=window, sinks=sinks, interpret=True)
        assert out.shape == want.shape == ops[0].shape[:3] + (v_dim,)
        _assert_close(out, want)
        _assert_close(paged_attention_reference(
            *ops, window=window, sinks=sinks), want)

    def test_a_sink_left_out_is_seen(self):
        ops, sinks, want = _sink_setup([5, 40], 1, 16, 8, 3, heads=4,
                                       kv_heads=2, dim=64, v_dim=64, sink=True)
        out = paged_attention(*ops, window=16, interpret=True)
        assert np.abs(np.asarray(out) - want).max() > 0.05

    def test_eight_and_four_kv_heads_of_192_and_128(self):
        """The two kinds of layer of the configuration that brought these:
        64 query heads over 8 KV heads (a ring) and over 4 (the pool), a
        decode step, bfloat16 pools."""
        for kv_heads, window, nb in ((8, 128, 3), (4, None, 12)):
            ops, sinks, want = _sink_setup(
                [130, 17], 1, window, 64 if window else 16, nb, heads=64,
                kv_heads=kv_heads, dim=192, v_dim=128, sink=window is not None)
            ops = tuple(a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a
                        for a in ops[:3]) + ops[3:]
            out = paged_attention(*ops, window=window, sinks=sinks,
                                  interpret=True)
            ref = paged_attention_reference(*ops, window=window, sinks=sinks)
            assert out.shape == (2, 1, 64, 128)
            _assert_close(out.astype(jnp.float32), ref.astype(jnp.float32),
                          tol=2e-2)

    @pytest.mark.parametrize("kw,match", [
        (dict(sinks=jnp.zeros(5)), "128-lane"),          # 5 x 16 lanes
        (dict(v_width=5 * 32), "128-lane"),
        (dict(v_width=7 * 16), "KV heads"),
    ])
    def test_what_the_two_refuse(self, kw, match):
        q, k_pool, v_pool, *rest = _setup([5], 1, heads=5, dim=16)
        if "v_width" in kw:
            v_pool = jnp.zeros(v_pool.shape[:3] + (kw.pop("v_width"),))
        with pytest.raises(ValueError, match=match):
            paged_attention(q, k_pool, v_pool, *rest, interpret=True, **kw)
        with pytest.raises(ValueError, match="one a query head"):
            paged_attention(*_setup([5], 1), sinks=jnp.zeros(3), interpret=True)

    def test_a_tile_of_q_is_held_to_its_share_of_vmem(self):
        """64 heads of 192 in chunks of two: a q block of 4,096 rows x 384
        lanes would be 3 MB a buffer; the tile is halved to 32 queries."""
        def grid(heads, kv_heads, dim, v_dim, t_tokens):
            q = jax.ShapeDtypeStruct((1, t_tokens, heads, dim), jnp.bfloat16)
            pools = [jax.ShapeDtypeStruct((1, 9, 16, kv_heads * d), jnp.bfloat16)
                     for d in (dim, v_dim)]
            jaxpr = jax.make_jaxpr(lambda q, k, v: paged_attention(
                q, k, v, jnp.zeros((1, 8), jnp.int32),
                jnp.zeros((1,), jnp.int32), 0, interpret=True))(q, *pools)
            found = []

            def walk(jp):
                for e in jp.eqns:
                    if e.primitive.name == "pallas_call":
                        found.append(tuple(e.params["grid_mapping"].grid))
                    for v in e.params.values():
                        if hasattr(v, "jaxpr"):
                            walk(v.jaxpr)
            walk(jaxpr.jaxpr)
            return found
        assert grid(64, 4, 192, 128, 256) == [(1, 8)]      # tiles of 32
        assert grid(64, 8, 128, 128, 256) == [(1, 4)]      # tiles of 64, as ever
