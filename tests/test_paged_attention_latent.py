"""The latent paged-attention kernel (one pool of rows shared by every head)
against ``latent_paged_attention_reference``.
``test_paged_attention_kernel.py`` held this class until PR 50; a file of its
own so that the test runner, whose unit is a file, shares the work.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from paged_kernel_cases import _assert_live_close
from ray_tpu.ops.paged_attention import (_LATENT_Q_TILE, _blocks_per_group,
                                         _latent_group_kv,
                                         latent_paged_attention,
                                         latent_paged_attention_reference)


class TestLatentWalk:
    """The latent kernel (one pool of rows shared by every head, keys the
    whole row, values its first ``value_lanes`` lanes) on the same walk,
    against ``latent_paged_attention_reference``. Blocks of 16 tokens, a row
    of two lane tiles and 16 heads: decode's 16 rows walk groups of 512
    positions (32 table entries, a full group's copies unrolled), a tile of
    16 queries' 256 rows groups of 256. Every pool block no live table entry
    names, the trash block included, and every sublayer but the one attended
    hold NaN on the kernel's side while the oracle reads a clean pool with a
    zero trash block."""
    LBT, LNB, LW, LH = 16, 72, 256, 16     # 1,152 positions: two groups and more
    GROUP = _latent_group_kv(LH)

    @classmethod
    def _ops(cls, lengths, t_tokens, *, seed=0, sublayers=1, layer=0):
        rng = np.random.default_rng(seed)
        S, bt, nb = len(lengths), cls.LBT, cls.LNB
        live = [0 if ln is None else min(-(-(ln + t_tokens) // bt), nb)
                for ln in lengths]
        blocks = sum(live) + 3              # trash, and two nobody names
        q = rng.standard_normal((S, t_tokens, cls.LH, cls.LW)) / 8
        clean = rng.standard_normal((sublayers, blocks, bt, cls.LW))
        clean[:, 0] = 0
        order = rng.permutation(np.arange(1, blocks))   # shuffled chains
        tables, nxt = np.zeros((S, nb), np.int32), 0
        for s, n in enumerate(live):
            tables[s, :n] = order[nxt:nxt + n]
            nxt += n
        poisoned = np.full_like(clean, np.nan)
        named = np.unique(tables[tables > 0])
        poisoned[layer, named] = clean[layer, named]
        as_f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
        rest = (jnp.asarray(tables),
                jnp.asarray([ln or 0 for ln in lengths], jnp.int32), layer)
        return as_f32(q), as_f32(clean), as_f32(poisoned), rest

    @classmethod
    def _check(cls, lengths, t_tokens, *, value_lanes=128, **kw):
        q, clean, poisoned, rest = cls._ops(lengths, t_tokens, **kw)
        ref = latent_paged_attention_reference(
            q, clean, *rest, value_lanes=value_lanes, scale=0.25)
        out = latent_paged_attention(
            q, poisoned, *rest, value_lanes=value_lanes, scale=0.25,
            interpret=True)
        assert out.shape == (len(lengths), t_tokens, cls.LH, value_lanes)
        # A parked slot is not walked: zeros, whatever the trash block holds.
        _assert_live_close(out, ref, lengths)

    @pytest.mark.parametrize("length", [0, 1, 15, 16, 17, 127, 128, 129,
                                        GROUP - 1, GROUP, GROUP + 1,
                                        LNB * LBT - 1])
    def test_decode_lengths(self, length):
        """A block's, a lane row's and a group's edges -1 / +0 / +1 and the
        full table, a parked slot before and after, so that the slot's first
        group is prefetched by a step that had one iteration."""
        assert self.GROUP == 512 == _blocks_per_group(
            self.LBT, self.LW, 4, self.GROUP) * self.LBT
        self._check([None, length, None, 40], 1, seed=length)

    @pytest.mark.parametrize("start", [37, 500])
    @pytest.mark.parametrize("t_tokens", [5, 16, 40])
    def test_query_tiles_from_a_nonzero_start(self, t_tokens, start):
        """T > 1 (the prefill) after a prefix hit, inside the first group and
        across the second's edge: one tile, a whole tile, two tiles and a
        ragged third whose pad queries lie past the last live block."""
        assert (t_tokens > _LATENT_Q_TILE) == (t_tokens == 40)
        assert _latent_group_kv(_LATENT_Q_TILE * self.LH) == 256
        self._check([start], t_tokens, seed=t_tokens)

    @pytest.mark.parametrize("sublayers,layer", [(3, 1), (3, 2)])
    @pytest.mark.parametrize("t_tokens", [1, 20])
    def test_sublayer_of_a_whole_pool(self, sublayers, layer, t_tokens):
        self._check([5, self.GROUP + 3], t_tokens, seed=layer,
                    sublayers=sublayers, layer=layer)

    @pytest.mark.parametrize("value_lanes", [128, LW])
    def test_values_are_the_rows_first_lanes(self, value_lanes):
        self._check([self.GROUP + 9, 3], 1, seed=2, value_lanes=value_lanes)

    @pytest.mark.parametrize("lengths,t_tokens", [
        ([None, 9, None], 1), ([None, 9, None], 20),
        ([None, 600, None, None, 0, 40, None], 1)])
    def test_parked_slot_is_zero_whatever_the_trash_block_holds(
            self, lengths, t_tokens):
        """An all-trash table is not walked: zeros, over a zero trash block
        (as the engine leaves it) and over one full of NaN, the live rows
        bit for bit the same and what the live slots give alone."""
        q, clean, poisoned, rest = self._ops(lengths, t_tokens, seed=9)
        run = lambda q, pool, tables, lens, layer: latent_paged_attention(  # noqa: E731
            q, pool, tables, lens, layer, value_lanes=128, scale=0.25,
            interpret=True)
        out = run(q, clean, *rest)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(run(q, poisoned, *rest)))
        live = np.flatnonzero([ln is not None for ln in lengths])
        assert not np.delete(np.asarray(out), live, axis=0).any()
        alone = run(q[live], clean, rest[0][live], rest[1][live], rest[2])
        np.testing.assert_array_equal(np.asarray(out)[live], np.asarray(alone))

    def test_pool_of_another_width_is_refused(self):
        q, clean, _poisoned, rest = self._ops([5], 1)
        with pytest.raises(ValueError, match="pool"):
            latent_paged_attention(q, clean[..., :-128], *rest,
                                   value_lanes=128, scale=0.25,
                                   interpret=True)


class TestKeepBits:
    """The latent kernel with one more operand, a keep bit a (query, kv
    position) (a learned selection's, ``ops/sparse_select.py``), against the
    oracle given the same bits. Every query keeps its own position, so no
    row of the softmax is empty; about a third of the rest."""

    @classmethod
    def _check_kept(cls, lengths, t_tokens, seed):
        q, clean, poisoned, rest = TestLatentWalk._ops(lengths, t_tokens,
                                                      seed=seed)
        rng = np.random.default_rng(seed + 100)
        S, N = len(lengths), TestLatentWalk.LNB * TestLatentWalk.LBT
        keep = rng.random((S, t_tokens, N)) < 0.3
        pos = (np.asarray([ln or 0 for ln in lengths])[:, None]
               + np.arange(t_tokens)[None])
        keep[np.arange(S)[:, None], np.arange(t_tokens)[None],
             np.minimum(pos, N - 1)] = True
        keep = jnp.asarray(keep)
        ref = latent_paged_attention_reference(
            q, clean, *rest, value_lanes=128, scale=0.25, keep=keep)
        out = latent_paged_attention(
            q, poisoned, *rest, value_lanes=128, scale=0.25, interpret=True,
            keep=keep)
        _assert_live_close(out, ref, lengths)
        dense = latent_paged_attention_reference(
            q, clean, *rest, value_lanes=128, scale=0.25)
        live = [s for s, ln in enumerate(lengths) if ln is not None]
        assert np.abs(np.asarray(ref - dense))[live].max() > 1e-3   # the bits bind

    @pytest.mark.parametrize("length", [1, 17, 511, 512, 513, 1100])
    def test_decode_steps_attend_the_kept_rows_alone(self, length):
        self._check_kept([None, length, None, 40], 1, seed=length)

    @pytest.mark.parametrize("start,t_tokens", [(37, 16), (37, 40), (500, 40),
                                                (0, 64)])
    def test_query_tiles_attend_the_kept_rows_alone(self, start, t_tokens):
        """One whole tile, two tiles and a ragged third (its pad queries keep
        nothing), across the second group's edge, and a prefill from 0."""
        self._check_kept([start], t_tokens, seed=t_tokens + start)

    def test_keep_bits_of_another_shape_are_refused(self):
        q, clean, _poisoned, rest = TestLatentWalk._ops([5], 1)
        with pytest.raises(ValueError, match="keep"):
            latent_paged_attention(q, clean, *rest, value_lanes=128,
                                   scale=0.25, interpret=True,
                                   keep=jnp.ones((1, 1, 100), bool))


# sha256 (first 16 hex digits) of the traced ``latent_paged_attention`` WITHOUT
# keep bits, a decode step and a ragged prefill, addresses and this file's
# line numbers taken out: PR 52's tree gives the same two (the body a caller
# that passes no bits runs is the parent's: the operand is a second kernel
# function around the first, ``_latent_keep_kernel``).
BODY_DIGESTS = {1: "49a3958b37790b14", 40: "441d77e21ed69373"}


@pytest.mark.parametrize("t_tokens", sorted(BODY_DIGESTS))
def test_the_body_that_takes_no_keep_bits_is_the_one_it_was(t_tokens):
    import hashlib
    import re

    import jax

    S, H, W, NB, bt = 3, 16, 256, 40, 16
    jaxpr = jax.make_jaxpr(lambda q, pool, tables, lengths: latent_paged_attention(
        q, pool, tables, lengths, 1, value_lanes=128, scale=0.25))(
        jnp.zeros((S, t_tokens, H, W), jnp.bfloat16),
        jnp.zeros((2, 50, bt, W), jnp.bfloat16),
        jnp.zeros((S, NB), jnp.int32), jnp.zeros((S,), jnp.int32))
    text = re.sub(r" at 0x[0-9a-f]+", "", str(jaxpr))
    text = re.sub(r"/[^ ]*paged_attention.py:\d+", "", text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == BODY_DIGESTS[t_tokens]
