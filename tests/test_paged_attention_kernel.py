"""Pallas paged-attention kernel vs the gather-path oracle (ISSUE 16).

The kernel (``ray_tpu/ops/paged_attention.py``) must be numerically
equivalent to ``paged_attention_reference`` — the table-gather + dense-mask
formulation the decode path used before — across per-slot lengths sitting
ON block boundaries and ±1 around them, for single-token decode and
multi-token (speculative verify / prefill) queries alike. The reserved
trash block 0 and dead table entries must be unable to influence any live
slot's output, and the kernel path must never materialize the
``[S, max_len, H, D]`` gather the roofline forbids. Tier-1 runs the kernel
in Pallas interpret mode (CPU); the compiled-TPU twin is marked ``slow``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import generate, transformer
from ray_tpu.ops.paged_attention import (_LATENT_Q_TILE, _blocks_per_group,
                                         _latent_group_kv,
                                         latent_paged_attention,
                                         latent_paged_attention_reference,
                                         paged_attention,
                                         paged_attention_append,
                                         paged_attention_reference)
from ray_tpu.serve.llm import LLMEngine

BT = 8   # block_tokens
NB = 6   # blocks per sequence (table width)
# 8 x 16 folds to 128 lanes: the kernel's walk (a loop over groups of table
# entries, each block one DMA). A width off the 128-lane grid takes the same
# groups through BlockSpecs; the odd widths below run that form.
H, D = 8, 16
GROUP = _blocks_per_group(BT, H * D, 4)    # table entries a loop iteration


def _setup(lengths, t_tokens, *, seed=0, pool_blocks=24, layers=1, layer=0,
           heads=H, dim=D, nb=NB, dead=0):
    """Random pool + one live block chain per slot; returns the operands of
    ``paged_attention`` / ``paged_attention_reference``: the pool is the
    whole model's, ``[layers, pool_blocks, BT, heads * dim]`` with distinct
    content in every layer, and ``layer`` picks the one attended. A length
    of None is a parked slot: length 0, an all-trash table. ``dead`` is the
    block id the entries past a live slot's chain hold (0: the trash block)."""
    rng = np.random.default_rng(seed)
    S = len(lengths)
    q = rng.standard_normal((S, t_tokens, heads, dim)).astype(np.float32)
    pool = (layers, pool_blocks, BT, heads * dim)
    k_pool = rng.standard_normal(pool).astype(np.float32)
    v_pool = rng.standard_normal(pool).astype(np.float32)
    tables = np.zeros((S, nb), np.int32)
    nxt = 1  # block 0 stays trash
    for s, ln in enumerate(lengths):
        if ln is None:
            continue
        live = min(-(-max(ln + t_tokens, 1) // BT), nb)
        tables[s, live:] = dead
        # A shuffled chain: the walk must dereference the table, not count.
        tables[s, :live] = rng.permutation(np.arange(nxt, nxt + live))
        nxt += live
    assert nxt <= pool_blocks - (dead > 0), (nxt, pool_blocks)
    lengths = [ln or 0 for ln in lengths]
    return (jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(tables), jnp.asarray(np.asarray(lengths, np.int32)),
            layer)


def _assert_close(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=tol,
                               rtol=tol)


def _assert_live_close(out, ref, lengths, *, parked_is_zero=True):
    """Live slots (a length, not None) against the oracle; a parked slot's
    rows are zeros where the walk skips it (the oracle attends the trash
    block's first row there, and the form off the lane grid still does)."""
    live = np.asarray([ln is not None for ln in lengths])
    _assert_close(np.asarray(out)[live], np.asarray(ref)[live])
    if parked_is_zero:
        assert not np.asarray(out)[~live].any()


class TestKernelOracleEquivalence:
    @pytest.mark.parametrize("lengths", [
        [0], [1], [5], [BT - 1], [BT], [BT + 1],      # block boundary +-1
        [2 * BT - 1], [2 * BT], [2 * BT + 1],
        [NB * BT - 1],                                 # table-capacity edge
        [0, 3, BT, 2 * BT + 1, NB * BT - 2],           # ragged batch
    ])
    def test_decode_lengths(self, lengths):
        ops = _setup(lengths, 1)
        out = paged_attention(*ops, interpret=True)
        ref = paged_attention_reference(*ops)
        _assert_close(out, ref)

    @pytest.mark.parametrize("t_tokens", [2, 4, 7])
    def test_multi_token_verify(self, t_tokens):
        """The speculative verify's T>1 queries: query t attends
        kv <= lengths + t, straddling block boundaries mid-chunk."""
        lengths = [0, BT - 1, BT, 13]
        ops = _setup(lengths, t_tokens, seed=3)
        out = paged_attention(*ops, interpret=True)
        ref = paged_attention_reference(*ops)
        _assert_close(out, ref)

    @pytest.mark.parametrize("heads,dim", [(H, D), (5, 16)])
    @pytest.mark.parametrize("nb,t_tokens", [
        (5, 1), (5, 4), (5, 7),                # narrower than one group
        (40, 1), (40, 4), (40, 7), (40, 130),  # two groups and a half
        (65, 1), (65, 7), (65, 130),           # four groups and one entry
    ])
    def test_walk_edges(self, nb, t_tokens, heads, dim):
        """The walk's edges in one batch: contexts of 0, 1, a group of table
        entries -1 / +0 / +1 and the whole table, parked slots (all-trash
        tables) between the live ones, so that a slot's first group is
        fetched from the slot before it, and a table whose width is no
        multiple of the group. Dead table entries hold the id of a block
        full of NaN while the oracle reads a clean pool: a row past the
        loop's bound may reach neither dot (0 x NaN is NaN)."""
        cap = nb * BT - t_tokens
        group = GROUP * BT
        lengths = [None, 0, 1, None, None, min(group - 1, cap),
                   min(group, cap), None, min(group + 1, cap), cap, None]
        blocks = sum(-(-(ln + t_tokens) // BT)
                     for ln in lengths if ln is not None) + 2
        q, k_pool, v_pool, tables, lens, layer = _setup(
            lengths, t_tokens, seed=nb + t_tokens, pool_blocks=blocks,
            layers=2, layer=1, heads=heads, dim=dim, nb=nb, dead=blocks - 1)
        ref = paged_attention_reference(q, k_pool, v_pool, tables, lens,
                                        layer)
        out = paged_attention(q, k_pool.at[:, -1].set(jnp.nan),
                              v_pool.at[:, -1].set(jnp.nan), tables, lens,
                              layer, interpret=True)
        _assert_live_close(out, ref, lengths,
                           parked_is_zero=heads * dim % 128 == 0)

    @pytest.mark.parametrize("block_tokens,width,itemsize,blocks", [
        (16, 1024, 2, 8), (16, 1600, 2, 8), (8, 128, 4, 16), (128, 1024, 2, 1),
        (256, 1024, 2, 1), (16, 8192, 2, 4), (16, 8192, 4, 2)])
    def test_group_is_128_positions_within_vmem(self, block_tokens, width,
                                                itemsize, blocks):
        """One loop iteration takes 128 kv positions' worth of table entries
        (never under one), fewer where four halves of K and V at this width
        would pass the buffers' share of VMEM."""
        assert _blocks_per_group(block_tokens, width, itemsize) == blocks

    @pytest.mark.parametrize("layers,layer", [(3, 1), (3, 2), (5, 4)])
    @pytest.mark.parametrize("t_tokens", [1, 4])
    def test_layer_of_a_whole_pool(self, layers, layer, t_tokens):
        """The kernel addresses the whole model's pool by layer: every layer
        holds distinct content, so a wrong layer index cannot pass."""
        ops = _setup([3, BT, 2 * BT + 1], t_tokens, seed=13, layers=layers,
                     layer=layer)
        out = paged_attention(*ops, interpret=True)
        _assert_close(out, paged_attention_reference(*ops))
        wrong = paged_attention_reference(*ops[:-1], (layer + 1) % layers)
        assert not np.allclose(np.asarray(out), np.asarray(wrong), atol=1e-2)
        # A traced layer index (a scan over layers) reads the same blocks.
        traced = jax.jit(lambda lyr: paged_attention(
            *ops[:-1], lyr, interpret=True))(jnp.int32(layer))
        _assert_close(traced, out)

    @pytest.mark.parametrize("heads,dim", [(5, 16), (3, 24), (25, 8),
                                           (25, 64)])
    @pytest.mark.parametrize("t_tokens", [1, 4])
    def test_folded_width_not_a_multiple_of_128(self, heads, dim, t_tokens):
        """``H*D`` off the 128-lane grid (gpt2-xl's 25 x 64 = 1600): Mosaic
        cannot slice such a pool for a DMA, so the groups are a grid axis
        and the blocks come through BlockSpecs whose last dimension is the
        array's own; heads stay static lane slices of it. A table of 20
        entries: one whole group and a quarter."""
        assert (heads * dim) % 128
        ops = _setup([0, BT - 1, 2 * BT + 1, None, GROUP * BT + 3], t_tokens,
                     seed=17, layers=2, layer=1, heads=heads, dim=dim, nb=20,
                     pool_blocks=32)
        out = paged_attention(*ops, interpret=True)
        # This form attends a parked slot's trash block, as the oracle does.
        _assert_close(out, paged_attention_reference(*ops))
        traced = jax.jit(lambda lyr: paged_attention(
            *ops[:-1], lyr, interpret=True))(jnp.int32(1))
        _assert_close(traced, out)

    def test_pool_of_another_width_is_refused(self):
        q, k_pool, v_pool, tables, lens, layer = _setup([5], 1)
        with pytest.raises(ValueError, match="pool"):
            paged_attention(q, k_pool[..., :-D], v_pool[..., :-D], tables,
                            lens, layer, interpret=True)

    @pytest.mark.parametrize("kv_heads,dim", [
        (2, 64),      # 5:1 over a 128-lane row: the walk, one chunk of lanes
        (4, 32),      # 5:1, two KV heads a 128-lane chunk in the prefill
        (5, 16),      # 2:1 over 80 lanes: the form with the groups on the grid
        (8, 16),      # 1:1: the multi-head program, one query head a KV head
    ])
    @pytest.mark.parametrize("t_tokens", [1, 3, 40])
    def test_grouped_query_heads_share_a_kv_head(self, kv_heads, dim,
                                                 t_tokens):
        """Query head ``h`` reads KV head ``h // R`` of a pool whose row
        holds the KV heads alone: decode, a verify of 3 and a prefill of
        40, a parked slot, lengths on and around block edges. Against the reference, which repeats the KV heads, and
        against an explicit multi-head pool that holds every KV head ``R``
        times: the same numbers from a fifth of the bytes."""
        ratio = {2: 5, 4: 5, 5: 2, 8: 1}[kv_heads]
        heads = kv_heads * ratio
        lengths = [0, BT - 1, 2 * BT + 1, None] if t_tokens < 40 else [3]
        _, k_pool, v_pool, tables, lens, layer = _setup(
            lengths, t_tokens, seed=23, layers=2, layer=1, heads=kv_heads,
            dim=dim, nb=8, pool_blocks=32)
        q = jnp.asarray(np.random.default_rng(5).standard_normal(
            (len(lengths), t_tokens, heads, dim)).astype(np.float32))
        out = paged_attention(q, k_pool, v_pool, tables, lens, layer,
                              interpret=True)
        assert out.shape == q.shape
        close = lambda ref: _assert_live_close(  # noqa: E731
            out, ref, lengths, parked_is_zero=kv_heads * dim % 128 == 0)
        close(paged_attention_reference(q, k_pool, v_pool, tables, lens,
                                        layer))
        close(generate._paged_attend(
            q, k_pool, v_pool, tables, lens, layer, scale=dim ** -0.5,
            kernel="gather"))
        spread = lambda pool: jnp.repeat(  # noqa: E731
            pool.reshape(pool.shape[:3] + (kv_heads, dim)), ratio,
            axis=3).reshape(pool.shape[:3] + (heads * dim,))
        close(paged_attention_reference(
            q, spread(k_pool), spread(v_pool), tables, lens, layer))

    def test_a_query_head_on_the_wrong_kv_head_is_seen(self):
        """The map shifted by one group moves the output: the test above
        would not pass on a kernel that gave every head KV head 0."""
        _, k_pool, v_pool, tables, lens, layer = _setup(
            [2 * BT + 1], 1, seed=3, heads=2, dim=64)
        q = jnp.asarray(np.random.default_rng(1).standard_normal(
            (1, 1, 10, 64)).astype(np.float32))
        out = paged_attention(q, k_pool, v_pool, tables, lens, layer,
                              interpret=True)
        shifted = paged_attention(jnp.roll(q, 5, axis=2), k_pool, v_pool,
                                  tables, lens, layer, interpret=True)
        assert float(jnp.abs(jnp.roll(shifted, -5, axis=2) - out).max()) > 0.1

    def test_scale_override(self):
        ops = _setup([11], 1, seed=5)
        out = paged_attention(*ops, scale=0.25, interpret=True)
        ref = paged_attention_reference(*ops, scale=0.25)
        _assert_close(out, ref)

    def test_trash_block_cannot_leak(self):
        """Poisoning the reserved trash block (and the dead tail of every
        table) must not move any live output by a single ULP."""
        lengths = [5, BT + 2]
        q, k_pool, v_pool, tables, lens, layer = _setup(lengths, 1, seed=7)
        out = paged_attention(q, k_pool, v_pool, tables, lens, layer,
                              interpret=True)
        k_bad = k_pool.at[:, 0].set(1e9)
        v_bad = v_pool.at[:, 0].set(-1e9)
        out_bad = paged_attention(q, k_bad, v_bad, tables, lens, layer,
                                  interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out_bad))

    @pytest.mark.parametrize("t_tokens", [1, 4])
    def test_parked_slot_is_zero_whatever_the_trash_block_holds(self,
                                                                t_tokens):
        """An all-trash table (a parked slot) is not walked: its rows are
        zeros, and a trash block full of NaN leaves them zeros and every
        live row bit for bit."""
        q, k_pool, v_pool, tables, lens, layer = _setup(
            [None, 9, None], t_tokens, seed=9)
        out = paged_attention(q, k_pool, v_pool, tables, lens, layer,
                              interpret=True)
        bad = paged_attention(q, k_pool.at[:, 0].set(jnp.nan),
                              v_pool.at[:, 0].set(jnp.nan), tables, lens,
                              layer, interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(bad))
        assert not np.asarray(out)[[0, 2]].any()
        _assert_close(out[1], paged_attention_reference(
            q, k_pool, v_pool, tables, lens, layer)[1])

    @pytest.mark.parametrize("lengths", [
        [None, 9, None, None, 130, None],     # first, between and last
        [9, None, 130],
        [None, None, 9, 130],
        [130, 9, None, None],
        [None, 0, None, 127, None, None, 128],   # a group's edge behind a gap
    ])
    @pytest.mark.parametrize("t_tokens", [1, 5])
    def test_live_slots_among_parked_ones(self, lengths, t_tokens):
        """The chain across parked steps: a live slot's first group is
        started by the parked step before it (or by the live one, where no
        parked step lies between) into the half ``half_ref`` names, whatever
        the number of groups the steps before it walked. Live rows equal the
        oracle's, and bit for bit what the same slots give with no parked
        slot among them."""
        q, k_pool, v_pool, tables, lens, layer = _setup(
            lengths, t_tokens, seed=len(lengths) + t_tokens, layers=2,
            layer=1, nb=20, pool_blocks=64)
        out = paged_attention(q, k_pool.at[:, 0].set(jnp.nan),
                              v_pool.at[:, 0].set(jnp.nan), tables, lens,
                              layer, interpret=True)
        _assert_live_close(out, paged_attention_reference(
            q, k_pool, v_pool, tables, lens, layer), lengths)
        live = np.flatnonzero([ln is not None for ln in lengths])
        alone = paged_attention(q[live], k_pool, v_pool, tables[live],
                                lens[live], layer, interpret=True)
        np.testing.assert_array_equal(np.asarray(out)[live],
                                      np.asarray(alone))

    @pytest.mark.parametrize("t_tokens", [1, 5, 40])
    def test_a_live_slot_of_length_zero_is_attended(self, t_tokens):
        """A prefill from position 0 has ``lengths == 0`` over a real first
        block: the test of a parked slot is its table's first entry, not
        its length."""
        ops = _setup([None, 0], t_tokens, seed=31)
        assert int(ops[3][1, 0]) != 0
        out = paged_attention(*ops, interpret=True)
        _assert_live_close(out, paged_attention_reference(*ops), [None, 0])
        assert np.asarray(out)[1].any()


def _scattered(k_pool, v_pool, k_row, v_row, tables, lens, layer):
    """What ``generate._forward_decode_paged``'s scatter leaves: slot ``s``'s
    row at ``pool[layer, tables[s, lens[s] // BT], lens[s] % BT]``, a slot at
    table capacity's in trash block 0."""
    tables, lens = np.asarray(tables), np.asarray(lens)
    cap = tables.shape[1] * BT
    pos = np.minimum(lens, cap - 1)
    blk = np.where(lens < cap, tables[np.arange(len(lens)), pos // BT], 0)
    return (k_pool.at[layer, blk, pos % BT].set(k_row),
            v_pool.at[layer, blk, pos % BT].set(v_row))


def _append_case(lengths, *, kv_heads=H, ratio=1, dim=D, nb=NB, seed=0,
                 pool_blocks=24):
    """``_setup`` for the appending call: one query a slot, ``ratio`` query
    heads a KV head, and the step's new rows."""
    _, k_pool, v_pool, tables, lens, layer = _setup(
        lengths, 1, seed=seed, layers=2, layer=1, heads=kv_heads, dim=dim,
        nb=nb, pool_blocks=pool_blocks)
    rng = np.random.default_rng(seed + 100)
    S = len(lengths)
    q = jnp.asarray(rng.standard_normal(
        (S, 1, kv_heads * ratio, dim)).astype(np.float32))
    k_row, v_row = (jnp.asarray(rng.standard_normal(
        (S, kv_heads * dim)).astype(np.float32)) for _ in range(2))
    return q, k_row, v_row, k_pool, v_pool, tables, lens, layer


def _assert_appended(lengths, ops, *, nb=NB):
    """The appending call against the scatter followed by the reference AND
    by the plain kernel (bit for bit: the row is laid where the scatter puts
    it before the dots), both pools against the scatter's bit for bit but
    for trash block 0, which the call leaves as it came."""
    q, k_row, v_row, k_pool, v_pool, tables, lens, layer = ops
    out, k_new, v_new = paged_attention_append(*ops, interpret=True)
    k_ref, v_ref = _scattered(k_pool, v_pool, k_row, v_row, tables, lens,
                              layer)
    live = [ln if ln is not None and ln < nb * BT else None for ln in lengths]
    _assert_live_close(out, paged_attention_reference(
        q, k_ref, v_ref, tables, lens, layer), live, parked_is_zero=False)
    keep = np.asarray([ln is not None for ln in live])
    np.testing.assert_array_equal(
        np.asarray(out)[keep], np.asarray(paged_attention(
            q, k_ref, v_ref, tables, lens, layer, interpret=True))[keep])
    parked = np.asarray([ln is None for ln in lengths])
    assert not np.asarray(out)[parked].any()
    for new, ref, old in ((k_new, k_ref, k_pool), (v_new, v_ref, v_pool)):
        np.testing.assert_array_equal(np.asarray(new)[:, 1:],
                                      np.asarray(ref)[:, 1:])
        np.testing.assert_array_equal(np.asarray(new)[:, 0],
                                      np.asarray(old)[:, 0])


class TestAppendingDecode:
    """``paged_attention_append``: a decode step's new row is an operand, the
    kernel lays it into the fetched block, attends it there and writes the
    block back (the GPT-2 family's decode program; every other call of the
    kernel passes no row and keeps the traced body the digests below hold)."""

    @pytest.mark.parametrize("slots", [1, 36])
    @pytest.mark.parametrize("kv_heads,ratio,dim", [(H, 1, D), (2, 4, 64)])
    @pytest.mark.parametrize("offset", [0, 1, BT - 1])
    def test_row_at_an_offset_of_its_block(self, offset, kv_heads, ratio,
                                           dim, slots):
        """The new row at ``offset`` of its block (0: the block is fresh,
        nothing else in it is the slot's), multi-head and grouped, one slot
        and 36 with parked ones between the live and one at table capacity:
        out, K pool and V pool are the scatter path's."""
        lengths = [b * BT + offset for b in (2, 0, 1, NB - 1, 3)]
        lengths = [lengths[0]] if slots == 1 else [
            None if s % 4 == 1 else NB * BT if s == 18
            else lengths[s % len(lengths)] for s in range(slots)]
        ops = _append_case(lengths, kv_heads=kv_heads, ratio=ratio, dim=dim,
                           seed=offset + slots, pool_blocks=6 * slots + 2)
        _assert_appended(lengths, ops)

    @pytest.mark.parametrize("lengths", [
        [None, 9, None, None, 130, None],      # a gap before the second group
        [127, 128, 129, None, 255, 256],       # a group's edge, both sides
        [40 * BT - 1, None, 40 * BT],          # the table's last row; capacity
    ])
    def test_the_row_lies_in_the_slots_last_group(self, lengths):
        """Contexts past one group of 128 positions: the row is laid into
        the LAST group's buffer, whichever half the chain across slots (and
        parked steps) put it in."""
        ops = _append_case(lengths, nb=40, seed=len(lengths),
                           pool_blocks=40 * len(lengths) + 2)
        _assert_appended(lengths, ops, nb=40)

    def test_a_slot_at_table_capacity_writes_nothing(self):
        """``lengths == NB * BT``: no block of the table can take the row.
        Both pools come back as they went in, trash block included (the
        scatter sent the row there), but for the neighbour's own row; the
        slot's output is dead, as it is after the scatter."""
        lengths = [NB * BT, 5]
        ops = _append_case(lengths, seed=3)
        _out, k_new, v_new = paged_attention_append(*ops, interpret=True)
        k_ref, v_ref = _scattered(*ops[3:5], ops[1][1:], ops[2][1:],
                                  ops[5][1:], ops[6][1:], ops[7])
        np.testing.assert_array_equal(np.asarray(k_new), np.asarray(k_ref))
        np.testing.assert_array_equal(np.asarray(v_new), np.asarray(v_ref))

    def test_no_row_goes_to_the_trash_block(self):
        """A live slot whose next table entry is unallocated (0), a parked
        slot: neither writes, whatever their rows hold."""
        lengths = [BT, None]
        q, k_row, v_row, k_pool, v_pool, tables, lens, layer = _append_case(
            lengths, seed=11)
        tables = tables.at[0, 1:].set(0)
        _out, k_new, v_new = paged_attention_append(
            q, k_row * jnp.nan, v_row, k_pool, v_pool, tables, lens, layer,
            interpret=True)
        np.testing.assert_array_equal(np.asarray(k_new), np.asarray(k_pool))
        np.testing.assert_array_equal(np.asarray(v_new), np.asarray(v_pool))

    @pytest.mark.parametrize("q_shape,width", [
        ((2, 3, H, D), H * D),        # several tokens a slot: the scatter's
        ((2, 1, 5, 16), 80),          # a row off the 128-lane grid
        ((130, 1, 64, 16), 1024),     # more rows than lie in VMEM at once
    ])
    def test_what_the_appending_call_refuses(self, q_shape, width):
        S = q_shape[0]
        pool = jnp.zeros((1, 8, BT, width), jnp.float32)
        with pytest.raises(ValueError, match="one query a slot"):
            paged_attention_append(
                jnp.zeros(q_shape), jnp.zeros((S, width)),
                jnp.zeros((S, width)), pool, pool,
                jnp.zeros((S, NB), jnp.int32), jnp.zeros((S,), jnp.int32), 0,
                interpret=True)

    def test_the_call_aliases_both_pools(self):
        """The pools are operands 4 and 5 of the Pallas call and its outputs
        1 and 2: an in-place update for a caller that donates them."""
        ops = _append_case([5, None])
        jaxpr = jax.make_jaxpr(lambda *a: paged_attention_append(
            *a, interpret=True))(*ops)
        found = []

        def walk(jp):
            for e in jp.eqns:
                if e.primitive.name == "pallas_call":
                    found.append((e.params["input_output_aliases"],
                                  [v.aval.shape for v in e.outvars]))
                for v in e.params.values():
                    if hasattr(v, "jaxpr"):
                        walk(v.jaxpr)
        walk(jaxpr.jaxpr)
        pool = ops[3].shape
        assert found == [(((4, 1), (5, 2)), [(2, H, 1, D), pool, pool])]


class TestTiledPrefill:
    """T > one q tile: the prefill use of the kernel. The grid's q-tile
    axis bounds VMEM by the tile (a 1024-token prefill taken as one block
    does not fit a v5e's scoped VMEM); every tile must still see exactly
    the kv range its own queries attend."""

    @staticmethod
    def _ops(t_tokens, start, *, bt=16, nb=64, heads=2, dim=16, seed=11):
        rng = np.random.default_rng(seed)
        q = rng.standard_normal((1, t_tokens, heads, dim)).astype(np.float32)
        pool = (1, nb + 1, bt, heads * dim)      # one layer's pool: [None]
        k_pool = rng.standard_normal(pool).astype(np.float32)
        v_pool = rng.standard_normal(pool).astype(np.float32)
        live = min(nb, -(-(start + t_tokens) // bt))
        table = np.zeros((1, nb), np.int32)
        # A shuffled chain: tile i must dereference ITS blocks, not 1..n.
        table[0, :live] = rng.permutation(np.arange(1, nb + 1))[:live]
        return (jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
                jnp.asarray(table), jnp.asarray([start], jnp.int32), 0)

    @pytest.mark.parametrize("t_tokens,start", [
        (1024, 0),      # the full-context bucket, 8 tiles
        (256, 48),      # prefix hit: tiles start mid-table
        (200, 16),      # ragged last tile (padded, sliced off)
    ])
    def test_prefill_matches_reference(self, t_tokens, start):
        ops = self._ops(t_tokens, start)
        out = jax.jit(lambda *a: paged_attention(*a, interpret=True))(*ops)
        ref = paged_attention_reference(*ops)
        assert out.shape == ref.shape
        _assert_close(out, ref)


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


class TestNoGatherMaterialization:
    def test_kernel_path_has_no_full_gather(self):
        """The acceptance bar of the roofline work: no intermediate of
        shape [S, NB*BT, H, D] exists anywhere in the kernel path's jaxpr
        (the reference path exists precisely to materialize it)."""
        ops = _setup([5, 9], 1)
        gathered = (2, NB * BT, H, D)

        def shapes(fn):
            jaxpr = jax.make_jaxpr(fn)(*ops)
            seen = set()

            def walk(jx):
                for eqn in jx.eqns:
                    for v in list(eqn.invars) + list(eqn.outvars):
                        aval = getattr(v, "aval", None)
                        if aval is not None and hasattr(aval, "shape"):
                            seen.add(tuple(aval.shape))
                    for sub in eqn.params.values():
                        if hasattr(sub, "jaxpr"):
                            walk(sub.jaxpr)
            walk(jaxpr.jaxpr)
            return seen

        kernel_fn = lambda *a: paged_attention(*a, interpret=True)
        assert gathered not in shapes(kernel_fn)
        assert gathered in shapes(paged_attention_reference)


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


class TestEngineKernelModes:
    def test_resolve_modes(self):
        assert generate.resolve_attention_kernel("gather") == "gather"
        assert generate.resolve_attention_kernel("interpret") == "interpret"
        assert generate.resolve_attention_kernel("pallas") == "pallas"
        # auto on this CPU suite resolves to the gather path
        assert generate.resolve_attention_kernel("auto") in (
            "gather", "pallas")
        with pytest.raises(ValueError):
            generate.resolve_attention_kernel("nope")

    def test_interpret_engine_token_identical_to_gather(self):
        """The interpret-mode Pallas kernel driving the full paged engine
        (prefill AND decode forwards) emits exactly the gather path's
        tokens — the CPU twin of the TPU deployment configuration."""
        cfg = transformer.tiny(max_seq_len=64)
        params = transformer.init_params(cfg, jax.random.key(0))
        kw = dict(prompt_buckets=(16,), chunk=4, slots=2, max_queue=0,
                  block_tokens=BT, pool_blocks=40)
        eng_g = LLMEngine(params, cfg, attention_kernel="gather",
                               name="kern-g", **kw)
        eng_i = LLMEngine(params, cfg, attention_kernel="interpret",
                               name="kern-i", **kw)
        for prompt in ([7, 3, 11], [2, 4, 6, 8, 10, 12, 14]):
            a = eng_g.generate(prompt, max_new_tokens=10)
            b = eng_i.generate(prompt, max_new_tokens=10)
            assert a == b
        assert eng_g.kv.active_blocks() == 0
        assert eng_i.kv.active_blocks() == 0

    @pytest.mark.parametrize("temperature", [0.0, 0.8])
    def test_appending_engine_token_identical_to_gather(self, temperature):
        """A pool row of whole 128-lane tiles: the interpret engine's decode
        program hands the kernel each step's new row (no scatter in it), the
        gather engine scatters and gathers. Streams that cross block edges,
        a follow-up turn that hits the first one's blocks (a copied tail
        block is written next), greedy and sampled: the same tokens, and
        the same rows in every block of both pools but the trash block."""
        cfg = transformer.tiny(d_model=128, max_seq_len=64)
        params = transformer.init_params(cfg, jax.random.key(0))
        kw = dict(prompt_buckets=(16, 32), chunk=4, slots=3, max_queue=0,
                  block_tokens=BT, pool_blocks=40)
        engines = [LLMEngine(params, cfg, attention_kernel=kernel,
                             name=f"app-{kernel}-{temperature}", **kw)
                   for kernel in ("gather", "interpret")]
        text = str(jax.make_jaxpr(
            lambda *a: generate._forward_decode_paged(
                *a, cfg, BT, kernel="interpret"))(
                    engines[1]._pg.params, jnp.zeros((3, 1), jnp.int32),
                    *engines[1]._pool, jnp.zeros((3, 5), jnp.int32),
                    jnp.zeros((3,), jnp.int32)))
        assert "scatter" not in text and "input_output_aliases" in text
        first = [7, 3, 11, 2, 4, 6, 8, 10, 12, 14, 9]
        outs = []
        for eng in engines:
            a = eng.generate(first, max_new_tokens=13,
                             temperature=temperature, seed=5)
            b = eng.generate(first + a[:4] + [1, 2, 3], max_new_tokens=9,
                             temperature=temperature, seed=6)
            outs.append((a, b))
            assert eng.kv.active_blocks() == 0
        assert outs[0] == outs[1]
        for got, want in zip(engines[1]._pool, engines[0]._pool):
            np.testing.assert_allclose(np.asarray(got)[:, 1:],
                                       np.asarray(want)[:, 1:], atol=1e-5)


@pytest.mark.slow
@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="compiled Pallas kernel needs a TPU")
class TestCompiledKernelTPU:
    """The compiled twin of TestKernelOracleEquivalence — identical cases,
    interpret=False, run only where a TPU backend is attached."""

    @pytest.mark.parametrize("lengths", [[0, 3, BT, 2 * BT + 1,
                                          NB * BT - 2]])
    @pytest.mark.parametrize("t_tokens", [1, 4])
    def test_compiled_matches_reference(self, lengths, t_tokens):
        ops = _setup(lengths, t_tokens)
        out = paged_attention(*ops, interpret=False)
        ref = paged_attention_reference(*ops)
        _assert_close(out, ref, tol=5e-3)  # bf16-ish TPU accumulate slack
