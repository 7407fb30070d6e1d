"""The appending decode kernel (``paged_attention_append``, PR 48) against
the scatter-then-attend form it replaces: a step's new K and V rows are
operands, the pools aliased outputs. Cases and oracle as in
``test_paged_attention_kernel.py``, which held this class until PR 50; a file
of its own so that the test runner, whose unit is a file, shares the work.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paged_kernel_cases import BT, D, H, NB, _assert_live_close, _setup
from ray_tpu.ops.paged_attention import (paged_attention,
                                         paged_attention_append,
                                         paged_attention_reference)


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
