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

from paged_kernel_cases import (BT, D, GROUP, H, NB, _assert_close,
                                _assert_live_close, _setup)
from ray_tpu.models import generate, transformer
from ray_tpu.ops.paged_attention import (_blocks_per_group, paged_attention,
                                         paged_attention_reference)
from ray_tpu.serve.llm import LLMEngine


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
