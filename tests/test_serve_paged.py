"""Paged KV cache, prefix reuse, and affinity routing (ISSUE 11).

Engine-level: ``LLMEngine`` must be token-identical to the single-sequence
``Generator`` oracle cold AND warm — a prefix-cache hit changes FLOPs, never
tokens; hit lengths must land exactly on hash-block boundaries; COW tail
forks must decode in isolation and drop every refcount at retire
(``active_blocks() == 0`` is the leak-check invariant — the suite's
``RAY_TPU_LEAK_CHECK_ENABLED=1`` teardown guard covers the thread/fd half).
Router-level: stale-load eviction on snapshot shrink and prefix-affinity
picks, as units on ``Router`` itself.
"""

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import engine_contract
import half_filled_bucket
import ray_tpu
from ray_tpu.models import generate, transformer
from ray_tpu.serve.handle import DeploymentHandle, Router
from ray_tpu.serve.llm import LLMEngine
from ray_tpu.util.blockhash import prefix_head_hash

BT = 8  # test block size: small enough to exercise multi-block prompts


@pytest.fixture(scope="module")
def tiny_model():
    cfg = transformer.tiny(max_seq_len=64)
    params = transformer.init_params(cfg, jax.random.key(0))
    return cfg, params


@pytest.fixture(scope="module")
def oracle(tiny_model):
    """Single-sequence reference decode (memoized — it is the slow path)."""
    cfg, params = tiny_model
    gen = generate.Generator(params, cfg)
    memo = {}

    def run(prompt, n, temperature=0.0, seed=0):
        key = (tuple(prompt), n, temperature, seed)
        if key not in memo:
            memo[key] = gen.generate(
                list(prompt), max_new_tokens=n,
                temperature=temperature, seed=seed)
        return memo[key]

    return run


@pytest.fixture(scope="module")
def paged(tiny_model):
    """Shared paged engine; pool sized so no test's chains evict another's
    (hit-length deltas below assume no LRU eviction)."""
    cfg, params = tiny_model
    eng = LLMEngine(params, cfg, prompt_buckets=(16, 32), chunk=4,
                    slots=2, max_queue=0, name="paged-test",
                    block_tokens=BT, pool_blocks=129)
    eng.warmup()
    return eng


def _hit_delta(eng, prompt, n, **kw):
    """Run one request and return (tokens, kv_hit_tokens delta)."""
    before = eng.kv.stats()["kv_hit_tokens"]
    out = eng.generate(list(prompt), max_new_tokens=n, **kw)
    return out, eng.kv.stats()["kv_hit_tokens"] - before


PROMPTS = [[7, 3, 11], [2, 4, 6, 8, 10], [1] * 9, [5, 9] * 7,
           list(range(100, 125))]  # last spans the 32 bucket


class TestPagedOracleEquivalence:
    def test_greedy_concurrent_across_buckets(self, paged, oracle):
        """Mixed-length prompts (both compile buckets) arriving staggered
        into 2 slots decode token-identically to the batch-1 oracle."""
        outs = [None] * len(PROMPTS)
        errs = []

        def client(i):
            try:
                time.sleep(i * 0.01)
                outs[i] = paged.generate(PROMPTS[i], max_new_tokens=12)
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(PROMPTS))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        for i, p in enumerate(PROMPTS):
            assert outs[i] == oracle(p, 12), f"prompt {i} diverged"
        assert paged.kv.active_blocks() == 0

    def test_warm_repeat_hits_and_matches(self, paged, oracle):
        """A repeated prompt hits its own retired chain — fewer prefill
        FLOPs, identical tokens."""
        p = list(range(200, 220))  # 20 tokens: 2 full blocks + tail
        cold, h0 = _hit_delta(paged, p, 8)
        warm, h1 = _hit_delta(paged, p, 8)
        assert cold == warm == oracle(p, 8)
        assert h0 == 0
        # Chain 28 tokens: full-block hit 24, capped tail walk adds ≤ bt-1;
        # at minimum both full blocks of the prompt hit.
        assert h1 >= 2 * BT

    @pytest.mark.parametrize("length,bucket", [(3, 16), (16, 16), (25, 32)])
    def test_prefill_rows_and_pad_rows_are_counted(self, paged, length,
                                                   bucket):
        """An admission adds its suffix BUCKET to ``prefill_rows_total`` and
        the bucket less the suffix to ``prefill_pad_rows_total``; a repeat
        of the prompt hits its own chain and adds its SUFFIX's bucket."""
        p = [230 + length] * length
        before = paged.stats()
        _out, hit = _hit_delta(paged, p, 2)
        cold = paged.stats()
        assert hit == 0
        assert cold["prefill_rows_total"] - before["prefill_rows_total"] == bucket
        assert (cold["prefill_pad_rows_total"]
                - before["prefill_pad_rows_total"]) == bucket - length
        _out, hit = _hit_delta(paged, p, 2)
        warm = paged.stats()
        if length >= BT:
            assert 0 < hit < length
        suffix = length - hit
        rows = warm["prefill_rows_total"] - cold["prefill_rows_total"]
        assert rows == paged._suffix_bucket(suffix)
        assert (warm["prefill_pad_rows_total"]
                - cold["prefill_pad_rows_total"]) == rows - suffix

    def test_sampled_matches_oracle(self, paged, oracle):
        p = PROMPTS[1]
        out = paged.generate(p, max_new_tokens=12, temperature=0.8, seed=123)
        assert out == oracle(p, 12, temperature=0.8, seed=123)

    def test_out_of_vocab_prompt_rejected(self, paged):
        """An out-of-range id would gather a NaN embedding that OUTLIVES the
        request in the shared pool (trash block + cached chain) — admission
        must reject it before it reaches the device."""
        with pytest.raises(ValueError, match="token ids"):
            paged.generate([1, 2, 256], max_new_tokens=4)
        with pytest.raises(ValueError, match="token ids"):
            paged.generate([-1, 2, 3], max_new_tokens=4)


class TestPrefixBoundaries:
    """Hit lengths land exactly on hash-block boundaries: a shared prefix
    one token short of a block hits nothing; at the boundary it hits the
    whole block; past it, still only the full blocks."""

    BASE = [31 + 2 * i for i in range(24)]  # 3 full blocks, distinctive

    @pytest.fixture(scope="class")
    def base_chain(self, paged, oracle):
        out = paged.generate(list(self.BASE), max_new_tokens=12)
        assert out == oracle(self.BASE, 12)
        return list(self.BASE) + out  # 36 tokens: 4 full blocks + tail(4)

    @pytest.mark.parametrize("shared,expected_hit", [
        (BT - 1, 0),        # one short of a block: nothing stable to hit
        (BT, BT),           # exactly one block
        (BT + 1, BT),       # one past: the odd token is re-prefilled
        (2 * BT, 2 * BT),
        (3 * BT, 3 * BT),
    ])
    def test_hit_at_offset(self, paged, oracle, base_chain, shared,
                           expected_hit):
        # Divergent suffix unique per offset so probes can't hit each other
        # (ids stay < vocab 256 — the engine rejects out-of-range tokens).
        probe = base_chain[:shared] + [220 + shared, 241, 242]
        out, hit = _hit_delta(paged, probe, 4)
        assert out == oracle(probe, 4), f"shared={shared} diverged"
        assert hit == expected_hit
        assert paged.kv.active_blocks() == 0

    def test_full_chain_tail_hit(self, paged, oracle):
        """Extending a whole retired chain (the multi-turn case) also hits
        the registered partial tail block, not just full blocks."""
        base = [171 + i for i in range(12)]
        out = paged.generate(base, max_new_tokens=6)
        assert out == oracle(base, 6)
        chain = base + out  # 18 tokens: 2 full blocks + 2-token tail
        probe = chain + [251, 252, 253]
        out, hit = _hit_delta(paged, probe, 4)
        assert out == oracle(probe, 4)
        assert hit == len(chain)  # 16 full + 2 tail


class TestCOWForkIsolation:
    def test_forked_tails_decode_independently(self, paged, oracle):
        """Two forks of one retired conversation share its partial tail
        block copy-on-write: both decode oracle-identically (no
        cross-contamination through the shared block) and every refcount
        drops to zero at retire."""
        base = [131 + i for i in range(12)]  # 12 tokens: 1 full block + tail
        out = paged.generate(base, max_new_tokens=6)
        chain = base + out  # 18 tokens: 2 full blocks + 2-token tail
        cows0 = paged.kv.stats()["kv_cow_copies"]
        hits0 = paged.kv.stats()["kv_hit_tokens"]
        forks = [chain + [211, 212, 213], chain + [221, 222, 223]]
        outs = [None, None]
        errs = []

        def client(i):
            try:
                outs[i] = paged.generate(forks[i], max_new_tokens=8)
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        for i in range(2):
            assert outs[i] == oracle(forks[i], 8), f"fork {i} diverged"
        # Each fork hit the whole chain, its 2-token tail included -> one
        # private COW copy apiece.
        assert paged.kv.stats()["kv_hit_tokens"] - hits0 == 2 * len(chain)
        assert paged.kv.stats()["kv_cow_copies"] - cows0 >= 2
        # Leak-check invariant: nothing stays pinned after retire.
        assert paged.kv.active_blocks() == 0
        assert paged.kv._ref == {}

    def test_stats_surface(self, paged):
        s = paged.stats()
        for key in ("kv_blocks_total", "kv_blocks_active", "kv_blocks_cached",
                    "kv_blocks_free", "kv_hit_tokens", "kv_miss_tokens",
                    "kv_cow_copies"):
            assert key in s
        assert s["kv_blocks_total"] == 128.0
        assert (s["kv_blocks_active"] + s["kv_blocks_cached"]
                + s["kv_blocks_free"]) == s["kv_blocks_total"]

    def test_describe_names_what_was_resolved(self, paged, tiny_model):
        """The non-numeric twin of ``stats``: the kernel as resolved (never
        "auto"), the buckets warmup compiled, and where the state lives."""
        d = paged.describe()
        assert d["engine"] == "LLMEngine"
        assert d["attention_kernel"] in ("pallas", "interpret", "gather")
        assert d["warmed_buckets"] == [16, 32]
        assert d["pool_blocks"] == 129 and d["block_tokens"] == BT
        assert d["params_devices"] and d["kv_pool_devices"]
        cold = LLMEngine(tiny_model[1], paged.config,
                         prompt_buckets=(16,), slots=1, max_queue=0,
                         name="paged-cold", block_tokens=BT,
                         pool_blocks=9)
        assert cold.describe()["warmed_buckets"] == []


def _per_call_cast(params, config):
    """The oracle: GPT-2's stored tree brought into the form the forwards
    read INSIDE the program, on every call, as the forwards themselves did
    before the working copy: ``cast(p[layer])`` of each stacked leaf, the
    head as the cast of the float32 table's transpose."""
    cast = lambda p: p.astype(config.dtype)
    out = {name: cast(params[name])
           for name in ("tok_embed", "pos_embed", "lnf_g", "lnf_b")
           if name in params}
    out["layers"] = tuple(
        jax.tree.map(lambda p: cast(p[layer]), params["blocks"])
        for layer in range(config.n_layers))
    out["head"] = cast(params["tok_embed"].T if config.tie_embeddings
                       else params["lm_head"])
    return out


def _oracle_prefill(params, tokens, pool, state, table, start_pos,
                    suffix_len, slot, config, block_tokens, **kw):
    return generate.GPT2_FAMILY.prefill(
        _per_call_cast(params, config), tokens, pool, state, table,
        start_pos, suffix_len, slot, config, block_tokens, **kw)


def _oracle_decode(params, tokens, pool, state, tables, lengths, config,
                   block_tokens, **kw):
    return generate.GPT2_FAMILY.decode(
        _per_call_cast(params, config), tokens, pool, state, tables,
        lengths, config, block_tokens, **kw)


_ORACLE_FAMILY = generate.GPT2_FAMILY._replace(
    working_params=None, prefill=_oracle_prefill, decode=_oracle_decode)


@dataclasses.dataclass(frozen=True)
class _PerCallCastConfig(transformer.TransformerConfig):
    """The same model, its serve programs handed the stored tree."""

    def paged_family(self):
        return _ORACLE_FAMILY


class TestWorkingParams:
    """GPT-2's serve programs read a working copy made once when the
    generator is built; the same programs fed a forward that casts per call
    give the same bits."""

    @staticmethod
    def _serve(gen):
        """Prefill two slots, then one decode chunk: slot 0 greedy, slot 1
        sampled. Returns (tokens, last logits, K pool) as numpy."""
        state = gen.init_state()
        table = np.zeros((2, gen.blocks_per_seq), np.int32)
        table[0, :2], table[1, :2] = (1, 2), (3, 4)
        prompts = ([5, 9, 3, 77, 21, 8, 1, 30, 2], [4, 4, 19])
        for slot, prompt in enumerate(prompts):
            padded = np.zeros((1, 16), np.int32)
            padded[0, :len(prompt)] = prompt
            *state, _aux = gen.prefill_fn(16)(
                gen.params, *state, table[slot], padded, 0, len(prompt),
                slot, 11 + slot)
        lengths = np.array([len(p) for p in prompts], np.int32)
        toks, pool, _state, last, _keys, _aux = gen.decode_fn(4)(
            gen.params, *state, table, lengths, np.ones(2, bool),
            np.array([True, False]), np.array([0.0, 0.8], np.float32))
        return np.asarray(toks), np.asarray(last), np.asarray(
            pool[0].astype(jnp.float32))

    @pytest.mark.parametrize("dtype,tied", [
        (jnp.bfloat16, True), (jnp.bfloat16, False), (jnp.float32, True)])
    def test_bitwise_equal_to_the_per_call_cast(self, dtype, tied):
        kw = dict(max_seq_len=64, dtype=dtype, tie_embeddings=tied)
        cfg = transformer.tiny(**kw)
        params = transformer.init_params(cfg, jax.random.key(3))
        assert params["tok_embed"].dtype == jnp.float32   # stored masters
        geometry = dict(slots=2, num_blocks=9, block_tokens=BT)
        gen = generate.PagedGenerator(params, cfg, **geometry)
        oracle = generate.PagedGenerator(
            params, _PerCallCastConfig(**dataclasses.asdict(cfg)),
            **geometry)
        assert oracle.params is params and oracle.params_working_bytes == 0
        # one array a matrix a layer, every leaf in the compute type
        assert len(gen.params["layers"]) == cfg.n_layers
        assert gen.params["layers"][0]["w_up"].shape == (cfg.d_model, cfg.d_ff)
        assert {leaf.dtype for leaf in jax.tree.leaves(gen.params)} == {
            jnp.dtype(dtype)}
        toks, last, k_pool = self._serve(gen)
        o_toks, o_last, o_pool = self._serve(oracle)
        np.testing.assert_array_equal(toks, o_toks)
        assert last.dtype == np.float32 and np.abs(last).max() > 0
        np.testing.assert_array_equal(last.view(np.uint32),
                                      o_last.view(np.uint32))
        np.testing.assert_array_equal(k_pool, o_pool)

    @pytest.mark.parametrize("tied", [True, False])
    def test_describe_counts_the_working_copy(self, tied):
        """Two bytes a parameter, and the tied table a second time as the
        head's matrix; at a width of whole lanes nothing is padded."""
        cfg = transformer.tiny(max_seq_len=64, dtype=jnp.bfloat16,
                               d_model=128, tie_embeddings=tied)
        params = transformer.init_params(cfg, jax.random.key(0))
        kw = dict(prompt_buckets=(16,), slots=1, max_queue=0,
                  block_tokens=BT, pool_blocks=9)
        eng = LLMEngine(params, cfg, name="working-gpt2", **kw)
        n_params = sum(leaf.size for leaf in jax.tree.leaves(params))
        held = n_params + (params["tok_embed"].size if tied else 0)
        assert eng.describe()["params_working_bytes"] == 2 * held
        assert not hasattr(eng, "params")      # the stored tree is not kept
        assert eng.generate([5, 9, 3], max_new_tokens=4)

    def test_a_family_without_the_function_reads_its_stored_tree(self):
        kw = dict(prompt_buckets=(16,), slots=1, max_queue=0,
                  block_tokens=BT, pool_blocks=9)
        from ray_tpu.models import longcat

        lcfg = longcat.tiny()
        lparams = longcat.init_params(lcfg, jax.random.key(0))
        leng = LLMEngine(lparams, lcfg, name="working-longcat", **kw)
        assert leng.describe()["params_working_bytes"] == 0
        assert leng._pg.params is lparams

    def test_set_params_serves_the_new_weights(self, tiny_model):
        cfg, params = tiny_model
        other = transformer.init_params(cfg, jax.random.key(9))
        kw = dict(prompt_buckets=(16,), slots=1, max_queue=0,
                  block_tokens=BT, pool_blocks=9)
        eng = LLMEngine(params, cfg, name="swap", **kw)
        fresh = LLMEngine(other, cfg, name="swap-fresh", **kw)
        # sampled: a tiny random model's greedy stream repeats its prompt
        ask = dict(max_new_tokens=12, temperature=1.0, seed=3)
        before = eng.generate([5, 9, 3, 77], **ask)
        eng.set_params(other)
        after = eng.generate([5, 9, 3, 77], **ask)
        assert after == fresh.generate([5, 9, 3, 77], **ask) != before
        assert eng.kv.stats()["kv_hit_tokens"] == 0  # the old K/V is gone


class TestPagedMetrics:
    def test_kv_metrics_exported(self, paged):
        from ray_tpu.core.metrics_export import (metrics_enabled,
                                                 serve_kv_block_occupancy,
                                                 serve_kv_hit_tokens_total)

        if not metrics_enabled():
            pytest.skip("metrics_export_enabled off")
        p = [61 + i for i in range(18)]
        paged.generate(p, max_new_tokens=4)
        paged.generate(p, max_new_tokens=4)  # warm: flushes hit tokens
        tags = {"deployment": paged.name}
        assert serve_kv_hit_tokens_total().get(tags) >= 2 * BT
        occ = serve_kv_block_occupancy()
        by_state = {s: occ.get({**tags, "state": s})
                    for s in ("active", "cached", "free")}
        assert sum(by_state.values()) == 128.0
        assert by_state["cached"] > 0  # retired chains stay reusable

    def test_ttft_phase_split(self, paged):
        from ray_tpu.core.metrics_export import (metrics_enabled,
                                                 serve_ttft_hist)

        if not metrics_enabled():
            pytest.skip("metrics_export_enabled off")
        paged.generate([91, 92, 93], max_new_tokens=4)
        h = serve_ttft_hist()
        snap = dict(h._snapshot()["samples"])
        counts = {}
        for tags, (_buckets, _sum, count) in snap.items():
            t = dict(tags)
            if t.get("deployment") == paged.name:
                counts[t["phase"]] = count
        for phase in ("total", "queued", "prefill", "decode"):
            assert counts.get(phase, 0) > 0, f"missing phase {phase}"


class TestCancelMidDispatchRace:
    def test_cancel_between_dispatch_and_commit_leaks_nothing(self, paged,
                                                              oracle):
        """_dispatch_prefill runs outside _state_lock; a cancel landing
        between the device dispatch and the block-table commit must neither
        leak the freshly pinned blocks (commit overwriting a freed slot)
        nor publish prefix digests pointing at freed blocks."""
        victim_prompt = [44 + 2 * i for i in range(2 * BT + 3)]
        state = {}
        orig_fn = paged._pg.prefill_fn

        def hooked(bucket):
            pf = orig_fn(bucket)

            def run(*args):
                out = pf(*args)
                req = state.get("victim")
                if req is not None and not req.done:
                    paged._cancel(req)  # lands inside the race window
                return out

            return run

        paged._pg.prefill_fn = hooked
        try:
            req = paged.submit(victim_prompt, max_new_tokens=6)
            state["victim"] = req
            out = list(paged.drive(req))
        finally:
            paged._pg.prefill_fn = orig_fn
            state["victim"] = None
        assert req.finish_reason == "cancelled"
        assert out == []  # cancelled before any decode chunk
        # The pins taken for the cancelled admission were dropped...
        assert paged.kv.active_blocks() == 0
        # ...and nothing was registered against the dropped blocks: a
        # same-prefix probe must miss the cache yet match the oracle.
        probe = victim_prompt + [201]
        out, hit = _hit_delta(paged, probe, 6)
        assert hit == 0
        assert out == oracle(probe, 6)
        assert paged.kv.active_blocks() == 0


# What the engine owes a request whatever it serves (tests/engine_contract.py);
# the streams a check hands back are held to the oracle.
@engine_contract.each_check
def test_engine_contract(tiny_model, oracle, check):
    cfg, params = tiny_model
    for prompt, toks in check(params, cfg, engine_contract.ENGINE_KW):
        assert toks == oracle(prompt, len(toks))


# The same seven over a pool row of whole 128-lane tiles and the interpreted
# Pallas kernel: there the decode program hands the kernel each step's new K
# and V row (``paged_attention_append``) where the engines above scatter it
# (64 lanes, the gather path). Held to the same single-sequence oracle: the
# length cap (a slot at table capacity writes nothing), a cancelled slot
# parked beside a live one, a requeue on an exhausted pool.
@pytest.fixture(scope="module")
def lane_model():
    cfg = transformer.tiny(d_model=128, max_seq_len=64)
    params = transformer.init_params(cfg, jax.random.key(0))
    gen = generate.Generator(params, cfg)
    return cfg, params, lambda prompt, n: gen.generate(
        list(prompt), max_new_tokens=n)


@engine_contract.each_check
def test_engine_contract_with_the_appending_kernel(lane_model, check):
    cfg, params, lane_oracle = lane_model
    kw = dict(engine_contract.ENGINE_KW, attention_kernel="interpret")
    for prompt, toks in check(params, cfg, kw):
        assert toks == lane_oracle(prompt, len(toks))


@pytest.mark.parametrize("d_model", [128, 64])
def test_a_half_filled_bucket_walks_for_its_real_rows(d_model):
    """100 tokens in the 256 bucket, two query tiles of the attention kernel
    (over whole lane tiles and, 64 lanes wide, with the groups on the grid):
    the first straddles the prompt's end, the second is pad rows alone; the
    table behind the prompt's blocks is the trash block. The last real row's
    logits are the gather path's, which attends every row."""
    cfg = transformer.tiny(d_model=d_model, max_seq_len=256)
    params = transformer.init_params(cfg, jax.random.key(0))
    seq = [int(t) for t in np.random.default_rng(7).integers(1, 200, 100)]
    got, want = (half_filled_bucket.last_row(params, cfg, seq, 256, kernel=k)
                 for k in ("interpret", "gather"))
    np.testing.assert_allclose(got, want, atol=2e-4)
    assert np.abs(want).max() > 0.1


# -- the look-ahead: one decode chunk queued behind the one that runs ---------

def _ahead_engine(tiny_model, pool_blocks, name):
    cfg, params = tiny_model
    eng = LLMEngine(params, cfg, prompt_buckets=(16, 32), chunk=4, slots=2,
                    max_queue=0, name=name, block_tokens=BT,
                    pool_blocks=pool_blocks)
    eng.warmup()
    return eng


@pytest.fixture(scope="module")
def ahead(tiny_model):
    return _ahead_engine(tiny_model, 129, "ahead-test")


def _step_until_done(eng, reqs, each_step=None, limit=400):
    """This thread is the driver: step by hand until every request is done,
    then hand out what each was delivered, in order."""
    for _ in range(limit):
        if all(r.done for r in reqs):
            break
        eng._step()
        if each_step is not None:
            each_step()
    assert all(r.done for r in reqs)
    return [list(eng.drive(r)) for r in reqs]


# (prompt, max_new, temperature, seed): unequal lengths, so slots retire at
# different steps; one sampled stream beside the greedy ones.
AHEAD_JOBS = [(PROMPTS[0], 5, 0.0, 0), (PROMPTS[1], 12, 0.0, 0),
              (PROMPTS[2], 9, 0.8, 123), (PROMPTS[3], 16, 0.0, 0),
              (PROMPTS[4], 4, 0.0, 0), (PROMPTS[1], 7, 0.0, 0)]


class TestDispatchAhead:
    @pytest.mark.parametrize("pool_blocks", [129, 7])
    def test_a_slot_is_reused_while_its_last_chunk_is_unfetched(
            self, tiny_model, oracle, pool_blocks):
        """More requests than slots, unequal ``max_new``: a request retired
        by count gives its slot to the next one a step before its own last
        tokens are on the host. Every request still gets exactly its
        tokens: the oracle's, and those of the same requests one at a time.
        With a pool that holds one of the longer requests but not two
        (7 blocks of 8: the trash block and six more, a request takes up to
        five) admission waits for blocks that are released at delivery, and
        still makes progress."""
        eng = _ahead_engine(tiny_model, pool_blocks, f"ahead-{pool_blocks}")
        before = eng.stats()
        reqs = [eng.submit(p, max_new_tokens=n, temperature=t, seed=sd)
                for p, n, t, sd in AHEAD_JOBS]
        def nobody_is_left_retiring():
            # Inside a step a retired request's chunk is fetched after its
            # slot is re-used; between steps every pending row's request
            # still holds its slot, or is done.
            for slot, req, _upto in (eng._pending.rows if eng._pending
                                     else ()):
                assert eng._slot_req[slot] is req or req.done

        outs = _step_until_done(eng, reqs, nobody_is_left_retiring)
        for (p, n, t, sd), out, req in zip(AHEAD_JOBS, outs, reqs):
            assert out == oracle(p, n, temperature=t, seed=sd)
            assert req.finish_reason == "stop" and req.emitted == n
        st = eng.stats()
        assert st["steps_ahead_total"] > before["steps_ahead_total"]
        assert eng._pending is None and eng.kv.active_blocks() == 0
        if pool_blocks == 7:
            assert st["admit_blocked_pool_s"] > 0
        for p, n, t, sd in AHEAD_JOBS:
            assert eng.generate(p, max_new_tokens=n, temperature=t,
                                seed=sd) == oracle(p, n, temperature=t,
                                                   seed=sd)
        assert eng.kv.active_blocks() == 0

    def test_slot_taken_before_the_former_requests_tokens_are_fetched(
            self, ahead, oracle):
        """Inside the step that re-uses a slot, the former request's last
        chunk is still on the device when the next request is admitted to
        its slot: delivery goes by the snapshot taken at dispatch."""
        seen = []
        orig = ahead._dispatch_prefill

        def spy(req, slot, st):
            pend = ahead._pending
            former = [r for s, r, _ in (pend.rows if pend else ())
                      if s == slot and r is not req]
            seen.append((slot, [(r.retiring, r.done, bool(r.blocks))
                                for r in former]))
            return orig(req, slot, st)

        ahead._dispatch_prefill = spy
        try:
            reqs = [ahead.submit(p, max_new_tokens=n)
                    for p, n in [(PROMPTS[0], 4), (PROMPTS[1], 12),
                                 (PROMPTS[2], 8)]]
            outs = _step_until_done(ahead, reqs)
        finally:
            ahead._dispatch_prefill = orig
        assert outs == [oracle(PROMPTS[0], 4), oracle(PROMPTS[1], 12),
                        oracle(PROMPTS[2], 8)]
        # The third request took the first one's slot while that one was
        # retired ("stop"), not done, its blocks still pinned.
        assert seen[2] == (seen[0][0], [("stop", False, True)])
        assert ahead._pending is None and ahead.kv.active_blocks() == 0

    def test_cancel_with_two_chunks_dispatched(self, ahead, oracle):
        """A cancel that lands when the request's second chunk has just been
        enqueued behind its first, neither fetched: slot and blocks are free
        at once, nothing is delivered to it afterwards, the stream beside
        it is untouched, and the pool's counts balance at the end."""
        state = {"calls": 0}
        orig = ahead._run_decode

        def hooked(*args):
            toks = orig(*args)
            state["calls"] += 1
            if state["calls"] == 2:
                assert ahead._pending is not None     # chunk 1, unfetched
                ahead._cancel(victim)
                state["after_cancel"] = (
                    victim.slot, list(victim.tokens),
                    ahead._slot_req.count(None), ahead.kv.active_blocks())
            return toks

        victim = ahead.submit(PROMPTS[3], max_new_tokens=32)
        other = ahead.submit(PROMPTS[1], max_new_tokens=12)
        other_blocks = -(-(len(PROMPTS[1]) + 12) // BT)
        ahead._run_decode = hooked
        try:
            outs = _step_until_done(ahead, [victim, other])
        finally:
            ahead._run_decode = orig
        assert state["after_cancel"] == (None, [], 1, other_blocks)
        assert victim.finish_reason == "cancelled"
        assert outs[0] == [] and victim.emitted == 0 and not victim.out_ids
        assert outs[1] == oracle(PROMPTS[1], 12)
        assert ahead._pending is None and ahead.kv.active_blocks() == 0
        st = ahead.kv.stats()
        assert st["kv_blocks_active"] == 0
        assert st["kv_blocks_cached"] + st["kv_blocks_free"] \
            == st["kv_blocks_total"]

    def test_the_last_chunk_is_delivered_by_a_draining_step(self, ahead,
                                                           oracle):
        """No further ``submit``: the step after the last dispatch finds
        nothing to dispatch, fetches the pending chunk and finishes the
        request. It dispatches nothing and counts no ``steps_total``."""
        from ray_tpu.util import tracing

        before = ahead.stats()
        t0 = tracing.now_ns()
        req = ahead.submit(PROMPTS[2], max_new_tokens=10)   # three chunks
        ahead._step()
        assert ahead._pending is not None and not req.tokens
        ahead._step()
        ahead._step()
        # All three chunks are dispatched, two delivered; the slot is still
        # the request's (retirement is the next step's).
        assert len(req.tokens) == 8 and not req.done
        assert ahead.stats()["steps_total"] - before["steps_total"] == 3
        ahead._step()
        assert req.done and req.finish_reason == "stop"
        assert list(ahead.drive(req)) == oracle(PROMPTS[2], 10)
        st = ahead.stats()
        assert st["steps_total"] - before["steps_total"] == 3
        assert st["steps_ahead_total"] - before["steps_ahead_total"] == 2
        assert ahead._pending is None and ahead.kv.active_blocks() == 0
        steps = [s for s in tracing.recorded(t0)
                 if s.name == "llm.step" and s.trace_id == ahead.trace_id]
        assert [(s.attrs["batch"], s.attrs["ahead"], s.attrs["tokens"])
                for s in steps] == [(1, False, 0), (1, True, 4),
                                    (1, True, 4), (0, False, 2)]
        phases = [[k.name.rsplit(".", 1)[1] for k in tracing.recorded(t0)
                   if k.parent_id == s.span_id] for s in steps]
        assert phases[0] == ["retire", "admit", "operands", "dispatch",
                             "observe"]
        assert phases[-1] == ["retire", "admit", "operands", "device_wait",
                              "deliver", "observe"]

    def test_a_dispatch_error_reaches_every_request_and_drops_the_pending(
            self, ahead, oracle):
        """The decode call fails with one chunk unfetched: the request that
        holds a slot, the one just retired by count (it holds none: only
        the pending record knows it) and the one still waiting all get the
        error; no pending record is left, and the engine serves again."""
        boom = RuntimeError("device fell over")
        state = {"calls": 0}
        orig = ahead._run_decode

        def hooked(*args):
            state["calls"] += 1
            if state["calls"] == 2:
                state["retiring"] = [r.retiring for r in reqs]
                raise boom
            return orig(*args)

        reqs = [ahead.submit(PROMPTS[0], max_new_tokens=4),    # one chunk
                ahead.submit(PROMPTS[1], max_new_tokens=16),
                ahead.submit(PROMPTS[2], max_new_tokens=8)]    # waits
        ahead._run_decode = hooked
        try:
            ahead._step()
            with pytest.raises(RuntimeError, match="fell over"):
                ahead._step()
        finally:
            ahead._run_decode = orig
        assert state["retiring"] == ["stop", None, None]
        assert ahead._pending is None
        for r in reqs:
            assert r.done and r.error is boom and not r.blocks
            with pytest.raises(RuntimeError, match="fell over"):
                list(ahead.drive(r))
        assert ahead.stats()["slots_busy"] == 0
        assert ahead.kv.active_blocks() == 0
        assert ahead.generate(PROMPTS[1], max_new_tokens=8) \
            == oracle(PROMPTS[1], 8)
        assert ahead._pending is None and ahead.kv.active_blocks() == 0

    def test_a_parked_slot_writes_to_the_trash_block(self, ahead, oracle):
        """A request retired by count takes its blocks with it; the slot's
        table row must be cleared all the same, or the parked slot's writes
        of later chunks land in blocks that a next turn shares."""
        p = list(range(60, 78))     # 18 + 8 tokens: three full blocks
        beside = ahead.submit(PROMPTS[0], max_new_tokens=4)     # slot 0
        first = ahead.submit(p, max_new_tokens=8)               # slot 1
        def parked_rows_are_trash():
            for slot, req in enumerate(ahead._slot_req):
                assert req is not None or not ahead._slot_table[slot].any()

        outs = _step_until_done(ahead, [first, beside],
                                parked_rows_are_trash)
        assert outs[0] == oracle(p, 8)
        # The next turn goes into slot 0; slot 1 stays parked beside it.
        turn2 = p + outs[0] + [9, 8, 7]
        out2, hit = _hit_delta(ahead, turn2, 6)
        assert hit >= 3 * BT and out2 == oracle(turn2, 6)
        assert ahead.kv.active_blocks() == 0

    def test_at_capacity_write_redirects_to_trash(self, tiny_model):
        """Direct forward unit: lengths == table capacity redirects the
        scatter to trash block 0 instead of clamping onto the last cell
        (the pre-fix behavior corrupted position cap-1)."""
        cfg, params = tiny_model
        nb_seq = 3
        pool = 8
        k_pool, v_pool = generate.init_block_pool(cfg, pool, BT)
        # Heads folded into the lanes; blocks stay dimension 1, so the
        # [:, 0] / [:, 1:] reads below index trash and live blocks as before.
        assert k_pool.shape == v_pool.shape == (
            cfg.n_layers, pool, BT, cfg.n_heads * cfg.head_dim)
        k_pool = k_pool + 1.5  # sentinel content
        v_pool = v_pool + 2.5
        tables = jnp.asarray(
            np.array([[1, 2, 3]], np.int32))          # fully live table
        cap = nb_seq * BT
        lengths = jnp.asarray(np.array([cap], np.int32))
        toks = jnp.asarray(np.array([[4]], np.int32))
        logits, k2, v2 = generate._forward_decode_paged(
            generate.working_params(params, cfg), toks, k_pool, v_pool,
            tables, lengths, cfg, BT)
        assert np.isfinite(np.asarray(logits)).all()
        # Every live block — in particular the last cell of block 3 —
        # keeps its sentinel; only trash block 0 absorbed the write.
        np.testing.assert_array_equal(np.asarray(k2[:, 1:]),
                                      np.asarray(k_pool[:, 1:]))
        np.testing.assert_array_equal(np.asarray(v2[:, 1:]),
                                      np.asarray(v_pool[:, 1:]))
        assert not np.array_equal(np.asarray(k2[:, 0]),
                                  np.asarray(k_pool[:, 0]))
        assert k2.shape == k_pool.shape and v2.shape == v_pool.shape


class _StubReplica:
    def __init__(self, key):
        class _Id:
            @staticmethod
            def hex():
                return key

        self.actor_id = _Id()


def _mk_router(replicas, load):
    r = Router.__new__(Router)
    r._name = "stub"
    r._replicas = replicas
    r._replica_load = load
    r._model_ids = {}
    r._ongoing = {}
    r._max_ongoing = 100
    r._lock = threading.Lock()
    r._last_refresh = time.monotonic()  # fresh — _refresh() is a no-op
    r._version = 0
    return r


class _FakeController:
    """get_snapshot.remote returns the canned table directly; the test
    monkeypatches ray_tpu.get to the identity so Router._refresh consumes
    it without a live controller actor."""

    def __init__(self, version, table):
        outer = self

        class _Method:
            @staticmethod
            def remote(_version, _timeout):
                return outer._version, outer._table

        self._version = version
        self._table = table
        self.get_snapshot = _Method()


class TestRouterStaleEviction:
    def test_refresh_evicts_departed_replicas(self, monkeypatch):
        """Shrinking replica set: ongoing counts, load entries, and affinity
        pins for replicas gone from the snapshot are evicted — a stale entry
        must not keep steering (or starving) the pow-2 pick."""
        monkeypatch.setattr(ray_tpu, "get", lambda x, **kw: x)
        a, b = _StubReplica("a"), _StubReplica("b")
        r = _mk_router([a, b], {})
        r._ongoing = {"a": 3, "b": 2}
        r._affinity_map().update({b"h-a": "a", b"h-b": "b"})
        r._controller = _FakeController(1, {"stub": {
            "replicas": [b],
            "max_ongoing_requests": 100,
            "model_ids": {},
            # Controller-side load table still carries the dead replica.
            "replica_load": {"a": {"slots_busy": 4.0, "slots_total": 4.0},
                             "b": {"slots_busy": 1.0, "slots_total": 4.0}},
        }})
        r._refresh(block=True)
        assert r._replicas == [b]
        assert r._ongoing == {"b": 2}
        assert r._replica_load == {"b": {"slots_busy": 1.0,
                                         "slots_total": 4.0}}
        assert r._affinity_map() == {b"h-b": "b"}
        # Picks route only to the survivor afterwards.
        for _ in range(5):
            _best, key = r._pick()
            assert key == "b"
            r._dec(key)


class TestPrefixAffinityRouting:
    def test_pick_prefers_affinity_replica(self):
        """An affinity-pinned replica wins the pick outright — even when
        pow-2 would prefer the other (lower ongoing) replica."""
        reps = [_StubReplica("a"), _StubReplica("b")]
        r = _mk_router(reps, {})
        r._affinity_map()[b"h1"] = "b"
        r._ongoing = {"a": 0, "b": 5}  # pow-2 would choose a
        for _ in range(10):
            _best, key = r._pick(prefix_hash=b"h1")
            assert key == "b"
            r._dec(key)

    def test_first_pick_records_affinity(self):
        reps = [_StubReplica("a"), _StubReplica("b")]
        r = _mk_router(reps, {})
        _best, key = r._pick(prefix_hash=b"h2")
        assert r._affinity_map()[b"h2"] == key
        # The same prefix sticks to that replica even though its ongoing
        # count is now higher than the other's.
        _best, key2 = r._pick(prefix_hash=b"h2")
        assert key2 == key

    def test_affinity_migrates_off_exhausted_replica(self):
        """A pinned replica reporting a full slot set loses the pick; the
        pow-2 winner inherits the pin (the prefix re-caches there)."""
        reps = [_StubReplica("a"), _StubReplica("b")]
        r = _mk_router(reps, {
            "b": {"slots_total": 2.0, "slots_busy": 2.0},
            "a": {"slots_total": 2.0, "slots_busy": 0.0},
        })
        r._affinity_map()[b"h3"] = "b"
        _best, key = r._pick(prefix_hash=b"h3")
        assert key == "a"
        assert r._affinity_map()[b"h3"] == "a"

    def test_affinity_map_lru_bound(self):
        r = _mk_router([_StubReplica("a")], {})
        r.AFFINITY_CAP = 3
        with r._lock:
            for i in range(5):
                r._note_affinity(b"k%d" % i, "a")
        assert list(r._affinity_map()) == [b"k2", b"k3", b"k4"]

    def test_handle_affinity_hash(self):
        from ray_tpu.core.config import config

        cfg = config()
        if not cfg.serve_prefix_affinity_enabled:
            pytest.skip("serve_prefix_affinity_enabled off")
        bt = int(cfg.serve_kv_block_tokens)
        prompt = list(range(2 * bt + 3))
        h = DeploymentHandle._affinity_hash([{"prompt_ids": prompt}])
        assert h == prefix_head_hash(
            prompt, bt, int(cfg.serve_prefix_affinity_blocks))
        assert h is not None
        # Sub-block prompts and non-LLM payloads produce no affinity key.
        assert DeploymentHandle._affinity_hash(
            [{"prompt_ids": prompt[:bt - 1]}]) is None
        assert DeploymentHandle._affinity_hash(["plain-arg"]) is None
        assert DeploymentHandle._affinity_hash([]) is None
