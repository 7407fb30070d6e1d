"""Speculative decoding in the paged serve engine (ISSUE 16).

Correctness bar: speculation changes THROUGHPUT, never tokens. Greedy
spec output must be token-for-token identical to the non-speculative paged
engine for any draft (aligned, misaligned, partially aligned — including
mid-request EWMA demotion of a hopeless draft); sampled output must follow
the target distribution (rejection sampling guarantees it for any draft —
checked empirically over fixed seeds); and the block-table advance on
partial acceptance must leave zero pinned blocks behind
(``active_blocks() == 0``), including when draft and target share a pool
under prefix-reuse COW forks. Runs under ``RAY_TPU_LEAK_CHECK_ENABLED=1``.
"""

import collections
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import generate, transformer
from ray_tpu.serve.llm import LLMEngine

BT = 8


@pytest.fixture(scope="module")
def models():
    """Target + three drafts. The 3x parameter scale pushes the random
    init out of its fixed-point attractor so greedy output is VARIED —
    a constant-token stream would vacuously pass identity checks."""
    cfg = transformer.tiny(max_seq_len=64)
    scale = lambda t: jax.tree.map(lambda p: p * 3.0, t)
    params = scale(transformer.init_params(cfg, jax.random.key(2)))
    miss = scale(transformer.init_params(cfg, jax.random.key(7)))
    near = jax.tree.map(
        lambda p, n: p + 0.05 * n, params,
        scale(transformer.init_params(cfg, jax.random.key(11))))
    return cfg, params, {"aligned": params, "near": near, "miss": miss}


ENG_KW = dict(prompt_buckets=(16, 32), chunk=4, slots=2, max_queue=4,
              block_tokens=BT, pool_blocks=80)
PROMPTS = [[5, 9, 3, 77, 21], [1, 2, 3], [9, 8, 7, 6, 5, 4, 3, 2, 1],
           [42] * 12]


def _spec_engine(models, draft, k=3, **kw):
    cfg, params, drafts = models
    merged = {**ENG_KW, **kw}
    return LLMEngine(params, cfg, draft_params=drafts[draft],
                          draft_config=cfg, spec_tokens=k,
                          name=f"spec-{draft}", **merged)


@pytest.fixture(scope="module")
def plain(models):
    cfg, params, _ = models
    return LLMEngine(params, cfg, name="spec-base", **ENG_KW)


class TestGreedyTokenIdentity:
    @pytest.mark.parametrize("draft", ["aligned", "near", "miss"])
    def test_matches_plain_engine(self, models, plain, draft):
        """Identical greedy tokens whatever the draft quality. The 'miss'
        draft's acceptance EWMA collapses below the floor mid-request —
        the demotion handoff (pending-carry consumption, last-logits
        refresh) must not skew a single token."""
        eng = _spec_engine(models, draft)
        for p in PROMPTS:
            assert eng.generate(p, max_new_tokens=20) == plain.generate(
                p, max_new_tokens=20)
        assert eng.kv.active_blocks() == 0
        # A slot's advance is the step's acceptance, a value: with a draft
        # model every chunk is fetched in the step that dispatched it (also
        # the plain chunks a demoted draft falls back to); without one the
        # same loop runs a chunk ahead.
        st = eng.stats()
        assert st["steps_total"] > 0 and st["steps_ahead_total"] == 0
        assert eng._pending is None
        assert plain.stats()["steps_ahead_total"] > 0

    def test_acceptance_rates_span_regimes(self, models):
        """The three drafts genuinely exercise different acceptance
        regimes: aligned ~1, near in between, miss ~0 (whereupon the gate
        stops proposing — proposed stays finite)."""
        ratios = {}
        for draft in ("aligned", "near", "miss"):
            eng = _spec_engine(models, draft)
            eng.generate(PROMPTS[0], max_new_tokens=20)
            st = eng.stats()
            assert st["spec_proposed_total"] > 0
            ratios[draft] = st["spec_accept_ratio"]
        assert ratios["aligned"] > 0.9
        assert ratios["miss"] < 0.2
        assert ratios["miss"] <= ratios["near"] <= ratios["aligned"]

    def test_concurrent_slots(self, models, plain):
        """Staggered concurrent requests share spec decode dispatches;
        per-slot acceptance state must not bleed across slots."""
        eng = _spec_engine(models, "near")
        outs = [None] * len(PROMPTS)
        errs = []

        def client(i):
            try:
                outs[i] = eng.generate(PROMPTS[i], max_new_tokens=16)
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        ts = [threading.Thread(target=client, args=(i,))
              for i in range(len(PROMPTS))]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs
        for i, p in enumerate(PROMPTS):
            assert outs[i] == plain.generate(p, max_new_tokens=16)
        assert eng.kv.active_blocks() == 0


class TestSampledDistribution:
    def test_aligned_draft_accepts_everything(self, models):
        """With draft == target, p(d)/q(d) == 1 — rejection sampling must
        accept every proposal regardless of temperature."""
        eng = _spec_engine(models, "aligned")
        eng.generate(PROMPTS[0], max_new_tokens=16, temperature=0.9, seed=3)
        assert eng.stats()["spec_accept_ratio"] == pytest.approx(1.0)
        assert eng.kv.active_blocks() == 0

    def test_fixed_seed_deterministic(self, models):
        """Same seed, fresh engines → identical sampled output (the spec
        RNG chain is a pure function of the slot key)."""
        a = _spec_engine(models, "near").generate(
            PROMPTS[0], max_new_tokens=12, temperature=0.8, seed=42)
        b = _spec_engine(models, "near").generate(
            PROMPTS[0], max_new_tokens=12, temperature=0.8, seed=42)
        assert a == b

    def test_distribution_preserved(self, models, plain):
        """Rejection sampling must leave the MARGINAL distribution of
        emitted tokens equal to the target's even under a mismatched
        draft: the empirical distribution of the first three sampled
        tokens over many fixed seeds stays close to the plain engine's
        (deterministic — the seed sweep is fixed)."""
        eng = _spec_engine(models, "miss")
        n, new = 150, 3

        def sweep(e, base_seed):
            cs = [collections.Counter() for _ in range(new)]
            for seed in range(n):
                out = e.generate(PROMPTS[1], max_new_tokens=new,
                                 temperature=1.0, seed=base_seed + seed)
                for i in range(new):
                    cs[i][out[i]] += 1
            return cs

        def l1(a, b):
            return sum(abs(a[t] - b[t]) for t in set(a) | set(b)) / n

        cs_spec = sweep(eng, 0)
        cs_b1 = sweep(plain, 10_000)
        cs_b2 = sweep(plain, 20_000)  # plain-vs-plain null calibrates L1
        for i in range(new):
            # The target distribution here is nearly flat over ~120 tokens,
            # so even two same-distribution 150-draw samples sit at L1 ~ 1.
            # Spec must stay within the null's neighborhood; residual-
            # sampling bugs (mass collapsing onto the draft's argmax) push
            # the divergence toward 2.
            null = l1(cs_b1[i], cs_b2[i])
            assert l1(cs_spec[i], cs_b1[i]) <= 1.3 * null + 0.1, (i, null)
        assert eng.kv.active_blocks() == 0


class TestBlockAccounting:
    def test_partial_acceptance_refcounts_drain(self, models):
        """Variable per-step advances (partial acceptance) must not skew
        the host block accounting: every refcount drains at retire."""
        eng = _spec_engine(models, "near")
        for p in PROMPTS:
            eng.generate(p, max_new_tokens=20)
            eng.generate(p, max_new_tokens=20, temperature=0.7, seed=1)
        assert eng.kv.active_blocks() == 0

    def test_cow_fork_shared_pool(self, models, plain):
        """Draft and target share the block tables under prefix reuse: a
        follow-up turn hits the retired chain, COW-forks the tail in BOTH
        pools, and still decodes token-identically."""
        eng = _spec_engine(models, "near")
        first = [3, 1, 4, 1, 5, 9, 2, 6]
        out1 = eng.generate(first, max_new_tokens=12)
        assert out1 == plain.generate(first, max_new_tokens=12)
        follow = first + out1[:5] + [7, 7]
        before = eng.kv.stats()["kv_hit_tokens"]
        out2 = eng.generate(follow, max_new_tokens=12)
        assert eng.kv.stats()["kv_hit_tokens"] > before  # the fork hit
        assert out2 == plain.generate(follow, max_new_tokens=12)
        assert eng.kv.active_blocks() == 0

    def test_draft_requires_config(self, models):
        cfg, params, drafts = models
        with pytest.raises(ValueError):
            LLMEngine(params, cfg, spec_tokens=2, **ENG_KW)
        with pytest.raises(ValueError):
            generate.PagedGenerator(params, cfg, slots=2, num_blocks=17,
                                    block_tokens=BT,
                                    draft_params=drafts["aligned"])


class TestLengthCapRegression:
    """Satellite: a slot at table capacity must finish as length_cap at
    the ENGINE layer before dispatch — and the forward itself may never
    silently overwrite the last cell when handed an at-capacity length."""

    def test_engine_finishes_length_cap(self, models, plain):
        eng = _spec_engine(models, "near")
        outs = {}
        for e in (eng, plain):
            outcome = {}
            toks = list(e.stream([5, 9, 3, 77, 21], max_new_tokens=500,
                                 result=outcome))
            assert outcome["finish_reason"] == "length_cap"
            # emitted never exceeds the table capacity minus the prompt
            assert len(toks) <= e.max_len - 5
            outs[e] = toks
        # Plain quantizes emission to chunk multiples while spec advances
        # by variable 1+accepted per scan step, so the exact stop point
        # near the cap differs — but the streams must agree token-for-token
        # on their common prefix, and spec may only ever get FURTHER.
        np, ns = len(outs[plain]), len(outs[eng])
        assert ns >= np
        assert outs[eng][:np] == outs[plain]
        assert eng.kv.active_blocks() == 0

    def test_at_capacity_write_redirects_to_trash(self, models):
        """Direct forward unit: lengths == table capacity redirects the
        scatter to trash block 0 instead of clamping onto the last cell
        (the pre-fix behavior corrupted position cap-1)."""
        cfg, params, _ = models
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
