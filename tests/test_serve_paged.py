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

import threading
import time

import jax
import pytest

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
        # Each fork hit the 2-token tail -> one private COW copy apiece.
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

    def test_describe_names_what_was_resolved(self, paged):
        """The non-numeric twin of ``stats``: the kernel as resolved (never
        "auto"), the buckets warmup compiled, and where the state lives."""
        d = paged.describe()
        assert d["engine"] == "LLMEngine"
        assert d["attention_kernel"] in ("pallas", "interpret", "gather")
        assert d["warmed_buckets"] == [16, 32]
        assert d["pool_blocks"] == 129 and d["block_tokens"] == BT
        assert d["params_devices"] and d["kv_pool_devices"]
        cold = LLMEngine(paged.params, paged.config,
                         prompt_buckets=(16,), slots=1, max_queue=0,
                         name="paged-cold", block_tokens=BT,
                         pool_blocks=9)
        assert cold.describe()["warmed_buckets"] == []


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
