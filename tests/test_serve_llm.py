"""Continuous-batching LLM serving tests: the engine's contract.

Engine-level: ``LLMEngine`` must be token-identical to the single-sequence
``Generator`` oracle under staggered concurrent arrivals, retire/refill
slots under load, shed with ``Saturated`` at the admission queue limit while
in-flight requests complete, keep decode-rate counters per-request, and pin
no KV block once it is idle. What needs no GPT-2 oracle (sampling
determinism, counters, cancellation, the edge cases) runs on both model
families through the same scheduler. Serve-level: the same engine behind
``llm_deployment`` through the full data plane (handle → router → replica),
plus KV-occupancy-aware routing units on the Router itself.
"""

import threading
import time

import jax
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.models import generate, longcat, transformer
from ray_tpu.serve.errors import Saturated
from ray_tpu.serve.handle import Router
from ray_tpu.serve.llm import LLMEngine, llm_deployment


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
def engines(tiny_model):
    """One shared slots=2 engine a model family, built when first asked
    for — tests drain it before finishing."""
    built = {}

    def get(family):
        if family not in built:
            if family == "gpt2":
                cfg, params = tiny_model
            else:
                cfg = longcat.tiny()
                params = longcat.init_params(cfg, jax.random.key(1))
            built[family] = LLMEngine(
                params, cfg, prompt_buckets=(16,), chunk=4, slots=2,
                max_queue=0, name=f"test-{family}")
            built[family].warmup()
        return built[family]

    return get


@pytest.fixture
def engine(request, engines):
    """GPT-2's engine, or the family a test names through ``both_families``."""
    return engines(getattr(request, "param", "gpt2"))


# The contract tests that compare with no GPT-2 oracle run once a family.
both_families = pytest.mark.parametrize("engine", ["gpt2", "longcat"],
                                        indirect=True)

PROMPTS = [[7, 3, 11], [2, 4, 6, 8, 10], [1] * 9, [5, 9] * 7]


def _drained(eng):
    """Idle: no slot busy, nothing queued, and no KV block still pinned."""
    s = eng.stats()
    return (s["slots_busy"] == 0 and s["queue_depth"] == 0
            and eng.kv.active_blocks() == 0)


class TestEngineEquivalence:
    def test_greedy_staggered_matches_single_sequence(self, engine, oracle):
        """Mixed-length prompts arriving staggered into 2 slots decode
        token-identically to the batch-1 oracle."""
        outs = [None] * len(PROMPTS)
        errs = []

        def client(i):
            try:
                time.sleep(i * 0.01)  # staggered arrivals
                outs[i] = engine.generate(PROMPTS[i], max_new_tokens=12)
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
        assert _drained(engine)

    def test_slot_retire_refill_under_load(self, engine, oracle):
        """3x more requests than slots: every slot retires and refills, all
        outputs stay oracle-equal, and the engine drains clean."""
        jobs = [(PROMPTS[i % len(PROMPTS)], 8 + (i % 3) * 4)
                for i in range(6)]
        outs = [None] * len(jobs)
        errs = []

        def client(i):
            try:
                outs[i] = engine.generate(jobs[i][0], max_new_tokens=jobs[i][1])
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(jobs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        for i, (p, n) in enumerate(jobs):
            assert outs[i] == oracle(p, n), f"request {i} diverged"
        assert _drained(engine)

    @both_families
    def test_sampled_deterministic_beside_greedy_traffic(self, engine):
        """A sampled request's tokens depend only on its seed — identical
        alone and batched beside concurrent greedy traffic."""
        alone = engine.generate(PROMPTS[0], max_new_tokens=12,
                                temperature=0.8, seed=123)
        outs = {}

        def greedy():
            outs["greedy"] = engine.generate(PROMPTS[1], max_new_tokens=12)

        def sampled():
            outs["sampled"] = engine.generate(PROMPTS[0], max_new_tokens=12,
                                              temperature=0.8, seed=123)

        threads = [threading.Thread(target=greedy),
                   threading.Thread(target=sampled)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert outs["sampled"] == alone
        assert _drained(engine)

    @both_families
    def test_per_request_decode_counters(self, engine):
        """decode_tps is per-request (the old engine-level counters raced);
        the aggregate under the lock sums every delivered token."""
        with engine._agg_lock:
            base = engine.decode_tokens
        results = [{}, {}]

        def client(i):
            list(engine.stream(PROMPTS[i], max_new_tokens=8,
                               result=results[i]))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for r in results:
            assert r["finish_reason"] == "stop"
            assert r["decode_tps"] > 0
        with engine._agg_lock:
            assert engine.decode_tokens == base + 16
        assert engine.decode_tokens_per_sec() > 0
        assert _drained(engine)

    @both_families
    def test_cancellation_frees_slot(self, engine, oracle):
        """Abandoning a stream mid-generation frees its slot and its blocks
        immediately for the next admission, which decodes what it would have
        decoded had the cancelled request never run."""
        after = engine.generate(PROMPTS[0], max_new_tokens=8)
        g = iter(engine.stream(PROMPTS[2], max_new_tokens=32))
        first = next(g)
        g.close()
        assert _drained(engine)
        assert engine.generate(PROMPTS[0], max_new_tokens=8) == after
        assert _drained(engine)
        if isinstance(engine.config, transformer.TransformerConfig):
            assert first == oracle(PROMPTS[2], 32)[0]
            assert after == oracle(PROMPTS[0], 8)

    @both_families
    def test_max_new_tokens_zero(self, engine):
        res = {}
        assert list(engine.stream(PROMPTS[0], max_new_tokens=0,
                                  result=res)) == []
        assert res["finish_reason"] == "stop"
        assert _drained(engine)

    @both_families
    def test_empty_prompt_raises(self, engine):
        with pytest.raises(ValueError, match="empty prompt"):
            engine.generate([], max_new_tokens=4)
        assert _drained(engine)


class TestAdmissionControl:
    def test_saturated_shed_while_inflight_completes(self, tiny_model, oracle):
        """slots=1, max_queue=1: one decoding + one queued fills the engine;
        the next submit sheds with ``Saturated`` and BOTH in-flight requests
        still complete oracle-equal. After they drain, submits succeed."""
        cfg, params = tiny_model
        eng = LLMEngine(params, cfg, prompt_buckets=(16,), chunk=4, slots=1,
                        max_queue=1, name="shed")
        eng.warmup()

        g1 = iter(eng.stream(PROMPTS[0], max_new_tokens=12))
        first = next(g1)  # drives a step: request 1 now holds the only slot
        g2 = iter(eng.stream(PROMPTS[1], max_new_tokens=8))  # queued

        with pytest.raises(Saturated):
            eng.generate(PROMPTS[2], max_new_tokens=4)

        assert [first] + list(g1) == oracle(PROMPTS[0], 12)
        assert list(g2) == oracle(PROMPTS[1], 8)
        assert _drained(eng)
        assert eng.generate(PROMPTS[2], max_new_tokens=4) == \
            oracle(PROMPTS[2], 4)
        assert _drained(eng)

    def test_a_burst_onto_idle_slots_is_not_shed(self, tiny_model):
        """The queue that ``max_queue`` bounds is what waits beyond the free
        slots: 4 idle slots and ``max_queue`` 2 hold a burst of 6 (four wait
        for the prefill budget, two for a slot), and shed the seventh."""
        cfg, params = tiny_model
        eng = LLMEngine(params, cfg, prompt_buckets=(16,), chunk=4, slots=4,
                        max_queue=2, name="burst")
        held = [eng.submit(PROMPTS[i % len(PROMPTS)], max_new_tokens=4)
                for i in range(6)]
        assert eng.stats()["queue_depth"] == 6.0
        assert eng.stats()["queue_limit"] == 2.0
        with pytest.raises(Saturated, match="waiting for a slot"):
            eng.submit(PROMPTS[0], max_new_tokens=4)
        for req in held:
            eng._cancel(req)
        assert _drained(eng)


@pytest.mark.parametrize("flag", ["serve_kv_paged_enabled",
                                  "serve_disaggregation_enabled"])
def test_engine_selection_flags_are_gone(flag):
    """One engine: the flags that chose between three are refused as
    unknown keys, not accepted and ignored."""
    from ray_tpu.core.config import Config

    with pytest.raises(ValueError, match="Unknown system_config keys"):
        Config({flag: 1})


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


class TestOccupancyRouting:
    def test_slots_exhausted(self):
        r = _mk_router([], {
            "full": {"slots_total": 4.0, "slots_busy": 4.0},
            "free": {"slots_total": 4.0, "slots_busy": 1.0},
            "plain": {"ongoing": 2.0},
        })
        assert r._slots_exhausted("full")
        assert not r._slots_exhausted("free")
        assert not r._slots_exhausted("plain")  # non-engine replica
        assert not r._slots_exhausted("unknown")

    def test_pick_prefers_free_slots(self):
        reps = [_StubReplica("full"), _StubReplica("free")]
        r = _mk_router(reps, {
            "full": {"slots_total": 2.0, "slots_busy": 2.0,
                     "queue_depth": 0.0},
            "free": {"slots_total": 2.0, "slots_busy": 0.0,
                     "queue_depth": 0.0},
        })
        for _ in range(10):
            best, key = r._pick()
            assert key == "free"
            r._dec(key)

    def test_all_shedding_requires_every_replica_over_limit(self):
        from ray_tpu.core.config import config

        limit = config().serve_admission_queue_limit
        assert limit > 0  # default knob enables shedding
        reps = [_StubReplica("a"), _StubReplica("b")]
        over = {"slots_total": 1.0, "slots_busy": 1.0,
                "queue_depth": float(limit)}
        under = dict(over, queue_depth=float(limit) - 1)
        assert _mk_router(reps, {"a": over, "b": over})._all_shedding(reps)
        assert not _mk_router(reps, {"a": over, "b": under})._all_shedding(reps)
        # A replica that doesn't report a queue (plain deployment) never sheds.
        assert not _mk_router(reps, {"a": over})._all_shedding(reps)
        assert not _mk_router(
            reps, {"a": over, "b": {"ongoing": 1.0}})._all_shedding(reps)

    def test_shedding_at_the_replicas_own_limit_beyond_its_free_slots(self):
        """A replica that reports ``queue_limit`` (its engine's ``max_queue``)
        is shed at that, not at the knob; what its free slots will take is
        not queue. 160 closed-loop clients on 128 slots with ``max_queue``
        64: 32 wait in steady state, up to 157 while the slots first fill."""
        from ray_tpu.core.config import config

        assert config().serve_admission_queue_limit == 32
        reps = [_StubReplica("a")]
        base = {"slots_total": 128.0, "queue_limit": 64.0}

        def shedding(**load):
            return _mk_router(reps, {"a": dict(base, **load)})._all_shedding(reps)

        assert not shedding(slots_busy=128.0, queue_depth=35.0)   # steady
        assert not shedding(slots_busy=3.0, queue_depth=157.0)    # filling
        assert shedding(slots_busy=128.0, queue_depth=64.0)
        assert shedding(slots_busy=120.0, queue_depth=72.0)
        assert not shedding(slots_busy=120.0, queue_depth=71.0)
        # queue_limit 0: the engine sheds nothing, so neither does the router.
        assert not shedding(slots_busy=128.0, queue_depth=500.0,
                            queue_limit=0.0)
        r = _mk_router(reps, {"a": dict(base, slots_busy=128.0,
                                        queue_depth=66.0)})
        assert r._retry_after_hint(reps) == pytest.approx(
            3 * config().serve_retry_after_item_s)

    def test_pick_sheds_when_all_over_limit(self):
        from ray_tpu.core.config import config

        limit = float(config().serve_admission_queue_limit)
        reps = [_StubReplica("a"), _StubReplica("b")]
        load = {"slots_total": 1.0, "slots_busy": 1.0, "queue_depth": limit}
        r = _mk_router(reps, {"a": load, "b": load})
        with pytest.raises(Saturated):
            r._pick()


@pytest.fixture
def serve_instance(ray_start_regular):
    yield serve
    serve.shutdown()


class TestServeDataPlane:
    def test_concurrent_streams_contract_and_occupancy(self, serve_instance,
                                                       tiny_model, oracle):
        """Concurrent streaming through handle → router → replica keeps the
        response contract and oracle-equal tokens; the replica's slot
        occupancy surfaces in the controller snapshot for routing."""
        cfg, _params = tiny_model
        LM = llm_deployment(
            cfg, lambda: transformer.init_params(cfg, jax.random.key(0)),
            name="LM", slots=2, chunk=4)
        handle = serve.run(LM.bind())

        outs = [None] * 3
        errs = []

        def client(i):
            try:
                toks = []
                last = None
                for item in handle.options(stream=True).remote(
                        {"prompt_ids": PROMPTS[i], "max_new_tokens": 8}):
                    assert {"token", "index", "decode_tps"} <= set(item)
                    assert item["index"] == len(toks)
                    toks.append(item["token"])
                    last = item
                assert last is not None
                assert last["finish_reason"] == "stop"
                outs[i] = toks
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        for i in range(3):
            assert outs[i] == oracle(PROMPTS[i], 8), f"stream {i} diverged"

        # KV-occupancy metrics reach the controller snapshot (poll: the
        # controller merges get_state once per poll period).
        from ray_tpu.serve.controller import get_or_create_controller

        controller = get_or_create_controller()
        deadline = time.monotonic() + 10
        load = {}
        while time.monotonic() < deadline:
            _v, table = ray_tpu.get(
                controller.get_snapshot.remote(-1, 0.0))
            load = table.get("LM", {}).get("replica_load", {})
            if load:
                break
            time.sleep(0.1)
        assert load, "replica_load never reached the controller snapshot"
        stats = next(iter(load.values()))
        assert stats["slots_total"] == 2.0
        assert stats["queue_depth"] == 0.0
        assert "slots_busy" in stats
