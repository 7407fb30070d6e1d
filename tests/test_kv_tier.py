"""Cluster-wide KV tier: prefix spill, directory, drain-by-migration
(ISSUE 17).

Directory-level: the ``ShardedPrefixDirectory`` is a bounded refcounted
cache — publisher refcounts gate removal, LRU capacity and TTL bound it,
every removal path reports through ``on_free`` exactly once, and
``dump``/``load`` round-trips entries across a shard-count change (GCS
restart). Tier-level: a chain spilled by one engine is fetched by another
(cluster-wide hit, token-identical to the single-sequence oracle), a cold
replica warms up from the store, and every publish drains to zero refs at
``close()`` (the suite's ``RAY_TPU_LEAK_CHECK_ENABLED=1`` teardown guard
covers the thread/fd half). Migration-level: a victim's chains travel a
``KVHandoffLane`` to a survivor and re-register as warm CACHED state with
``migrated`` hit attribution; the router REWRITES a drained replica's
affinity entries to the migration target. End-to-end: a mid-run scale-down
under active multi-turn sessions completes via drain-then-retire with zero
dropped streams and token-identical output.
"""

import threading
import time

import jax
import pytest

import ray_tpu
from ray_tpu.core.config import Config, set_config
from ray_tpu.core.gcs_shards import ShardedPrefixDirectory
from ray_tpu.models import generate, transformer
from ray_tpu.serve import kv_tier
from ray_tpu.serve.handle import Router
from ray_tpu.serve.llm import LLMEngine
from ray_tpu.util import blockhash

BT = 8  # test block size: small enough to exercise multi-block prompts


@pytest.fixture(scope="module", autouse=True)
def tier_enabled():
    """Flip the tier on for this module only; engines read the flag at
    construction, so every engine below is built inside this scope."""
    from ray_tpu.core.config import config as get_config

    prev = get_config()
    set_config(Config({"kv_tier_enabled": True,
                       "kv_tier_drain_timeout_s": 5.0}))
    yield
    set_config(prev)


@pytest.fixture(autouse=True)
def fresh_local_tier():
    kv_tier.reset_local_backend()
    yield
    kv_tier.reset_local_backend()


@pytest.fixture(scope="module")
def tiny_model():
    cfg = transformer.tiny(max_seq_len=64)
    params = transformer.init_params(cfg, jax.random.key(0))
    return cfg, params


@pytest.fixture(scope="module")
def oracle(tiny_model):
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


def _mk_engine(tiny_model, name):
    cfg, params = tiny_model
    eng = LLMEngine(params, cfg, prompt_buckets=(16, 32), chunk=4,
                         slots=2, max_queue=0, name=name, block_tokens=BT,
                         pool_blocks=129)
    eng.warmup()
    return eng


def _d(i):
    return bytes([i]) * 16


# -- directory units ----------------------------------------------------------


class TestPrefixDirectory:
    def test_publisher_refcounts_gate_removal(self):
        freed = []
        d = ShardedPrefixDirectory(4, on_free=lambda dg, e: freed.append(dg))
        assert d.publish(_d(1), b"obj", 16, 2) is True
        assert d.publish(_d(1), b"obj", 16, 2) is False  # second publisher
        assert d.release(_d(1)) is False  # one publisher still holds it
        assert freed == []
        assert d.match([_d(1)]) is not None
        assert d.release(_d(1)) is True
        assert freed == [_d(1)]  # on_free exactly once, at zero refs
        assert d.match([_d(1)]) is None

    def test_match_longest_first_and_counters(self):
        d = ShardedPrefixDirectory(4)
        d.publish(_d(1), b"a", 8, 1)
        d.publish(_d(2), b"b", 16, 2)
        j, entry = d.match([_d(1), _d(2), _d(9)])
        assert j == 1 and entry["meta"] == b"b"  # longest wins
        assert d.match([_d(9)]) is None
        st = d.stats()
        assert st["prefix_dir_hits"] == 1 and st["prefix_dir_misses"] == 1

    def test_lru_capacity_eviction(self):
        freed = []
        d = ShardedPrefixDirectory(1, max_entries=3,
                                   on_free=lambda dg, e: freed.append(dg))
        for i in range(1, 6):
            d.publish(_d(i), b"x", 8, 1)
        assert d.stats()["prefix_dir_entries"] == 3
        assert freed == [_d(1), _d(2)]  # oldest out first
        # A match MRU-touches: the touched entry survives the next insert.
        assert d.match([_d(3)]) is not None
        d.publish(_d(6), b"x", 8, 1)
        assert d.match([_d(3)]) is not None
        assert d.match([_d(4)]) is None  # LRU victim instead

    def test_ttl_expiry(self):
        freed = []
        d = ShardedPrefixDirectory(2, ttl_s=0.05,
                                   on_free=lambda dg, e: freed.append(dg))
        d.publish(_d(1), b"x", 8, 1)
        assert d.match([_d(1)]) is not None
        time.sleep(0.08)
        assert d.match([_d(1)]) is None  # expired on the read path
        assert freed == [_d(1)]
        st = d.stats()
        assert st["prefix_dir_expired"] == 1

    def test_drop_is_unconditional(self):
        d = ShardedPrefixDirectory(2)
        d.publish(_d(1), b"x", 8, 1)
        d.publish(_d(1), b"x", 8, 1)  # refs = 2
        assert d.drop(_d(1)) is True  # fetch-miss self-heal ignores refs
        assert d.match([_d(1)]) is None

    def test_dump_load_across_shard_counts(self):
        d = ShardedPrefixDirectory(2, max_entries=8)
        for i in range(1, 5):
            d.publish(_d(i), b"m%d" % i, 8 * i, i)
        data = d.dump()
        d2 = ShardedPrefixDirectory(3, max_entries=8)  # GCS restart, resharded
        d2.load(data)
        assert d2.stats()["prefix_dir_entries"] == 4
        for i in range(1, 5):
            j, entry = d2.match([_d(i)])
            assert entry["meta"] == b"m%d" % i
            assert entry["tokens"] == 8 * i

    def test_load_preserves_lru_order(self):
        d = ShardedPrefixDirectory(1, max_entries=4)
        for i in range(1, 4):
            d.publish(_d(i), b"x", 8, 1)
            time.sleep(0.002)  # distinct wall-clock stamps
        d2 = ShardedPrefixDirectory(1, max_entries=4)
        d2.load(d.dump())
        d2.publish(_d(7), b"x", 8, 1)
        d2.publish(_d(8), b"x", 8, 1)  # over cap: evicts the OLDEST restored
        assert d2.match([_d(1)]) is None
        assert d2.match([_d(3)]) is not None


# -- tier client (local backend) ----------------------------------------------


class TestKVTierClient:
    def test_prefix_aliases_match_shorter_probe(self):
        t = kv_tier.KVTier("t")
        payload = {"k": None, "v": None, "tokens": list(range(24))}
        assert t.publish_chain([_d(1), _d(2), _d(3)], payload, 24, 3)
        # A probe covering only the first block still matches (alias entry).
        j, entry = t.match([_d(1)])
        assert j == 0 and entry["blocks"] == 1 and entry["tokens"] == 8
        j, entry = t.match([_d(1), _d(2)])
        assert j == 1 and entry["blocks"] == 2
        t.close()

    def test_fetch_miss_drops_entry(self):
        t = kv_tier.KVTier("t")
        t.publish_chain([_d(1)], {"k": None}, 8, 1)
        backend = t._resolve()
        with backend._lock:  # payload lost behind the directory's back
            backend._payloads.clear()
        m = t.match([_d(1)])
        assert m is not None
        assert t.fetch(_d(1), m[1]) is None
        assert t.match([_d(1)]) is None  # self-heal: entry dropped
        t.close()

    def test_close_drains_refs_to_zero(self):
        a = kv_tier.KVTier("a")
        b = kv_tier.KVTier("b")
        a.publish_chain([_d(1), _d(2)], {"k": None}, 16, 2)
        b.publish_chain([_d(1), _d(2)], {"k": None}, 16, 2)  # second pub
        a.close()
        st = a.stats()
        assert st["prefix_dir_entries"] == 2  # b still publishes them
        b.close()
        st = b.stats()
        assert st["prefix_dir_entries"] == 0
        assert st["prefix_dir_refs"] == 0
        assert st["prefix_dir_payloads"] == 0


# -- cluster-wide hits (bare engines, shared local tier) ----------------------


class TestClusterWideHit:
    def test_second_engine_fetches_from_store(self, tiny_model, oracle):
        """A computes and spills; B — which never saw the prompt — pulls
        the prefix from the store instead of recomputing, token-identical."""
        a = _mk_engine(tiny_model, "tier-a")
        b = _mk_engine(tiny_model, "tier-b")
        try:
            prompt = [5, 9] * 8  # 2 full blocks
            out_a = a.generate(list(prompt), max_new_tokens=8)
            assert a.stats()["kv_tier_spilled_blocks"] >= 2
            out_b = b.generate(list(prompt), max_new_tokens=8)
            assert out_a == out_b == oracle(prompt, 8)
            st = b.stats()
            assert st["kv_tier_hits_store"] >= BT  # >= one fetched block
            assert st["kv_tier_hits_local"] == 0
        finally:
            a.close()
            b.close()
        assert kv_tier._local_backend().stats()["prefix_dir_refs"] == 0

    def test_multi_turn_extension_hits_full_chain(self, tiny_model, oracle):
        """Turn 2 (= turn-1 prompt + output + new text) on a DIFFERENT
        engine covers A's whole spilled chain — the cluster-wide multi-turn
        path that makes replica death lossless."""
        a = _mk_engine(tiny_model, "tier-a2")
        b = _mk_engine(tiny_model, "tier-b2")
        try:
            p1 = [5, 9] * 8
            out1 = a.generate(list(p1), max_new_tokens=8)
            p2 = list(p1) + out1 + [3, 3]  # 26 tokens: 3 full blocks spilled
            out2 = b.generate(list(p2), max_new_tokens=4)
            assert out2 == oracle(p2, 4)
            assert b.stats()["kv_tier_hits_store"] >= 3 * BT
        finally:
            a.close()
            b.close()

    def test_cold_replica_warmup_vs_fresh_prefill(self, tiny_model, oracle):
        """A cold engine's first request over a spilled chain prefills ONLY
        the uncovered suffix — its engine-reported hit length equals the
        store hit, where a fresh engine with no tier hits nothing."""
        a = _mk_engine(tiny_model, "tier-a3")
        prompt = [7, 2] * 10  # 20 tokens: 2 full blocks
        out = a.generate(list(prompt), max_new_tokens=8)
        cold = _mk_engine(tiny_model, "tier-cold")
        try:
            out_cold = cold.generate(list(prompt), max_new_tokens=8)
            assert out_cold == out == oracle(prompt, 8)
            st = cold.stats()
            # Both probe-able full blocks came from the store — the cold
            # engine prefilled ONLY the uncovered suffix (its LOCAL lookup
            # saw nothing: kv.hit_tokens counts local hits only).
            assert st["kv_tier_hits_store"] == 2 * BT
            assert cold.kv.stats()["kv_hit_tokens"] == 0
        finally:
            a.close()
            cold.close()

    def test_flag_off_restores_private_kv(self, tiny_model):
        """kv_tier_enabled=0: no tier object, no directory traffic — the
        engine is byte-identical to the pre-tier LLMEngine."""
        from ray_tpu.core.config import config as get_config

        prev = get_config()
        set_config(Config({"kv_tier_enabled": False}))
        try:
            a = _mk_engine(tiny_model, "off-a")
            b = _mk_engine(tiny_model, "off-b")
            assert a._tier is None and b._tier is None
            prompt = [5, 9] * 8
            a.generate(list(prompt), max_new_tokens=8)
            b.generate(list(prompt), max_new_tokens=8)
            assert "kv_tier_spilled_blocks" not in a.stats()
            st = kv_tier._local_backend().stats()
            assert st["prefix_dir_published"] == 0
            a.close()
            b.close()
        finally:
            set_config(prev)


# -- drain migration ----------------------------------------------------------


class TestDrainMigration:
    def test_chains_migrate_over_lane(self, tiny_model, oracle):
        """Victim's tracked chains travel the handoff lane to the survivor,
        re-register as CACHED state, and attribute follow-up hits to
        ``migrated``; streams stay token-identical."""
        victim = _mk_engine(tiny_model, "mig-victim")
        survivor = _mk_engine(tiny_model, "mig-survivor")
        try:
            p1 = [5, 9] * 8
            out1 = victim.generate(list(p1), max_new_tokens=8)
            got = {}
            th = threading.Thread(
                target=lambda: got.setdefault(
                    "n", survivor.kv_migrate_in("kvtest-mig-1")))
            th.start()
            sent = victim.kv_migrate_out("kvtest-mig-1")
            th.join()
            assert sent >= 1 and got["n"] >= 1
            # Imported chains are pure cache (no pinned blocks).
            assert survivor.kv.stats()["kv_blocks_active"] == 0
            p2 = list(p1) + out1 + [3, 3]
            out2 = survivor.generate(list(p2), max_new_tokens=4)
            assert out2 == oracle(p2, 4)
            st = survivor.stats()
            assert st["kv_tier_hits_migrated"] >= 3 * BT
            assert st["kv_tier_hits_store"] == 0  # lane beat the store
        finally:
            victim.close()
            survivor.close()

    def test_migrate_out_without_survivor_lane_times_out(self, tiny_model):
        from ray_tpu.core.config import config as get_config

        prev = get_config()
        set_config(Config({"kv_tier_enabled": True,
                           "kv_tier_drain_timeout_s": 0.2}))
        try:
            victim = _mk_engine(tiny_model, "mig-lonely")
            victim.generate([5, 9] * 8, max_new_tokens=8)
            assert victim.kv_migrate_out("kvtest-nobody-home") == 0
            victim.close()
        finally:
            set_config(prev)


# -- router affinity rewrite --------------------------------------------------


class TestAffinityRewrite:
    def _router(self, aff):
        r = Router.__new__(Router)
        r.__dict__["_affinity"] = dict(aff)
        return r

    def test_drained_replica_entries_rewritten_to_target(self):
        r = self._router({b"h1": "victim", b"h2": "live-b", b"h3": "gone"})
        r._sweep_affinity_locked(
            live={"live-a", "live-b"},
            migrations={"victim": "live-a"})
        assert r._affinity_map() == {b"h1": "live-a", b"h2": "live-b"}

    def test_chain_following_and_cycle_safety(self):
        r = self._router({b"h1": "v1", b"h2": "v3"})
        r._sweep_affinity_locked(
            live={"live"},
            migrations={"v1": "v2", "v2": "live", "v3": "v4", "v4": "v3"})
        # v1 -> v2 -> live resolves; the v3 <-> v4 cycle sweeps.
        assert r._affinity_map() == {b"h1": "live"}


# -- GCS-backed directory (runtime backend) -----------------------------------


class TestRuntimeBackend:
    def test_snapshot_roundtrip_and_stale_self_heal(self, ray_start_regular):
        """Directory state rides kv_dump/kv_load; a restored entry whose
        payload is gone drops on first fetch — no dangling object ids."""
        import numpy as np

        from ray_tpu.core.runtime import get_runtime

        t = kv_tier.KVTier("rt")
        payload = {"k": np.ones((2, 1, BT, 4, 16), np.float32),
                   "v": np.ones((2, 1, BT, 4, 16), np.float32),
                   "tokens": list(range(BT))}
        assert t.publish_chain([_d(1)], payload, BT, 1)
        rt = get_runtime()
        assert not isinstance(t._resolve(), kv_tier._LocalBackend)
        m = rt.gcs.prefix_match([_d(1)])
        assert m is not None
        assert t.fetch(_d(1), m[1])["k"].shape[1] == 1
        dump = rt.gcs.kv_dump()
        # Restart-over-snapshot: the publisher dies (pin + entry go), THEN
        # the directory restores from the stale snapshot — its locator now
        # points at a freed object.
        t.close()
        assert rt.gcs.prefix_stats()["prefix_dir_entries"] == 0
        rt.gcs.kv_load(dump)
        assert rt.gcs.prefix_stats()["prefix_dir_entries"] == 1
        m = rt.gcs.prefix_match([_d(1)])
        assert m is not None
        assert t.fetch(_d(1), m[1]) is None  # object gone
        # The failed fetch dropped the entry (self-heal): no dangling
        # object ids survive a GCS restart over a stale snapshot.
        assert rt.gcs.prefix_stats()["prefix_dir_entries"] == 0


# -- end-to-end: scale-down under active sessions -----------------------------


@pytest.fixture
def serve_instance(ray_start_regular):
    from ray_tpu import serve

    yield serve
    serve.shutdown()


class TestScaleDownE2E:
    def test_multi_turn_sessions_survive_forced_scale_down(
            self, serve_instance, tiny_model, oracle):
        """2 replicas -> 1 mid-run: the victim drains (in-flight streams
        finish), migrates its chains to the survivor, and retires; every
        session's turn 2 is token-identical to the no-drain tokens, zero
        streams drop, and the controller publishes the migration rewrite."""
        from ray_tpu.serve.controller import get_or_create_controller
        from ray_tpu.serve.llm import llm_deployment

        serve = serve_instance
        cfg, _params = tiny_model
        # ray_tpu.init (the ray_start_regular fixture) RESET the global
        # config from its system_config — re-apply the tier knobs before
        # any replica or controller reads them.
        set_config(Config({"kv_tier_enabled": True,
                           "kv_tier_drain_timeout_s": 5.0}))
        LM = llm_deployment(
            cfg, lambda: transformer.init_params(cfg, jax.random.key(0)),
            name="LM", slots=4, chunk=4, num_replicas=2)
        handle = serve.run(LM.bind())
        controller = get_or_create_controller()

        sessions = [[11 + i, 3 + i] * 9 for i in range(6)]  # 18 tokens
        turn1 = [None] * len(sessions)
        errs = []

        def run_turn(i, prompt, out):
            try:
                toks = []
                for item in handle.options(stream=True).remote(
                        {"prompt_ids": prompt, "max_new_tokens": 8}):
                    toks.append(item["token"])
                    if "finish_reason" in item:
                        assert item["finish_reason"] == "stop"
                out[i] = toks
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=run_turn,
                                    args=(i, sessions[i], turn1))
                   for i in range(len(sessions))]
        for t in threads:
            t.start()
        # Mid-run scale-down: streams are in flight RIGHT NOW.
        time.sleep(0.3)
        assert ray_tpu.get(
            controller.set_target_replicas.remote("LM", 1), timeout=10)
        for t in threads:
            t.join()
        assert not errs, errs
        for i, prompt in enumerate(sessions):
            assert turn1[i] == oracle(prompt, 8), \
                f"turn-1 stream {i} diverged across the scale-down"

        # The drain must resolve: one routed replica + a migration
        # rewrite in the snapshot.
        deadline = time.monotonic() + 30
        migrations, reps = {}, []
        while time.monotonic() < deadline:
            _v, table = ray_tpu.get(
                controller.get_snapshot.remote(-1, 0.0))
            entry = table.get("LM", {})
            migrations = entry.get("migrations", {})
            reps = entry.get("replicas", [])
            if len(reps) == 1 and migrations:
                break
            time.sleep(0.2)
        assert len(reps) == 1, "scale-down never converged"
        assert migrations, "drain-then-retire published no migration"

        # Turn 2 extends every session's chain — served by the
        # survivor, token-identical to a run that never scaled.
        turn2 = [None] * len(sessions)
        threads = []
        for i, prompt in enumerate(sessions):
            p2 = list(prompt) + turn1[i] + [2, 4]
            threads.append(threading.Thread(
                target=run_turn, args=(i, p2, turn2)))
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs, errs
        hits_migrated = 0.0
        for i, prompt in enumerate(sessions):
            p2 = list(prompt) + turn1[i] + [2, 4]
            assert turn2[i] == oracle(p2, 8), \
                f"turn-2 stream {i} diverged after drain"
        # The victim's sessions now hit as `migrated` on the survivor.
        _v, table = ray_tpu.get(controller.get_snapshot.remote(-1, 0.0))
        for m in table["LM"]["replica_load"].values():
            hits_migrated += float(m.get("kv_tier_hits_migrated") or 0)
        deadline = time.monotonic() + 10
        while hits_migrated == 0 and time.monotonic() < deadline:
            time.sleep(0.3)  # load poll lags by a poll period
            _v, table = ray_tpu.get(
                controller.get_snapshot.remote(-1, 0.0))
            for m in table["LM"]["replica_load"].values():
                hits_migrated += float(
                    m.get("kv_tier_hits_migrated") or 0)
        assert hits_migrated > 0, \
            "no migrated-source hits: drain shipped no usable chains"
