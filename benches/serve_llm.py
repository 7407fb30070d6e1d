"""Continuous-batching LLM serving benchmark (ISSUE 9 + ISSUE 11 metrics).

Round 1 (ISSUE 9): A/B of the slotted continuous-batching ``LLMEngine``
against the same engine pinned to one slot (the batch-1 replica baseline it
replaced): aggregate tokens/s and client-observed p50/p99 TTFT at
concurrency 1/4/16 on the same box. Clients are threads issuing sequential
streaming ``generate`` calls — the same call pattern a Serve replica sees
from its actor threads — so the numbers include scheduler + admission
overhead, not just device time.

Round 2 (ISSUE 11): paged-vs-slotted A/B AT EQUAL SLOTS under prefix-heavy
traffic — the workload paged KV + prefix reuse targets:

- ``shared_prefix``: every request = one fixed system prefix (half the
  context) + a short unique user suffix. The paged engine prefills the
  prefix once and serves the rest from cache.
- ``multiturn``: each client runs N-turn conversations whose prompt is the
  full prior history; the paged engine re-prefills only the newest turn.

Paged rows record the measured cache hit rate; the headline metrics are
``speedup_tokens_vs_slotted`` and ``ttft_p50_speedup_vs_slotted``.

Round 4 (ISSUE 17): cluster-wide KV tier A/B on two engines sharing one
tier — cross-replica hit rate with the tier on vs the per-replica
baseline, cold-engine first-request TTFT from the store vs recompute for
a ≥4-block chain, and a mid-run drain migration (victim → survivor over
the KV handoff lane) with token-identical post-drain streams.

``--quick`` is the serve smoke path: a short A/B, a paged-engine COW-fork
smoke, a KV-tier spill/fetch/migrate round trip, and a deploy through
``llm_deployment`` streaming concurrent requests over the full data plane
(handle → pow-2 router → replica).

Usage:: python benches/serve_llm.py [--quick] [--round 2]
"""

from __future__ import annotations

import argparse
import json
import os
import threading
import time
from typing import List

import numpy as np

PROMPT_LEN = 8
NEW_TOKENS = 48  # prompt bucket 16 + 48 decode == tiny max_seq_len 64


def _prompt(client: int, rep: int) -> List[int]:
    return [(client * 31 + rep * 7 + j) % 250 + 1 for j in range(PROMPT_LEN)]


def bench_engine(eng, concurrency: int, reps: int) -> dict:
    """Drive one engine with ``concurrency`` client threads, each streaming
    ``reps`` sequential requests; returns aggregate tokens/s + TTFT tails."""
    ttfts: List[float] = []
    counts = [0] * concurrency
    errors: List[BaseException] = []
    lock = threading.Lock()

    def client(i: int) -> None:
        try:
            for r in range(reps):
                t0 = time.perf_counter()
                first = None
                for _tok in eng.stream(_prompt(i, r),
                                       max_new_tokens=NEW_TOKENS):
                    if first is None:
                        first = time.perf_counter() - t0
                    counts[i] += 1
                with lock:
                    ttfts.append(first)
        except BaseException as e:  # noqa: BLE001 — surfaced below
            with lock:
                errors.append(e)

    threads = [threading.Thread(target=client, args=(i,), name=f"cli-{i}")
               for i in range(concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    return {
        "requests": concurrency * reps,
        "tokens": sum(counts),
        "tokens_per_s": round(sum(counts) / wall, 1),
        "ttft_ms_p50": round(float(np.percentile(ttfts, 50)) * 1e3, 2),
        "ttft_ms_p99": round(float(np.percentile(ttfts, 99)) * 1e3, 2),
    }


def bench_modes(concurrencies, reps: int, slots: int, chunk: int) -> List[dict]:
    import jax

    from ray_tpu.models import transformer
    from ray_tpu.serve.llm import LLMEngine

    on_tpu = any(d.platform == "tpu" for d in jax.devices())
    cfg = (transformer.gpt2_small(max_seq_len=256) if on_tpu
           else transformer.tiny(max_seq_len=64))
    params = transformer.init_params(cfg, jax.random.key(0))

    results = []
    # max_queue=0: no admission shedding — the A/B measures throughput of
    # admitted work, and the baseline must accept the same request count.
    engines = {
        "batch1": LLMEngine(params, cfg, chunk=chunk, slots=1,
                            max_queue=0, name="bench-b1"),
        "continuous": LLMEngine(params, cfg, chunk=chunk, slots=slots,
                                max_queue=0, name="bench-cb"),
    }
    for eng in engines.values():
        eng.warmup()
    base_tps = {}
    for conc in concurrencies:
        for mode, eng in engines.items():
            row = {
                "metric": "serve_llm",
                "mode": mode,
                "slots": eng.slots,
                "chunk": chunk,
                "concurrency": conc,
                "new_tokens": NEW_TOKENS,
                **bench_engine(eng, conc, reps),
                "platform": "tpu" if on_tpu else "cpu",
            }
            if mode == "batch1":
                base_tps[conc] = row["tokens_per_s"]
            else:
                row["speedup_vs_batch1"] = round(
                    row["tokens_per_s"] / base_tps[conc], 2)
            print(json.dumps(row), flush=True)
            results.append(row)
    return results


def _model(mid: bool = False):
    import jax

    from ray_tpu.models import transformer

    on_tpu = any(d.platform == "tpu" for d in jax.devices())
    if on_tpu:
        cfg = transformer.gpt2_small(max_seq_len=256)
    elif mid:
        # Prefix-reuse A/B needs prefill COMPUTE to dominate dispatch
        # overhead, or cached-prefix savings vanish into scheduler noise —
        # a mid-size config keeps CPU runs honest and fast enough.
        cfg = transformer.tiny(d_model=256, n_layers=4, n_heads=8,
                               d_ff=1024, max_seq_len=128)
    else:
        cfg = transformer.tiny(max_seq_len=64)
    return cfg, transformer.init_params(cfg, jax.random.key(0)), on_tpu


def bench_traffic(eng, traffic: str, concurrency: int, reps: int,
                  max_len: int) -> dict:
    """Prefix-heavy traffic generator: ``shared_prefix`` requests reuse one
    system prefix; ``multiturn`` conversations resend their full history
    each turn. Token ids stay within the tiny vocab (256)."""
    prefix = [(j * 13 + 5) % 250 + 1 for j in range(max_len // 2)]
    user_len = max(2, max_len // 16)
    turn_new = user_len + 2
    turns = 3
    ttfts: List[float] = []
    counts = [0] * concurrency
    errors: List[BaseException] = []
    lock = threading.Lock()

    def one(i: int, prompt: List[int], n: int) -> List[int]:
        t0 = time.perf_counter()
        first = None
        out = []
        for tok in eng.stream(prompt, max_new_tokens=n):
            if first is None:
                first = time.perf_counter() - t0
            out.append(tok)
            counts[i] += 1
        with lock:
            ttfts.append(first)
        return out

    def client(i: int) -> None:
        try:
            if traffic == "shared_prefix":
                sfx_len = max(2, max_len // 8)
                for r in range(reps):
                    sfx = [(i * 37 + r * 11 + j) % 250 + 1
                           for j in range(sfx_len)]
                    one(i, prefix + sfx, max_len // 8 + 4)
            else:  # multiturn
                short_prefix = prefix[:max_len // 4]
                for r in range(reps):
                    history = list(short_prefix)
                    for turn in range(turns):
                        history += [(i * 41 + r * 17 + turn * 5 + j) % 250 + 1
                                    for j in range(user_len)]
                        history += one(i, history, turn_new)
        except BaseException as e:  # noqa: BLE001 — surfaced below
            with lock:
                errors.append(e)

    threads = [threading.Thread(target=client, args=(i,), name=f"cli-{i}")
               for i in range(concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    return {
        "requests": len(ttfts),
        "tokens": sum(counts),
        "tokens_per_s": round(sum(counts) / wall, 1),
        "ttft_ms_p50": round(float(np.percentile(ttfts, 50)) * 1e3, 2),
        "ttft_ms_p99": round(float(np.percentile(ttfts, 99)) * 1e3, 2),
    }


def bench_prefix_modes(concurrencies, reps: int, slots: int,
                       chunk: int) -> List[dict]:
    """ISSUE 11 A/B: paged (prefix cache + COW) vs slotted at EQUAL slots
    under shared-prefix and multi-turn traffic."""
    from ray_tpu.serve.llm import LLMEngine, PagedLLMEngine

    cfg, params, on_tpu = _model(mid=True)
    engines = {
        "slotted": LLMEngine(params, cfg, chunk=chunk, slots=slots,
                             max_queue=0, name="bench-slotted"),
        "paged": PagedLLMEngine(params, cfg, chunk=chunk, slots=slots,
                                max_queue=0, name="bench-paged"),
    }
    for eng in engines.values():
        eng.warmup()
    results = []
    for conc in concurrencies:
        for traffic in ("shared_prefix", "multiturn"):
            base = {}
            for mode, eng in engines.items():
                kv0 = eng.kv.stats() if mode == "paged" else None
                row = {
                    "metric": "serve_llm_prefix",
                    "mode": mode,
                    "traffic": traffic,
                    "slots": slots,
                    "chunk": chunk,
                    "concurrency": conc,
                    **bench_traffic(eng, traffic, conc, reps,
                                    cfg.max_seq_len),
                    "platform": "tpu" if on_tpu else "cpu",
                }
                if mode == "slotted":
                    base = row
                else:
                    kv1 = eng.kv.stats()
                    hit = kv1["kv_hit_tokens"] - kv0["kv_hit_tokens"]
                    miss = kv1["kv_miss_tokens"] - kv0["kv_miss_tokens"]
                    row["kv_hit_rate"] = round(hit / max(1.0, hit + miss), 3)
                    row["kv_cow_copies"] = kv1["kv_cow_copies"]
                    row["speedup_tokens_vs_slotted"] = round(
                        row["tokens_per_s"] / base["tokens_per_s"], 2)
                    row["ttft_p50_speedup_vs_slotted"] = round(
                        base["ttft_ms_p50"] / row["ttft_ms_p50"], 2)
                print(json.dumps(row), flush=True)
                results.append(row)
    return results


def _tpot_traffic(eng, concurrency: int, reps: int, new_tokens: int) -> dict:
    """Decode-heavy traffic: short prompts, long generations; returns
    tokens/s plus per-request TPOT (decode seconds / decode token)."""
    tpots: List[float] = []
    counts = [0] * concurrency
    errors: List[BaseException] = []
    lock = threading.Lock()

    def client(i: int) -> None:
        try:
            for r in range(reps):
                res: dict = {}
                for _tok in eng.stream(_prompt(i, r), max_new_tokens=new_tokens,
                                       result=res):
                    counts[i] += 1
                with lock:
                    if res.get("decode_tps"):
                        tpots.append(1e3 / res["decode_tps"])
        except BaseException as e:  # noqa: BLE001 — surfaced below
            with lock:
                errors.append(e)

    threads = [threading.Thread(target=client, args=(i,), name=f"cli-{i}")
               for i in range(concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    return {
        "requests": concurrency * reps,
        "tokens": sum(counts),
        "tokens_per_s": round(sum(counts) / wall, 1),
        "tpot_ms_p50": round(float(np.percentile(tpots, 50)), 2),
        "tpot_ms_p99": round(float(np.percentile(tpots, 99)), 2),
    }


def bench_spec_modes(concurrency: int, reps: int, chunk: int,
                     slots: int = 4) -> List[dict]:
    """ISSUE 16 round 3: speculative decoding A/B on the paged engine,
    decode-heavy traffic, equal quality (greedy spec is token-identical
    to the baseline by construction — asserted below, not assumed).

    The aligned-family rows share ONE target model: the mid config with
    layers 1..3's residual output projections zeroed, so the whole stack
    computes exactly what its layer-0 slice computes while still paying
    4 layers of FLOPs — the draft (that 1-layer slice, sharing embeddings)
    then proposes what the target would have said, pinning acceptance at
    ~1.0. That isolates the SCHEDULING win (tokens per verify dispatch)
    from draft quality, which is model-dependent. The misaligned row uses
    a random 1-layer draft against the REAL 4-layer target to show the
    acceptance-EWMA gate demoting a useless draft back to ~baseline
    throughput instead of melting down.
    """
    import jax

    from ray_tpu.models import transformer
    from ray_tpu.serve.llm import PagedLLMEngine

    cfg, params, on_tpu = _model(mid=True)
    # Unscaled random inits collapse greedy decode onto a repeat-last-token
    # attractor, which would make ANY two models "agree" and fake high
    # acceptance; 3x scaling breaks the attractor so agreement is earned.
    params = jax.tree.map(lambda p: p * 3.0, params)
    draft_cfg = transformer.tiny(d_model=cfg.d_model, n_layers=1,
                                 n_heads=cfg.n_heads, d_ff=cfg.d_ff,
                                 max_seq_len=cfg.max_seq_len)

    def slice_draft(p):
        return {**{k: v for k, v in p.items() if k != "blocks"},
                "blocks": jax.tree.map(lambda a: a[:1], p["blocks"])}

    def zero_tail_layers(p):
        def z(path_key, a):
            if path_key in ("wo", "bo", "w_down", "b_down"):
                return a.at[1:].set(0.0)
            return a
        return {**p, "blocks": {k: z(k, v) for k, v in p["blocks"].items()}}

    aligned_target = zero_tail_layers(params)
    aligned_draft = slice_draft(aligned_target)
    random_draft = slice_draft(jax.tree.map(
        lambda p: p * 3.0, transformer.init_params(cfg, jax.random.key(99))))

    kw = dict(chunk=chunk, slots=slots, max_queue=0)
    results = []

    def decode_len(k: int) -> int:
        """Largest request length that (a) divides evenly into whole
        dispatches — a partially-used last dispatch still pays for the
        full ``chunk*(k+1)`` verify and would bill phantom compute to
        TPOT — and (b) keeps the spec headroom gate open to the end."""
        per = chunk * (k + 1)
        cap = cfg.max_seq_len - PROMPT_LEN - per
        return min(88, cap) // per * per

    def run(mode, target, extra_kw, base_row=None, **tags):
        k = extra_kw.get("spec_tokens", 0)
        new_tokens = decode_len(k)
        eng = PagedLLMEngine(target, cfg, name=f"bench-{mode}", **kw,
                             **extra_kw)
        eng.warmup()
        row = {
            "metric": "serve_llm_spec", "mode": mode, "slots": slots,
            "chunk": chunk, "concurrency": concurrency,
            "new_tokens": new_tokens, **tags,
            **_tpot_traffic(eng, concurrency, reps, new_tokens),
            "platform": "tpu" if on_tpu else "cpu",
        }
        if k:
            st = eng.stats()
            row["spec_accept_ratio"] = round(st["spec_accept_ratio"], 3)
        if base_row is not None:
            row["tpot_speedup_vs_baseline"] = round(
                base_row["tpot_ms_p50"] / row["tpot_ms_p50"], 2)
            row["tokens_speedup_vs_baseline"] = round(
                row["tokens_per_s"] / base_row["tokens_per_s"], 2)
            # Equal quality is an assertion, not a caption: greedy spec
            # must reproduce the baseline engine's tokens exactly.
            base_eng = PagedLLMEngine(target, cfg, name=f"chk-{mode}", **kw)
            a = base_eng.generate(_prompt(0, 0), max_new_tokens=new_tokens)
            b = eng.generate(_prompt(0, 0), max_new_tokens=new_tokens)
            assert a == b, f"{mode}: spec diverged from baseline"
            row["quality"] = "token_identical_greedy"
        print(json.dumps(row), flush=True)
        results.append(row)
        return row

    base = run("pr11_baseline", aligned_target, {})
    run("spec_off_draft_loaded", aligned_target,
        dict(draft_params=aligned_draft, draft_config=draft_cfg,
             spec_tokens=0), base)
    for k in (2, 4, 8):
        run(f"spec_on_k{k}", aligned_target,
            dict(draft_params=aligned_draft, draft_config=draft_cfg,
                 spec_tokens=k), base, draft_aligned=True, draft_layers=1)
    real_base = run("baseline_real_target", params, {})
    run("spec_misaligned_k4", params,
        dict(draft_params=random_draft, draft_config=draft_cfg,
             spec_tokens=4), real_base, draft_aligned=False, draft_layers=1)
    return results


def smoke_paged_cow() -> dict:
    """Quick smoke: the paged engine serves a conversation, then two COW
    forks of its retired tail decode independently."""
    from ray_tpu.serve.llm import PagedLLMEngine

    cfg, params, _on_tpu = _model()
    eng = PagedLLMEngine(params, cfg, chunk=4, slots=2, max_queue=0,
                         name="smoke-paged")
    eng.warmup()
    base = [(7 * j + 3) % 250 + 1 for j in range(12)]
    chain = base + eng.generate(base, max_new_tokens=6)
    forks = [eng.generate(chain + [50 + i, 51, 52], max_new_tokens=6)
             for i in range(2)]
    st = eng.kv.stats()
    assert st["kv_hit_tokens"] > 0, "forks missed the retired chain"
    assert st["kv_cow_copies"] >= 1, "no COW copy on tail fork"
    assert eng.kv.active_blocks() == 0, "blocks leaked after retire"
    assert forks[0] != forks[1] or forks[0], "fork outputs empty"
    row = {
        "metric": "serve_llm_paged_cow_smoke",
        "kv_hit_tokens": st["kv_hit_tokens"],
        "kv_cow_copies": st["kv_cow_copies"],
        "ok": True,
    }
    print(json.dumps(row), flush=True)
    return row


def smoke_jit_warmup() -> dict:
    """Quick smoke: jitcheck-instrumented warmup — report how many XLA
    compilations warmup pays and their wall seconds, then assert a warmed
    mixed burst compiles NOTHING (the steady-state contract the TPOT
    numbers above rest on)."""
    import jax

    from ray_tpu.devtools import jitcheck
    from ray_tpu.serve.llm import PagedLLMEngine

    was = jitcheck.installed()
    if not was:
        jitcheck.install()
    try:
        cfg, params, _on_tpu = _model()
        t0 = time.perf_counter()
        n0, s0 = jitcheck.total_compiles(), jitcheck.total_compile_seconds()
        eng = PagedLLMEngine(params, cfg, chunk=4, slots=2, max_queue=0,
                             name="smoke-jit")
        eng.warmup()
        warm_s = time.perf_counter() - t0
        warm_compiles = jitcheck.total_compiles() - n0
        warm_compile_s = jitcheck.total_compile_seconds() - s0
        for i in range(3):  # mixed burst: greedy + sampled, varied lengths
            eng.generate([(7 * j + i) % 250 + 1 for j in range(6 + 4 * i)],
                         max_new_tokens=5, temperature=0.0 if i % 2 else 0.7,
                         seed=i)
        steady_compiles = jitcheck.total_compiles() - n0 - warm_compiles
        assert steady_compiles == 0, (
            f"warmed engine compiled {steady_compiles}x in steady state")
        row = {
            "metric": "serve_llm_jit_warmup_smoke",
            "warmup_compiles": warm_compiles,
            "warmup_compile_s": round(warm_compile_s, 3),
            "warmup_wall_s": round(warm_s, 3),
            "steady_state_compiles": steady_compiles,
            "ok": True,
        }
        print(json.dumps(row), flush=True)
        return row
    finally:
        if not was:
            jitcheck.uninstall()


def smoke_dataplane(concurrency: int = 4, reps: int = 2) -> dict:
    """Serve smoke: stream concurrent requests through the FULL data plane
    (handle → router → replica actor → engine) and check the contract."""
    import jax

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.models import transformer
    from ray_tpu.serve.llm import llm_deployment

    cfg = transformer.tiny(max_seq_len=64)
    LM = llm_deployment(
        cfg, lambda: transformer.init_params(cfg, jax.random.key(0)),
        name="LM", slots=4, chunk=4)

    ray_tpu.init()
    handle = serve.run(LM.bind())
    counts = [0] * concurrency
    errors: List[BaseException] = []

    def client(i: int) -> None:
        try:
            for r in range(reps):
                last = None
                for item in handle.options(stream=True).remote(
                        {"prompt_ids": _prompt(i, r), "max_new_tokens": 8}):
                    assert {"token", "index", "decode_tps"} <= set(item)
                    counts[i] += 1
                    last = item
                assert last is not None and "finish_reason" in last
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    serve.shutdown()
    ray_tpu.shutdown()
    if errors:
        raise errors[0]
    row = {
        "metric": "serve_llm_dataplane_smoke",
        "concurrency": concurrency,
        "tokens": sum(counts),
        "tokens_per_s": round(sum(counts) / wall, 1),
        "ok": True,
    }
    print(json.dumps(row), flush=True)
    return row


def bench_kv_tier_modes(reps: int, slots: int, chunk: int) -> List[dict]:
    """ISSUE 17 round 4: cluster-wide KV tier A/B on two engines sharing
    one tier (the in-process stand-in for two replicas + the object store).

    Three measurements:

    - **Hit rate** — a 2-turn session mix whose turn 2 lands on the OTHER
      engine (rebalanced routing, the cross-replica reuse case): with the
      tier off every cross hit is a full re-prefill (per-replica hit rate);
      with it on, turn 2 pulls the spilled chain from the store
      (cluster-wide hit rate).
    - **Cold-engine TTFT** — a chain ≥4 blocks long spilled by engine A;
      a COLD engine's first-request TTFT fetching it from the store vs a
      tier-less engine recomputing the same prefix (the warm-up headline:
      fetch must beat recompute when prefill compute dominates).
    - **Drain migration** — victim ships its chains over the handoff lane
      to the survivor mid-run and retires; the survivor's turn-2 streams
      are asserted TOKEN-IDENTICAL to the victim's own (pre-drain) output
      and attribute their hits to ``migrated``.
    """
    import jax  # noqa: F401 — device probe via _model

    from ray_tpu.core.config import Config, set_config
    from ray_tpu.core.config import config as get_config
    from ray_tpu.serve import kv_tier
    from ray_tpu.serve.llm import PagedLLMEngine

    cfg, params, on_tpu = _model(mid=True)
    bt = int(get_config().serve_kv_block_tokens)
    prev_cfg = get_config()
    results: List[dict] = []
    platform = "tpu" if on_tpu else "cpu"

    def mk(name: str) -> PagedLLMEngine:
        eng = PagedLLMEngine(params, cfg, chunk=chunk, slots=slots,
                             max_queue=0, name=name)
        eng.warmup()
        return eng

    def timed_stream(eng, prompt, n):
        t0 = time.perf_counter()
        first = None
        toks = []
        for tok in eng.stream(list(prompt), max_new_tokens=n):
            if first is None:
                first = time.perf_counter() - t0
            toks.append(tok)
        return toks, first

    def session_mix(tier_on: bool) -> dict:
        kv_tier.reset_local_backend()
        set_config(Config({"kv_tier_enabled": tier_on}))
        a, b = mk("hit-a"), mk("hit-b")
        n_sessions = 2 * max(2, reps // 2)
        t1_len = 5 * bt // 2  # 2 full blocks + half a block of turn 1
        hist = []
        for i in range(n_sessions):
            p = [(i * 17 + j * 3) % 250 + 1 for j in range(t1_len)]
            eng = a if i % 2 == 0 else b
            hist.append(list(p) + eng.generate(list(p), max_new_tokens=8))
        ttfts = []
        for i, h in enumerate(hist):
            eng = b if i % 2 == 0 else a  # turn 2 on the OTHER replica
            _toks, first = timed_stream(eng, h + [9, 9], 8)
            ttfts.append(first)
        hit = miss = store = 0.0
        for eng in (a, b):
            st = eng.kv.stats()
            hit += st["kv_hit_tokens"]
            miss += st["kv_miss_tokens"]
            if tier_on:
                es = eng.stats()
                store += es["kv_tier_hits_store"]
                store += es["kv_tier_hits_migrated"]
        spilled = sum(e.stats().get("kv_tier_spilled_blocks", 0.0)
                      for e in (a, b)) if tier_on else 0.0
        a.close()
        b.close()
        return {
            "metric": "serve_llm_kv_tier_hit_rate",
            "mode": "cluster_tier" if tier_on else "per_replica",
            "sessions": n_sessions, "slots": slots, "chunk": chunk,
            # Cluster-wide rate counts store/migrated-sourced tokens as
            # hits; the per-replica baseline can only count local ones.
            "hit_rate": round((hit + store) / max(1.0, hit + miss), 3),
            "kv_tier_hit_tokens": store,
            "kv_tier_spilled_blocks": spilled,
            "ttft_ms_p50_turn2": round(
                float(np.percentile(ttfts, 50)) * 1e3, 2),
            "platform": platform,
        }

    try:
        base = session_mix(tier_on=False)
        tier = session_mix(tier_on=True)
        if base["hit_rate"] > 0:
            tier["hit_rate_vs_per_replica"] = round(
                tier["hit_rate"] / base["hit_rate"], 2)
        assert tier["hit_rate"] > base["hit_rate"], \
            "cluster tier did not beat the per-replica hit rate"
        for row in (base, tier):
            print(json.dumps(row), flush=True)
            results.append(row)

        # -- cold-engine warm-up: store fetch vs recompute, chain >= 4
        # blocks. Model sized so prefill COMPUTE dominates the fixed
        # per-request cost (decode chunk + scheduling) — the regime the
        # warm-up path targets; a toy config would drown the prefill
        # saving in dispatch noise.
        from ray_tpu.models import transformer

        cold_cfg = transformer.tiny(d_model=384, n_layers=6, n_heads=8,
                                    d_ff=1536, max_seq_len=256)
        cold_params = transformer.init_params(cold_cfg, jax.random.key(0))
        chain_blocks = 8
        long_p = [(j * 11 + 7) % 250 + 1
                  for j in range(chain_blocks * bt + 4)]

        def mk_cold(name: str) -> PagedLLMEngine:
            # Two buckets only: the full-prompt one (recompute pays it)
            # and the short-suffix one (the fetch path's prefill).
            eng = PagedLLMEngine(cold_params, cold_cfg, chunk=2, slots=2,
                                 max_queue=0, name=name,
                                 prompt_buckets=(16, 256))
            eng.warmup()
            return eng

        kv_tier.reset_local_backend()
        set_config(Config({"kv_tier_enabled": True}))
        warm = mk_cold("cold-src")
        out_warm = warm.generate(list(long_p), max_new_tokens=8)
        fetch_ttfts, recompute_ttfts = [], []
        n_rounds = max(2, min(4, reps // 2))
        for r in range(n_rounds):
            cold = mk_cold(f"cold-fetch-{r}")
            toks, first = timed_stream(cold, long_p, 8)
            assert toks == out_warm, "store-fetched decode diverged"
            assert cold.stats()["kv_tier_hits_store"] >= chain_blocks * bt, \
                "cold engine did not fetch the spilled chain"
            cold.close()
            fetch_ttfts.append(first)
        set_config(Config({"kv_tier_enabled": False}))
        for r in range(n_rounds):
            cold = mk_cold(f"cold-recompute-{r}")
            toks, first = timed_stream(cold, long_p, 8)
            assert toks == out_warm, "recompute decode diverged"
            cold.close()
            recompute_ttfts.append(first)
        set_config(Config({"kv_tier_enabled": True}))
        warm.close()
        fetch_ms = round(float(np.percentile(fetch_ttfts, 50)) * 1e3, 2)
        recompute_ms = round(
            float(np.percentile(recompute_ttfts, 50)) * 1e3, 2)
        row = {
            "metric": "serve_llm_kv_tier_cold_ttft",
            "chain_blocks": chain_blocks, "prompt_tokens": len(long_p),
            "ttft_ms_p50_store_fetch": fetch_ms,
            "ttft_ms_p50_recompute": recompute_ms,
            "fetch_speedup_vs_recompute": round(recompute_ms / fetch_ms, 2),
            "platform": platform,
        }
        print(json.dumps(row), flush=True)
        results.append(row)

        # -- drain migration: victim -> survivor over the handoff lane
        kv_tier.reset_local_backend()
        victim, survivor = mk("drain-victim"), mk("drain-survivor")
        n_sessions = 4
        t1_len = 3 * bt
        hist, baseline_t2 = [], []
        for i in range(n_sessions):
            p = [(i * 13 + j * 5) % 250 + 1 for j in range(t1_len)]
            h = list(p) + victim.generate(list(p), max_new_tokens=8)
            hist.append(h)
        for h in hist:  # the victim's own turn 2: the identity baseline
            baseline_t2.append(victim.generate(h + [9, 9],
                                               max_new_tokens=8))
        got: dict = {}
        th = threading.Thread(target=lambda: got.setdefault(
            "n", survivor.kv_migrate_in("bench-kvdrain")))
        th.start()
        sent = victim.kv_migrate_out("bench-kvdrain")
        th.join()
        victim.close()  # retire AFTER the chains shipped
        assert sent >= 1 and got.get("n", 0) >= 1, "drain moved no chains"
        ttfts = []
        for i, h in enumerate(hist):
            toks, first = timed_stream(survivor, h + [9, 9], 8)
            assert toks == baseline_t2[i], \
                "post-drain stream diverged from the victim's own output"
            ttfts.append(first)
        mig_hits = survivor.stats()["kv_tier_hits_migrated"]
        assert mig_hits > 0, "survivor attributed no hits to migration"
        survivor.close()
        row = {
            "metric": "serve_llm_kv_tier_drain",
            "sessions": n_sessions, "chains_migrated": got["n"],
            "kv_tier_hits_migrated": mig_hits,
            "ttft_ms_p50_turn2_after_drain": round(
                float(np.percentile(ttfts, 50)) * 1e3, 2),
            "quality": "token_identical_across_drain",
            "platform": platform,
        }
        print(json.dumps(row), flush=True)
        results.append(row)
    finally:
        set_config(prev_cfg)
        kv_tier.reset_local_backend()
    return results


def smoke_kv_tier() -> dict:
    """Quick smoke: spill → cross-engine store fetch round trip, plus one
    drain-migrated session, token-identical throughout."""
    from ray_tpu.core.config import Config, set_config
    from ray_tpu.core.config import config as get_config
    from ray_tpu.serve import kv_tier
    from ray_tpu.serve.llm import PagedLLMEngine

    cfg, params, _on_tpu = _model()
    prev_cfg = get_config()
    set_config(Config({"kv_tier_enabled": True}))
    kv_tier.reset_local_backend()
    try:
        kw = dict(chunk=4, slots=2, max_queue=0)
        a = PagedLLMEngine(params, cfg, name="smoke-tier-a", **kw)
        a.warmup()
        b = PagedLLMEngine(params, cfg, name="smoke-tier-b", **kw)
        b.warmup()
        p = [(7 * j + 3) % 250 + 1 for j in range(32)]
        out_a = a.generate(list(p), max_new_tokens=6)
        out_b = b.generate(list(p), max_new_tokens=6)
        assert out_a == out_b, "store-fetched decode diverged"
        store_hits = b.stats()["kv_tier_hits_store"]
        assert store_hits > 0, "no cluster-wide hit on the shared prompt"
        got: dict = {}
        th = threading.Thread(target=lambda: got.setdefault(
            "n", b.kv_migrate_in("smoke-kvdrain")))
        th.start()
        sent = a.kv_migrate_out("smoke-kvdrain")
        th.join()
        assert sent >= 1 and got.get("n", 0) >= 1, "migration moved nothing"
        a.close()
        h = list(p) + out_a + [9]
        out_t2 = b.generate(h, max_new_tokens=6)
        assert out_t2, "post-drain turn 2 produced nothing"
        mig_hits = b.stats()["kv_tier_hits_migrated"]
        b.close()
        backend_stats = kv_tier._local_backend().stats()
        assert backend_stats["prefix_dir_refs"] == 0, \
            "directory refs leaked after both engines closed"
        row = {
            "metric": "serve_llm_kv_tier_smoke",
            "kv_tier_store_hits": store_hits,
            "chains_migrated": got["n"],
            "kv_tier_hits_migrated": mig_hits,
            "ok": True,
        }
        print(json.dumps(row), flush=True)
        return row
    finally:
        set_config(prev_cfg)
        kv_tier.reset_local_backend()


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--quick", action="store_true",
                        help="CI smoke: short engine A/B + data-plane check")
    parser.add_argument("--reps", type=int, default=8,
                        help="sequential requests per client thread")
    parser.add_argument("--slots", type=int, default=8)
    parser.add_argument("--chunk", type=int, default=8)
    parser.add_argument("--round", type=int, default=0,
                        help="write BENCH_serve_rNN.json at repo root")
    args = parser.parse_args()

    from ray_tpu.util.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.quick:
        results = bench_modes([4], reps=2, slots=4, chunk=args.chunk)
        results += bench_prefix_modes([4], reps=2, slots=4, chunk=args.chunk)
        results.append(smoke_paged_cow())
        results.append(smoke_kv_tier())
        results.append(smoke_jit_warmup())
        results.append(smoke_dataplane())
    elif args.round >= 4:
        # Round 4 (ISSUE 17): cluster-wide KV tier A/B — cross-replica hit
        # rate, cold-engine warm-up from the store, drain migration.
        results = bench_kv_tier_modes(reps=args.reps, slots=args.slots,
                                      chunk=args.chunk)
    elif args.round >= 3:
        # Round 3 (ISSUE 16): speculative-decoding TPOT A/B on the paged
        # engine — decode-heavy traffic, equal (asserted-identical) quality.
        results = bench_spec_modes(concurrency=4, reps=args.reps,
                                   chunk=args.chunk, slots=args.slots)
    else:
        results = bench_modes([1, 4, 16], reps=args.reps,
                              slots=args.slots, chunk=args.chunk)
        results += bench_prefix_modes([4, 16], reps=args.reps,
                                      slots=args.slots, chunk=args.chunk)

    if args.round:
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            f"BENCH_serve_r{args.round:02d}.json")
        existing = []
        if os.path.exists(path):
            with open(path) as f:
                existing = json.load(f).get("results", [])
        with open(path, "w") as f:
            json.dump({"results": existing + results}, f, indent=1)
        print(f"wrote {path}")
    return 0


if __name__ == "__main__":
    main()
