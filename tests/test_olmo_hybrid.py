"""Olmo-Hybrid on the paged serve path, against its plain reference.

Every comparison is with ``benchmark/reference/olmo_hybrid_plain.py`` (the
file the benchmark's ``correct`` uses: float32, the recurrence token by
token, a full causal softmax, no cache) on seeded weights at a small size:
``olmo_hybrid.tiny()``, one period or two, width 64, 4 heads of 16, key 8 /
value 16, convolution 4.

Tolerance 2e-4 on logits everywhere: program and reference are both float32
here (``conftest`` pins matmul precision to ``highest``), so what differs is
only the order of summation: the chunk-wise scan and its triangular solve
against the token-by-token recurrence, the kernel's online softmax against a
dense one, a state held transposed. A state that is zeroed, stale or another
slot's, a wrong convolution tail, a dropped layer or a rotated key moves
logits by 1e-2 and more (``test_the_check_has_teeth_on_the_state``).
"""

import dataclasses
import inspect
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import engine_contract
import half_filled_bucket
from benchmark.manifest import load_file
from ray_tpu.models import longcat, olmo_hybrid, transformer
from ray_tpu.models.generate import PagedGenerator
from ray_tpu.serve.llm import LLMEngine, llm_deployment

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ref = load_file(REPO, "benchmark/reference/olmo_hybrid_plain.py")
TOL = 2e-4
BT = 16


def ref_logits(model, seq):
    cfg, params = model
    config = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    return np.asarray(ref.forward(ref.weights(params),
                                  jnp.asarray([seq], jnp.int32), config))[0]


def served_gap(model, prompt, toks):
    logits = ref_logits(model, list(prompt) + list(toks))
    rows = logits[len(prompt) - 1:len(prompt) - 1 + len(toks)]
    return float((rows.max(-1) - rows[np.arange(len(toks)), toks]).max())


@pytest.fixture(scope="module")
def model():
    """Two periods: the second's layers index the state and the pool past
    the first's."""
    cfg = olmo_hybrid.tiny(num_hidden_layers=8,
                           layer_types=olmo_hybrid._PERIOD * 2)
    return cfg, olmo_hybrid.init_params(cfg, jax.random.key(1))


@pytest.fixture(scope="module")
def engine(model):
    cfg, params = model
    eng = LLMEngine(params, cfg, prompt_buckets=(16, 64), chunk=4,
                    slots=2, max_queue=0, name="olmo-test",
                    block_tokens=BT, pool_blocks=33,
                    attention_kernel="interpret")
    eng.warmup()
    return eng


def prefill(gen, params, dev, table, prompt, slot, bucket):
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(prompt)] = prompt
    return gen.prefill_fn(bucket)(
        params, *dev, np.asarray(table, np.int32), padded, 0, len(prompt),
        slot, 0)[:4]


@pytest.mark.parametrize("kernel", ["gather", "interpret"])
def test_paged_prefill_and_decode_match_the_reference(model, kernel):
    """Two sequences prefill into slots 0 and 2 (buckets 64 and 16, so one
    has a padded tail of 27 and one of 5), slot 1 stays parked; then both
    decode a chunk in one program. Logits, not tokens, against the
    reference's full pass; the init's decay is what the issue asks for."""
    cfg, params = model
    gen = PagedGenerator(params, cfg, slots=3, num_blocks=9, block_tokens=BT,
                         max_len=64, attention_kernel=kernel)
    dev = gen.init_state()
    rng = np.random.default_rng(0)
    a = [int(t) for t in rng.integers(1, cfg.vocab_size, 37)]
    b = [int(t) for t in rng.integers(1, cfg.vocab_size, 11)]
    dev = prefill(gen, params, dev, [1, 2, 3, 0], a, 0, 64)
    dev = prefill(gen, params, dev, [4, 5, 0, 0], b, 2, 16)
    np.testing.assert_allclose(np.asarray(dev[2][0]), ref_logits(model, a)[36],
                               atol=TOL)
    np.testing.assert_allclose(np.asarray(dev[2][2]), ref_logits(model, b)[10],
                               atol=TOL)
    tables = np.asarray([[1, 2, 3, 0], [0] * 4, [4, 5, 0, 0]], np.int32)
    toks, pool, state, last, keys, aux = gen.decode_fn(4)(
        params, *dev, tables, np.asarray([37, 0, 11], np.int32),
        np.asarray([True, False, True]), np.ones(3, bool),
        np.zeros(3, np.float32))
    assert aux is None
    toks = np.asarray(toks)
    for slot, seq in ((0, a), (2, b)):
        full = seq + [int(t) for t in toks[slot]]
        logits = ref_logits(model, full)
        rows = logits[len(seq) - 1:len(full) - 1]
        gap = rows.max(-1) - rows[np.arange(4), toks[slot]]
        assert gap.max() <= TOL, gap
        np.testing.assert_allclose(np.asarray(last[slot]), logits[-1],
                                   atol=TOL)
    # the parked slot's state never moved from zero
    assert not np.asarray(state[0][:, 1]).any()
    assert not np.asarray(state[1][:, :, 1]).any()


def test_a_half_filled_bucket_walks_for_its_real_rows(model):
    """100 tokens in the 256 bucket, two query tiles of the attention kernel:
    the first straddles the prompt's end, the second is pad rows alone and is
    skipped; the table behind the prompt's blocks is the trash block. The last
    real row's logits are the reference's."""
    cfg, params = model
    seq = [int(t) for t in np.random.default_rng(7).integers(1, 200, 100)]
    np.testing.assert_allclose(half_filled_bucket.last_row(params, cfg, seq, 256),
                               ref_logits(model, seq)[99], atol=TOL)


def test_the_decay_init_gives_the_state_a_memory(model):
    """alpha over a prompt: a median near 0.9, spread over (0, 1), not
    driven to 0 (a state that does nothing) nor all 1."""
    cfg, params = model
    x = jax.random.normal(jax.random.key(0), (256, cfg.hidden_size)) * 3.0
    alphas = []
    for period in params["periods"]:
        for kind, lw in zip(cfg.period, period):
            if kind == olmo_hybrid.LINEAR:
                g, beta = olmo_hybrid._gates(lw, x)
                alphas.append(np.exp(np.asarray(g)))
                assert 0.0 < float(beta.min()) and float(beta.max()) < 2.0
                assert float(beta.max()) > 1.0
    alpha = np.concatenate([a.ravel() for a in alphas])
    assert 0.8 < np.median(alpha) < 0.99, np.median(alpha)
    assert np.quantile(alpha, 0.1) > 0.3 and np.quantile(alpha, 0.9) < 1.0


def test_the_check_has_teeth_on_the_state(model):
    """The same prefill and decode, with slot 0's recurrent state zeroed
    before ONE decode step: the logits after the chunk leave the reference's
    by far more than the tolerance (and the conv tail alone, zeroed, does
    too). A check that passed this would not see a wrong state."""
    cfg, params = model
    gen = PagedGenerator(params, cfg, slots=1, num_blocks=5, block_tokens=BT,
                         max_len=64, attention_kernel="gather")
    rng = np.random.default_rng(3)
    a = [int(t) for t in rng.integers(1, cfg.vocab_size, 30)]
    step = gen.decode_fn(1)

    def run(damage):
        dev = prefill(gen, params, gen.init_state(), [1, 2, 3, 0], a, 0, 64)
        toks = []
        for t in range(6):
            pool, state, last, keys = dev
            if t == 2 and damage is not None:
                state = damage(state)
            tok, *dev, _aux = step(
                params, pool, state, last, keys,
                np.asarray([[1, 2, 3, 0]], np.int32),
                np.asarray([30 + t], np.int32), np.ones(1, bool),
                np.ones(1, bool), np.zeros(1, np.float32))
            toks.append(int(np.asarray(tok)[0, 0]))
        return toks, np.asarray(dev[2][0])

    toks, last = run(None)
    want = ref_logits(model, a + toks)[-1]
    np.testing.assert_allclose(last, want, atol=TOL)
    for damage in (lambda st: (jnp.zeros_like(st[0]), st[1]),
                   lambda st: (st[0], jnp.zeros_like(st[1]))):
        toks_d, last_d = run(damage)
        # judged on the sequence the damaged run itself served
        off = np.abs(last_d - ref_logits(model, a + toks_d)[-1]).max()
        assert off > 100 * TOL, off


def test_engine_serves_the_family_and_refuses_the_prefix_cache(model, engine):
    """Concurrent streams through the one engine and block manager agree
    with the reference; the same prompt again returns the same tokens with
    no prefix hit, nothing registered, and the refusals counted."""
    assert model[0].paged_family().unsupported == ("prefix_cache",)
    prompts = [[7, 3, 11, 200, 5], list(range(30, 52))]
    outs = [None, None]

    def run(i):
        outs[i] = engine.generate(prompts[i], max_new_tokens=8)

    threads = [threading.Thread(target=run, args=(i,)) for i in range(2)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    for p, o in zip(prompts, outs):
        assert len(o) == 8 and served_gap(model, p, o) <= TOL
    before = engine.stats()
    again = engine.generate(prompts[1], max_new_tokens=8)
    after = engine.stats()
    assert again == outs[1]
    assert after["kv_hit_tokens"] == before["kv_hit_tokens"] == 0
    assert after["kv_blocks_cached"] == 0 and engine.kv.active_blocks() == 0
    assert (after["prefix_lookups_refused_total"]
            - before["prefix_lookups_refused_total"]) == 1
    assert after["state_resets_total"] == after["prefix_lookups_refused_total"]
    assert after["state_slot_steps_total"] > before["state_slot_steps_total"]
    cfg = model[0]
    s_bytes = cfg.n_linear * 2 * (
        cfg.linear_key_head_dim * cfg.linear_num_value_heads
        * cfg.linear_value_head_dim * 4 + 3 * cfg.conv_channels * 4)
    assert after["state_bytes"] == s_bytes
    d = engine.describe()
    assert d["model_family"] == "OlmoHybridConfig"
    assert d["kv_pool_shapes"] == [[2, 33, BT, 64]] * 2
    assert d["slot_state_shapes"] == [[6, 2, 8, 64], [6, 3, 2, 128]]


def test_a_slots_second_request_equals_a_fresh_engines(model, engine):
    """Admission zeroes the slot's state through the prefill program: the
    request that follows another in a slot is served what an engine that
    never saw the first serves."""
    cfg, params = model
    engine.generate(list(range(60, 100)), max_new_tokens=12)
    p = [9, 8, 7, 250, 1, 2, 3]
    second = engine.generate(p, max_new_tokens=8)
    fresh = LLMEngine(params, cfg, prompt_buckets=(16, 64), chunk=4, slots=2,
                      max_queue=0, name="olmo-fresh", block_tokens=BT,
                      pool_blocks=33, attention_kernel="interpret")
    assert fresh.generate(p, max_new_tokens=8) == second
    assert served_gap(model, p, second) <= TOL


def test_a_parked_slots_state_stands_still_across_a_chunk(model, engine):
    """Slot 1 keeps what its last request left (no request holds it); slot 0
    decodes. After whole chunks slot 1's state and tail are bit for bit what
    they were, slot 0's moved."""
    engine.generate([5, 6, 7, 8], max_new_tokens=4)      # leaves a residue
    first = engine.stream([11, 12, 13], max_new_tokens=16)
    next(first)                                          # it holds slot 0,
    engine.generate([11, 12, 13], max_new_tokens=4)      # so this takes 1
    list(first)
    before = [np.asarray(a) for a in engine._slot_state]
    assert before[0][:, 1].any()
    engine.generate([21, 22, 23, 24, 25], max_new_tokens=8)   # slot 0 alone
    after = [np.asarray(a) for a in engine._slot_state]
    np.testing.assert_array_equal(after[0][:, 1], before[0][:, 1])
    np.testing.assert_array_equal(after[1][:, :, 1], before[1][:, :, 1])
    assert not np.array_equal(after[0][:, 0], before[0][:, 0])


def test_steps_and_prefills_say_what_the_state_did(model, engine):
    from ray_tpu.util import tracing

    t0 = tracing.now_ns()
    engine.generate([1, 2, 3], max_new_tokens=4)
    steps = [s for s in tracing.recorded(t0) if s.name == "llm.step"
             and s.attrs.get("engine") == "olmo-test" and s.attrs["batch"]]
    assert steps and all(s.attrs["state_slots"] == s.attrs["batch"]
                         for s in steps)


def _program_operands(cfg, params, slots=2):
    gen = PagedGenerator(params, cfg, slots=slots, num_blocks=9,
                         block_tokens=BT, max_len=64, attention_kernel="gather")
    pool, state, last, keys = gen.init_state()
    params = gen.params      # what the programs are called with
    n_dev = len(jax.tree.leaves((params, pool, state, last, keys)))
    nb = gen.blocks_per_seq
    decode = gen.decode_fn(4).lower(
        params, pool, state, last, keys, np.zeros((slots, nb), np.int32),
        np.zeros(slots, np.int32), np.zeros(slots, bool), np.ones(slots, bool),
        np.zeros(slots, np.float32))
    pre = gen.prefill_fn(16).lower(
        params, pool, state, last, keys, np.zeros(nb, np.int32),
        np.zeros((1, 16), np.int32), 0, 16, 0, 0)
    count = lambda low: len(jax.tree.leaves(low.args_info))  # noqa: E731
    return (state, n_dev, count(decode), count(pre),
            len(jax.tree.leaves(params)))


@pytest.mark.parametrize("family", ["gpt2", "longcat", "olmo_hybrid"])
def test_an_empty_slot_state_adds_no_operand(family, model):
    """GPT-2's and LongCat's programs take the operands they took: weights
    (GPT-2's as its working tree holds them: one array a matrix a layer),
    the pool's arrays, ``last`` and ``keys``, then 5 (decode) or 6 (prefill)
    host operands; the slot state of a family that keeps one adds exactly
    its two arrays."""
    if family == "gpt2":
        cfg = transformer.TransformerConfig(
            vocab_size=128, d_model=32, n_layers=2, n_heads=2, d_ff=64,
            max_seq_len=64, dtype=jnp.float32)
        params = transformer.init_params(cfg, jax.random.key(0))
        n_pool, n_state = 2, 0
    elif family == "longcat":
        cfg = longcat.tiny()
        params = longcat.init_params(cfg, jax.random.key(0))
        n_pool, n_state = 1, 0
    else:
        cfg, params = model
        n_pool, n_state = 2, 2
    state, n_dev, n_decode, n_prefill, n_params = _program_operands(
        cfg, params)
    assert len(state) == n_state
    if family != "gpt2":
        assert n_params == len(jax.tree.leaves(params))
    assert n_dev == n_params + n_pool + n_state + 2
    assert n_decode == n_dev + 5
    assert n_prefill == n_dev + 6


def test_the_engine_has_no_hook_for_the_family():
    from ray_tpu.serve import llm

    src = inspect.getsource(llm)
    assert not any(word in src for word in (
        "gdn", "gated_delta", "recurren", "linear_attention", "conv_"))


def test_a_program_lowers_one_period(model):
    """Two periods, three linear layers each: the lowered decode program
    holds the period function once (its kernel call three times, not six)."""
    cfg, params = model
    gen = PagedGenerator(params, cfg, slots=2, num_blocks=9, block_tokens=BT,
                         max_len=64, attention_kernel="interpret")
    pool, state, last, keys = gen.init_state()
    text = gen.decode_fn(2).lower(
        params, pool, state, last, keys, np.zeros((2, 4), np.int32),
        np.zeros(2, np.int32), np.zeros(2, bool), np.ones(2, bool),
        np.zeros(2, np.float32)).as_text()
    assert text.count("func.func private @period") == 1
    assert text.count("call @period") == cfg.n_periods


# What the engine owes a request whatever it serves (tests/engine_contract.py);
# the streams a check hands back are held to the reference.
@engine_contract.each_check
def test_engine_contract(model, check):
    cfg, params = model
    for prompt, toks in check(params, cfg, engine_contract.ENGINE_KW):
        assert served_gap(model, prompt, toks) < TOL


def test_llm_deployment_streams_the_family(ray_start_regular, model):
    from ray_tpu import serve

    cfg, _params = model
    try:
        LM = llm_deployment(
            cfg, lambda: olmo_hybrid.init_params(cfg, jax.random.key(1)),
            name="OlmoHybrid", slots=2, chunk=4)
        handle = serve.run(LM.bind())
        prompt = [5, 9, 200, 31, 77, 2]
        items = list(handle.options(stream=True).remote(
            {"prompt_ids": prompt, "max_new_tokens": 6}))
        toks = [it["token"] for it in items]
        assert [it["index"] for it in items] == list(range(6))
        assert items[-1]["finish_reason"] == "stop"
        assert served_gap(model, prompt, toks) <= TOL
    finally:
        serve.shutdown()
