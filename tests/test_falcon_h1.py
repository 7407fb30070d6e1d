"""Falcon-H1 on the paged serve path, against its plain reference.

Every comparison is with ``benchmark/reference/falcon_h1_plain.py`` (the
file the benchmark's ``correct`` uses: float32, the recurrence token by
token, a full causal softmax a query head, no cache) on seeded weights at a
small size: ``falcon_h1.tiny()``, two layers or three, width 64, 4 query
heads over 2 KV heads of 16, 4 SSM heads of 16 in 2 groups, state 128,
convolution 4, chunks of 16, EVERY multiplier another number than 1.

Tolerance 2e-4 on logits everywhere: program and reference are both float32
here (``conftest`` pins matmul precision to ``highest``), so what differs is
only the order of summation: the chunk-wise scan against the token-by-token
recurrence, the kernel's online softmax over a KV head's rows against a
dense one a query head, a state held folded. A state that is zeroed, stale
or another slot's, a wrong convolution tail, a query head on the wrong KV
head, a dropped branch or multiplier moves logits by 1e-2 and more
(``test_the_check_has_teeth``, ``test_no_multiplier_is_dead``).
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
from ray_tpu.models import falcon_h1
from ray_tpu.models.generate import PagedGenerator
from ray_tpu.serve.llm import LLMEngine, llm_deployment

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ref = load_file(REPO, "benchmark/reference/falcon_h1_plain.py")
TOL = 2e-4
BT = 16
MULTIPLIERS = ("embedding_multiplier", "lm_head_multiplier",
               "attention_in_multiplier", "attention_out_multiplier",
               "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier",
               "ssm_multipliers", "mlp_multipliers")


def as_dict(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def ref_logits(model, seq):
    cfg, params = model
    return np.asarray(ref.forward(ref.weights(params),
                                  jnp.asarray([seq], jnp.int32),
                                  as_dict(cfg)))[0]


def served_gap(model, prompt, toks):
    logits = ref_logits(model, list(prompt) + list(toks))
    rows = logits[len(prompt) - 1:len(prompt) - 1 + len(toks)]
    return float((rows.max(-1) - rows[np.arange(len(toks)), toks]).max())


@pytest.fixture(scope="module")
def model():
    """Three layers: the later ones index the state and the pool past the
    first's."""
    cfg = falcon_h1.tiny(num_hidden_layers=3)
    return cfg, falcon_h1.init_params(cfg, jax.random.key(1))


@pytest.fixture(scope="module")
def engine(model):
    cfg, params = model
    eng = LLMEngine(params, cfg, prompt_buckets=(16, 64), chunk=4,
                    slots=2, max_queue=0, name="falcon-test",
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


def test_tiny_sets_every_multiplier_off_one(model):
    cfg = model[0]
    for name in MULTIPLIERS:
        value = getattr(cfg, name)
        for m in (value if isinstance(value, tuple) else (value,)):
            assert m != 1.0, name
    assert cfg.num_attention_heads // cfg.num_key_value_heads == 2
    assert cfg.mamba_n_heads // cfg.mamba_n_groups == 2


@pytest.mark.parametrize("kernel", ["gather", "interpret"])
def test_paged_prefill_and_decode_match_the_reference(model, kernel):
    """Two sequences prefill into slots 0 and 2 (buckets 64 and 16: one has
    a padded tail of 27 and crosses two chunk boundaries, one a tail of 5),
    slot 1 stays parked; then both decode a chunk in one program. Logits,
    not tokens, against the reference's full pass."""
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
    # the pool's row is the KV heads', every layer keeps both kinds of memory
    assert [p.shape for p in pool] == [(3, 9, BT, 2 * 16)] * 2
    assert state[0].shape == (3, 3, 128, 64) and state[0].dtype == jnp.float32
    assert state[1].shape == (3, 3, 3, 64 + 2 * 2 * 128)
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


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_no_multiplier_is_dead(model, name):
    """Each published multiplier moved ALONE (a tuple's entries one at a
    time) changes the program's logits, and program and reference still
    agree: neither side drops one or folds it away."""
    cfg, params = model
    seq = [int(t) for t in np.random.default_rng(5).integers(1, 256, 21)]
    base = ref_logits(model, seq)[-1]
    value = getattr(cfg, name)
    moved = ([tuple(m * (1.5 if j == i else 1.0) for j, m in enumerate(value))
              for i in range(len(value))] if isinstance(value, tuple)
             else [value * 1.5])
    for new in moved:
        cfg2 = cfg.replace(**{name: new})
        gen = PagedGenerator(params, cfg2, slots=1, num_blocks=5,
                             block_tokens=BT, max_len=64,
                             attention_kernel="gather")
        dev = prefill(gen, params, gen.init_state(), [1, 2, 0, 0], seq, 0, 64)
        got = np.asarray(dev[2][0])
        assert np.abs(got - base).max() > 100 * TOL, (name, new)
        np.testing.assert_allclose(
            got, ref_logits((cfg2, params), seq)[-1], atol=TOL)


def test_the_init_conditions_the_published_multipliers():
    """At the published multipliers (widths cut: this is a CPU test) the
    init gives what its docstring says: branches of the order of the stream,
    logits of a standard deviation near one, decays spread so that a state
    remembers."""
    cfg = falcon_h1.FalconH1Config(
        vocab_size=512, hidden_size=256, intermediate_size=512,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=32, mamba_d_ssm=256, mamba_n_heads=8, mamba_d_head=32,
        mamba_d_state=32, mamba_n_groups=2, max_seq_len=128,
        dtype=jnp.float32, param_dtype=jnp.float32)
    params = falcon_h1.init_params(cfg, jax.random.key(3))
    seq = [int(t) for t in np.random.default_rng(1).integers(1, 512, 96)]
    logits = ref_logits((cfg, params), seq)
    assert 0.5 < logits.std() < 2.0, logits.std()
    c = as_dict(cfg)
    x = (np.asarray(params["tok_embed"])[seq] * cfg.embedding_multiplier)
    assert 0.8 < np.sqrt((x ** 2).mean()) < 1.25
    lw = params["layers"][0]
    u = ref._rms(jnp.asarray(x), lw["norm_in"], cfg.rms_norm_eps)
    mix = cfg.ssm_out_multiplier * np.asarray(ref.mixer(lw, u, c))
    att = cfg.attention_out_multiplier * np.asarray(ref.attention(
        lw, u * cfg.attention_in_multiplier, c))
    ffn = np.asarray(ref.ffn(lw["ffn"], u, c))
    for name, branch in (("mixer", mix), ("attention", att), ("ffn", ffn)):
        rms = float(np.sqrt((branch ** 2).mean()))
        assert 0.25 < rms < 2.5, (name, rms)
    a = np.exp(np.concatenate([np.asarray(l["A_log"]) for l in params["layers"]]))
    assert a.min() >= 1.0 and a.max() <= 16.0
    dt = np.log1p(np.exp(np.concatenate(
        [np.asarray(l["dt_bias"]) for l in params["layers"]])))
    assert dt.min() >= 0.99e-3 and dt.max() <= 1.01e-1
    memory = 1.0 / (dt * a)             # tokens a head remembers
    assert np.median(memory) > 5 and memory.max() > 50


def test_the_check_has_teeth(model):
    """The same prefill and decode, damaged before ONE decode step: slot 0's
    SSM state zeroed, its convolution tail zeroed, its K/V rows rolled by a
    KV head (what a query head on the wrong KV head reads). The logits after
    the chunk leave the reference's by far more than the tolerance."""
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
                pool, state = damage(pool, state)
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
    roll = lambda p: jnp.roll(p, cfg.head_dim, axis=-1)  # noqa: E731
    for damage in (lambda p, st: (p, (jnp.zeros_like(st[0]), st[1])),
                   lambda p, st: (p, (st[0], jnp.zeros_like(st[1]))),
                   lambda p, st: ((roll(p[0]), roll(p[1])), st)):
        toks_d, last_d = run(damage)
        # judged on the sequence the damaged run itself served
        off = np.abs(last_d - ref_logits(model, a + toks_d)[-1]).max()
        assert off > 100 * TOL, off


def test_engine_serves_the_family_and_refuses_the_prefix_cache(model, engine):
    """Concurrent streams through the one engine and block manager agree
    with the reference; the same prompt again returns the same tokens with
    no prefix hit, nothing registered, and the refusals counted."""
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
    # 3 layers x 2 slots x (state 128 x 64 float32 + tail 3 x 576 float32)
    assert after["state_bytes"] == 3 * 2 * (128 * 64 * 4 + 3 * 576 * 4)
    d = engine.describe()
    assert d["model_family"] == "FalconH1Config"
    assert d["kv_pool_shapes"] == [[3, 33, BT, 32]] * 2
    assert d["slot_state_shapes"] == [[3, 2, 128, 64], [3, 3, 2, 576]]
    assert (d["kv_heads"], d["ssm_heads"], d["ssm_state"],
            d["state_layers"]) == (2, 4, 128, cfg.num_hidden_layers)


def test_a_slots_second_request_starts_from_a_zero_state(model, engine):
    """Admission writes the slot's state from zero through the prefill
    program: the request that follows a retired one in a slot is served what
    an engine that never saw the first serves."""
    cfg, params = model
    engine.generate(list(range(60, 100)), max_new_tokens=12)
    p = [9, 8, 7, 250, 1, 2, 3]
    second = engine.generate(p, max_new_tokens=8)
    fresh = LLMEngine(params, cfg, prompt_buckets=(16, 64), chunk=4, slots=2,
                      max_queue=0, name="falcon-fresh", block_tokens=BT,
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


def test_the_engine_needed_no_edit_for_the_family():
    """PR 31's seam holds a family whose every layer keeps both kinds of
    memory: the engine names nothing of it."""
    from ray_tpu.serve import llm

    src = inspect.getsource(llm)
    assert not any(word in src for word in (
        "falcon", "ssd", "mamba", "ssm_", "n_kv_heads"))


def test_a_program_lowers_one_layer(model):
    """Three layers: the lowered decode program holds the layer function
    once, called three times."""
    cfg, params = model
    gen = PagedGenerator(params, cfg, slots=2, num_blocks=9, block_tokens=BT,
                         max_len=64, attention_kernel="interpret")
    pool, state, last, keys = gen.init_state()
    text = gen.decode_fn(2).lower(
        params, pool, state, last, keys, np.zeros((2, 4), np.int32),
        np.zeros(2, np.int32), np.zeros(2, bool), np.ones(2, bool),
        np.zeros(2, np.float32)).as_text()
    assert text.count("func.func private @layer") == 1
    assert text.count("call @layer") == cfg.num_hidden_layers


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
            cfg, lambda: falcon_h1.init_params(cfg, jax.random.key(1)),
            name="FalconH1", slots=2, chunk=4)
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
