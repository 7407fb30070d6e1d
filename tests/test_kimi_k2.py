"""Kimi-K2 on the paged serve path, against its plain reference.

Every comparison is with ``benchmark/reference/kimi_k2_plain.py`` (the file
the benchmark's ``correct`` uses: float32, unabsorbed attention a head at a
time, a loop over experts, no cache) on seeded weights at a small size:
hidden 64, 4 heads, one dense and two expert layers, 32 routed experts of
which 4 are held, top-4, one shared expert, YaRN of factor 8 over 16 original
positions, interpreted kernels.

Tolerance 2e-4 on logits everywhere: program and reference are both float32
here (``conftest`` pins matmul precision to ``highest``), so what differs is
only the order of summation: absorbed against unabsorbed products, the
kernel's online softmax against a dense one, a grouped product over sorted
pairs against a loop over experts, a wide product whole against its column
blocks. A missing shared expert, a normaliser over the held picks alone,
plain rotary or a skipped dense FFN moves logits by 1e-2 and more (each is
planted below).
"""

import math
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import engine_contract
import half_filled_bucket
from benchmark.manifest import load_file
from ray_tpu.models import kimi_k2
from ray_tpu.models.generate import PagedGenerator
from ray_tpu.ops import layers, moe
from ray_tpu.serve.llm import LLMEngine, llm_deployment

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ref = load_file(REPO, "benchmark/reference/kimi_k2_plain.py")
TOL = 2e-4
BT = 16


def ref_config(cfg: kimi_k2.KimiK2Config, held=None) -> dict:
    """The flat keys the reference reads, as a configuration's file has
    them, for a program config object."""
    first, count = held if held is not None else cfg.held
    return {
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
        "rope_scaling": dict(cfg.rope_scaling, type="yarn"),
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "first_k_dense_replace": cfg.first_k_dense_replace,
        "held": {"first": first, "count": count,
                 "of": cfg.n_routed_experts}}


@pytest.fixture(scope="module")
def model():
    cfg = kimi_k2.tiny()
    return cfg, kimi_k2.init_params(cfg, jax.random.key(1))


def ref_logits(model, seq):
    cfg, params = model
    return np.asarray(ref.forward(ref.weights(params),
                                  jnp.asarray([seq], jnp.int32),
                                  ref_config(cfg))[0])


def _prefill(gen, params, pool, last, keys, table, suffix, start, slot, bucket):
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(suffix)] = suffix
    pool, _state, last, keys, aux = gen.prefill_fn(bucket)(
        params, pool, (), last, keys, np.asarray(table, np.int32), padded,
        start, len(suffix), slot, 0)
    return pool, last, keys, aux


# -- (a) paged prefill, prefix hit, copy-on-write fork, decode ----------------

@pytest.mark.parametrize("kernel", ["gather", "interpret"])
def test_paged_prefill_and_decode_match_the_reference(model, kernel):
    """As LongCat's: A prefills 37 tokens; B hits A's two full blocks, forks
    the partial third copy-on-write and prefills its 6-token suffix at
    ``start_pos`` 37; both decode a chunk in one program. Logits, not
    tokens, against the reference's full pass. Positions run past YaRN's 16
    original ones, so every part of the blend is in the scores."""
    cfg, params = model
    V, E = cfg.vocab_size, cfg.expert_layers
    gen = PagedGenerator(params, cfg, slots=2, num_blocks=8, block_tokens=BT,
                         max_len=64, attention_kernel=kernel)
    pool, _state, last, keys = gen.init_state()
    rng = np.random.default_rng(0)
    a = [int(t) for t in rng.integers(1, V, 37)]
    b = a + [int(t) for t in rng.integers(1, V, 6)]

    pool, last, keys, aux = _prefill(gen, params, pool, last, keys,
                                     [1, 2, 3, 0], a, 0, 0, 64)
    np.testing.assert_allclose(np.asarray(last[0]), ref_logits(model, a)[36],
                               atol=TOL)
    # every real token routed top-k in every EXPERT layer; the dense layer
    # has no router, pads routed nowhere, no pick is zero-compute
    assert int(aux[0]) == 37 * cfg.num_experts_per_tok * E
    assert int(aux[1]) == 0
    block3 = np.asarray(pool[0][:, 3])

    pool = gen.copy_fn()(pool, 3, 5)
    pool, last, keys, _ = _prefill(gen, params, pool, last, keys,
                                   [1, 2, 5, 0], b[37:], 37, 1, 16)
    np.testing.assert_allclose(np.asarray(last[1]), ref_logits(model, b)[42],
                               atol=TOL)
    np.testing.assert_array_equal(np.asarray(pool[0][:, 3]), block3)

    tables = np.asarray([[1, 2, 3, 0], [1, 2, 5, 0]], np.int32)
    toks, pool, _state, last, keys, aux = gen.decode_fn(4)(
        params, pool, (), last, keys, tables, np.asarray([37, 43], np.int32),
        np.ones(2, bool), np.ones(2, bool), np.zeros(2, np.float32))
    toks = np.asarray(toks)
    assert int(aux[0]) == 2 * 4 * cfg.num_experts_per_tok * E
    assert int(aux[-1]) == 4                       # token steps
    for slot, seq in ((0, a), (1, b)):
        full = seq + [int(t) for t in toks[slot]]
        logits = ref_logits(model, full)
        rows = logits[len(seq) - 1:len(full) - 1]
        gap = rows.max(-1) - rows[np.arange(4), toks[slot]]
        assert gap.max() <= TOL, gap
        np.testing.assert_allclose(np.asarray(last[slot]), logits[-1],
                                   atol=TOL)
    # one row a token a LAYER (dense and expert alike), padded to 128 lanes
    assert pool[0].shape == (cfg.num_hidden_layers, 8, BT, 128)
    assert not np.asarray(pool[0][:, 1, :, cfg.latent_width:]).any()


def test_a_half_filled_bucket_walks_for_its_real_rows(model):
    """27 tokens in the 64 bucket, four query tiles of the latent kernel: one
    whole, one that straddles the prompt's end, two of pad rows alone that
    are skipped; the table behind the prompt's blocks is the trash block. The
    last real row's logits are the reference's."""
    cfg, params = model
    seq = [int(t) for t in np.random.default_rng(7).integers(1, 200, 27)]
    np.testing.assert_allclose(half_filled_bucket.last_row(params, cfg, seq, 64),
                               ref_logits(model, seq)[26], atol=TOL)


def test_an_idle_slot_routes_to_no_expert_and_the_live_one_is_whole(model):
    """Slot 1 is idle: it counts no pick and reaches no routed expert, and
    the live slot's logits are what they are without it: the shared
    expert's products, which run over every row, skip nothing a live slot
    needs."""
    cfg, params = model
    gen = PagedGenerator(params, cfg, slots=2, num_blocks=8, block_tokens=BT,
                         max_len=64, attention_kernel="gather")
    pool, _state, last, keys = gen.init_state()
    seq = [5, 9, 200, 31, 77, 2, 14]
    pool, last, keys, _ = _prefill(gen, params, pool, last, keys,
                                   [1, 2, 0, 0], seq, 0, 0, 16)
    toks, _p, _s, last, _k, aux = gen.decode_fn(2)(
        params, pool, (), last, keys,
        np.asarray([[1, 2, 0, 0], [0, 0, 0, 0]], np.int32),
        np.asarray([len(seq), 0], np.int32), np.asarray([True, False]),
        np.ones(2, bool), np.zeros(2, np.float32))
    assert int(aux[0]) == 1 * 2 * cfg.num_experts_per_tok * cfg.expert_layers
    full = seq + [int(t) for t in np.asarray(toks)[0]]
    np.testing.assert_allclose(np.asarray(last[0]),
                               ref_logits(model, full)[-1], atol=TOL)


# -- (b) the stack: layer 0 is dense and has no router -------------------------

def test_the_dense_layer_is_layer_zero_and_has_no_router(model):
    cfg, params = model
    kinds = [sorted(k for k in lp if k not in ("attn", "norm_attn", "norm_ffn"))
             for lp in params["layers"]]
    assert kinds == [["ffn"]] + [["experts", "router", "router_bias",
                                  "shared"]] * cfg.expert_layers
    assert params["layers"][0]["ffn"]["w_gate"].shape == (64, 160)
    assert params["layers"][1]["shared"]["w_gate"].shape == (64, 32)
    assert params["layers"][1]["experts"]["w_gate_up"].shape == (4, 64, 64)
    assert params["layers"][1]["router"].shape == (64, 32)   # all 32 outputs
    assert kimi_k2.describe(cfg) == {
        "expert_layers": 2, "dense_layers": 1,
        "shared_expert_params": 3 * 64 * 32}
    full = kimi_k2.kimi_k2_share()
    assert (full.num_hidden_layers, full.expert_layers, full.held,
            full.vocab_size, full.max_seq_len) == (7, 6, (0, 12), 20480, 3072)
    assert kimi_k2.describe(full)["shared_expert_params"] == 44_040_192


# -- (c) planted faults: each moves logits past the tolerance -----------------

def _fault(name, params, cfg):
    """(params, config) of the program with one fault planted."""
    if name == "no_shared_expert":
        zero = lambda lp: dict(lp, shared=jax.tree.map(  # noqa: E731
            jnp.zeros_like, lp["shared"])) if "shared" in lp else lp
        return dict(params, layers=[zero(lp) for lp in params["layers"]]), cfg
    if name == "dense_ffn_skipped":
        l0 = dict(params["layers"][0], ffn=jax.tree.map(
            jnp.zeros_like, params["layers"][0]["ffn"]))
        return dict(params, layers=[l0] + params["layers"][1:]), cfg
    if name == "plain_rotary":       # every pair keeps its frequency: the
        # original context so long that no pair turns fewer than beta_fast
        # times over it; factor, and with it the softmax scale, as they are
        return params, cfg.replace(rope_scaling=dict(
            cfg.rope_scaling, original_max_position_embeddings=1e30))
    if name == "no_mscale":          # the softmax scale without m ** 2
        return params, cfg.replace(rope_scaling=dict(cfg.rope_scaling,
                                                     mscale_all_dim=0.0))
    raise KeyError(name)


@pytest.mark.parametrize("fault", ["no_shared_expert", "dense_ffn_skipped",
                                   "plain_rotary", "no_mscale"])
def test_a_planted_fault_moves_logits_past_the_tolerance(model, fault):
    cfg, params = model
    seq = [int(t) for t in np.random.default_rng(3).integers(1, 256, 40)]
    want = ref_logits(model, seq)[-1]
    bad_params, bad_cfg = _fault(fault, params, cfg)
    if fault == "plain_rotary":
        assert bad_cfg.mscale == cfg.mscale                  # only the rotary
        np.testing.assert_array_equal(
            np.asarray(layers.rope_frequencies(
                cfg.rope_theta, 8, dict(bad_cfg.rope_scaling))),
            np.asarray(layers.rope_frequencies(cfg.rope_theta, 8)))
    got = {}
    for name, (p, c) in {"whole": (params, cfg),
                         "fault": (bad_params, bad_cfg)}.items():
        gen = PagedGenerator(p, c, slots=1, num_blocks=8, block_tokens=BT,
                             max_len=64, attention_kernel="gather")
        pool, _state, last, keys = gen.init_state()
        _pool, last, _keys, _aux = _prefill(gen, p, pool, last, keys,
                                            [1, 2, 3, 0], seq, 0, 0, 64)
        got[name] = np.asarray(last[0])
    np.testing.assert_allclose(got["whole"], want, atol=TOL)
    assert np.abs(got["fault"] - want).max() > 50 * TOL, fault


# -- (d) the router ------------------------------------------------------------

@pytest.fixture(scope="module")
def layer():
    k = jax.random.split(jax.random.key(5), 3)
    N, D, E = 40, 32, 24
    return {"h": jax.random.normal(k[0], (N, D)),
            "w_r": jax.random.normal(k[1], (D, E)) * 0.3,
            "bias": jax.random.normal(k[2], (E,)) * 0.05}


def test_sigmoid_scores_bias_picks_and_weights_sum_to_the_scale(layer):
    h, w_r = layer["h"], layer["w_r"]
    s = jax.nn.sigmoid(h @ w_r)
    route = lambda b: moe.route_topk(  # noqa: E731
        h, w_r, b, topk=4, scale=2.827, score="sigmoid", renormalise=True)
    idx0, w0 = route(jnp.zeros_like(layer["bias"]))
    idx1, w1 = route(layer["bias"])
    # the bias changes picks ...
    assert (np.sort(idx0, -1) != np.sort(idx1, -1)).any()
    np.testing.assert_array_equal(np.sort(idx0, -1),
                                  np.sort(jax.lax.top_k(s, 4)[1], -1))
    np.testing.assert_array_equal(
        np.sort(idx1, -1), np.sort(jax.lax.top_k(s + layer["bias"], 4)[1], -1))
    # ... and never weights: the UNBIASED sigmoid scores, renormalised over
    # the picks, times the scale; they sum to the scale
    for idx, w in ((idx0, w0), (idx1, w1)):
        picked = jnp.take_along_axis(s, idx, axis=-1)
        np.testing.assert_allclose(
            w, 2.827 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(w).sum(-1), 2.827, rtol=1e-5)
    # scores are each output's own sigmoid, not a softmax over the outputs
    _i, plain = moe.route_topk(h, w_r, layer["bias"], topk=4, scale=1.0,
                               score="sigmoid")
    np.testing.assert_allclose(plain, jnp.take_along_axis(s, idx1, axis=-1),
                               rtol=1e-5)
    assert float(np.asarray(s).sum(-1).min()) > 4.0
    with pytest.raises(ValueError, match="scoring rule"):
        moe.route_topk(h, w_r, layer["bias"], topk=4, scale=1.0, score="tanh")


def test_longcats_rule_is_the_default_bit_for_bit(layer):
    """``route_topk`` without the new arguments is the rule it had: float32
    softmax over all outputs, the bias selects, ``scale * s`` unrenormalised
    (written out here as the function's body was)."""
    h, w_r, bias = layer["h"], layer["w_r"], layer["bias"]
    logits = jnp.einsum("nd,de->ne", h.astype(jnp.float32),
                        w_r.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), 3)
    want = 6.0 * jnp.take_along_axis(s, idx, axis=-1)
    got_idx, got = moe.route_topk(h, w_r, bias, topk=3, scale=6.0)
    np.testing.assert_array_equal(np.asarray(got_idx), np.asarray(idx))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- (e) the share: all shares + the shared expert once = the uncut layer ------

@pytest.mark.parametrize("shares", [32, 8])
def test_shares_sum_to_the_uncut_layer(shares):
    """The parts that all the shares' held experts give, plus the shared
    expert ONCE, are the uncut reference's expert layer. Every share
    normalises a token's weights over all its picks, most of which lie on
    other shares: a normaliser over the picks held here would make each
    share's weights sum to the scale, and the sum of shares too large."""
    per = 32 // shares
    cfg = kimi_k2.tiny(held=(0, 32))              # the uncut layer's weights
    params = kimi_k2.init_params(cfg, jax.random.key(2))
    lp = params["layers"][1]
    lw = ref.weights(params)["layers"][1]
    h = jax.random.normal(jax.random.key(4), (1, 24, cfg.hidden_size))
    live = jnp.ones((1, 24), bool)
    rc = ref_config(cfg)
    shared = np.asarray(ref.shared_part(lw, h))
    uncut = np.asarray(ref.routed_part(lw, h, rc)) + shared
    assert np.abs(shared).max() > 0.01            # the part counted once
    prog, plain = [], []
    for first in range(0, 32, per):
        part = cfg.replace(held=(first, per))
        lp_part = dict(lp, experts=jax.tree.map(
            lambda w: w[first:first + per], lp["experts"]))
        out, counts = kimi_k2.expert_layer(lp_part, h, live, part)
        prog.append(np.asarray(out))
        assert int(counts[0]) == 24 * cfg.num_experts_per_tok
        lw_part = dict(lw, w_gate_up=lw["w_gate_up"][first:first + per],
                       w_down=lw["w_down"][first:first + per])
        plain.append(np.asarray(ref.routed_part(
            lw_part, h, ref_config(cfg, held=(first, per)))))
    # every chip computes the shared expert alike: count it once
    np.testing.assert_allclose(sum(prog) - (shares - 1) * shared, uncut,
                               atol=TOL)
    np.testing.assert_allclose(sum(plain) + shared, uncut, atol=TOL)
    out, counts = kimi_k2.expert_layer(lp, h, live, cfg)
    np.testing.assert_allclose(np.asarray(out), uncut, atol=TOL)
    assert int(counts[0]) == int(counts[2]) == 24 * 4 and int(counts[1]) == 0


def test_a_normaliser_over_the_held_picks_alone_is_not_the_layer():
    """The wrong normaliser, written out: weights renormalised over the
    picks that land on THIS share. The shares then do not sum to the layer."""
    cfg = kimi_k2.tiny(held=(0, 32))
    params = kimi_k2.init_params(cfg, jax.random.key(2))
    lp = params["layers"][1]
    lw = ref.weights(params)["layers"][1]
    h = jax.random.normal(jax.random.key(4), (1, 24, cfg.hidden_size))
    flat = h.reshape(24, -1)
    uncut = np.asarray(ref.routed_part(lw, h, ref_config(cfg)))[0]
    idx, w = moe.route_topk(flat, lp["router"], lp["router_bias"], topk=4,
                            scale=cfg.routed_scaling_factor, score="sigmoid",
                            renormalise=True)
    right, wrong = 0.0, 0.0
    for first in range(0, 32, 8):
        ex = jax.tree.map(lambda a: a[first:first + 8], lp["experts"])
        here = (idx >= first) & (idx < first + 8)
        w_here = jnp.where(here, w, 0.0)
        w_bad = cfg.routed_scaling_factor * w_here / (
            w_here.sum(-1, keepdims=True) + 1e-20)
        for weights, name in ((w, "right"), (w_bad, "wrong")):
            out, _ = moe.held_experts_ffn(
                flat, idx, weights, ex["w_gate_up"], ex["w_down"],
                held=(first, 8), n_routed=32)
            if name == "right":
                right = right + np.asarray(out)
            else:
                wrong = wrong + np.asarray(out)
    np.testing.assert_allclose(right, uncut, atol=TOL)
    assert np.abs(wrong - uncut).max() > 50 * TOL


# -- (f) YaRN -------------------------------------------------------------------

def test_yarn_frequencies_against_the_closed_form():
    """Kimi-K2.5's published parameters: 32 pairs of a 64-wide rotary part,
    base 50,000, factor 64 over 4,096 original positions, beta 32 / 1. The
    correction dimensions are floor(8.91) = 8 and ceil(19.16) = 20: pairs
    0-8 keep their frequency, pairs 20-31 are slowed 64 times, the 11
    between blend linearly."""
    y = dict(kimi_k2.KimiK2Config().rope_scaling)
    got = np.asarray(layers.rope_frequencies(50000.0, 64, y))
    plain = np.asarray(layers.rope_frequencies(50000.0, 64))
    theta = 50000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(plain, theta, rtol=1e-5)
    dim = lambda turns: 64 * math.log(4096 / (turns * 2 * math.pi)) / (  # noqa: E731
        2 * math.log(50000.0))
    assert (math.floor(dim(32)), math.ceil(dim(1))) == (8, 20)
    np.testing.assert_allclose(got[:9], theta[:9], rtol=1e-5)        # kept
    np.testing.assert_allclose(got[20:], theta[20:] / 64, rtol=1e-5)  # slowed
    g = 1.0 - (np.arange(9, 20) - 8) / 12.0
    np.testing.assert_allclose(
        got[9:20], theta[9:20] / 64 * (1 - g) + theta[9:20] * g, rtol=1e-5)
    assert (got[9:] < plain[9:] * 0.95).all()      # it is not plain rotary
    # the reference states the same frequencies on its own
    np.testing.assert_allclose(got, np.asarray(ref.yarn_inv_freq(
        {"rope_scaling": y, "qk_rope_head_dim": 64, "rope_theta": 50000.0})),
        rtol=1e-6)
    # rope(..., base=) alone is what it was; freqs= replaces the frequencies
    x = jax.random.normal(jax.random.key(0), (1, 5, 2, 64))
    pos = jnp.arange(5)[None] + 3000
    np.testing.assert_array_equal(
        np.asarray(layers.rope(x, pos, base=50000.0)),
        np.asarray(layers.rope(x, pos, base=50000.0, freqs=jnp.asarray(plain))))
    assert np.abs(np.asarray(layers.rope(x, pos, base=50000.0))
                  - np.asarray(layers.rope(x, pos, freqs=jnp.asarray(got)))
                  ).max() > 0.1


def test_the_softmax_scale_carries_mscale_squared():
    cfg = kimi_k2.kimi_k2_share()
    m = 0.1 * 1.0 * math.log(64.0) + 1.0
    assert abs(m - 1.4159) < 1e-4 and abs(cfg.mscale - m) < 1e-12
    spec = cfg.latent_spec()
    assert abs(spec.softmax_scale - 192 ** -0.5 * m * m) < 1e-12
    assert spec.q_scale is None and spec.kv_scale is None and spec.heads_major
    assert abs(ref.softmax_scale(
        {"rope_scaling": dict(cfg.rope_scaling), "qk_nope_head_dim": 128,
         "qk_rope_head_dim": 64}) - spec.softmax_scale) < 1e-12
    assert layers.yarn_mscale(1.0) == 1.0


# -- (g) the engine and the deployment -----------------------------------------

@pytest.fixture(scope="module")
def engine(model):
    cfg, params = model
    eng = LLMEngine(params, cfg, prompt_buckets=(16, 64), chunk=4,
                    slots=2, max_queue=0, name="kimi-test",
                    block_tokens=BT, pool_blocks=33,
                    attention_kernel="interpret")
    eng.warmup()
    return eng


def _served_gap(model, prompt, toks):
    logits = ref_logits(model, list(prompt) + list(toks))
    rows = logits[len(prompt) - 1:len(prompt) - 1 + len(toks)]
    return float((rows.max(-1) - rows[np.arange(len(toks)), toks]).max())


def test_engine_serves_the_family_with_prefix_reuse(model, engine):
    prompts = [[7, 3, 11, 200, 5], list(range(30, 52))]
    outs = [None, None]

    def run(i):
        outs[i] = engine.generate(prompts[i], max_new_tokens=8)

    threads = [threading.Thread(target=run, args=(i,)) for i in range(2)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    for p, o in zip(prompts, outs):
        assert len(o) == 8 and _served_gap(model, p, o) <= TOL
    before = engine.kv.stats()
    turn2 = prompts[1] + outs[1] + [9, 8, 7]
    out2 = engine.generate(turn2, max_new_tokens=4)
    after = engine.kv.stats()
    # rows only, no slot state: the prefix cache is ON for this family
    assert after["kv_hit_tokens"] - before["kv_hit_tokens"] == 30
    assert after["kv_cow_copies"] - before["kv_cow_copies"] == 1
    assert _served_gap(model, turn2, out2) <= TOL
    assert engine.kv.active_blocks() == 0
    s = engine.stats()
    E, k = model[0].expert_layers, model[0].num_experts_per_tok
    assert s["moe_steps_total"] > 0 and s["moe_picks_zero_total"] == 0
    assert 0 < s["moe_picks_held_total"] <= s["moe_picks_total"]
    assert s["moe_picks_total"] % (E * k) == 0
    assert 0 < s["moe_experts_hit_total"] <= s["moe_steps_total"] * E * 4
    assert s["moe_prefill_picks_total"] >= (5 + 22 + 3) * E * k
    assert s["moe_prefill_picks_zero_total"] == 0
    d = engine.describe()
    assert d["model_family"] == "KimiK2Config"
    assert d["kv_pool_shapes"] == [[3, 33, BT, 128]]
    assert d["slot_state_shapes"] == [] and d["params_working_bytes"] == 0
    assert (d["expert_layers"], d["dense_layers"],
            d["shared_expert_params"]) == (2, 1, 3 * 64 * 32)


def test_the_family_names_its_counts_as_longcat_does(model):
    from ray_tpu.models import longcat

    fam = model[0].paged_family()
    assert fam.aux_counts == longcat.PAGED_FAMILY.aux_counts
    assert fam.unsupported == ()
    assert fam.init_slot_state is None and fam.working_params is None
    import inspect

    from ray_tpu.serve import llm
    source = inspect.getsource(llm).lower()
    assert "kimi" not in source and "moe" not in source


def test_held_pairs_are_stamped_on_the_step_span(model, engine):
    from ray_tpu.util import tracing

    engine.generate([1, 2, 3], max_new_tokens=4)
    steps = [s for s in tracing.recorded() if s.name == "llm.step"
             and (s.attrs or {}).get("engine") == "kimi-test"
             and (s.attrs or {}).get("tokens")]
    assert steps and all("moe_held_pairs" in s.attrs for s in steps)


def test_the_programs_carry_the_named_scopes(model):
    cfg, params = model
    gen = PagedGenerator(params, cfg, slots=2, num_blocks=8, block_tokens=BT,
                         max_len=64, attention_kernel="gather")
    pool, state, last, keys = gen.init_state()
    text = gen.decode_fn(2).lower(
        params, pool, state, last, keys, np.zeros((2, 4), np.int32),
        np.zeros(2, np.int32), np.ones(2, bool), np.ones(2, bool),
        np.zeros(2, np.float32)).as_text(debug_info=True)
    for scope in ("moe_shared", "moe_experts", "dense_ffn", "kv_pool_write"):
        assert scope in text, scope


# What the engine owes a request whatever it serves (tests/engine_contract.py);
# the streams a check hands back are held to the reference.
@engine_contract.each_check
def test_engine_contract(model, check):
    cfg, params = model
    for prompt, toks in check(params, cfg, engine_contract.ENGINE_KW):
        assert _served_gap(model, prompt, toks) < TOL


def test_llm_deployment_streams_the_family(ray_start_regular, model):
    from ray_tpu import serve

    cfg, _params = model
    try:
        LM = llm_deployment(
            cfg, lambda: kimi_k2.init_params(cfg, jax.random.key(1)),
            name="Kimi", slots=2, chunk=4)
        handle = serve.run(LM.bind())
        prompt = [5, 9, 200, 31, 77, 2]
        items = list(handle.options(stream=True).remote(
            {"prompt_ids": prompt, "max_new_tokens": 6}))
        toks = [it["token"] for it in items]
        assert [it["index"] for it in items] == list(range(6))
        assert items[-1]["finish_reason"] == "stop"
        assert _served_gap(model, prompt, toks) <= TOL
    finally:
        serve.shutdown()
