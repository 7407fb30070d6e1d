"""Nemotron-H on the paged serve path, against its plain reference.

Every comparison is with ``benchmark/reference/nemotron_h_plain.py`` (the
file the benchmark's ``correct`` uses: float32, the recurrence token by
token, a full causal softmax a query head, the experts a loop, no cache) on
seeded weights at a small size: ``nemotron_h.tiny()``, seven layers
``MEM*EM*`` (3 mixers, 2 expert layers, 2 attention layers: the later layers
of a kind index the state and the pool past the first's), width 64, 4 query
heads over 2 KV heads of 64, 4 mixer heads of 16 in 2 groups, state 128, 8
routed experts of which 4 held, top-3, a shared expert.

Tolerance 2e-4 on logits everywhere: program and reference are both float32
here (``conftest`` pins matmul precision to ``highest``), so what differs is
only the order of summation: the chunk-wise scan against the token-by-token
recurrence, the kernel's online softmax against a dense one, the grouped
product against a loop. A zeroed state, a wrong tail, a query head on the
wrong KV head, an un-squared activation, a missing shared expert or a
skipped layer moves logits by 1e-2 and more.
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
from ray_tpu.models import nemotron_h
from ray_tpu.models.generate import PagedGenerator
from ray_tpu.ops import moe
from ray_tpu.serve.llm import LLMEngine, llm_deployment

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ref = load_file(REPO, "benchmark/reference/nemotron_h_plain.py")
TOL = 2e-4
BT = 16


def ref_config(cfg, held=None, **over) -> dict:
    """The configuration's dict as the benchmark's file would state it."""
    c = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    first, count = held or cfg.held
    c["held"] = {"first": first, "count": count, "of": cfg.n_routed_experts}
    c.update(over)
    return c


def ref_logits(model, seq, **over):
    cfg, params = model
    return np.asarray(ref.forward(ref.weights(params),
                                  jnp.asarray([seq], jnp.int32),
                                  ref_config(cfg, **over)))[0]


def served_gap(model, prompt, toks):
    logits = ref_logits(model, list(prompt) + list(toks))
    rows = logits[len(prompt) - 1:len(prompt) - 1 + len(toks)]
    return float((rows.max(-1) - rows[np.arange(len(toks)), toks]).max())


@pytest.fixture(scope="module")
def model():
    cfg = nemotron_h.tiny()
    return cfg, nemotron_h.init_params(cfg, jax.random.key(1))


@pytest.fixture(scope="module")
def engine(model):
    cfg, params = model
    eng = LLMEngine(params, cfg, prompt_buckets=(16, 64), chunk=4,
                    slots=2, max_queue=0, name="nemotron-test",
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


def last_row(cfg, params, seq):
    """The program's logits after a prefill of ``seq`` (the gather path)."""
    gen = PagedGenerator(params, cfg, slots=1, num_blocks=5, block_tokens=BT,
                         max_len=64, attention_kernel="gather")
    dev = prefill(gen, params, gen.init_state(), [1, 2, 0, 0], seq, 0, 64)
    return np.asarray(dev[2][0])


def test_the_stack_is_read_off_the_pattern(model):
    cfg = model[0]
    assert (cfg.mixer_layers, cfg.expert_layers, cfg.attention_layers) == (3, 2, 2)
    assert [cfg.kind_index(l) for l in range(7)] == [0, 0, 1, 0, 1, 2, 1]
    assert cfg.n_layers == 2                    # the pool's layers
    full = nemotron_h.NemotronHConfig()
    assert (full.mixer_layers, full.expert_layers, full.attention_layers) == (
        23, 23, 6)
    assert (full.d_inner, full.conv_channels, full.in_proj_width) == (
        4096, 6144, 10304)
    cut = nemotron_h.nemotron_nano_share()
    assert cut.hybrid_override_pattern == "MEMEM*EMEMEM*" == nemotron_h.PATTERN[:13]
    assert (cut.mixer_layers, cut.expert_layers, cut.attention_layers) == (6, 5, 2)
    assert cut.state_bytes_per_slot == 12_804_096
    with pytest.raises(ValueError):
        nemotron_h.tiny(hybrid_override_pattern="MEM-EM*")
    with pytest.raises(ValueError):
        nemotron_h.tiny(hybrid_override_pattern="MEMEMEM")   # no pool


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
    toks = np.asarray(toks)
    for slot, seq in ((0, a), (2, b)):
        full = seq + [int(t) for t in toks[slot]]
        logits = ref_logits(model, full)
        rows = logits[len(seq) - 1:len(full) - 1]
        gap = rows.max(-1) - rows[np.arange(4), toks[slot]]
        assert gap.max() <= TOL, gap
        np.testing.assert_allclose(np.asarray(last[slot]), logits[-1],
                                   atol=TOL)
    # the pool is the two attention layers', the state the three mixers'
    assert [p.shape for p in pool] == [(2, 9, BT, 2 * 64)] * 2
    assert state[0].shape == (3, 3, 128, 64) and state[0].dtype == jnp.float32
    assert state[1].shape == (3, 3, 3, 64 + 2 * 2 * 128)
    # the parked slot's state never moved from zero, and it routed nowhere:
    # 4 token steps x 2 live slots x 2 expert layers x top-3
    assert not np.asarray(state[0][:, 1]).any()
    assert not np.asarray(state[1][:, :, 1]).any()
    counts = dict(zip(moe.PICK_COUNT_NAMES, np.asarray(aux)))
    assert counts["picks"] == 4 * 2 * 2 * 3 and counts["picks_zero"] == 0
    assert 0 < counts["picks_held"] < counts["picks"]
    assert np.asarray(aux)[-1] == 4                     # moe_steps_total


def test_a_half_filled_bucket_walks_for_its_real_rows(model):
    """100 tokens in the 256 bucket, two query tiles of the attention kernel:
    the first straddles the prompt's end, the second is pad rows alone and is
    skipped; the table behind the prompt's blocks is the trash block. The last
    real row's logits are the reference's."""
    cfg, params = model
    seq = [int(t) for t in np.random.default_rng(7).integers(1, 200, 100)]
    np.testing.assert_allclose(half_filled_bucket.last_row(params, cfg, seq, 256),
                               ref_logits(model, seq)[99], atol=TOL)


# -- each published constant moved alone moves both sides alike -----------------

@pytest.mark.parametrize("moved", [
    "routed_scaling_factor", "norm_topk_prob", "correction_bias",
    "squared_activation", "group_norm_gain"])
def test_no_published_constant_is_dead(model, moved, monkeypatch):
    """The constant moved ALONE changes the program's logits, and program
    and reference still agree: neither side drops it or folds it away. The
    squared activation has no switch in the program (one form an expert
    family): it is taken out of the program from outside, and the reference
    is told ``mlp_hidden_act: relu``."""
    cfg, params = model
    seq = [int(t) for t in np.random.default_rng(5).integers(1, 256, 21)]
    base = ref_logits(model, seq)[-1]
    np.testing.assert_allclose(last_row(cfg, params, seq), base, atol=TOL)
    over = {}
    if moved == "routed_scaling_factor":
        cfg = cfg.replace(routed_scaling_factor=4.0)
    elif moved == "norm_topk_prob":
        cfg = cfg.replace(norm_topk_prob=False)
    elif moved == "correction_bias":      # large enough to change the picks
        params = dict(params, layers=[
            dict(lp, router_bias=lp["router_bias"] * 25.0)
            if "router_bias" in lp else lp for lp in params["layers"]])
    elif moved == "group_norm_gain":      # one GROUP's gains: the second's
        E = cfg.d_inner
        scale = jnp.where(jnp.arange(E) >= E // 2, 1.5, 1.0)
        params = dict(params, layers=[
            dict(lp, ssm_norm=lp["ssm_norm"] * scale)
            if "ssm_norm" in lp else lp for lp in params["layers"]])
    else:
        over = {"mlp_hidden_act": "relu"}
        monkeypatch.setattr(moe, "_expert_hidden",
                            lambda p, F, form: jax.nn.relu(p))
        monkeypatch.setattr(nemotron_h, "relu2_ffn", lambda fp, x, dtype: (
            jax.nn.relu(x @ fp["w_up"]) @ fp["w_down"]).astype(dtype))
        nemotron_h._layer_fn.cache_clear()
    try:
        got = last_row(cfg, params, seq)
    finally:
        monkeypatch.undo()
        nemotron_h._layer_fn.cache_clear()
    assert np.abs(got - base).max() > 100 * TOL, moved
    np.testing.assert_allclose(
        got, ref_logits((cfg, params), seq, **over)[-1], atol=TOL)


def test_the_norm_is_taken_over_each_group(model, monkeypatch):
    """``RMSNorm_group``: the mean square over each group's channels. Taken
    over the whole ``d_inner`` instead, the program leaves the reference."""
    cfg, params = model
    seq = [int(t) for t in np.random.default_rng(6).integers(1, 256, 21)]
    want = ref_logits(model, seq)[-1]
    plain = nemotron_h._gated_out
    monkeypatch.setattr(nemotron_h, "_gated_out", lambda lw, y, z, c: plain(
        lw, y, z, c.replace(n_groups=1)))
    nemotron_h._layer_fn.cache_clear()
    try:
        got = last_row(cfg, params, seq)
    finally:
        monkeypatch.undo()
        nemotron_h._layer_fn.cache_clear()
    assert np.abs(got - want).max() > 100 * TOL


def test_the_router_is_kimis_rule(model):
    """Sigmoid scores in float32, the bias selects and never weighs, the
    unbiased scores renormalised over the picks and scaled by 2.5: program
    and reference pick the same experts with the same weights."""
    cfg, params = model
    lp = params["layers"][1]
    lw = ref.weights(params)["layers"][1]
    h = jax.random.normal(jax.random.key(7), (24, cfg.hidden_size))
    idx, w = moe.route_topk(h, lp["router"], lp["router_bias"],
                            topk=cfg.num_experts_per_tok,
                            scale=cfg.routed_scaling_factor, score="sigmoid",
                            renormalise=True)
    ridx, rw = ref.router(lw, h, ref_config(cfg))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
    np.testing.assert_allclose(np.asarray(w), np.asarray(rw), atol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, atol=1e-5)
    assert lp["router"].dtype == lp["router_bias"].dtype == jnp.float32


# -- the share: both shares + the shared expert once = the uncut layer ----------

def test_shares_sum_to_the_uncut_layer():
    """With 8 routed experts, the parts that the shares ``held = (0, 4)`` and
    ``(4, 4)`` give, the shared expert counted ONCE, are the uncut
    reference's expert layer."""
    cfg = nemotron_h.tiny(held=(0, 8))              # the uncut layer's weights
    params = nemotron_h.init_params(cfg, jax.random.key(2))
    lp = params["layers"][1]
    lw = ref.weights(params)["layers"][1]
    h = jax.random.normal(jax.random.key(4), (1, 24, cfg.hidden_size))
    live = jnp.ones((1, 24), bool)
    uncut_c = ref_config(cfg)
    shared = np.asarray(ref.shared_part(lw, h[0], uncut_c))
    uncut = np.asarray(ref.experts(lw, h[0], uncut_c))
    assert np.abs(shared).max() > 0.01              # the part counted once
    assert np.abs(uncut - shared).max() > 0.01      # and the routed part
    prog, plain = [], []
    for first in (0, 4):
        part = cfg.replace(held=(first, 4))
        lp_part = dict(lp, experts=jax.tree.map(
            lambda w: w[first:first + 4], lp["experts"]))
        out, counts = nemotron_h.expert_layer(lp_part, h, live, part)
        prog.append(np.asarray(out[0]))
        assert int(counts[0]) == 24 * cfg.num_experts_per_tok
        lw_part = dict(lw, w_up=lw["w_up"][first:first + 4],
                       w_down=lw["w_down"][first:first + 4])
        plain.append(np.asarray(ref.routed_part(
            lw_part, h[0], ref_config(cfg, held=(first, 4)))))
    # both chips compute the shared expert alike: count it once
    np.testing.assert_allclose(sum(prog) - shared, uncut, atol=TOL)
    np.testing.assert_allclose(sum(plain) + shared, uncut, atol=TOL)


def test_the_init_conditions_each_sublayer():
    """At the published constants (widths cut: this is a CPU test) the init
    gives what its docstring says: each kind of sublayer adds about one to
    the stream's mean square, logits have a standard deviation near one, the
    router's scores spread, decays spread so that a state remembers."""
    cfg = nemotron_h.NemotronHConfig(
        vocab_size=512, hidden_size=256, num_hidden_layers=3,
        hybrid_override_pattern="ME*", num_attention_heads=4,
        num_key_value_heads=2, head_dim=32, mamba_num_heads=8,
        mamba_head_dim=32, n_groups=2, ssm_state_size=32, n_routed_experts=16,
        num_experts_per_tok=6, moe_intermediate_size=128,
        moe_shared_expert_intermediate_size=256, held=(0, 16), max_seq_len=128,
        dtype=jnp.float32, param_dtype=jnp.float32)
    params = nemotron_h.init_params(cfg, jax.random.key(3))
    seq = [int(t) for t in np.random.default_rng(1).integers(1, 512, 96)]
    logits = ref_logits((cfg, params), seq)
    assert 0.5 < logits.std() < 2.0, logits.std()
    c, w = ref_config(cfg), ref.weights(params)
    x = jnp.asarray(np.asarray(params["tok_embed"])[seq])
    assert 0.8 < float(jnp.sqrt((x ** 2).mean())) < 1.25
    for kind, lw in zip("ME*", w["layers"]):
        u = ref._rms(x, lw["norm"], cfg.layer_norm_epsilon)
        rms = float(jnp.sqrt((ref.SUBLAYERS[kind](lw, u, c) ** 2).mean()))
        assert 0.3 < rms < 2.5, (kind, rms)
    scores = jax.nn.sigmoid(ref._rms(x, w["layers"][1]["norm"], 1e-5)
                            @ w["layers"][1]["router"])
    assert float(scores.std()) > 0.15               # not a router of 0.01
    a = np.exp(np.asarray(params["layers"][0]["A_log"]))
    dt = np.log1p(np.exp(np.asarray(params["layers"][0]["dt_bias"])))
    assert a.min() >= 1.0 and a.max() <= 16.0
    assert dt.min() >= 0.99e-3 and dt.max() <= 1.01e-1
    assert (1.0 / (dt * a)).max() > 20              # tokens a head remembers


def test_the_check_has_teeth(model):
    """The same prefill and decode, damaged before ONE decode step: slot 0's
    state zeroed, its convolution tail zeroed, its K/V rows rolled by a KV
    head (what a query head on the wrong KV head reads). The logits after
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
    np.testing.assert_allclose(last, ref_logits(model, a + toks)[-1], atol=TOL)
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
    no prefix hit, nothing registered, and the refusals counted; the state
    AND the expert counters are in one ``stats()``."""
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
    # 3 mixer layers x 2 slots x (state 128 x 64 float32 + tail 3 x 576 float32)
    assert after["state_bytes"] == 3 * 2 * (128 * 64 * 4 + 3 * 576 * 4)
    assert after["moe_steps_total"] > before["moe_steps_total"]
    picks = after["moe_picks_total"] - before["moe_picks_total"]
    assert picks > 0 and after["moe_picks_zero_total"] == 0
    assert 0 < after["moe_picks_held_total"] < after["moe_picks_total"]
    assert after["moe_prefill_picks_total"] > 0
    d = engine.describe()
    assert d["model_family"] == "NemotronHConfig"
    assert d["kv_pool_shapes"] == [[2, 33, BT, 128]] * 2
    assert d["slot_state_shapes"] == [[3, 2, 128, 64], [3, 3, 2, 576]]
    assert (d["mixer_layers"], d["expert_layers"], d["attention_layers"],
            d["held"]) == (3, 2, 2, 4)
    assert d["state_bytes_per_slot"] * 2 == after["state_bytes"]


def test_the_step_span_carries_the_state_and_the_experts(model, engine):
    """``llm.step`` carries ``state_slots`` AND ``moe_held_pairs``: the two
    largest byte streams of a decode step in one program."""
    from ray_tpu.util import tracing

    t0 = tracing.now_ns()
    engine.generate([4, 5, 6, 7, 8, 9], max_new_tokens=8)
    steps = [s for s in tracing.recorded(t0) if s.name == "llm.step"
             and s.attrs.get("engine") == "nemotron-test"
             and s.attrs.get("tokens")]
    assert steps and all("moe_held_pairs" in s.attrs for s in steps)
    assert any(s.attrs.get("state_slots") == s.attrs["batch"] > 0
               for s in steps)


def test_a_slots_second_request_starts_from_a_zero_state(model, engine):
    cfg, params = model
    engine.generate(list(range(60, 100)), max_new_tokens=12)
    p = [9, 8, 7, 250, 1, 2, 3]
    second = engine.generate(p, max_new_tokens=8)
    fresh = LLMEngine(params, cfg, prompt_buckets=(16, 64), chunk=4, slots=2,
                      max_queue=0, name="nemotron-fresh", block_tokens=BT,
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


def test_the_engine_and_the_manager_needed_no_edit_for_the_family(model):
    """PR 31's seam holds a family with a slot state and routed experts in
    one program: the engine and the block manager name nothing of it."""
    from ray_tpu.models import generate
    from ray_tpu.serve import llm

    src = inspect.getsource(llm) + inspect.getsource(generate.KVBlockManager)
    assert not any(word in src for word in (
        "nemotron", "hybrid_override", "mamba", "ssd", "mixer_layers"))
    fam = model[0].paged_family()
    assert fam.unsupported == ("prefix_cache",)
    assert [n.decode for n in fam.aux_counts][-1] == "moe_steps_total"


def test_a_program_lowers_one_function_a_kind(model):
    """Seven layers of three kinds: the lowered decode program holds three
    layer functions, called 3 + 2 + 2 times; and it carries the scopes and
    the kernels' names the profiler shows."""
    cfg, params = model
    gen = PagedGenerator(params, cfg, slots=2, num_blocks=9, block_tokens=BT,
                         max_len=64, attention_kernel="interpret")
    pool, state, last, keys = gen.init_state()
    args = (params, pool, state, last, keys, np.zeros((2, 4), np.int32),
            np.zeros(2, np.int32), np.ones(2, bool), np.ones(2, bool),
            np.zeros(2, np.float32))
    text = gen.decode_fn(1).lower(*args).as_text(debug_info=True)
    assert text.count("func.func private @layer") == 3
    assert text.count("call @layer") == cfg.num_hidden_layers
    for scope in ("ssm_mixer", "ssm_conv", "moe_router", "moe_experts",
                  "moe_shared", "attn_full", "kv_pool_write"):
        assert scope in text, scope
    jaxpr = str(jax.make_jaxpr(gen.decode_fn(1))(*args))
    assert "ssd_decode" in jaxpr and "paged_decode_attn" in jaxpr


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
            cfg, lambda: nemotron_h.init_params(cfg, jax.random.key(1)),
            name="NemotronH", slots=2, chunk=4)
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
