"""LongCat-Flash on the paged serve path, against its plain reference.

Every comparison is with ``benchmark/reference/longcat_plain.py`` (the file
the benchmark's ``correct`` uses: float32, unabsorbed attention, a loop over
experts, no cache) on seeded weights at a small size: hidden 64, 4 heads,
16 routed + 8 zero-compute experts of which 4 are held, top-3, 2 layers,
interpreted kernels.

Tolerance 2e-4 on logits everywhere: program and reference are both float32
here (``conftest`` pins matmul precision to ``highest``), so what differs is
only the order of summation: absorbed against unabsorbed products, the
kernel's online softmax against a dense one, a grouped product over sorted
pairs against a loop over experts. A wrong block, a missing rotary, a
dropped pick or an unscaled latent moves logits by 1e-2 and more.
"""

import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import engine_contract
import half_filled_bucket
from benchmark.manifest import load_file
from ray_tpu.models import longcat
from ray_tpu.models.generate import PagedGenerator
from ray_tpu.ops import moe
from ray_tpu.serve.llm import LLMEngine, llm_deployment

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ref = load_file(REPO, "benchmark/reference/longcat_plain.py")
TOL = 2e-4
BT = 16


def ref_config(cfg: longcat.LongCatConfig, held=None) -> dict:
    """The flat keys the reference reads, as a configuration's file has
    them, for a program config object."""
    first, count = held if held is not None else cfg.held
    return {
        "hidden_size": cfg.hidden_size, "q_lora_rank": cfg.q_lora_rank,
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "rms_norm_eps": cfg.rms_norm_eps,
        "rope_theta": cfg.rope_theta, "moe_topk": cfg.moe_topk,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "expert_ffn_hidden_size": cfg.expert_ffn_hidden_size,
        "zero_expert_num": cfg.zero_expert_num,
        "held": {"first": first, "count": count,
                 "of": cfg.n_routed_experts}}


@pytest.fixture(scope="module")
def model():
    cfg = longcat.tiny()
    return cfg, longcat.init_params(cfg, jax.random.key(1))


def ref_logits(model, seq):
    cfg, params = model
    return np.asarray(ref.forward(ref.weights(params),
                                  jnp.asarray([seq], jnp.int32),
                                  ref_config(cfg))[0])


# -- (a) paged prefill, prefix hit, copy-on-write fork, decode ----------------

@pytest.mark.parametrize("kernel", ["gather", "interpret"])
def test_paged_prefill_and_decode_match_the_reference(model, kernel):
    """Sequence A prefills 37 tokens into blocks 1-3. Sequence B is A's 37
    tokens plus 6 more: it HITS A's two full blocks in place and forks A's
    partial third block copy-on-write (``copy_block`` 3 -> 5), then prefills
    only its 6-token suffix at ``start_pos`` 37. Both then decode a chunk in
    one program. Logits, not tokens, against the reference's full pass."""
    cfg, params = model
    V = cfg.vocab_size
    gen = PagedGenerator(params, cfg, slots=2, num_blocks=8, block_tokens=BT,
                         max_len=64, attention_kernel=kernel)
    pool, _state, last, keys = gen.init_state()
    rng = np.random.default_rng(0)
    a = [int(t) for t in rng.integers(1, V, 37)]
    b = a + [int(t) for t in rng.integers(1, V, 6)]

    def prefill(pool, last, keys, table, suffix, start, slot, bucket):
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(suffix)] = suffix
        pool, _state, last, keys, aux = gen.prefill_fn(bucket)(
            params, pool, (), last, keys, np.asarray(table, np.int32), padded,
            start, len(suffix), slot, 0)
        return pool, last, keys, aux

    pool, last, keys, aux = prefill(pool, last, keys, [1, 2, 3, 0], a, 0, 0, 64)
    np.testing.assert_allclose(np.asarray(last[0]), ref_logits(model, a)[36],
                               atol=TOL)
    # every real token routed top-k in every layer; pads routed nowhere
    assert int(aux[0]) == 37 * cfg.moe_topk * cfg.num_layers
    block3 = np.asarray(pool[0][:, 3])

    pool = gen.copy_fn()(pool, 3, 5)
    pool, last, keys, _ = prefill(pool, last, keys, [1, 2, 5, 0], b[37:], 37,
                                  1, 16)
    np.testing.assert_allclose(np.asarray(last[1]), ref_logits(model, b)[42],
                               atol=TOL)
    # the fork is private: B's suffix rows went to block 5, not to A's 3
    np.testing.assert_array_equal(np.asarray(pool[0][:, 3]), block3)

    tables = np.asarray([[1, 2, 3, 0], [1, 2, 5, 0]], np.int32)
    toks, pool, _state, last, keys, aux = gen.decode_fn(4)(
        params, pool, (), last, keys, tables, np.asarray([37, 43], np.int32),
        np.ones(2, bool), np.ones(2, bool), np.zeros(2, np.float32))
    toks = np.asarray(toks)
    assert int(aux[0]) == 2 * 4 * cfg.moe_topk * cfg.num_layers
    for slot, seq in ((0, a), (1, b)):
        full = seq + [int(t) for t in toks[slot]]
        logits = ref_logits(model, full)
        # each served token within TOL of the reference's largest logit ...
        rows = logits[len(seq) - 1:len(full) - 1]
        gap = rows.max(-1) - rows[np.arange(4), toks[slot]]
        assert gap.max() <= TOL, gap
        # ... and the carry after the chunk is the reference's next row
        np.testing.assert_allclose(np.asarray(last[slot]), logits[-1],
                                   atol=TOL)


def test_a_half_filled_bucket_walks_for_its_real_rows(model):
    """27 tokens in the 64 bucket, four query tiles of the latent kernel: one
    whole, one that straddles the prompt's end, two of pad rows alone that
    are skipped; the table behind the prompt's blocks is the trash block. The
    last real row's logits are the reference's."""
    cfg, params = model
    seq = [int(t) for t in np.random.default_rng(7).integers(1, 200, 27)]
    np.testing.assert_allclose(half_filled_bucket.last_row(params, cfg, seq, 64),
                               ref_logits(model, seq)[26], atol=TOL)


def test_an_idle_slot_routes_to_no_expert(model):
    cfg, params = model
    gen = PagedGenerator(params, cfg, slots=2, num_blocks=8, block_tokens=BT,
                         max_len=64, attention_kernel="gather")
    pool, _state, last, keys = gen.init_state()
    _t, _p, _s, _l, _k, aux = gen.decode_fn(2)(
        params, pool, (), last, keys, np.zeros((2, 4), np.int32),
        np.zeros(2, np.int32), np.asarray([True, False]), np.ones(2, bool),
        np.zeros(2, np.float32))
    assert int(aux[0]) == 1 * 2 * cfg.moe_topk * cfg.num_layers


# -- (b) absorbed decode attention against the unabsorbed reference -----------

@pytest.mark.parametrize("kernel", ["gather", "interpret"])
def test_absorbed_latent_attention_matches_unabsorbed(model, kernel):
    """One MLA sublayer: rows for 20 positions written through the paged
    path, then ONE decode query with ``W_kvb`` absorbed into q and into the
    output, against the reference's sublayer, which up-projects every row to
    per-head keys and values."""
    cfg, params = model
    ap = params["layers"][1]["attn"][1]
    T = 21
    x = jax.random.normal(jax.random.key(3), (1, T, cfg.hidden_size))
    want = np.asarray(ref._mla(ap, x, ref_config(cfg)))[0]
    (pool,) = cfg.paged_family().init_pool(cfg, 6, BT)
    table = jnp.asarray([[2, 4]], jnp.int32)
    pos = jnp.arange(T - 1)[None]
    out, pool = longcat._mla(
        ap, x[:, :T - 1], pool, 3, table[0][pos // BT], pos % BT, table,
        jnp.zeros((1,), jnp.int32), pos, cfg, kernel)
    np.testing.assert_allclose(np.asarray(out)[0], want[:T - 1], atol=TOL)
    last = jnp.asarray([[T - 1]])
    out, pool = longcat._mla(
        ap, x[:, T - 1:], pool, 3, table[0][last // BT], last % BT, table,
        jnp.asarray([T - 1], jnp.int32), last, cfg, kernel)
    np.testing.assert_allclose(np.asarray(out)[0, 0], want[T - 1], atol=TOL)
    # the cache holds kv_lora_rank + qk_rope_head_dim numbers a token, padded
    assert pool.shape == (cfg.attn_sublayers, 6, BT, 128)
    assert not np.asarray(pool[3, 2, :, cfg.latent_width:]).any()


# -- (c) the share: all shares + the zero-compute part once = the uncut layer -

def test_shares_sum_to_the_uncut_layer():
    cfg = longcat.tiny(held=(0, 16))              # the uncut layer's weights
    params = longcat.init_params(cfg, jax.random.key(2))
    lp = params["layers"][0]
    lw = ref.weights(params)["layers"][0]
    h = jax.random.normal(jax.random.key(4), (1, 24, cfg.hidden_size))
    uncut = np.asarray(ref.moe(lw, h, ref_config(cfg)))
    zero_only = np.asarray(ref.moe(lw, h, ref_config(cfg, held=(0, 0))))
    assert np.abs(zero_only).max() > 0.01         # the part counted once
    shares_prog, shares_ref = [], []
    for first in range(0, 16, 4):
        part = cfg.replace(held=(first, 4))
        lp_part = dict(lp, experts=jax.tree.map(
            lambda w: w[first:first + 4], lp["experts"]))
        out, _counts = longcat.expert_layer(lp_part, h, jnp.ones((1, 24), bool), part)
        shares_prog.append(np.asarray(out))
        lw_part = dict(lw, w_gate_up=lw["w_gate_up"][first:first + 4],
                       w_down=lw["w_down"][first:first + 4])
        shares_ref.append(np.asarray(ref.moe(
            lw_part, h, ref_config(cfg, held=(first, 4)), zero_part=False)))
    # every chip computes the zero-compute part alike: count it once
    np.testing.assert_allclose(sum(shares_prog) - 3 * zero_only, uncut,
                               atol=TOL)
    np.testing.assert_allclose(sum(shares_ref) + zero_only, uncut, atol=TOL)
    # and the uncut program layer is the uncut reference layer
    out, counts = longcat.expert_layer(lp, h, jnp.ones((1, 24), bool), cfg)
    np.testing.assert_allclose(np.asarray(out), uncut, atol=TOL)
    assert int(counts[0]) == int(counts[1]) + int(counts[2]) == 24 * 3


# -- (d) the router and the dropless layer ------------------------------------

def _expert(w_gate_up, w_down, e, x):
    F = w_down.shape[1]
    gu = x @ w_gate_up[e]
    return (jax.nn.silu(gu[..., :F]) * gu[..., F:]) @ w_down[e]


@pytest.fixture(scope="module")
def layer():
    k = jax.random.split(jax.random.key(5), 5)
    N, D, F, E, Z = 40, 32, 16, 8, 4
    return {"h": jax.random.normal(k[0], (N, D)),
            "w_r": jax.random.normal(k[1], (D, E + Z)) * 0.3,
            "bias": jax.random.normal(k[2], (E + Z,)) * 0.05,
            "w_gate_up": jax.random.normal(k[3], (E, D, 2 * F)) * 0.2,
            "w_down": jax.random.normal(k[4], (E, F, D)) * 0.2,
            "E": E, "Z": Z}


def test_selection_bias_changes_picks_not_weights(layer):
    h, w_r = layer["h"], layer["w_r"]
    s = jax.nn.softmax(h @ w_r, axis=-1)
    idx0, w0 = moe.route_topk(h, w_r, jnp.zeros_like(layer["bias"]),
                              topk=3, scale=6.0)
    idx1, w1 = moe.route_topk(h, w_r, layer["bias"], topk=3, scale=6.0)
    assert (np.sort(idx0, -1) != np.sort(idx1, -1)).any()
    # a pick's weight is scale * s there, with or without the bias
    for idx, w in ((idx0, w0), (idx1, w1)):
        np.testing.assert_allclose(
            w, 6.0 * jnp.take_along_axis(s, idx, axis=-1), rtol=1e-5)
    # the unbiased picks are the top of s itself; weights are not renormalised
    np.testing.assert_array_equal(np.sort(idx0, -1),
                                  np.sort(jax.lax.top_k(s, 3)[1], -1))
    assert not np.allclose(np.asarray(w0).sum(-1), 6.0)


def test_a_zero_compute_pick_adds_w_times_h(layer):
    h, E = layer["h"], layer["E"]
    N = h.shape[0]
    idx = jnp.tile(jnp.asarray([[E, E + 2, E + 3]], jnp.int32), (N, 1))
    w = jax.random.uniform(jax.random.key(6), (N, 3)) + 0.1
    out, counts = moe.held_experts_ffn(
        h, idx, w, layer["w_gate_up"], layer["w_down"], held=(0, E),
        n_routed=E)
    np.testing.assert_allclose(out, w.sum(-1, keepdims=True) * h, atol=1e-5)
    assert [int(c) for c in counts] == [3 * N, 3 * N, 0, 0, 0, 0, 0]


def test_nothing_is_dropped_when_every_token_picks_one_held_expert(layer):
    h, E = layer["h"], layer["E"]
    N = h.shape[0]
    # pick 0: held expert 5 for EVERY token; picks 1, 2: absent experts
    idx = jnp.tile(jnp.asarray([[5, 1, 2]], jnp.int32), (N, 1))
    w = jax.random.uniform(jax.random.key(7), (N, 3)) + 0.1
    out, counts = moe.held_experts_ffn(
        h, idx, w, layer["w_gate_up"][4:8], layer["w_down"][4:8],
        held=(4, 4), n_routed=E)
    want = w[:, :1] * _expert(layer["w_gate_up"], layer["w_down"], 5, h)
    np.testing.assert_allclose(out, want, atol=1e-5)
    # all N pairs on one expert, one expert hit, none dropped
    assert [int(c) for c in counts] == [3 * N, 0, N, N, 1, 0, 0]


def test_masked_tokens_route_nowhere(layer):
    h, E = layer["h"], layer["E"]
    idx, w = moe.route_topk(h, layer["w_r"], layer["bias"], topk=3, scale=6.0)
    valid = jnp.arange(h.shape[0]) < 10
    out, counts = moe.held_experts_ffn(
        h, idx, w, layer["w_gate_up"], layer["w_down"], held=(0, E),
        n_routed=E, valid=valid)
    full, _ = moe.held_experts_ffn(
        h, idx, w, layer["w_gate_up"], layer["w_down"], held=(0, E),
        n_routed=E)
    np.testing.assert_allclose(out[:10], full[:10], atol=1e-5)
    assert not np.asarray(out[10:]).any() and int(counts[0]) == 30


# -- (e) the engine and the deployment ----------------------------------------

@pytest.fixture(scope="module")
def engine(model):
    cfg, params = model
    eng = LLMEngine(params, cfg, prompt_buckets=(16, 64), chunk=4,
                    slots=2, max_queue=0, name="longcat-test",
                    block_tokens=BT, pool_blocks=33,
                    attention_kernel="interpret")
    eng.warmup()
    return eng


def test_the_engine_counts_a_chains_groups_by_the_latent_walks():
    """``kv_groups_total`` counts groups of what the family's decode walk
    fetches together: the latent kernel's 512 positions under decode's few
    rows (32 entries of 16 tokens), not the grouped-query kernels' 128. A
    chain of 36 blocks cut from a fresh pool is ONE full group, and a run;
    cut from single blocks apart it is one that is none; one of 20 blocks
    has no full group and counts as one group that is none."""
    cfg = longcat.tiny(max_seq_len=640)
    eng = LLMEngine(longcat.init_params(cfg, jax.random.key(1)), cfg,
                    prompt_buckets=(16,), chunk=4, slots=1, max_queue=0,
                    name="longcat-groups", block_tokens=BT, pool_blocks=81)

    def counted(max_new):
        before = eng.stats()
        req = eng.submit([7, 3, 11, 200, 5], max_new_tokens=max_new)
        eng._step()                       # admitted: its table row is written
        eng._cancel(req)
        after = eng.stats()
        return tuple(int(after[k] - before[k])
                     for k in ("kv_groups_total", "kv_run_groups_total"))

    assert counted(560) == (1, 1)
    assert counted(300) == (1, 0)
    held = eng.kv.alloc(int(eng.kv.stats()["kv_blocks_free"]))
    eng.kv.release(held[::2])                       # single blocks, apart
    assert counted(560) == (1, 0)
    eng.kv.release(held[1::2])
    assert eng.kv.active_blocks() == 0


def _served_gap(model, prompt, toks):
    logits = ref_logits(model, list(prompt) + list(toks))
    rows = logits[len(prompt) - 1:len(prompt) - 1 + len(toks)]
    return float((rows.max(-1) - rows[np.arange(len(toks)), toks]).max())


def test_engine_serves_the_family_with_prefix_reuse(model, engine):
    """Concurrent streams through the one engine and block manager; a
    follow-up turn hits the first one's chain (full blocks and the
    copy-on-write tail) and its tokens still are the reference's."""
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
    assert after["kv_hit_tokens"] - before["kv_hit_tokens"] == 30
    assert after["kv_cow_copies"] - before["kv_cow_copies"] == 1
    assert _served_gap(model, turn2, out2) <= TOL
    assert engine.kv.active_blocks() == 0
    s = engine.stats()
    L, k = model[0].num_layers, model[0].moe_topk
    assert s["moe_steps_total"] > 0
    assert 0 < s["moe_picks_zero_total"] + s["moe_picks_held_total"] \
        <= s["moe_picks_total"]
    assert s["moe_picks_total"] % (L * k) == 0
    assert 0 < s["moe_experts_hit_total"] <= s["moe_steps_total"] * L * 4
    assert s["moe_prefill_picks_total"] >= (5 + 22 + 3) * L * k
    d = engine.describe()
    assert d["model_family"] == "LongCatConfig"
    assert d["kv_pool_shapes"] == [[4, 33, BT, 128]]


def test_the_family_names_its_counts_and_the_engine_only_folds_them(model):
    """The engine knows no family's counters: it folds ``PagedFamily.
    aux_counts`` by position, and the family builds that table from the
    names ``ops/moe.py`` gives its array, so a reorder there follows here
    and a count without a name is an error, not a mislabel."""
    from ray_tpu.ops import moe

    fam = model[0].paged_family()
    assert [a.decode for a in fam.aux_counts] == [
        "moe_picks_total", "moe_picks_zero_total", "moe_picks_held_total",
        "moe_held_pairs_max_total", "moe_experts_hit_total",
        "moe_bounded_calls_total", "moe_extra_windows_total",
        "moe_steps_total"]
    assert [a.prefill for a in fam.aux_counts] == [
        "moe_prefill_picks_total", "moe_prefill_picks_zero_total",
        "moe_prefill_picks_held_total", None, None,
        "moe_prefill_bounded_calls_total", "moe_prefill_extra_windows_total",
        None]
    assert {a.step_attr: a.decode for a in fam.aux_counts if a.step_attr} \
        == {"moe_held_pairs": "moe_picks_held_total"}
    assert len(fam.aux_counts) == moe.PICK_COUNTS + 1
    import inspect

    from ray_tpu.serve import llm
    assert "moe" not in inspect.getsource(llm)


def test_held_pairs_are_stamped_on_the_step_span(model, engine):
    from ray_tpu.util import tracing

    engine.generate([1, 2, 3], max_new_tokens=4)
    # The counts come to the host with the chunk's tokens: they are stamped
    # on the step that delivered it, a step after the one that dispatched.
    steps = [s for s in tracing.recorded() if s.name == "llm.step"
             and (s.attrs or {}).get("engine") == "longcat-test"
             and (s.attrs or {}).get("tokens")]
    assert steps and all("moe_held_pairs" in s.attrs for s in steps)


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
            cfg, lambda: longcat.init_params(cfg, jax.random.key(1)),
            name="LongCat", slots=2, chunk=4)
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


def test_the_handle_lets_through_what_the_engine_can_hold(model):
    """128 slots and 160 closed-loop clients: with the deployment default of
    100 ongoing requests the router held a fifth of the slots empty."""
    cfg, _params = model
    wide = llm_deployment(cfg, lambda: None, name="wide", slots=128,
                          max_queue=64)
    assert wide.config.max_ongoing_requests == 192
    narrow = llm_deployment(cfg, lambda: None, name="narrow", slots=36,
                            max_queue=64)
    assert narrow.config.max_ongoing_requests == 100
