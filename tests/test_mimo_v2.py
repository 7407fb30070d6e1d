"""MiMo-V2-Flash (``mimo_v2_flash``) on the paged serve path, against its
plain reference.

Every comparison is with ``benchmark/reference/mimo_v2_plain.py`` (the file
the benchmark's ``correct`` uses: float32, a dense masked softmax with the
sink written out, a loop over experts, no cache and no ring) on seeded
weights at a small size whose ``sliding_window`` (16) is SHORTER than the
contexts tested: hidden 64, 16 query heads of 96 (the first 32 dimensions
rotated) over 8 KV heads in a window layer and 4 in a full one, V heads of 64,
the cut's seven layers (full and dense, four window, full, window), six with
32 routed experts of which 4 are held, top-4, no shared expert, ring blocks
of 8 (a ring of 24 rows), interpreted kernels.

Tolerance 2e-4 on logits everywhere: program and reference are both float32
here (``conftest`` pins matmul precision to ``highest``), so what differs is
only the order of summation. Each planted fault moves logits by 1e-2 and
more.
"""

import inspect
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import engine_contract
import half_filled_bucket
from benchmark.manifest import load_file
from ray_tpu.models import mimo_v2
from ray_tpu.models.generate import PagedGenerator
from ray_tpu.serve.llm import LLMEngine, llm_deployment

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ref = load_file(REPO, "benchmark/reference/mimo_v2_plain.py")
TOL = 2e-4
BT = 16
WINDOW = 16


def ref_config(cfg: mimo_v2.MimoV2Config, held=None) -> dict:
    """The flat keys the reference reads, as a configuration's file has
    them, for a program config object."""
    first, count = held if held is not None else cfg.held
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "swa_num_key_value_heads", "head_dim", "v_head_dim",
            "sliding_window", "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor", "moe_intermediate_size", "rope_theta",
            "swa_rope_theta", "partial_rotary_factor", "attention_value_scale",
            "layernorm_epsilon", "add_swa_attention_sink_bias",
            "add_full_attention_sink_bias")
    return dict({k: getattr(cfg, k) for k in keys},
                hybrid_layer_pattern=list(cfg.hybrid_layer_pattern),
                moe_layer_freq=list(cfg.moe_layer_freq),
                held={"first": first, "count": count,
                      "of": cfg.n_routed_experts})


@pytest.fixture(scope="module")
def model():
    cfg = mimo_v2.tiny(max_seq_len=128)
    return cfg, mimo_v2.init_params(cfg, jax.random.key(1))


@pytest.fixture(scope="module")
def engine(model):
    cfg, params = model
    eng = LLMEngine(params, cfg, prompt_buckets=(16, 64), chunk=4, slots=2,
                    max_queue=0, name="mimo-test", block_tokens=BT,
                    pool_blocks=33, attention_kernel="interpret")
    eng.warmup()
    return eng


def ref_logits(model, seq):
    cfg, params = model
    pad = -len(seq) % 64                  # the reference takes blocks of queries
    return np.asarray(ref.forward(
        ref.weights(params), jnp.asarray([list(seq) + [0] * pad], jnp.int32),
        ref_config(cfg))[0])[:len(seq)]


def served_gap(model, prompt, toks):
    logits = ref_logits(model, list(prompt) + list(toks))
    rows = logits[len(prompt) - 1:len(prompt) - 1 + len(toks)]
    return float((rows.max(-1) - rows[np.arange(len(toks)), toks]).max())


def prefill(gen, dev, table, prompt, slot, bucket):
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(prompt)] = prompt
    return gen.prefill_fn(bucket)(
        gen.params, *dev, np.asarray(table, np.int32), padded, 0, len(prompt),
        slot, 0)


def forced_decode(gen, cfg, kernel, dev, table, seq, start, slot, slots=2):
    """Decode ``seq[start:]`` token by token through the family's program
    (teacher-forced): the logits a step, the state after."""
    pool, state = dev[0], dev[1]
    tables = np.zeros((slots, len(table)), np.int32)
    tables[slot] = table
    lengths = np.zeros(slots, np.int32)
    lengths[slot] = start
    active = jnp.arange(slots) == slot
    tables = jnp.asarray(tables)
    step = jax.jit(lambda p, t, pool, st, ln: cfg.paged_family().decode(
        p, t, pool, st, tables, ln, cfg, BT, kernel=kernel, active=active))
    rows, capped = [], 0
    for t in range(start, len(seq)):
        tok = np.zeros((slots, 1), np.int32)
        tok[slot, 0] = seq[t]
        logits, pool, state, aux = step(gen.params, tok, pool, state, lengths)
        rows.append(np.asarray(logits[slot, 0]))
        capped += int(aux[-1])
        lengths[slot] += 1
    return np.stack(rows), (pool, state), capped


# -- (a) the program against the reference ------------------------------------

@pytest.mark.parametrize("kernel", ["gather", "interpret"])
def test_paged_prefill_and_decode_match_the_reference(model, kernel):
    """A 40-token prompt (2.5 windows: a ring takes its last 24 rows), then
    60 decode steps to a context of 100 = 6 windows, four wraps of the ring,
    through the full layers' pool (rows of 4 x 96 | 4 x 64) and the window
    layers' rings (8 x 96 | 8 x 64, the sink in every softmax), in slot 1 of
    2 with slot 0 parked: every step's logits are the reference's."""
    cfg, params = model
    seq = [int(t) for t in np.random.default_rng(0).integers(1, 256, 100)]
    want = ref_logits(model, seq)
    gen = PagedGenerator(params, cfg, slots=2, num_blocks=20, block_tokens=BT,
                         max_len=128, attention_kernel=kernel)
    table = [3, 5, 2, 7, 9, 11, 4, 6]
    dev = gen.init_state()
    parked = [np.asarray(a[:, 0]) for a in dev[1]]
    *dev, aux = prefill(gen, dev, table, seq[:40], 1, 64)
    np.testing.assert_allclose(np.asarray(dev[2][1]), want[39], atol=TOL)
    assert int(aux[0]) == 40 * cfg.num_experts_per_tok * cfg.expert_layers
    rows, (pool, state), capped = forced_decode(
        gen, cfg, kernel, dev, table, seq, 40, 1)
    np.testing.assert_allclose(rows, want[40:], atol=TOL)
    # every decode step's context was past the window of 16
    assert capped == 60
    # the parked slot's rings were left bit for bit
    for before, after in zip(parked, state):
        np.testing.assert_array_equal(np.asarray(after[:, 0]), before)
    assert np.asarray(state[0][:, 1]).any()
    # K's rows and V's rows differ in width, and by the layer's kind
    assert [a.shape[-1] for a in pool] == [4 * 96, 4 * 64]
    assert [a.shape[-1] for a in state] == [8 * 96, 8 * 64]


def test_a_half_filled_bucket_walks_for_its_real_rows(model):
    """100 tokens in the 256 bucket, two query tiles of the attention kernel:
    the first straddles the prompt's end, the second is pad rows alone and is
    skipped; the table behind the prompt's blocks is the trash block. The last
    real row's logits are the reference's."""
    cfg, params = model
    seq = [int(t) for t in np.random.default_rng(7).integers(1, 200, 100)]
    np.testing.assert_allclose(half_filled_bucket.last_row(params, cfg, seq, 256),
                               ref_logits(model, seq)[99], atol=TOL)


def test_a_short_prompt_under_the_window(model):
    """Contexts under, at and one over the window: prefill 10 tokens, decode
    to 20."""
    cfg, params = model
    seq = [int(t) for t in np.random.default_rng(4).integers(1, 256, 20)]
    want = ref_logits(model, seq)
    gen = PagedGenerator(params, cfg, slots=1, num_blocks=9, block_tokens=BT,
                         max_len=128, attention_kernel="interpret")
    table = [1, 2, 3, 4, 5, 6, 7, 8]
    *dev, _aux = prefill(gen, gen.init_state(), table, seq[:10], 0, 16)
    np.testing.assert_allclose(np.asarray(dev[2][0]), want[9], atol=TOL)
    rows, _dev, capped = forced_decode(gen, cfg, "interpret", dev, table, seq,
                                       10, 0, slots=1)
    np.testing.assert_allclose(rows, want[10:], atol=TOL)
    assert capped == 4            # the tokens at positions 16..19


# -- (b) planted faults --------------------------------------------------------

FAULTS = ("no_sink", "v_unscaled", "window_ignored", "all_dims_rotated",
          "window_base_from_full", "window_kv_map_from_full")


def plant(fault, cfg, monkeypatch):
    """The program with ONE fault planted; returns the config to run."""
    if fault == "no_sink":
        def sinkless(plain):
            return lambda *a, sinks=None, **kw: plain(*a, **kw)
        for name in ("paged_attention", "paged_attention_reference"):
            monkeypatch.setattr(mimo_v2, name, sinkless(getattr(mimo_v2, name)))
    elif fault == "v_unscaled":
        return cfg.replace(attention_value_scale=1.0)
    elif fault == "window_ignored":
        # full attention in the window layers wherever the whole context is
        # at hand, the prefill (the sink stays)
        def unwindowed(plain):
            def call(q, *rest, **kw):
                if q.shape[1] > 1:
                    kw["window"] = None
                return plain(q, *rest, **kw)
            return call
        for name in ("paged_attention", "paged_attention_reference"):
            monkeypatch.setattr(mimo_v2, name, unwindowed(getattr(mimo_v2, name)))
    elif fault == "all_dims_rotated":
        monkeypatch.setattr(mimo_v2.MimoV2Config, "rotary_dim",
                            property(lambda c: c.head_dim))
    elif fault == "window_base_from_full":
        monkeypatch.setattr(mimo_v2.MimoV2Config, "rope_base",
                            lambda c, layer: c.rope_theta)
    else:
        # a window layer's query head h reads KV head h // (H / KV_full): the
        # rows it then finds under its own map hold KV head j // 2's
        assert fault == "window_kv_map_from_full"
        plain = mimo_v2._project_kv

        def mapped(lw, a, layer, c):
            k, v = plain(lw, a, layer, c)
            if c.is_window(layer):
                times = c.swa_num_key_value_heads // c.num_key_value_heads
                heads = jnp.arange(k.shape[2]) // times
                k, v = k[:, :, heads], v[:, :, heads]
            return k, v
        monkeypatch.setattr(mimo_v2, "_project_kv", mapped)
    return cfg


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_moves_logits_past_the_tolerance(model, fault,
                                                         monkeypatch):
    """The sink left out of the window layers, ``v`` left unscaled, the
    window ignored in prefill, all 96 dimensions rotated, the window layers
    given the full layers' rotary base, a window layer's query-to-KV-head map
    taken from the full layers' count: each moves the logits after a 40-token
    prefill by far more than the tolerance."""
    cfg, params = model
    seq = [int(t) for t in np.random.default_rng(3).integers(1, 256, 40)]
    want = ref_logits(model, seq)[-1]

    def last_row(c):
        gen = PagedGenerator(params, c, slots=1, num_blocks=8, block_tokens=BT,
                             max_len=64, attention_kernel="gather")
        *dev, _aux = prefill(gen, gen.init_state(), [1, 2, 3, 0], seq, 0, 64)
        return np.asarray(dev[2][0])

    np.testing.assert_allclose(last_row(cfg), want, atol=TOL)
    moved = np.abs(last_row(plant(fault, cfg, monkeypatch)) - want).max()
    assert moved > 50 * TOL, (fault, moved)


def test_the_router_is_kimis_rule(model):
    """Sigmoid scores in float32, the bias selects and never weighs, the
    unbiased scores renormalised over the picks, scale 1.0: program and
    reference pick the same experts with the same weights."""
    from ray_tpu.ops import moe

    cfg, params = model
    lp = params["layers"][2]
    lw = ref.weights(params)["layers"][2]
    h = jax.random.normal(jax.random.key(7), (24, cfg.hidden_size))
    idx, w = moe.route_topk(h, lp["router"], lp["router_bias"],
                            topk=cfg.num_experts_per_tok,
                            scale=cfg.route_scale, score="sigmoid",
                            renormalise=True)
    ridx, rw = ref.router(lw, h, ref_config(cfg))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
    np.testing.assert_allclose(np.asarray(w), np.asarray(rw), atol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, atol=1e-5)


# -- (c) the share: all shares = the uncut layer --------------------------------

@pytest.mark.parametrize("shares", [8, 4])
def test_shares_sum_to_the_uncut_layer(shares):
    """32 routed experts as shares of 4 (or 8): the parts that all the chips'
    held experts give add up to the uncut reference's expert layer. There is
    NO shared expert in this family, so nothing is counted once."""
    per = 32 // shares
    cfg = mimo_v2.tiny(held=(0, 32))              # the uncut layer's weights
    params = mimo_v2.init_params(cfg, jax.random.key(2))
    lp = params["layers"][1]
    lw = ref.weights(params)["layers"][1]
    assert "shared" not in lp and "shared" not in lw
    h = jax.random.normal(jax.random.key(4), (1, 24, cfg.hidden_size))
    live = jnp.ones((1, 24), bool)
    uncut = np.asarray(ref.routed_part(lw, h, ref_config(cfg)))
    assert np.abs(uncut).max() > 0.01
    prog, plain = [], []
    for first in range(0, 32, per):
        part = cfg.replace(held=(first, per))
        lp_part = dict(lp, experts=jax.tree.map(
            lambda w: w[first:first + per], lp["experts"]))
        out, counts = mimo_v2.expert_layer(lp_part, h, live, part)
        prog.append(np.asarray(out))
        assert int(counts[0]) == 24 * cfg.num_experts_per_tok
        lw_part = dict(lw, w_gate_up=lw["w_gate_up"][first:first + per],
                       w_down=lw["w_down"][first:first + per])
        plain.append(np.asarray(ref.routed_part(
            lw_part, h, ref_config(cfg, held=(first, per)))))
    np.testing.assert_allclose(sum(prog), uncut, atol=TOL)
    np.testing.assert_allclose(sum(plain), uncut, atol=TOL)


# -- (d) the ring: bounded, whatever the context --------------------------------

def test_a_ring_never_holds_more_than_its_rows(model, engine):
    """What the window layers pin for a slot is the ring, at any context:
    ``state_bytes`` is the same before and after a decode to 4x the window
    and more, and is ``sliding_window`` + a block, K rows of 8 x 96 and V
    rows of 8 x 64, a window layer a slot."""
    cfg, _params = model
    before = engine.stats()
    toks = engine.generate(list(range(1, 31)), max_new_tokens=60)   # to 90
    after = engine.stats()
    assert len(toks) == 60 and 90 > 4 * cfg.sliding_window
    assert served_gap(model, list(range(1, 31)), toks) <= TOL
    ring_rows = cfg.sliding_window + cfg.window_block_tokens
    assert cfg.ring_rows == ring_rows == 24
    per_slot = 5 * ring_rows * 8 * (96 + 64) * 4       # layers, rows, row
    assert cfg.ring_bytes_per_slot == per_slot
    assert before["state_bytes"] == after["state_bytes"] == 2 * per_slot
    assert (after["window_capped_slot_steps_total"]
            > before["window_capped_slot_steps_total"])
    d = engine.describe()
    assert d["model_family"] == "MimoV2Config"
    # the pool is the TWO full layers'; the rings are the five window layers'
    assert d["kv_pool_shapes"] == [[2, 33, BT, 4 * 96], [2, 33, BT, 4 * 64]]
    assert d["slot_state_shapes"] == [[5, 2, 3, 8, 8 * 96], [5, 2, 3, 8, 8 * 64]]
    assert (d["window_layers"], d["full_layers"], d["kv_heads_window"],
            d["kv_heads_full"], d["ring_rows"], d["expert_layers"],
            d["dense_layers"], d["window_tokens"]) == (5, 2, 8, 4, 24, 6, 1, 16)


def test_engine_serves_the_family_and_refuses_the_prefix_cache(model, engine):
    """Two streams through the one engine and block manager agree with the
    reference past the window; the same prompt again returns the same tokens
    with no prefix hit, and the refusals are counted."""
    prompts = [[7, 3, 11, 200, 5], list(range(30, 62))]
    outs = [engine.generate(p, max_new_tokens=24) for p in prompts]
    for p, o in zip(prompts, outs):
        assert len(o) == 24 and served_gap(model, p, o) <= TOL
    before = engine.stats()
    again = engine.generate(prompts[1], max_new_tokens=24)
    after = engine.stats()
    assert again == outs[1]
    assert after["kv_hit_tokens"] == before["kv_hit_tokens"] == 0
    assert after["kv_blocks_cached"] == 0 and engine.kv.active_blocks() == 0
    assert (after["prefix_lookups_refused_total"]
            - before["prefix_lookups_refused_total"]) == 1
    assert after["state_slot_steps_total"] > before["state_slot_steps_total"]
    assert after["moe_picks_total"] > before["moe_picks_total"]


def test_a_slots_second_request_is_served_as_by_a_fresh_engine(model, engine):
    """A slot's rings hold its last occupant's rows; admission writes the
    new prompt's over them and the walk reads no row past the context."""
    cfg, params = model
    engine.generate(list(range(60, 100)), max_new_tokens=40)
    p = [9, 8, 7, 250, 1, 2, 3]
    second = engine.generate(p, max_new_tokens=8)
    fresh = LLMEngine(params, cfg, prompt_buckets=(16, 64), chunk=4, slots=2,
                      max_queue=0, name="mimo-fresh", block_tokens=BT,
                      pool_blocks=33, attention_kernel="interpret")
    assert fresh.generate(p, max_new_tokens=8) == second
    assert served_gap(model, p, second) <= TOL


def test_the_engine_and_the_manager_needed_no_edit_for_the_family(model):
    """PR 31's seam holds a family whose K rows, V rows, full layers and
    window layers all differ in width: the engine and the block manager name
    nothing of it."""
    from ray_tpu.models import generate
    from ray_tpu.serve import llm

    src = inspect.getsource(llm) + inspect.getsource(generate.KVBlockManager)
    assert not any(word in src for word in (
        "mimo", "sliding", "window_layers", "ring_", "sinks", "v_head_dim"))
    fam = model[0].paged_family()
    assert fam.unsupported == ("prefix_cache",)
    assert [n.decode for n in fam.aux_counts][-2:] == [
        "moe_steps_total", "window_capped_slot_steps_total"]


def test_the_programs_carry_the_named_scopes_and_kernel_names(model):
    cfg, params = model
    gen = PagedGenerator(params, cfg, slots=2, num_blocks=8, block_tokens=BT,
                         max_len=64, attention_kernel="interpret")
    pool, state, last, keys = gen.init_state()
    args = (params, pool, state, last, keys, np.zeros((2, 4), np.int32),
            np.zeros(2, np.int32), np.ones(2, bool), np.ones(2, bool),
            np.zeros(2, np.float32))
    text = gen.decode_fn(2).lower(*args).as_text(debug_info=True)
    for scope in ("attn_window", "attn_full", "window_ring_write",
                  "kv_pool_write", "moe_router", "moe_experts", "dense_ffn"):
        assert scope in text, scope
    assert "moe_shared" not in text                 # there is no shared expert
    jaxpr = str(jax.make_jaxpr(gen.decode_fn(1))(*args))
    # the profiler tells window from full by the kernel's own name
    assert "window_decode_attn" in jaxpr and "paged_decode_attn" in jaxpr


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
            cfg, lambda: mimo_v2.init_params(cfg, jax.random.key(1)),
            name="MimoV2", slots=2, chunk=4)
        handle = serve.run(LM.bind())
        prompt = [5, 9, 200, 31, 77, 2]
        items = list(handle.options(stream=True).remote(
            {"prompt_ids": prompt, "max_new_tokens": 20}))
        toks = [it["token"] for it in items]
        assert [it["index"] for it in items] == list(range(20))
        assert items[-1]["finish_reason"] == "stop"
        assert served_gap(model, prompt, toks) <= TOL      # past the window
    finally:
        serve.shutdown()
