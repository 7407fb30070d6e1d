"""Trinity (``afmoe``) on the paged serve path, against its plain reference.

Every comparison is with ``benchmark/reference/afmoe_plain.py`` (the file the
benchmark's ``correct`` uses: float32, a dense masked softmax, a loop over
experts, no cache and no ring) on seeded weights at a small size whose
``sliding_window`` (16) is SHORTER than the contexts tested: hidden 64, 4
query heads over 2 KV heads of 64, layers sliding, sliding, sliding, full,
sliding, the first dense and four with 32 routed experts of which 4 are held,
top-4, one shared expert, ring blocks of 8 (a ring of 24 rows), interpreted
kernels.

Tolerance 2e-4 on logits everywhere: program and reference are both float32
here (``conftest`` pins matmul precision to ``highest``), so what differs is
only the order of summation. Each planted fault moves logits by 1e-2 and
more.
"""

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
from ray_tpu.models import afmoe
from ray_tpu.models.generate import PagedGenerator
from ray_tpu.serve.llm import LLMEngine, llm_deployment

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ref = load_file(REPO, "benchmark/reference/afmoe_plain.py")
TOL = 2e-4
BT = 16
WINDOW = 16


def ref_config(cfg: afmoe.AfmoeConfig, held=None) -> dict:
    """The flat keys the reference reads, as a configuration's file has
    them, for a program config object."""
    first, count = held if held is not None else cfg.held
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "sliding_window", "num_dense_layers",
            "num_experts_per_tok", "route_scale", "route_norm",
            "moe_intermediate_size", "rope_theta", "rms_norm_eps",
            "mup_enabled")
    return dict({k: getattr(cfg, k) for k in keys},
                layer_types=list(cfg.layer_types),
                held={"first": first, "count": count, "of": cfg.num_experts})


@pytest.fixture(scope="module")
def model():
    cfg = afmoe.tiny(max_seq_len=128)
    return cfg, afmoe.init_params(cfg, jax.random.key(1))


@pytest.fixture(scope="module")
def engine(model):
    cfg, params = model
    eng = LLMEngine(params, cfg, prompt_buckets=(16, 64), chunk=4, slots=2,
                    max_queue=0, name="afmoe-test", block_tokens=BT,
                    pool_blocks=33, attention_kernel="interpret")
    eng.warmup()
    return eng


def ref_logits(model, seq, cfg=None):
    cfg0, params = model
    pad = -len(seq) % 64                  # the reference takes blocks of queries
    return np.asarray(ref.forward(
        ref.weights(params), jnp.asarray([list(seq) + [0] * pad], jnp.int32),
        ref_config(cfg or cfg0))[0])[:len(seq)]


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
    """A 40-token prompt (2.5 windows: the ring takes its last 24 rows), then
    60 decode steps to a context of 100 = 6 windows, four wraps of the ring,
    through the full layer's pool and the window layers' rings, in slot 1 of
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

@pytest.mark.parametrize("fault", ["window_ignored", "rope_in_the_full_layer",
                                   "no_gate", "no_shared_expert",
                                   "no_post_norms"])
def test_a_planted_fault_moves_logits_past_the_tolerance(model, fault,
                                                         monkeypatch):
    """The window ignored (full attention in a sliding layer), rotation
    applied in the full layer, the gate left out, the shared expert left
    out, the post-sublayer norms left out: each moves the logits after a
    40-token prefill by far more than the tolerance."""
    cfg, params = model
    seq = [int(t) for t in np.random.default_rng(3).integers(1, 256, 40)]
    want = ref_logits(model, seq)[-1]

    def last_row(p, c):
        gen = PagedGenerator(p, c, slots=1, num_blocks=8, block_tokens=BT,
                             max_len=64, attention_kernel="gather")
        *dev, _aux = prefill(gen, gen.init_state(), [1, 2, 3, 0], seq, 0, 64)
        return np.asarray(dev[2][0])

    np.testing.assert_allclose(last_row(params, cfg), want, atol=TOL)
    bad_params, bad_cfg = params, cfg
    layers = [dict(lp) for lp in params["layers"]]
    if fault == "window_ignored":
        bad_cfg = cfg.replace(sliding_window=4096, window_block_tokens=64)
    elif fault == "rope_in_the_full_layer":
        monkeypatch.setattr(afmoe, "_rotates", lambda c, layer: True)
    elif fault == "no_gate":
        # a gate of zeros is sigmoid = 1/2 everywhere, which the norm after
        # the sublayer takes out again: the gate left out
        for lp in layers:
            lp["w_g"] = jnp.zeros_like(lp["w_g"])
        bad_params = dict(params, layers=layers)
    elif fault == "no_shared_expert":
        for lp in layers:
            if "shared" in lp:
                lp["shared"] = dict(lp["shared"], w_down=jnp.zeros_like(
                    lp["shared"]["w_down"]))
        bad_params = dict(params, layers=layers)
    else:
        monkeypatch.setattr(afmoe, "rms_norm", skip_post_norms(afmoe.rms_norm))
    moved = np.abs(last_row(bad_params, bad_cfg) - want).max()
    assert moved > 50 * TOL, (fault, moved)


def skip_post_norms(norm):
    """``rms_norm`` that leaves out the norm AFTER each sublayer. A layer
    calls it six times, in this order: the input's, q's, k's, the attention
    output's, the feed-forward input's, the feed-forward output's; the final
    norm is the call after the last layer's."""
    calls = [0]

    def skipping(x, g, eps):
        n = calls[0] % 6
        calls[0] += 1
        return x if n in (3, 5) else norm(x, g, eps)
    return skipping


def test_the_router_is_kimis_rule(model):
    """Sigmoid scores in float32, the bias selects and never weighs, the
    unbiased scores renormalised over the picks and scaled: program and
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
    np.testing.assert_allclose(np.asarray(w).sum(-1), cfg.route_scale,
                               atol=1e-5)


# -- (c) the share: all shares + the shared expert once = the uncut layer ------

@pytest.mark.parametrize("shares", [16, 8])
def test_shares_sum_to_the_uncut_layer(shares):
    """The parts that all the chips' held experts give, plus the shared
    expert ONCE, are the uncut reference's expert layer."""
    per = 32 // shares
    cfg = afmoe.tiny(held=(0, 32))                # the uncut layer's weights
    params = afmoe.init_params(cfg, jax.random.key(2))
    lp = params["layers"][1]
    lw = ref.weights(params)["layers"][1]
    h = jax.random.normal(jax.random.key(4), (1, 24, cfg.hidden_size))
    live = jnp.ones((1, 24), bool)
    shared = np.asarray(ref.shared_part(lw, h))
    uncut = np.asarray(ref.routed_part(lw, h, ref_config(cfg))) + shared
    assert np.abs(shared).max() > 0.01            # the part counted once
    prog, plain = [], []
    for first in range(0, 32, per):
        part = cfg.replace(held=(first, per))
        lp_part = dict(lp, experts=jax.tree.map(
            lambda w: w[first:first + per], lp["experts"]))
        out, counts = afmoe.expert_layer(lp_part, h, live, part)
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


# -- (d) the ring: bounded, whatever the context --------------------------------

def test_a_ring_never_holds_more_than_its_rows(model, engine):
    """What the window layers pin for a slot is the ring, at any context:
    ``state_bytes`` is the same before and after a decode to 4x the window
    and more, and is ``sliding_window`` + a block, for K and V, a window
    layer a slot."""
    cfg, _params = model
    before = engine.stats()
    toks = engine.generate(list(range(1, 31)), max_new_tokens=60)   # to 90
    after = engine.stats()
    assert len(toks) == 60 and 90 > 4 * cfg.sliding_window
    assert served_gap(model, list(range(1, 31)), toks) <= TOL
    ring_rows = cfg.sliding_window + cfg.window_block_tokens
    assert cfg.ring_rows == ring_rows == 24
    per_slot = 4 * 2 * ring_rows * 2 * 64 * 4        # layers, K|V, rows, row
    assert cfg.ring_bytes_per_slot == per_slot
    assert before["state_bytes"] == after["state_bytes"] == 2 * per_slot
    assert (after["window_capped_slot_steps_total"]
            > before["window_capped_slot_steps_total"])
    d = engine.describe()
    assert d["model_family"] == "AfmoeConfig"
    # the pool is the ONE full layer's; the rings are the four window layers'
    assert d["kv_pool_shapes"] == [[1, 33, BT, 128]] * 2
    assert d["slot_state_shapes"] == [[4, 2, 3, 8, 128]] * 2
    assert (d["window_layers"], d["full_layers"], d["window_tokens"],
            d["window_ring_bytes_per_slot"], d["expert_layers"],
            d["dense_layers"]) == (4, 1, 16, per_slot, 4, 1)


def test_the_walk_starts_at_the_window(model):
    """The decode kernel of a window layer starts no copy of a block wholly
    behind the window: the walk's bounds, counted over contexts up to 40
    windows, never span more than the ring; and rings whose rows behind the
    window are NaN give the same logits (a copied block's rows, masked, would
    meet p = 0 as values: 0 x NaN)."""
    from ray_tpu.ops.paged_attention import (_tile_first_block,
                                             _tile_last_block)

    cfg, params = model
    rb, R = cfg.window_block_tokens, cfg.ring_blocks
    lengths = jnp.arange(0, 40 * WINDOW)
    first = np.asarray(jax.vmap(lambda s: _tile_first_block(
        lengths, s, 0, 1, rb, WINDOW))(jnp.arange(len(lengths))))
    last = np.asarray(jax.vmap(lambda s: _tile_last_block(
        lengths, s, 0, 1, 1, rb, R, ring=True))(jnp.arange(len(lengths))))
    assert (last - first + 1).max() == R == 3
    np.testing.assert_array_equal(
        first, np.maximum(np.arange(40 * WINDOW) - WINDOW + 1, 0) // rb)
    seq = [int(t) for t in np.random.default_rng(5).integers(1, 256, 61)]
    want = ref_logits(model, seq)
    gen = PagedGenerator(params, cfg, slots=1, num_blocks=9, block_tokens=BT,
                         max_len=128, attention_kernel="interpret")
    table = [1, 2, 3, 4, 5, 6, 7, 8]
    *dev, _aux = prefill(gen, gen.init_state(), table, seq[:60], 0, 64)
    # position 60 = block 7 (ring entry 1), its window 45..60 = blocks 5..7:
    # every entry of the ring is live; of block 5 (entry 2) rows 0..4 hold
    # positions 40..44, behind the window: masked, and finite
    rows, _dev, _capped = forced_decode(gen, cfg, "interpret", dev, table, seq,
                                        60, 0, slots=1)
    np.testing.assert_allclose(rows[0], want[60], atol=TOL)


# -- (e) the engine --------------------------------------------------------------

def test_engine_serves_the_family_and_refuses_the_prefix_cache(model, engine):
    """Concurrent streams through the one engine and block manager agree
    with the reference past the window; the same prompt again returns the
    same tokens with no prefix hit, and the refusals are counted."""
    prompts = [[7, 3, 11, 200, 5], list(range(30, 62))]
    outs = [None, None]

    def run(i):
        outs[i] = engine.generate(prompts[i], max_new_tokens=24)

    threads = [threading.Thread(target=run, args=(i,)) for i in range(2)]
    [t.start() for t in threads]
    [t.join() for t in threads]
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
    assert after["state_resets_total"] == after["prefix_lookups_refused_total"]
    assert after["state_slot_steps_total"] > before["state_slot_steps_total"]
    assert after["moe_picks_total"] > before["moe_picks_total"]


def test_a_slots_second_request_is_served_as_by_a_fresh_engine(model, engine):
    """A slot's rings hold its last occupant's rows; admission writes the
    new prompt's over them and the walk reads no row past the context: the
    request that follows a long one in a slot is served what an engine that
    never saw the first serves."""
    cfg, params = model
    engine.generate(list(range(60, 100)), max_new_tokens=40)
    p = [9, 8, 7, 250, 1, 2, 3]
    second = engine.generate(p, max_new_tokens=8)
    fresh = LLMEngine(params, cfg, prompt_buckets=(16, 64), chunk=4, slots=2,
                      max_queue=0, name="afmoe-fresh", block_tokens=BT,
                      pool_blocks=33, attention_kernel="interpret")
    assert fresh.generate(p, max_new_tokens=8) == second
    assert served_gap(model, p, second) <= TOL


def test_a_parked_slots_ring_stands_still_across_a_chunk(model, engine):
    """Slot 1 keeps what its last request left (no request holds it); slot 0
    decodes. After whole chunks slot 1's rings are bit for bit what they
    were, slot 0's moved."""
    engine.generate([5, 6, 7, 8], max_new_tokens=4)      # leaves a residue
    first = engine.stream([11, 12, 13], max_new_tokens=16)
    next(first)                                          # it holds slot 0,
    engine.generate([11, 12, 13], max_new_tokens=4)      # so this takes 1
    list(first)
    before = [np.asarray(a) for a in engine._slot_state]
    assert before[0][:, 1].any()
    engine.generate([21, 22, 23, 24, 25], max_new_tokens=8)   # slot 0 alone
    after = [np.asarray(a) for a in engine._slot_state]
    for b, a in zip(before, after):
        np.testing.assert_array_equal(a[:, 1], b[:, 1])
    assert not np.array_equal(after[0][:, 0], before[0][:, 0])


def test_the_engine_and_the_manager_needed_no_edit_for_the_family(model):
    """PR 31's seam holds a family whose window layers' rows are a state a
    slot: the engine and the block manager name nothing of it."""
    from ray_tpu.models import generate
    from ray_tpu.serve import llm

    src = inspect.getsource(llm) + inspect.getsource(generate.KVBlockManager)
    assert not any(word in src for word in (
        "afmoe", "sliding", "window_layers", "ring_"))
    fam = model[0].paged_family()
    assert fam.unsupported == ("prefix_cache",)
    assert [n.decode for n in fam.aux_counts][-2:] == [
        "moe_steps_total", "window_capped_slot_steps_total"]


def test_the_programs_carry_the_named_scopes_and_kernel_names(model):
    cfg, params = model
    gen = PagedGenerator(params, cfg, slots=2, num_blocks=8, block_tokens=BT,
                         max_len=64, attention_kernel="interpret")
    pool, state, last, keys = gen.init_state()
    text = gen.decode_fn(2).lower(
        params, pool, state, last, keys, np.zeros((2, 4), np.int32),
        np.zeros(2, np.int32), np.ones(2, bool), np.ones(2, bool),
        np.zeros(2, np.float32)).as_text(debug_info=True)
    for scope in ("attn_window", "attn_full", "attn_gate", "window_ring_write",
                  "kv_pool_write", "moe_router", "moe_shared", "moe_experts",
                  "dense_ffn"):
        assert scope in text, scope
    jaxpr = str(jax.make_jaxpr(gen.decode_fn(1))(
        params, pool, state, last, keys, np.zeros((2, 4), np.int32),
        np.zeros(2, np.int32), np.ones(2, bool), np.ones(2, bool),
        np.zeros(2, np.float32)))
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
            cfg, lambda: afmoe.init_params(cfg, jax.random.key(1)),
            name="Afmoe", slots=2, chunk=4)
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
