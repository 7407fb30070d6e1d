"""LFM2 on the paged serve path, against its plain reference.

Every comparison is with ``benchmark/reference/lfm2_plain.py`` (the file the
benchmark's ``correct`` uses: float32, the convolution three shifted copies
of a whole sequence, a full causal softmax a query head, the experts a loop,
no cache and no tail) on seeded weights at a small size: ``lfm2.tiny()``,
six layers ``conv, conv, full_attention, conv, conv, full_attention`` (the
first dense, five expert layers: the later layers of a mixer index the tail
and the pool past the first's), width 64, 4 query heads over 2 KV heads of
16, 8 experts of 32, all held, top-2.

Tolerance 2e-4 on logits everywhere: program and reference are both float32
here (``conftest`` pins matmul precision to ``highest``), so what differs is
only the order of summation: the kernel's online softmax against a dense
one, the grouped or batched product against a loop, a window of the tail
against three shifted copies. A zeroed tail, reversed taps, a head norm's
gain or the rotation's base moved alone moves logits by 1e-2 and more; the
faults the cell's check is held to (the gate or a norm left out, a bias that
weighs, a pick too few) are planted by the benchmark's own launchers
(``benchmark/tests/test_lfm2_cell.py::test_each_lfm2_launcher_plants_the_fault_it_says``).
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
from ray_tpu.models import lfm2
from ray_tpu.models.generate import PagedGenerator
from ray_tpu.ops import causal_conv, moe
from ray_tpu.serve.llm import LLMEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ref = load_file(REPO, "benchmark/reference/lfm2_plain.py")
TOL = 2e-4
BT = 16


def ref_config(cfg, held=None, **over) -> dict:
    """The configuration's dict as the benchmark's file would state it."""
    c = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    first, count = held or cfg.held
    c["held"] = {"first": first, "count": count, "of": cfg.num_experts}
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
    cfg = lfm2.tiny()
    return cfg, lfm2.init_params(cfg, jax.random.key(1))


@pytest.fixture(scope="module")
def engine(model):
    cfg, params = model
    eng = LLMEngine(params, cfg, prompt_buckets=(16, 64), chunk=4,
                    slots=2, max_queue=0, name="lfm2-test",
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


_GENERATORS = {}


def one_slot(cfg, params, fresh=False):
    """A generator of one slot on the gather path; one a config, whatever
    the weights (its programs take them as an operand), so that a config's
    programs are traced once a module. ``fresh``: traced anew, for a test
    that has patched the program."""
    make = lambda: PagedGenerator(  # noqa: E731
        params, cfg, slots=1, num_blocks=5, block_tokens=BT, max_len=64,
        attention_kernel="gather")
    if fresh:
        return make()
    if cfg not in _GENERATORS:
        _GENERATORS[cfg] = make()
    return _GENERATORS[cfg]


def last_row(cfg, params, seq, fresh=False):
    """The program's logits after a prefill of ``seq`` (the gather path)."""
    gen = one_slot(cfg, params, fresh)
    dev = prefill(gen, params, gen.init_state(), [1, 2, 0, 0], seq, 0, 64)
    return np.asarray(dev[2][0])


def test_the_stack_is_read_off_layer_types(model):
    cfg = model[0]
    assert (cfg.conv_layers, cfg.attention_layers, cfg.expert_layers) == (4, 2, 5)
    assert [cfg.kind_index(l) for l in range(6)] == [0, 1, 0, 2, 3, 1]
    assert [cfg.ffn_kind(l) for l in range(3)] == ["dense", "experts", "experts"]
    assert cfg.n_layers == 2 and cfg.head_dim == 16      # the pool's layers
    full = lfm2.Lfm2Config()
    assert (full.conv_layers, full.attention_layers, full.expert_layers) == (
        18, 6, 22)
    assert full.head_dim == 64 and full.n_routed_experts == 32
    cut = lfm2.lfm2_8b_a1b_stage()
    assert cut.layer_types == lfm2.LAYER_TYPES[:10] == (
        ("conv",) * 2 + ("full_attention", "conv", "conv", "conv") * 2)
    assert (cut.conv_layers, cut.attention_layers, cut.expert_layers) == (8, 2, 8)
    assert cut.state_bytes_per_slot == 65_536 and cut.held == (0, 32)
    assert lfm2.lfm2_8b_a1b_stage(num_hidden_layers=14).state_bytes_per_slot == 90_112
    with pytest.raises(ValueError):
        lfm2.tiny(layer_types=("conv",) * 5 + ("sliding_attention",))
    with pytest.raises(ValueError):
        lfm2.tiny(layer_types=("conv",) * 6)                  # no pool
    with pytest.raises(ValueError):
        lfm2.tiny(conv_bias=True)


@pytest.mark.parametrize("layer_types, dense", [
    (("conv", "full_attention"), 2), (("full_attention", "conv", "conv"), 1)])
def test_every_kind_of_layer_matches_the_reference(layer_types, dense):
    """Each mixer under each feed-forward: a convolution and attention over
    dense feed-forwards; attention over a dense one in FRONT of convolutions
    over experts (``tiny()`` itself, in every other test, has attention over
    experts)."""
    cfg = lfm2.tiny(num_hidden_layers=len(layer_types),
                    layer_types=layer_types, num_dense_layers=dense)
    params = lfm2.init_params(cfg, jax.random.key(5))
    seq = [int(t) for t in np.random.default_rng(2).integers(1, 256, 29)]
    want = ref_logits((cfg, params), seq)
    for n in (1, 2, 3, 29):
        np.testing.assert_allclose(last_row(cfg, params, seq[:n]),
                                   want[n - 1], atol=TOL)


def test_paged_prefill_and_decode_match_the_reference(model):
    """Two sequences prefill into slots 0 and 2 (buckets 64 and 16: one has
    a padded tail of 27, one of 5), slot 1 stays parked; then both decode a
    chunk in one program, through the attention kernel interpreted (the
    gather path serves every other test of this file). Logits, not tokens,
    against the reference's full pass."""
    cfg, params = model
    kernel = "interpret"
    gen = PagedGenerator(params, cfg, slots=3, num_blocks=9, block_tokens=BT,
                         max_len=64, attention_kernel=kernel)
    dev = gen.init_state()
    rng = np.random.default_rng(0)
    a = [int(t) for t in rng.integers(1, cfg.vocab_size, 37)]
    b = [int(t) for t in rng.integers(1, cfg.vocab_size, 11)]
    dev = prefill(gen, params, dev, [1, 2, 3, 0], a, 0, 64)
    dev = prefill(gen, params, dev, [4, 5, 0, 0], b, 2, 16)
    after_prefill = np.asarray(dev[2])
    tables = np.asarray([[1, 2, 3, 0], [0] * 4, [4, 5, 0, 0]], np.int32)
    toks, pool, state, last, keys, aux = gen.decode_fn(4)(
        params, *dev, tables, np.asarray([37, 0, 11], np.int32),
        np.asarray([True, False, True]), np.ones(3, bool),
        np.zeros(3, np.float32))
    toks = np.asarray(toks)
    for slot, seq in ((0, a), (2, b)):
        full = seq + [int(t) for t in toks[slot]]
        logits = ref_logits(model, full)
        np.testing.assert_allclose(after_prefill[slot], logits[len(seq) - 1],
                                   atol=TOL)
        rows = logits[len(seq) - 1:len(full) - 1]
        gap = rows.max(-1) - rows[np.arange(4), toks[slot]]
        assert gap.max() <= TOL, gap
        np.testing.assert_allclose(np.asarray(last[slot]), logits[-1],
                                   atol=TOL)
    # the pool is the two attention layers', the state the four convolution
    # layers' tails ALONE: two rows a slot, no recurrent state
    assert [p.shape for p in pool] == [(2, 9, BT, 2 * 16)] * 2
    assert len(state) == 1 and state[0].shape == (4, 2, 3, 64)
    # the parked slot's tail never moved from zero, and it routed nowhere:
    # 4 token steps x 2 live slots x 5 expert layers x top-2
    assert not np.asarray(state[0][:, :, 1]).any()
    assert np.asarray(state[0][:, :, 0]).any()
    counts = dict(zip(moe.PICK_COUNT_NAMES, np.asarray(aux)))
    assert counts["picks"] == 4 * 2 * 5 * 2 and counts["picks_zero"] == 0
    assert counts["picks_held"] == counts["picks"]      # every expert held
    assert np.asarray(aux)[-1] == 4                     # moe_steps_total


@pytest.mark.parametrize("length", [13, 14, 15, 16, 17])
def test_the_tail_is_the_last_two_real_rows_not_the_buckets(model, length):
    """Prompts 3, 2 and 1 short of a bucket's edge, equal to it and past it
    (the 16 bucket; 17 takes the 64 bucket): after the prefill the slot's
    tail holds ``B * u`` of the prompt's last two REAL rows, and a decode
    chunk from there agrees with the reference's one full pass."""
    cfg, params = model
    bucket = 16 if length <= 16 else 64
    gen = one_slot(cfg, params)
    seq = [int(t) for t in np.random.default_rng(length).integers(1, 256, length)]
    dev = prefill(gen, params, gen.init_state(), [1, 2, 3, 0], seq, 0, bucket)
    after_prefill = np.asarray(dev[2][0])
    # the first convolution layer's tail, by hand from the reference's parts
    w = ref.weights(params)
    x = jnp.asarray(np.asarray(params["tok_embed"])[seq])
    lw = w["layers"][0]
    p = ref._rms(x, lw["norm_op"], cfg.norm_eps) @ lw["mixer"]["w_in"]
    D = cfg.hidden_size
    want = np.asarray(p[-2:, :D] * p[-2:, 2 * D:])
    np.testing.assert_allclose(np.asarray(dev[1][0][0, :, 0]), want, atol=1e-5)
    toks, _pool, _state, last, _keys, _aux = gen.decode_fn(4)(
        params, *dev, np.asarray([[1, 2, 3, 0]], np.int32),
        np.asarray([length], np.int32), np.ones(1, bool), np.ones(1, bool),
        np.zeros(1, np.float32))
    full = seq + [int(t) for t in np.asarray(toks)[0]]
    logits = ref_logits(model, full)     # ONE full pass: causal, so its row
    np.testing.assert_allclose(after_prefill, logits[length - 1], atol=TOL)
    rows = logits[length - 1:len(full) - 1]
    assert (rows.max(-1) - rows[np.arange(4), np.asarray(toks)[0]]).max() <= TOL
    np.testing.assert_allclose(np.asarray(last[0]), logits[-1], atol=TOL)


def test_a_half_filled_bucket_walks_for_its_real_rows(model):
    """100 tokens in the 256 bucket, two query tiles of the attention kernel:
    the first straddles the prompt's end, the second is pad rows alone and is
    skipped; the table behind the prompt's blocks is the trash block. The last
    real row's logits are the reference's."""
    cfg, params = model
    seq = [int(t) for t in np.random.default_rng(7).integers(1, 200, 100)]
    np.testing.assert_allclose(half_filled_bucket.last_row(params, cfg, seq, 256),
                               ref_logits(model, seq)[99], atol=TOL)


def test_causal_conv_with_three_taps_and_no_bias():
    """``ops/causal_conv.py`` at ``K = 3``, ``bias=None`` (three families
    call it with four taps and a bias): a prefill of 5 real rows in a bucket
    of 8, then decode steps with one slot parked, against the sum written
    out."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(9, 6)).astype(np.float32)
    w = rng.normal(size=(3, 6)).astype(np.float32)
    padded = np.concatenate([np.zeros((2, 6), np.float32), x])
    want = sum(padded[j:j + 9] * w[j] for j in range(3))
    tail = jnp.zeros((2, 2, 3, 6), jnp.float32)           # [layers, K-1, slots, C]
    bucket = np.concatenate([x[:5], 9.0 * np.ones((3, 6), np.float32)])
    y, tail = causal_conv.prefill(jnp.asarray(bucket), jnp.asarray(w), None,
                                  tail, 1, 2, 5)
    np.testing.assert_allclose(np.asarray(y)[:5], want[:5], atol=1e-6)
    np.testing.assert_array_equal(np.asarray(tail)[1, :, 2], x[3:5])
    assert not np.asarray(tail)[0].any() and not np.asarray(tail)[1, :, :2].any()
    for t in range(5, 9):
        pre = np.zeros((3, 6), np.float32)
        pre[2] = x[t]
        pre[0] = 7.0                                      # a parked slot's row
        y, tail = causal_conv.decode(jnp.asarray(pre), jnp.asarray(w), None,
                                     tail, 1, jnp.asarray([False, True, True]))
        np.testing.assert_allclose(np.asarray(y)[2], want[t], atol=1e-6)
    np.testing.assert_array_equal(np.asarray(tail)[1, :, 2], x[7:9])
    assert not np.asarray(tail)[1, :, 0].any()            # parked: bit for bit
    # a prompt shorter than the tail: zeros stand before the sequence's start
    _y, short = causal_conv.prefill(jnp.asarray(bucket), jnp.asarray(w), None,
                                    jnp.zeros((1, 2, 1, 6)), 0, 0, 1)
    np.testing.assert_array_equal(np.asarray(short)[0, :, 0],
                                  np.stack([np.zeros(6, np.float32), x[0]]))


# -- each part of the layer moved alone moves both sides alike ------------------

def _mixers(params, kind, change):
    return dict(params, layers=[
        dict(lp, mixer=change(lp["mixer"])) if kind in lp["mixer"] else lp
        for lp in params["layers"]])


@pytest.mark.parametrize("moved", [
    "taps", "q_norm_gain", "rope_theta", "expert_bias"])
def test_no_part_of_the_layer_is_dead(model, moved):
    """The part moved ALONE changes the program's logits, and program and
    reference still agree: neither side drops it or folds it away."""
    cfg, params = model
    seq = [int(t) for t in np.random.default_rng(5).integers(1, 256, 21)]
    base = ref_logits(model, seq)[-1]
    if moved == "taps":              # reversed: the window's order matters
        params = _mixers(params, "conv", lambda m: dict(m, conv=m["conv"][::-1]))
    elif moved == "q_norm_gain":
        name = "q_norm"
        scale = jnp.where(jnp.arange(cfg.head_dim) < cfg.head_dim // 2, 2.0, 0.5)
        params = _mixers(params, name, lambda m: dict(m, **{name: m[name] * scale}))
    elif moved == "rope_theta":
        cfg = cfg.replace(rope_theta=3.0)
    else:                             # large enough to change the picks
        params = dict(params, layers=[
            dict(lp, router_bias=lp["router_bias"] * 25.0)
            if "router_bias" in lp else lp for lp in params["layers"]])
    got = last_row(cfg, params, seq)
    assert np.abs(got - base).max() > 100 * TOL, moved
    np.testing.assert_allclose(got, ref_logits((cfg, params), seq)[-1], atol=TOL)


def test_the_router_picks_by_the_bias_and_weighs_without_it(model):
    """Sigmoid scores in float32; the bias selects and never weighs; the
    unbiased scores renormalised over the picks (scale 1): program and
    reference pick the same experts with the same weights. Ties: two experts
    with the same score and bias go to the lower index on both sides."""
    cfg, params = model
    lp = params["layers"][1]
    lw = ref.weights(params)["layers"][1]["ffn"]
    h = jax.random.normal(jax.random.key(7), (24, cfg.hidden_size))
    idx, w = moe.route_topk(h, lp["router"], lp["router_bias"],
                            topk=cfg.num_experts_per_tok,
                            scale=cfg.routed_scaling_factor, score="sigmoid",
                            renormalise=True)
    ridx, rw = ref.router(lw, h, ref_config(cfg))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
    np.testing.assert_allclose(np.asarray(w), np.asarray(rw), atol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, atol=1e-5)
    assert lp["router"].dtype == lp["router_bias"].dtype == jnp.float32
    assert float(jnp.abs(lp["router_bias"]).max()) > 0          # it is seeded
    # a bias that lifts expert 5 over every other: picked by every token,
    # weighed by its own score alone
    lifted = lp["router_bias"].at[5].add(10.0)
    idx5, w5 = moe.route_topk(h, lp["router"], lifted, topk=2, scale=1.0,
                              score="sigmoid", renormalise=True)
    assert (np.asarray(idx5)[:, 0] == 5).all()
    s = jax.nn.sigmoid(h @ lp["router"])
    picked = jnp.take_along_axis(s, idx5, axis=-1)
    np.testing.assert_allclose(np.asarray(w5), np.asarray(
        picked / picked.sum(-1, keepdims=True)), atol=1e-6)
    # ties: a router of zeros scores every expert 0.5
    zero = jnp.zeros_like(lp["router"])
    tie, wt = moe.route_topk(h, zero, jnp.zeros((cfg.num_experts,)), topk=2,
                             scale=1.0, score="sigmoid", renormalise=True)
    rtie, _ = ref.router(dict(lw, router=zero, expert_bias=jnp.zeros(
        (cfg.num_experts,))), h, ref_config(cfg))
    np.testing.assert_array_equal(np.asarray(tie), np.asarray(rtie))
    assert (np.asarray(tie) == [0, 1]).all()
    np.testing.assert_allclose(np.asarray(wt), 0.5, atol=1e-6)


# -- the share: the uncut layer, and two halves that sum to it -----------------

def test_shares_sum_to_the_uncut_layer():
    """The cell holds every expert: ``held = (0, 8)`` here IS the uncut
    layer (the degenerate share), and the parts the shares ``(0, 4)`` and
    ``(4, 4)`` give sum to it: there is no shared expert to count once."""
    cfg = lfm2.tiny()
    params = lfm2.init_params(cfg, jax.random.key(2))
    lp = params["layers"][1]
    lw = ref.weights(params)["layers"][1]["ffn"]
    h = jax.random.normal(jax.random.key(4), (1, 24, cfg.hidden_size))
    live = jnp.ones((1, 24), bool)
    uncut = np.asarray(ref.experts(lw, h[0], ref_config(cfg)))
    assert np.abs(uncut).max() > 0.01
    whole, counts = lfm2.expert_layer(lp, h, live, cfg)
    np.testing.assert_allclose(np.asarray(whole[0]), uncut, atol=TOL)
    assert int(counts[0]) == int(counts[2]) == 24 * cfg.num_experts_per_tok
    prog, plain = [], []
    for first in (0, 4):
        part = cfg.replace(held=(first, 4))
        lp_part = dict(lp, experts=jax.tree.map(
            lambda w: w[first:first + 4], lp["experts"]))
        out, counts = lfm2.expert_layer(lp_part, h, live, part)
        prog.append(np.asarray(out[0]))
        assert 0 < int(counts[2]) < int(counts[0])
        lw_part = dict(lw, **{k: lw[k][first:first + 4]
                              for k in ("w_13", "w_2")})
        plain.append(np.asarray(ref.experts(
            lw_part, h[0], ref_config(cfg, held=(first, 4)))))
    np.testing.assert_allclose(sum(prog), uncut, atol=TOL)
    np.testing.assert_allclose(sum(plain), uncut, atol=TOL)


def test_a_decode_step_of_the_cells_shape_walks_the_capacity_form():
    """32 experts held and 128 tokens x top-4 = 512 pairs: past the one
    product's 8 x 32, so the decode program's expert layer walks passes of
    64 rows an expert (``ops/moe.py:_walk_capacity``). At a small width: an
    even router takes one pass, one that sends every token to the same four
    experts takes two, and both agree with the reference's loop."""
    assert moe.held_capacity(128, 4, (0, 32), 32) == 64
    assert not moe._one_product_call(128, 4, 32)
    assert moe.held_row_bound(128, 4, (0, 32), 32) is None
    cfg = lfm2.tiny(num_experts=32, num_experts_per_tok=4, held=(0, 32))
    params = lfm2.init_params(cfg, jax.random.key(3))
    lp = params["layers"][1]
    lw = ref.weights(params)["layers"][1]["ffn"]
    h = jax.random.normal(jax.random.key(8), (128, 1, cfg.hidden_size))
    live = jnp.ones((128, 1), bool)
    for bias, passes in ((lp["router_bias"], 0),
                         (lp["router_bias"].at[:4].add(10.0), 1)):
        out, counts = lfm2.expert_layer(dict(lp, router_bias=bias), h,
                                           live, cfg)
        c = dict(zip(moe.PICK_COUNT_NAMES, np.asarray(counts)))
        assert c["bounded_calls"] == 1 and c["extra_windows"] == passes, c
        assert c["picks_held"] == 512
        want = ref.experts(dict(lw, expert_bias=bias), h[:, 0], ref_config(cfg))
        np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(want),
                                   atol=TOL)


def test_the_init_conditions_each_sublayer():
    """At the published constants (widths cut: this is a CPU test) the init
    gives what its docstring says: each kind of sublayer adds about one to
    the stream's mean square, logits have a standard deviation near one, the
    router's scores spread, attention's scores have a spread near two."""
    cfg = lfm2.Lfm2Config(
        vocab_size=256, hidden_size=256, intermediate_size=512,
        moe_intermediate_size=64, num_hidden_layers=3,
        layer_types=("conv", "full_attention", "conv"), num_dense_layers=1,
        num_attention_heads=8, num_key_value_heads=2, num_experts=16,
        held=(0, 16), max_seq_len=128, dtype=jnp.float32,
        param_dtype=jnp.float32)
    params = lfm2.init_params(cfg, jax.random.key(3))
    c, w = ref_config(cfg), ref.weights(params)
    x = jax.random.normal(jax.random.key(9), (48, 256))
    # the tied head: a unit-scale row against the table gives unit logits
    logits = ref._head(ref._rms(x, w["norm_f"], cfg.norm_eps), w["tok_embed"])
    assert 0.5 < float(logits.std()) < 2.0, float(logits.std())
    for l, lw in enumerate(w["layers"]):
        a = ref._rms(x, lw["norm_op"], cfg.norm_eps)
        rms = float(jnp.sqrt((ref.MIXERS[cfg.layer_types[l]](
            lw["mixer"], a, c) ** 2).mean()))
        assert 0.3 < rms < 2.5, (l, rms)
        f = ref._rms(x, lw["norm_ffn"], cfg.norm_eps)
        ffn = ref.dense_ffn if l < 1 else ref.experts
        rms = float(jnp.sqrt((ffn(lw["ffn"], f, c) ** 2).mean()))
        # a routed expert is at HALF scale: four picks that weigh one in sum
        # add 1/16 to the mean square (``init_params``: a pick that changes
        # hands moves the stream by 0.18)
        assert (0.3 < rms < 2.5) if l < 1 else (0.15 < rms < 0.4), (l, rms)
    scores = jax.nn.sigmoid(x @ w["layers"][1]["ffn"]["router"])
    assert float(scores.std()) > 0.15               # not a router of 0.01
    mw = w["layers"][1]["mixer"]
    q = ref._rms((x @ mw["w_q"]).reshape(48, 8, 32), mw["q_norm"], 1e-5)
    k = ref._rms((x @ mw["w_k"]).reshape(48, 2, 32), mw["k_norm"], 1e-5)
    s = jnp.einsum("thd,sd->hts", q, k[:, 0]) * 32 ** -0.5
    assert 1.3 < float(s.std()) < 2.8, float(s.std())
    assert 3.0 < float(jnp.sqrt(((x @ mw["w_q"]) ** 2).mean())) < 5.0


def test_the_check_has_teeth(model):
    """The same prefill and decode, damaged before ONE decode step: slot 0's
    tail zeroed. The logits two steps on leave the reference's by far more
    than the tolerance."""
    cfg, params = model
    gen = one_slot(cfg, params)
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
            if t == 3:
                at_three = np.asarray(dev[2][0])
        return toks, at_three

    toks, last = run(None)
    np.testing.assert_allclose(last, ref_logits(model, a + toks[:4])[-1],
                               atol=TOL)
    toks_d, last_d = run(lambda p, st: (p, (jnp.zeros_like(st[0]),)))
    # judged on the sequence the damaged run itself served; a zeroed tail is
    # forgotten two tokens on, so the row read is the next but one
    off = np.abs(last_d - ref_logits(model, a + toks_d[:4])[-1]).max()
    assert off > 100 * TOL, off


def test_engine_serves_the_family_and_refuses_the_prefix_cache(model, engine):
    """Concurrent streams through the one engine and block manager agree
    with the reference; the same prompt again returns the same tokens with
    no prefix hit, nothing registered, and the refusals counted; the tail's
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
    # 4 convolution layers x 2 slots x 2 rows of 64 float32: the tail alone
    assert after["state_bytes"] == 4 * 2 * 2 * 64 * 4
    assert after["moe_steps_total"] > before["moe_steps_total"]
    assert after["moe_picks_total"] > before["moe_picks_total"]
    assert after["moe_picks_zero_total"] == 0
    assert after["moe_picks_held_total"] == after["moe_picks_total"]
    assert after["moe_prefill_picks_total"] > 0
    assert "moe_extra_windows_total" in after and "moe_bounded_calls_total" in after
    d = engine.describe()
    assert d["model_family"] == "Lfm2Config"
    assert d["kv_pool_shapes"] == [[2, 33, BT, 32]] * 2
    assert d["slot_state_shapes"] == [[4, 2, 2, 64]]
    assert (d["conv_layers"], d["attention_layers"], d["dense_layers"],
            d["expert_layers"], d["held"], d["kv_heads"]) == (4, 2, 1, 5, 8, 2)
    assert d["state_bytes_per_slot"] * 2 == after["state_bytes"]


def test_the_spans_carry_the_tail_and_the_experts(model, engine):
    """``llm.step`` carries ``state_slots`` AND ``moe_held_pairs``,
    ``llm.prefill`` ``state_reset``."""
    from ray_tpu.util import tracing

    t0 = tracing.now_ns()
    tracing.set_context(("lfm2-test-trace", tracing.new_span_id(), True))
    try:
        engine.generate([4, 5, 6, 7, 8, 9], max_new_tokens=8)
    finally:
        tracing.set_context(None)
    spans = tracing.recorded(t0)
    steps = [s for s in spans if s.name == "llm.step" and s.attrs.get("tokens")
             and s.attrs.get("engine") == "lfm2-test"]
    assert steps and all("moe_held_pairs" in s.attrs for s in steps)
    assert any(s.attrs.get("state_slots") == s.attrs["batch"] > 0
               for s in steps)
    prefills = [s for s in spans if s.name == "llm.prefill"]
    assert prefills and all(s.attrs["state_reset"] is True for s in prefills)


def test_a_slots_second_request_starts_from_a_zero_tail(model, engine):
    cfg, params = model
    engine.generate(list(range(60, 100)), max_new_tokens=12)
    p = [9, 8, 7, 250, 1, 2, 3]
    second = engine.generate(p, max_new_tokens=8)
    # what a fresh engine would serve: the reference's own tokens
    assert served_gap(model, p, second) <= TOL
    (tail,) = engine._slot_state
    assert np.asarray(tail).any()
    # a ONE-token prompt: its tail is a row of zeros and its own row
    one = engine.generate([77], max_new_tokens=6)
    assert served_gap(model, [77], one) <= TOL


def test_a_parked_slots_tail_stands_still_across_a_chunk(model, engine):
    """Slot 1 keeps what its last request left (no request holds it); slot 0
    decodes. After whole chunks slot 1's tail is bit for bit what it was,
    slot 0's moved."""
    engine.generate([5, 6, 7, 8], max_new_tokens=4)      # leaves a residue
    first = engine.stream([11, 12, 13], max_new_tokens=16)
    next(first)                                          # it holds slot 0,
    engine.generate([11, 12, 13], max_new_tokens=4)      # so this takes 1
    list(first)
    (before,) = [np.asarray(a) for a in engine._slot_state]
    assert before[:, :, 1].any()
    engine.generate([21, 22, 23, 24, 25], max_new_tokens=8)   # slot 0 alone
    (after,) = [np.asarray(a) for a in engine._slot_state]
    np.testing.assert_array_equal(after[:, :, 1], before[:, :, 1])
    assert not np.array_equal(after[:, :, 0], before[:, :, 0])


def test_the_engine_and_the_manager_needed_no_edit_for_the_family(model):
    """PR 31's seam holds a family whose slot state is a convolution's tail
    alone: the engine and the block manager name nothing of it."""
    from ray_tpu.models import generate
    from ray_tpu.serve import llm

    src = inspect.getsource(llm) + inspect.getsource(generate.KVBlockManager)
    assert not any(word in src for word in (
        "lfm2", "layer_types", "short_conv", "conv_L_cache", "conv_layers"))
    fam = model[0].paged_family()
    assert fam.unsupported == ("prefix_cache",)
    assert [n.decode for n in fam.aux_counts][-1] == "moe_steps_total"
    # the one routed layer (``ops/moe.py``), bound under the family's names
    assert "moe.expert_layer(" in inspect.getsource(lfm2.expert_layer)


def test_a_program_lowers_one_function_a_kind(model):
    """Six layers of three kinds (conv + dense, conv + experts, attention +
    experts): the lowered decode program holds three layer functions, called
    six times; and it carries the scopes and the kernel's name the profiler
    shows."""
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
    for scope in ("short_conv", "attn_full", "kv_pool_write", "moe_router",
                  "moe_experts", "dense_ffn"):
        assert scope in text, scope
    assert "moe_shared" not in text                 # there is no shared expert
    jaxpr = str(jax.make_jaxpr(gen.decode_fn(1))(*args))
    assert "paged_decode_attn" in jaxpr
    with pytest.raises(ValueError, match="one token a step"):
        lfm2.forward_decode_paged(params, jnp.zeros((2, 2), jnp.int32), pool,
                                  state, jnp.zeros((2, 4), jnp.int32),
                                  jnp.zeros(2, jnp.int32), cfg, BT)


# What the engine owes a request whatever it serves (tests/engine_contract.py);
# the streams a check hands back are held to the reference.
@engine_contract.each_check
def test_engine_contract(model, check):
    cfg, params = model
    for prompt, toks in check(params, cfg, engine_contract.ENGINE_KW):
        assert served_gap(model, prompt, toks) < TOL
