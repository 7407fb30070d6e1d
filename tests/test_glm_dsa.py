"""GLM-5 (latent attention behind a learned sparse selection) on the paged
serve path, against its plain reference.

Every comparison is with ``benchmark/reference/glm_dsa_plain.py`` (the file
the benchmark's ``correct`` uses: float32, unabsorbed attention a head at a
time, the selection by ``jax.lax.top_k``, a loop over experts, no cache) on
seeded weights at a small size: hidden 64, 4 heads, one dense and two expert
layers, an indexer of 4 heads x 16 that keeps ``index_topk`` = 12 tokens a
query (UNDER every context checked, so every row past its 12th selects), 32
routed experts of which 4 are held, top-4, one shared expert, interpreted
kernels.

Tolerance 2e-4 on logits everywhere: program and reference are both float32
here (``conftest`` pins matmul precision to ``highest``), so what differs is
only the order of summation (absorbed against unabsorbed products, the
kernel's online softmax against a dense one, a grouped product over sorted
pairs against a loop over experts); the sound program reads 2e-6 to 4e-6.
A selection differs from the reference's only where two index scores lie
within that rounding of each other at the cut: none does on these seeds (the
chosen sets are compared bit for bit below). Each planted fault moves logits
by 1e-2 and more.
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
from ray_tpu.models import glm_dsa
from ray_tpu.models.generate import PagedGenerator
from ray_tpu.ops import sparse_select
from ray_tpu.serve.llm import LLMEngine, llm_deployment

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ref = load_file(REPO, "benchmark/reference/glm_dsa_plain.py")
cell = load_file(REPO, "benchmark/tests/test_glm_5_cell.py")
TOL = 2e-4
BT = 16


def ref_config(cfg: glm_dsa.GlmDsaConfig, held=None) -> dict:
    """The flat keys the reference reads, as a configuration's file has
    them, for a program config object."""
    first, count = held if held is not None else cfg.held
    return {
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "rms_norm_eps": cfg.rms_norm_eps,
        "rope_parameters": {"rope_theta": cfg.rope_theta,
                            "rope_type": "default"},
        "index_n_heads": cfg.index_n_heads,
        "index_head_dim": cfg.index_head_dim, "index_topk": cfg.index_topk,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "first_k_dense_replace": cfg.first_k_dense_replace,
        "held": {"first": first, "count": count,
                 "of": cfg.n_routed_experts}}


@pytest.fixture(scope="module")
def model():
    cfg = glm_dsa.tiny()
    return cfg, glm_dsa.init_params(cfg, jax.random.key(1))


def ref_logits(model, seq):
    cfg, params = model
    return np.asarray(ref.forward(ref.weights(params),
                                  jnp.asarray([seq], jnp.int32),
                                  ref_config(cfg))[0])


def _prefill(gen, params, pool, last, keys, table, suffix, slot, bucket):
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(suffix)] = suffix
    pool, _state, last, keys, aux = gen.prefill_fn(bucket)(
        params, pool, (), last, keys, np.asarray(table, np.int32), padded,
        0, len(suffix), slot, 0)
    return pool, last, keys, aux


# -- (a) paged prefill through both pools, then decode -------------------------

@pytest.mark.parametrize("kernel", ["gather", "interpret"])
def test_paged_prefill_and_decode_match_the_reference(model, kernel):
    """A prefills 37 tokens, B 22 (both far past ``index_topk`` = 12, so all
    but a prompt's first 12 rows select); both decode a chunk in one program.
    Logits, not tokens, against the reference's full pass."""
    cfg, params = model
    V, E, k = cfg.vocab_size, cfg.expert_layers, cfg.index_topk
    gen = PagedGenerator(params, cfg, slots=2, num_blocks=8, block_tokens=BT,
                         max_len=64, attention_kernel=kernel)
    pool, _state, last, keys = gen.init_state()
    rng = np.random.default_rng(0)
    a = [int(t) for t in rng.integers(1, V, 37)]
    b = [int(t) for t in rng.integers(1, V, 22)]

    pool, last, keys, aux = _prefill(gen, params, pool, last, keys,
                                     [1, 2, 3, 0], a, 0, 64)
    np.testing.assert_allclose(np.asarray(last[0]), ref_logits(model, a)[36],
                               atol=TOL)
    assert int(aux[0]) == 37 * cfg.num_experts_per_tok * E
    assert not np.asarray(aux[-4:]).any()       # a prefill's selection: uncounted
    pool, last, keys, _ = _prefill(gen, params, pool, last, keys,
                                   [4, 5, 0, 0], b, 1, 32)
    np.testing.assert_allclose(np.asarray(last[1]), ref_logits(model, b)[21],
                               atol=TOL)

    tables = np.asarray([[1, 2, 3, 0], [4, 5, 0, 0]], np.int32)
    toks, pool, _state, last, keys, aux = gen.decode_fn(4)(
        params, pool, (), last, keys, tables, np.asarray([37, 22], np.int32),
        np.ones(2, bool), np.ones(2, bool), np.zeros(2, np.float32))
    toks = np.asarray(toks)
    assert int(aux[0]) == 2 * 4 * cfg.num_experts_per_tok * E
    # token steps; then rows chosen, rows visible, capped slot-steps, slot-steps
    contexts = [n + 1 + t for n in (37, 22) for t in range(4)]
    assert [int(x) for x in aux[-5:]] == [4, 8 * k, sum(contexts), 8, 8]
    for slot, seq in ((0, a), (1, b)):
        full = seq + [int(t) for t in toks[slot]]
        logits = ref_logits(model, full)
        rows = logits[len(seq) - 1:len(full) - 1]
        gap = rows.max(-1) - rows[np.arange(4), toks[slot]]
        assert gap.max() <= TOL, gap
        np.testing.assert_allclose(np.asarray(last[slot]), logits[-1],
                                   atol=TOL)
    # two arrays: a latent row a token a LAYER padded to 128 lanes, and the
    # indexer's key; blocks are dimension 1 of both
    assert pool[0].shape == (cfg.num_hidden_layers, 8, BT, 128)
    assert pool[1].shape == (cfg.num_hidden_layers, 8, BT, cfg.index_head_dim)
    assert not np.asarray(pool[0][:, 1, :, cfg.latent_width:]).any()
    assert np.asarray(pool[1][:, 1]).all()

    # a block copy carries a token's index key with its latent row
    before = [np.asarray(p[:, 3]) for p in pool]
    pool = gen.copy_fn()(pool, 3, 7)
    for p, was in zip(pool, before):
        assert was.any()
        np.testing.assert_array_equal(np.asarray(p[:, 7]), was)
        np.testing.assert_array_equal(np.asarray(p[:, 3]), was)


def test_a_half_filled_bucket_walks_for_its_real_rows(model):
    """27 tokens in the 64 bucket, four query tiles of the latent kernel: one
    whole, one that straddles the prompt's end, two of pad rows alone that
    are skipped; the table behind the prompt's blocks is the trash block. The
    last real row's logits are the reference's."""
    cfg, params = model
    seq = [int(t) for t in np.random.default_rng(7).integers(1, 200, 27)]
    np.testing.assert_allclose(half_filled_bucket.last_row(params, cfg, seq, 64),
                               ref_logits(model, seq)[26], atol=TOL)


def test_a_selection_that_keeps_everything_is_the_dense_layer(model):
    """``index_topk`` above every context: the selection keeps every visible
    row, the program's logits are the reference's at that ``index_topk`` and
    the ones the program gives with the selection taken out."""
    cfg, params = model
    wide = cfg.replace(index_topk=1000)
    seq = [int(t) for t in np.random.default_rng(5).integers(1, 256, 40)]
    want = np.asarray(ref.forward(
        ref.weights(params), jnp.asarray([seq], jnp.int32),
        ref_config(wide))[0])
    assert np.abs(want[-1] - ref_logits(model, seq)[-1]).max() > 50 * TOL

    def last_row(c, monkey=None):
        gen = PagedGenerator(params, c, slots=1, num_blocks=8,
                             block_tokens=BT, max_len=64,
                             attention_kernel="gather")
        pool, _state, last, keys = gen.init_state()
        return np.asarray(_prefill(gen, params, pool, last, keys,
                                   [1, 2, 3, 0], seq, 0, 64)[1][0])

    got = last_row(wide)
    np.testing.assert_allclose(got, want[39], atol=TOL)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(glm_dsa, "keep_bits", glm_dsa.keep_bits)
        exec(cell.FAULTS["selection_ignored"].split(
            "from benchmark import run")[0], {})
        np.testing.assert_allclose(last_row(cfg), got, atol=TOL)


def test_the_chosen_sets_are_the_references(model):
    """One sublayer's selection for a 48-token sequence, the program's
    (index keys written to the pool and gathered back through the table,
    ``select``) against the reference's (``selection``): the same SETS, bit
    for bit, where no two scores tie; 12 a row from the 12th on."""
    cfg, params = model
    T = 48
    ip = params["layers"][1]["indexer"]
    a = jax.random.normal(jax.random.key(7), (1, T, cfg.hidden_size))
    cq = jax.random.normal(jax.random.key(8), (1, T, cfg.q_lora_rank))
    positions = jnp.arange(T)[None]
    table = jnp.asarray([[3, 1, 2, 0]], jnp.int32)
    _latent, index = glm_dsa.init_pool(cfg, 5, BT)
    blk, off = table[0, positions // BT], positions % BT
    index = index.at[1, blk, off].set(
        glm_dsa.index_keys(ip, a, positions, cfg))
    for kernel in ("gather", "interpret"):
        keep = np.asarray(glm_dsa.select(
            cq, ip=ip, a=a, index=index, sub=1, tables=table,
            positions=positions, c=cfg, kernel=kernel))[0] > 0
        want = np.asarray(ref.selection(
            ref.weights(params)["layers"][1]["indexer"], a[0], cq[0],
            ref_config(cfg)))
        assert keep.shape == (T, 4 * BT) and not keep[:, T:].any()
        np.testing.assert_array_equal(keep[:, :T], want)
        np.testing.assert_array_equal(
            want.sum(-1), np.minimum(np.arange(T) + 1, cfg.index_topk))


# -- (b) the stack ---------------------------------------------------------------

def test_every_layer_has_an_indexer_and_layer_zero_no_router(model):
    cfg, params = model
    kinds = [sorted(k for k in lp if k not in ("attn", "norm_attn", "norm_ffn"))
             for lp in params["layers"]]
    assert kinds == [["ffn", "indexer"]] + [[
        "experts", "indexer", "router", "router_bias", "shared"]] * 2
    assert params["layers"][0]["indexer"]["w_q"].shape == (48, 4 * 16)
    assert params["layers"][1]["router"].shape == (64, 32)   # all 32 outputs
    assert glm_dsa.describe(cfg) == {
        "expert_layers": 2, "dense_layers": 1, "index_heads": 4,
        "index_topk": 12, "index_key_bytes_per_token": 64,
        "shared_expert_params": 3 * 64 * 32}
    full = glm_dsa.glm_5_share()
    assert (full.num_hidden_layers, full.first_k_dense_replace,
            full.expert_layers, full.held, full.vocab_size,
            full.max_seq_len) == (5, 1, 4, (0, 8), 19456, 8192)
    d = glm_dsa.describe(full)
    assert (d["index_heads"], d["index_topk"],
            d["index_key_bytes_per_token"]) == (32, 2048, 256)
    spec = full.latent_spec()
    assert (spec.nope, spec.rope, spec.rank, spec.pool_width) == (
        192, 64, 512, 640)
    assert abs(spec.softmax_scale - 256 ** -0.5) < 1e-12
    assert spec.rope_scaling is None and spec.heads_major


# -- (c) planted faults: each moves logits past the tolerance -------------------

def _last_rows(cfg, params, seq, kernel="gather"):
    """Logits after the prefill and after a decode chunk of 4, its tokens
    and its counts by name."""
    gen = PagedGenerator(params, cfg, slots=1, num_blocks=8, block_tokens=BT,
                         max_len=64, attention_kernel=kernel)
    pool, _state, last, keys = gen.init_state()
    pool, last, keys, _ = _prefill(gen, params, pool, last, keys,
                                   [1, 2, 3, 4], seq, 0, 64)
    first = np.asarray(last[0])
    toks, _p, _s, last, _k, _a = gen.decode_fn(4)(
        params, pool, (), last, keys, np.asarray([[1, 2, 3, 4]], np.int32),
        np.asarray([len(seq)], np.int32), np.ones(1, bool), np.ones(1, bool),
        np.zeros(1, np.float32))
    aux = dict(zip([n.decode for n in glm_dsa.AUX_COUNTS],
                   np.asarray(_a).tolist()))
    return (first, np.asarray(last[0]), [int(t) for t in np.asarray(toks)[0]],
            aux)


@pytest.fixture(scope="module")
def sound(model):
    """The sound program's logits after a 40-token prefill and after the
    decode chunk behind it, held to the reference once for all the faults."""
    cfg, params = model
    seq = [int(t) for t in np.random.default_rng(3).integers(1, 256, 40)]
    first, last, toks, _ = _last_rows(cfg, params, seq)
    np.testing.assert_allclose(first, ref_logits(model, seq)[-1], atol=TOL)
    np.testing.assert_allclose(last, ref_logits(model, seq + toks)[-1],
                               atol=TOL)
    return seq, first


@pytest.mark.parametrize("fault", sorted(cell.FAULTS))
def test_a_planted_fault_moves_logits_past_the_tolerance(model, sound, fault,
                                                         monkeypatch):
    """The launchers of ``benchmark/tests/test_glm_5_cell.py`` (what the chip
    runs plant, one a run), on the program as it is named today: with the
    patch the logits after a 40-token prefill, or after the decode chunk
    behind it, leave the reference's by far more than the tolerance."""
    cfg, params = model
    seq, first = sound
    for name in ("keep_bits", "select", "index_keys"):
        monkeypatch.setattr(glm_dsa, name, getattr(glm_dsa, name))
    monkeypatch.setattr(sparse_select, "index_scores",
                        sparse_select.index_scores)
    exec(cell.FAULTS[fault].split("from benchmark import run")[0], {})
    bad_first, bad_last, bad_toks, _ = _last_rows(cfg, params, seq)
    moved = max(np.abs(bad_first - first).max(),
                np.abs(bad_last - ref_logits(model, seq + bad_toks)[-1]).max())
    assert moved > 50 * TOL, (fault, moved)


def test_the_selected_rows_are_the_keep_bits_that_were_set(model, sound,
                                                           monkeypatch):
    """``dsa_selected_rows_total`` witnesses the selection, not the traffic:
    the sound chunk of 4 steps past ``index_topk`` counts 4 x ``index_topk``
    rows, and with the selection ignored every visible row."""
    cfg, params = model
    seq, _ = sound
    aux = _last_rows(cfg, params, seq)[3]
    assert aux["dsa_selected_rows_total"] == 4 * cfg.index_topk
    visible = sum(len(seq) + 1 + t for t in range(4))
    assert aux["dsa_context_rows_total"] == visible
    monkeypatch.setattr(glm_dsa, "keep_bits", glm_dsa.keep_bits)
    exec(cell.FAULTS["selection_ignored"].split("from benchmark import run")[0],
         {})
    assert _last_rows(cfg, params, seq)[3]["dsa_selected_rows_total"] == visible


# -- (d) the share: all shares + the shared expert once = the uncut layer ------

@pytest.mark.parametrize("shares", [32, 4])
def test_shares_sum_to_the_uncut_layer(shares):
    """The parts that all the shares' held experts give, plus the shared
    expert ONCE, are the uncut reference's expert layer (32 routed experts
    as 32 shares of one and as 4 shares of 8). The layer is Kimi-K2's own
    function, called with this family's config."""
    per = 32 // shares
    cfg = glm_dsa.tiny(held=(0, 32))              # the uncut layer's weights
    params = glm_dsa.init_params(cfg, jax.random.key(2))
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
        out, counts = glm_dsa.expert_layer(lp_part, h, live, part)
        prog.append(np.asarray(out))
        assert int(counts[0]) == 24 * cfg.num_experts_per_tok
        lw_part = dict(lw, w_gate_up=lw["w_gate_up"][first:first + per],
                       w_down=lw["w_down"][first:first + per])
        plain.append(np.asarray(ref.routed_part(
            lw_part, h, ref_config(cfg, held=(first, per)))))
    np.testing.assert_allclose(sum(prog) - (shares - 1) * shared, uncut,
                               atol=TOL)
    np.testing.assert_allclose(sum(plain) + shared, uncut, atol=TOL)
    # the one routed layer (``ops/moe.py``), bound under the family's names
    assert "moe.expert_layer(" in inspect.getsource(glm_dsa.expert_layer)


# -- (e) the engine and the deployment -----------------------------------------

@pytest.fixture(scope="module")
def engine(model):
    cfg, params = model
    eng = LLMEngine(params, cfg, prompt_buckets=(16, 64), chunk=4,
                    slots=2, max_queue=0, name="glm-test",
                    block_tokens=BT, pool_blocks=33,
                    attention_kernel="interpret")
    eng.warmup()
    return eng


def _served_gap(model, prompt, toks):
    logits = ref_logits(model, list(prompt) + list(toks))
    rows = logits[len(prompt) - 1:len(prompt) - 1 + len(toks)]
    return float((rows.max(-1) - rows[np.arange(len(toks)), toks]).max())


def test_engine_serves_the_family_and_is_served_no_prefix_hit(model, engine):
    cfg = model[0]
    prompts = [[7, 3, 11, 200, 5], list(range(30, 52))]
    outs = [None, None]

    def run(i):
        outs[i] = engine.generate(prompts[i], max_new_tokens=8)

    threads = [threading.Thread(target=run, args=(i,)) for i in range(2)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    for p, o in zip(prompts, outs):
        assert len(o) == 8 and _served_gap(model, p, o) <= TOL
    before = engine.stats()
    turn2 = prompts[1] + outs[1] + [9, 8, 7]
    out2 = engine.generate(turn2, max_new_tokens=4)
    after = engine.stats()
    # the family refuses the prefix cache: no lookup, no hit, no copy
    assert after["kv_hit_tokens"] == after["kv_cow_copies"] == 0
    assert (after["prefix_lookups_refused_total"]
            - before["prefix_lookups_refused_total"]) == 1
    assert _served_gap(model, turn2, out2) <= TOL
    assert engine.kv.active_blocks() == 0
    s = engine.stats()
    E, k = cfg.expert_layers, cfg.num_experts_per_tok
    assert s["moe_steps_total"] > 0 and s["moe_picks_total"] % (E * k) == 0
    # the selection's counters: every slot-step of turn 2 (33+ rows) is capped
    grew = {n: after[n] - before[n] for n in (
        "dsa_selected_rows_total", "dsa_context_rows_total",
        "dsa_capped_slot_steps_total", "dsa_slot_steps_total")}
    assert grew["dsa_slot_steps_total"] == grew["dsa_capped_slot_steps_total"] == 4
    assert grew["dsa_selected_rows_total"] == 4 * cfg.index_topk
    assert grew["dsa_context_rows_total"] == sum(len(turn2) + 1 + t
                                                 for t in range(4))
    d = engine.describe()
    assert d["model_family"] == "GlmDsaConfig"
    assert d["kv_pool_shapes"] == [[3, 33, BT, 128], [3, 33, BT, 16]]
    assert d["slot_state_shapes"] == [] and d["params_working_bytes"] == 0
    assert (d["expert_layers"], d["dense_layers"], d["index_heads"],
            d["index_topk"], d["index_key_bytes_per_token"]) == (2, 1, 4, 12, 64)


def test_the_family_names_its_counts_and_the_engine_names_no_family(model):
    from ray_tpu.models.generate import EXPERT_AUX_COUNTS

    fam = model[0].paged_family()
    assert fam.aux_counts[:len(EXPERT_AUX_COUNTS)] == EXPERT_AUX_COUNTS
    assert [c.decode for c in fam.aux_counts[-4:]] == [
        "dsa_selected_rows_total", "dsa_context_rows_total",
        "dsa_capped_slot_steps_total", "dsa_slot_steps_total"]
    assert fam.unsupported == ("prefix_cache",)
    assert fam.init_slot_state is None and fam.working_params is None

    from ray_tpu.models import generate
    from ray_tpu.serve import llm
    for module in (llm, generate):
        source = inspect.getsource(module).lower()
        assert "glm" not in source and "dsa" not in source


def test_selected_rows_are_stamped_on_the_step_span(model, engine):
    from ray_tpu.util import tracing

    engine.generate([1, 2, 3], max_new_tokens=4)
    steps = [s for s in tracing.recorded() if s.name == "llm.step"
             and (s.attrs or {}).get("engine") == "glm-test"
             and (s.attrs or {}).get("tokens")]
    assert steps and all("dsa_rows" in s.attrs and "moe_held_pairs" in s.attrs
                         for s in steps)


def test_the_programs_carry_the_named_scopes(model):
    cfg, params = model
    gen = PagedGenerator(params, cfg, slots=2, num_blocks=8, block_tokens=BT,
                         max_len=64, attention_kernel="gather")
    pool, state, last, keys = gen.init_state()
    text = gen.decode_fn(2).lower(
        params, pool, state, last, keys, np.zeros((2, 4), np.int32),
        np.zeros(2, np.int32), np.ones(2, bool), np.ones(2, bool),
        np.zeros(2, np.float32)).as_text(debug_info=True)
    for scope in ("dsa_index_scores", "dsa_select", "dsa_gather",
                  "attn_sparse", "index_pool_write", "kv_pool_write",
                  "moe_shared", "moe_experts", "dense_ffn"):
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
            cfg, lambda: glm_dsa.init_params(cfg, jax.random.key(1)),
            name="Glm", slots=2, chunk=4)
        handle = serve.run(LM.bind())
        prompt = [5, 9, 200, 31, 77, 2, 8, 1, 90, 44, 17, 6, 250, 33]
        items = list(handle.options(stream=True).remote(
            {"prompt_ids": prompt, "max_new_tokens": 6}))
        toks = [it["token"] for it in items]
        assert [it["index"] for it in items] == list(range(6))
        assert items[-1]["finish_reason"] == "stop"
        assert _served_gap(model, prompt, toks) <= TOL
    finally:
        serve.shutdown()
