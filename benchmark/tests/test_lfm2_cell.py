"""The configuration ``lfm2-8b-a1b`` and its cell: its ``counts`` against
numbers worked by hand, the cut against ``published`` and the catalog's row
(by agreement on the keys both have), the program's tree against the counts,
the readers on a program that lacks the counters, the lists the cell joins
(membership, not position), and ``--rehearse`` runs of the cell: traced,
untraced, and with every slot's tail zeroed every 16th token step, which has
to come out not correct."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.manifest import Manifest, config_count, load_function

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG = "lfm2-8b-a1b"
CELL = "lfm2-8b-a1b.conv-decode"
TRAFFIC = "conv-decode"
COUNTS = "benchmark/reduce/lfm2_counts.py"
REFERENCE = "benchmark/reference/lfm2_plain.py"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CUT_TYPES = ["conv"] * 2 + ["full_attention", "conv", "conv", "conv"] * 2

# By hand, from the published widths (hidden 2048; 32 query heads over 8 KV
# heads of 64; convolution 3; a dense width of 7168; 32 experts of 1792,
# top-4; vocabulary 65536, the table tied):
# a convolution mixer: W_in 2048*6144 = 12,582,912; taps 3*2048 = 6,144;
#   W_out 2048*2048 = 4,194,304                          -> 16,783,360
# attention: W_q, W_o 2048*2048 each; W_k, W_v 2048*512 each; two head
#   norms of 64                                           -> 10,485,888
# a dense feed-forward: 3*2048*7168                       -> 44,040,192
# an expert: 3*2048*1792 = 11,010,048; the router 2048*32 + its bias 32 =
#   65,568; an expert layer's feed-forward: 32*11,010,048 + 65,568
#                                                         -> 352,387,104
# a layer's two norms: 4,096
# the cut: 2 dense convolution layers (60,827,648 each), 2 attention and 6
#   convolution layers over experts (362,877,088 and 369,174,560), the table
#   134,217,728 and the final norm 2,048                  -> 3,196,676,608
# (ISSUE 55's first depth, 14 layers, 3 and 9 of them: 4,667,077,376)
CONV, ATTENTION, DENSE, EXPERT, EXPERT_FFN = (
    16_783_360, 10_485_888, 44_040_192, 11_010_048, 352_387_104)
TOTAL, TOTAL_AT_14 = 3_196_676_608, 4_667_077_376
PUBLISHED_TOTAL, PUBLISHED_ACTIVE = 8_339_930_560, 1_557_528_576
# Keys of the source that say a SHAPE or a constant the layer applies: each
# has to be in ``published`` whatever the catalog later prunes.
SHAPE_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "layer_types", "num_dense_layers",
    "num_attention_heads", "num_key_value_heads", "conv_L_cache", "conv_bias",
    "num_experts", "num_experts_per_tok", "use_expert_bias", "norm_topk_prob",
    "routed_scaling_factor", "rope_theta", "norm_eps",
    "max_position_embeddings")
CUT = {"num_hidden_layers": 10, "layer_types": CUT_TYPES,
       "max_position_embeddings": 2176}


@pytest.fixture(scope="module")
def lfm2_config():
    return Manifest(ROOT).load_config(CONFIG)


def test_lfm2_counts_by_hand(lfm2_config):
    c = lfm2_config
    assert CONV == 2048 * 6144 + 3 * 2048 + 2048 * 2048
    assert ATTENTION == 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    assert DENSE == 3 * 2048 * 7168 and EXPERT == 3 * 2048 * 1792
    assert EXPERT_FFN == 32 * EXPERT + 2048 * 32 + 32
    count = lambda name: load_function(ROOT, f"{COUNTS}:{name}")  # noqa: E731
    assert (count("conv_layers")(c), count("attention_layers")(c),
            count("expert_layers")(c)) == (8, 2, 8)
    assert count("head_dim")(c) == 64
    assert count("short_conv_params")(c) == CONV
    assert count("attention_params")(c) == ATTENTION
    assert count("dense_ffn_params")(c) == DENSE
    assert count("expert_params")(c) == EXPERT
    assert count("expert_ffn_params")(c) == EXPERT_FFN
    layers = lambda attention, conv: (  # noqa: E731
        2 * (CONV + DENSE + 4096) + attention * (ATTENTION + EXPERT_FFN + 4096)
        + conv * (CONV + EXPERT_FFN + 4096) + 65536 * 2048 + 2048)
    assert TOTAL == layers(2, 6) and TOTAL_AT_14 == layers(3, 9)
    assert count("param_count")(c) == TOTAL
    assert round(TOTAL * 2 / 1e9, 2) == 6.39             # GB in bfloat16
    assert round(8 * 32 * EXPERT * 2 / 1e9, 2) == 5.64   # of it the experts
    # the issue's 4,667M = 9.33 GB (8.46 GB of experts): its first depth
    at_14 = dict(c, num_hidden_layers=14, layer_types=c["published"]["layer_types"][:14])
    assert count("param_count")(at_14) == TOTAL_AT_14
    assert round(TOTAL_AT_14 * 2 / 1e9, 2) == 9.33
    assert count("kv_bytes_per_context_token")(at_14) == 6_144
    assert count("state_bytes_per_slot")(at_14) == 90_112
    # every pick lands here: 4 experts a token, all 32 held
    per_token = (8 * 4 * 2048 * 2048 + 2 * (ATTENTION - 128) + 2 * DENSE
                 + 8 * (2048 * 32 + 4 * EXPERT) + 65536 * 2048)
    assert config_count(ROOT, c, "params_per_token") == per_token == 730_333_184
    # 2 attention layers x (K and V) x 8 KV heads x 64 x 2 B
    assert config_count(ROOT, c, "kv_bytes_per_context_token") == 4_096
    # 8 convolution layers x 2 rows x 2048 x 2 B: the tail ALONE
    assert config_count(ROOT, c, "state_bytes_per_slot") == 65_536
    assert "recurrent_bytes_per_slot" not in c["counts"]  # there is no state
    assert config_count(ROOT, c, "expert_weight_bytes") == 22_020_096
    assert config_count(ROOT, c, "expert_layers") == 8
    assert config_count(ROOT, c, "short_conv_weight_bytes") == 2 * CONV == 33_566_720
    assert config_count(ROOT, c, "short_conv_matched_bytes_per_step") == (
        8 * 2 * (2048 * 6144 + 3 * 2048)) == 201_424_896
    # a snapshot of a slot's state weighs as much as 16 tokens of its own
    # K/V, one block (15 at the first depth: 90,112 B beside 6,144 B a token)
    assert 65_536 // 4_096 == 16 and -(-90_112 // 6_144) == 15
    # the published model, by the same functions: the card's 8.3B-A1.5B, with
    # the table TIED (untied the sums read 8.47B and 1.69B)
    pub = dict(c["published"], held={"of": 32}, n_routed_experts=32)
    assert count("param_count")(pub) == PUBLISHED_TOTAL
    assert count("params_per_token")(pub) == PUBLISHED_ACTIVE
    assert (round(PUBLISHED_TOTAL / 1e9, 2), round(PUBLISHED_ACTIVE / 1e9, 2)) == (
        8.34, 1.56)
    assert round((PUBLISHED_TOTAL + 65536 * 2048) / 1e9, 2) == 8.47
    assert (count("conv_layers")(pub), count("attention_layers")(pub),
            count("expert_layers")(pub)) == (18, 6, 22)


def test_the_lfm2_program_holds_what_the_counts_say(lfm2_config):
    """The program's own tree at the cell's sizes (shapes only), and what its
    engine would report as ``state_bytes`` for 128 slots and hold as a pool."""
    import jax

    from benchmark.drivers import common

    traffic = Manifest(ROOT).load_traffic(TRAFFIC)["engine"]
    slots = traffic["slots"]
    blocks = traffic["system_config"]["serve_kv_pool_blocks"]
    cfg = common.model_config(lfm2_config, rehearse=False)
    init = common.resolve(lfm2_config["init"])
    tree = jax.eval_shape(lambda k: init(cfg, k), jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(tree)) == TOTAL
    assert "lm_head" not in tree                          # the table is tied
    assert len(tree["layers"]) == 10
    kinds = ["conv" if "w_in" in lw["mixer"] else "full_attention"
             for lw in tree["layers"]]
    assert kinds == CUT_TYPES == list(cfg.layer_types)
    assert ["ffn" in lw for lw in tree["layers"]] == [True] * 2 + [False] * 8
    expert = tree["layers"][2]["experts"]
    assert expert["w_gate_up"].shape == (32, 2048, 2 * 1792)   # every expert
    assert expert["w_down"].shape == (32, 1792, 2048)
    assert tree["layers"][0]["mixer"]["conv"].shape == (3, 2048)   # no bias
    assert set(tree["layers"][0]["mixer"]) == {"w_in", "conv", "w_out"}
    assert tree["layers"][2]["mixer"]["w_q"].shape == (32, 2048, 64)
    assert tree["layers"][2]["mixer"]["w_kv"].shape == (2048, 2 * 8 * 64)
    assert tree["layers"][2]["mixer"]["q_norm"].shape == (64,)
    assert str(tree["layers"][2]["router"].dtype) == "float32"
    assert str(tree["layers"][2]["router_bias"].dtype) == "float32"
    state = jax.eval_shape(lambda: cfg.paged_family().init_slot_state(cfg, slots))
    assert len(state) == 1                                # the tail ALONE
    assert state[0].shape == (8, 2, slots, 2048) and str(state[0].dtype) == "bfloat16"
    assert sum(x.size * x.dtype.itemsize for x in state) == slots * 65_536
    pool = jax.eval_shape(lambda: cfg.paged_family().init_pool(cfg, blocks, 16))
    assert sum(x.size * x.dtype.itemsize for x in pool) == blocks * 16 * 4_096
    assert pool[0].shape == (2, blocks, 16, 8 * 64)       # the attention layers
    # every slot at its longest reservation, and the trash block
    longest = -(-(1024 + 1024 + traffic["chunk"]) // 16)
    assert blocks == slots * longest + 1 == 16513
    # every width and constant the program runs is the file's
    for key in SHAPE_KEYS:
        if hasattr(cfg, key) and key != "max_position_embeddings":
            got = getattr(cfg, key)
            assert (list(got) if key == "layer_types" else got) == lfm2_config[key], key
    assert cfg.held == (0, lfm2_config["n_routed_experts"]) == (0, 32)
    assert cfg.num_experts == lfm2_config["held"]["of"] == 32
    assert cfg.max_seq_len == lfm2_config["context_tokens"] == 2176
    # the decode step at 128 slots walks the capacity form in passes
    from ray_tpu.ops import moe
    assert moe.held_capacity(slots, 4, cfg.held, 32) == 64
    assert not moe._one_product_call(slots, 4, 32)


def test_the_lfm2_file_states_the_cut_the_floors_and_every_published_width(
        lfm2_config):
    c, pub = lfm2_config, lfm2_config["published"]
    assert sorted(c["reduced"]) == sorted(CUT) == sorted(c["reduced_why"])
    for key, value in pub.items():
        assert c[key] == CUT.get(key, value), key
    # the floors: whole periods and four layers after the dense ones; every
    # expert (floor 8); the whole vocabulary (floor an eighth)
    assert pub["layer_types"][:10] == CUT_TYPES and len(pub["layer_types"]) == 24
    assert (pub["layer_types"].count("conv"),
            pub["layer_types"].count("full_attention")) == (18, 6)
    assert CUT_TYPES[pub["num_dense_layers"]:] == [
        "full_attention", "conv", "conv", "conv"] * 2
    # the fallback states the wall clock that forced it
    assert "346 s traced" in c["reduced_why"]["num_hidden_layers"]
    assert c["num_experts"] == pub["num_experts"] == c["n_routed_experts"] == 32
    assert c["vocab_size"] == pub["vocab_size"] == 65536
    assert c["held"] == {**c["held"], "first": 0, "count": 32, "of": 32}
    assert c["context_tokens"] == c["max_position_embeddings"]
    # depth ONLY: no width, head count, expert count, constant, the picks a
    # token or the vocabulary is among the cuts
    assert not set(c["reduced"]) & (set(SHAPE_KEYS) - set(CUT))
    for key in SHAPE_KEYS:
        assert key in pub, key
    for key in ("tie_word_embeddings", "chunk_order", "final_norm", "router",
                "head_dim", "qk_norm", "rotary", "expert_bias", "init",
                "stored_dtype", "context_tokens"):
        assert key in c["assumed"], key
    stands = c["deployment"]["stands_for"]
    assert "two pipeline stages" in stands and "embedding AND the head" in stands
    assert {"reckoned", "compiled"} <= set(c["deployment"]["memory"])
    entry = Manifest(ROOT).configs[CONFIG]
    assert entry["source"] == c["source"] and entry["reduced"] == c["reduced"]
    assert 1 <= len(entry["why"]) <= 200


def test_lfm2_published_agrees_with_the_catalog_where_both_speak(lfm2_config):
    """Every key present in BOTH ``published`` and the catalog's row agrees,
    and the row still is this model. Not equality of the two dicts: the
    catalog prunes keys that say nothing of shape (PERF.md section 7)."""
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = next(r for r in rows if r["source_url"] == lfm2_config["source"])
    pub = lfm2_config["published"]
    both = set(pub) & set(row["config"])
    assert len(both) >= 18
    for key in both:
        assert pub[key] == row["config"][key], key
    assert row["config"].get("model_type", "lfm2_moe") == "lfm2_moe"


def test_the_lfm2_rehearsal_overlay_is_the_tiny_models_sizes(lfm2_config):
    from benchmark.drivers import common
    from benchmark.run import _merge

    merged = _merge(lfm2_config, lfm2_config["rehearse"])
    tiny = common.model_config(merged, rehearse=True)
    for key in SHAPE_KEYS:
        if hasattr(tiny, key) and key != "max_position_embeddings":
            got = getattr(tiny, key)
            assert (list(got) if key == "layer_types" else got) == merged[key], key
    assert tiny.held == (0, merged["n_routed_experts"]) == (0, 8)
    assert tiny.num_experts == merged["held"]["of"] == 8
    assert tiny.max_seq_len == merged["context_tokens"] == 128


def _run(config, before, after, polled=()):
    return {"counters": {"before": before, "after": after,
                         "polled": list(polled)},
            "config": config, "root": ROOT, "trace": None, "chunk": 8,
            "traffic": Manifest(ROOT).load_traffic(TRAFFIC),
            "t_open": 0.0, "t_close": 1.0}


NEW_METRICS = ("lfm2_expert_ffn_ms_per_step.batch", "lfm2_expert_ffn_roofline",
               "short_conv_ms_per_step.batch", "short_conv_roofline",
               "moe_decode_extra_passes_share")
JOINED_METRICS = (
    "paged_attn_roofline", "state_cache_share", "prefill_dev_share.batch",
    "prefill_pad_rows_share", "moe_load_imbalance",
    "expert_layer_tokens_per_expert", "expert_rows_overflow_share")


def test_lfm2_readers_find_nothing_where_there_is_nothing_to_read(lfm2_config):
    """A program without the tail's or the expert counters (the parent commit
    has no such configuration; GPT-2 has neither counter), and an untraced
    run: every metric the cell adds or joins by name is left out and nothing
    raises."""
    man = Manifest(ROOT)
    poll = {"t": 0.5, "slots_busy": 3.0, "slots_total": 4.0,
            "kv_blocks_active": 10.0}
    for config in (lfm2_config, man.load_config("gpt2-medium")):
        run = _run(config, {"steps_total": 1.0}, {"steps_total": 9.0}, [poll])
        for name in NEW_METRICS + JOINED_METRICS:
            assert man.reader(name)(run) is None, name
    # traced, but a configuration without the count: nothing, and no raise
    reader = load_function(ROOT, "benchmark/readers/short_conv.py:weights_roofline")
    spec = {"count": "short_conv_matched_bytes_per_step", "pattern": "x",
            "step_pattern": "^jit_paged_decode"}
    run = _run(man.load_config("gpt2-medium"), {}, {})
    assert reader(dict(run, trace={"devices": {}, "host": []}), spec) is None
    assert reader(dict(_run(lfm2_config, {}, {}),
                       trace={"devices": {}, "host": []}), spec) is None


def test_lfm2_readers_by_hand(lfm2_config):
    man = Manifest(ROOT)
    # 10 decode calls of 8 token steps, 126 of 128 slots active in each: a
    # token step offers 8 expert layers 126 x 4 picks, every one held
    steps = 10 * 8
    names = ("steps_total", "state_slot_steps_total", "moe_steps_total",
             "moe_picks_held_total", "moe_held_pairs_max_total",
             "moe_experts_hit_total", "moe_bounded_calls_total",
             "moe_extra_windows_total")
    before = {k: 0.0 for k in names}
    after = {"steps_total": 10.0, "state_slot_steps_total": steps * 126.0,
             "moe_steps_total": float(steps),
             "moe_picks_held_total": steps * 8 * 126 * 4.0,
             "moe_held_pairs_max_total": steps * 8 * 30.0,
             "moe_experts_hit_total": steps * 8 * 32.0,
             "moe_bounded_calls_total": steps * 8.0,
             "moe_extra_windows_total": 16.0}
    poll = {"t": 0.5, "slots_busy": 126.0, "slots_total": 128.0,
            "kv_blocks_active": 7200.0, "state_bytes": 128 * 65_536.0}
    run = _run(lfm2_config, before, after, [poll, dict(poll, t=2.0)])
    state, kv = 126 * 65_536, 7200 * 16 * 4_096
    assert man.reader("state_cache_share")(run) == pytest.approx(
        100.0 * state / (state + kv))
    assert 1 < man.reader("state_cache_share")(run) < 2   # two rows a layer
    # 126 x 4 picks over 32 experts: 15.75 tokens an expert a step
    assert man.reader("expert_layer_tokens_per_expert")(run) == pytest.approx(
        126 * 4 / 32)
    assert man.reader("moe_load_imbalance")(run) == pytest.approx(
        30 * 32 / (126 * 4))
    # 16 passes beyond the first in 640 expert-layer calls
    assert man.reader("moe_decode_extra_passes_share")(run) == pytest.approx(2.5)
    for name in ("lfm2_expert_ffn_roofline", "short_conv_roofline",
                 "paged_attn_roofline"):
        assert man.reader(name)(run) is None, name          # no trace
    # the two rooflines on a trace written by hand: one whole decode call of
    # 8 steps between the two the edges cut, the matched fusions inside it
    dev = {"XLA Modules": [["jit_paged_decode(1)", 0, 10], ["jit_paged_decode(1)", 100, 800],
                           ["jit_paged_decode(1)", 1000, 10]],
           "XLA Ops": [["fusion:fusion:f32[128,6144]", 110, 100],
                       ["multiply_reduce_fusion:fusion:f32[128,2048]", 220, 50],
                       ["fusion:fusion:f32[128,2048]", 300, 70],       # not matched
                       ["fusion:fusion:f32[32,64,3584]", 400, 200],
                       ["fusion:fusion:bf16[2048,1792]", 610, 20],
                       ["fusion:fusion:f32[32,64,2048]", 640, 100],
                       ["fusion:fusion:f32[128,6144]", 1001, 5]]}     # a cut call's
    traced = dict(run, trace={"devices": {"/device:TPU:0": dev}, "host": []},
                  peaks={"hbm_bytes_per_s": 1e9})
    assert man.reader("short_conv_ms_per_step.batch")(traced) == pytest.approx(
        150e-9 / 8 * 1e3)
    assert man.reader("short_conv_roofline")(traced) == pytest.approx(
        100.0 * 8 * 201_424_896 / 1e9 / 150e-9)
    assert man.reader("lfm2_expert_ffn_ms_per_step.batch")(traced) == pytest.approx(
        320e-9 / 8 * 1e3)
    assert man.reader("lfm2_expert_ffn_roofline")(traced) == pytest.approx(
        100.0 * 32 * 8 * 8 * 22_020_096 / 1e9 / 320e-9)


def test_the_new_metrics_are_files_with_this_cell_alone():
    """Five new metrics, each a file under ``benchmark/metrics/`` with this
    cell alone in its list; four on readers that were there, the mixer's
    roofline on a reader in a new file; the two rooflines take their bytes
    from functions kept under ``benchmark/reduce/``."""
    man = Manifest(ROOT)
    readers = {
        NEW_METRICS[0]: "benchmark/readers/device.py:op_ms_per_step",
        NEW_METRICS[1]: "benchmark/readers/expert_layers.py:ffn_roofline",
        NEW_METRICS[2]: "benchmark/readers/device.py:op_ms_per_step",
        NEW_METRICS[3]: "benchmark/readers/short_conv.py:weights_roofline",
        NEW_METRICS[4]: "benchmark/readers/spans.py:counter_ratio"}
    for name, reader in readers.items():
        with open(man.metric_file(name)) as f:
            spec = json.load(f)
        assert spec["reader"] == reader
        assert man.per_layer[name]["workloads"] == [CELL]
        assert man.per_layer[name]["layer"] == spec["layer"]
        assert man.per_layer[name]["moves"] == spec["moves"] == "serve_out_tok_s"
        if "pattern" in spec:
            assert spec["step_pattern"] == "^jit_paged_decode"
    specs = {n: json.load(open(man.metric_file(n))) for n in NEW_METRICS}
    assert specs[NEW_METRICS[0]]["pattern"] == specs[NEW_METRICS[1]]["pattern"]
    assert specs[NEW_METRICS[2]]["pattern"] == specs[NEW_METRICS[3]]["pattern"]
    # [experts, capacity rows, hidden_size] and the two widths between: the
    # cell's own sizes; reason-decode's pattern names 2,688 and cannot match
    assert "32,64,(3584|2048)" in specs[NEW_METRICS[0]]["pattern"]
    assert specs[NEW_METRICS[3]]["count"] == "short_conv_matched_bytes_per_step"
    assert specs[NEW_METRICS[4]]["counter"] == "moe_extra_windows_total"
    assert specs[NEW_METRICS[4]]["over"] == ["moe_bounded_calls_total"]
    assert specs[NEW_METRICS[0]]["layer"] == man.per_layer[
        "expert_layer_ffn_roofline"]["layer"]
    counts = man.load_config(CONFIG)["counts"]
    for key in ("expert_weight_bytes", "expert_layers", "short_conv_weight_bytes",
                "short_conv_matched_bytes_per_step"):
        assert counts[key].startswith("benchmark/reduce/lfm2_counts.py:")


def test_the_lfm2_cell_joins_the_lists_the_issue_names():
    """Membership only: the next PR appends cells, configurations and
    metrics, and joins this cell to further lists, without this test's
    leave. No count of cells or configurations is pinned."""
    man = Manifest(ROOT)
    assert man.check() == []
    names = {m["name"] for m in man.metrics_of(CELL, "per_layer")}
    assert set(NEW_METRICS) | set(JOINED_METRICS) <= names
    # every list that every OTHER closed serve cell is on
    closed = [w for w in man.per_layer["decode_step_ms.batch"]["workloads"]
              if w != CELL]
    everywhere = set.intersection(*(
        {m["name"] for m in man.metrics_of(w, "per_layer")} for w in closed))
    assert everywhere <= names
    assert {"decode_step_ms.batch", "device_idle_share.batch",
            "hbm_peak_share.batch", "kv_blocks_peak_share",
            "pool_blocked_share", "dispatch_ahead_share", "step_host_share",
            "replica_warmup_s", "warmup_lower_s"} <= names
    # NOT the grouped product's metrics, NOT reason-decode's capacity pair
    # (its pattern names another shape), NOT a state kernel's (there is no
    # state), NOT another family's
    assert not {"moe_ffn_ms_per_step.batch", "expert_layer_ffn_roofline",
                "expert_capacity_ffn_roofline", "ssd_state_roofline",
                "gdn_state_roofline", "mla_attn_roofline",
                "windowed_attn_roofline", "shared_expert_ms_per_step.batch",
                "moe_ffn_roofline", "moe_held_tokens_per_expert"} & names
    assert {"serve_out_tok_s", "setup_s"} <= {
        m["name"] for m in man.metrics_of(CELL, "end_to_end")}
    assert man.cells[CELL] == {**man.cells[CELL], "chips": 1,
                               "config": CONFIG, "traffic": TRAFFIC}
    assert CONFIG in man.configs
    for entry in (man.doc["configs"] + man.doc["workloads"]
                  + man.doc["end_to_end"] + man.doc["per_layer"]):
        for key in ("why", "layer", "source"):
            text = entry.get(key, "x")
            assert 1 <= len(text) <= 200 and text.isprintable(), (entry["name"], key)
    traffic = man.load_traffic(TRAFFIC)
    assert traffic["driver"] == "serve_closed"
    eng = traffic["engine"]
    assert (traffic["clients"], eng["slots"], eng["chunk"], eng["max_queue"]) == (
        160, 128, 8, 64)
    assert eng["system_config"] == {"serve_kv_pool_blocks": 16513,
                                    "serve_kv_block_tokens": 16,
                                    "serve_llm_prefill_tokens": 2048}
    assert traffic["prompt_tokens"] == {"dist": "uniform", "lo": 128, "hi": 1024}
    assert traffic["output_tokens"] == {"dist": "uniform", "lo": 256, "hi": 1024}
    assert (traffic["block_requests"], traffic["sub_block_requests"]) == (160, 16)
    assert traffic["check"]["requests"] == 4
    assert "PLACEHOLDER" not in json.dumps(traffic)
    assert "PLACEHOLDER" not in json.dumps(man.load_config(CONFIG))


def _env():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_ENABLE_COMPILATION_CACHE"] = "0"
    return env


def _rehearse(trace: int, launcher=None):
    # 12 s of window: a loaded CPU (the test runner's six workers) must still
    # finish a request a slot inside it for the check to have its sample
    args = ["--workload", CELL, "--seed", str(2 ** 31 + 55), "--seconds", "12",
            "--trace", str(trace), "--rehearse"]
    cmd = ([sys.executable, "benchmark/run.py"] + args if launcher is None
           else [sys.executable, "-c", launcher] + args)
    proc = subprocess.run(cmd, cwd=ROOT, env=_env(), capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    return json.loads(lines[-1]), json.loads(lines[-2])["detail"]


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_lfm2_cell(trace):
    last, detail = _rehearse(trace)
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert last["device"]["platform"] == "cpu"            # never a chip result
    assert detail["check"]["checked"] >= 1 and detail["compiles_in_window"] == 0
    open_, close = (detail["realised"][f"engine_at_{k}"] for k in ("open", "close"))
    # the tail AND the experts, in one program's counters
    assert close["state_slot_steps_total"] > open_["state_slot_steps_total"]
    assert close["moe_picks_held_total"] > open_["moe_picks_held_total"]
    assert close["moe_picks_held_total"] == close["moe_picks_total"]   # all held
    assert close["moe_picks_zero_total"] == 0
    # 4 convolution layers x 4 slots x 2 rows of 64 float32
    assert close["state_bytes"] == open_["state_bytes"] == 4 * 4 * 2 * 64 * 4
    assert close["prefix_lookups_refused_total"] == close["state_resets_total"] > 0
    assert close["kv_hit_tokens"] == 0
    if trace:
        # every metric listed for the cell that reads counters, spans or the
        # host's clock; the device trace's need a chip. The two shares of
        # bounded calls have nothing to divide by here: with 8 experts the
        # tiny model's expert layer takes neither the row bound nor the
        # capacity form (``moe_bounded_calls_total`` stays 0)
        man = Manifest(ROOT)
        listed = {m["name"]: m["source"] for m in man.metrics_of(CELL, "per_layer")}
        absent = {n for n, source in listed.items() if source == "device_trace"}
        absent |= {"moe_decode_extra_passes_share", "expert_rows_overflow_share",
                   "hbm_peak_share.batch"}       # the CPU reports no memory peak
        assert close["moe_bounded_calls_total"] == 0
        assert set(listed) - absent <= set(last["metrics"]), sorted(
            set(listed) - absent - set(last["metrics"]))
        assert not absent & set(last["metrics"])
        assert 0 < last["metrics"]["state_cache_share"]["value"] < 100
        # 4 slots x top-2 over 8 experts, all held: 1 token an expert at most
        assert 0.3 < last["metrics"]["expert_layer_tokens_per_expert"]["value"] <= 1.0
    else:
        assert {"setup_s", "serve_out_tok_s"} <= set(last["metrics"])


# The same command, started through a wrapper that plants ONE fault in the
# program from outside it (the program has no option for any of them). For
# the chip, at the cell's sizes: ``python3 -c "from
# benchmark.tests.test_lfm2_cell import FAULTS as F; exec(F['zeroed_tail'])"
# --workload lfm2-8b-a1b.conv-decode --seed N --seconds 45 --trace 0``
# (readings: ``check.why`` in benchmark/traffic/conv-decode.json).
_HEAD = """
import sys
sys.path.insert(0, ".")
import jax, jax.numpy as jnp
from ray_tpu.models import lfm2
from ray_tpu.ops import causal_conv, moe
from ray_tpu.serve import llm
"""
_TAIL = """
from benchmark import run
sys.argv = ["benchmark/run.py"] + sys.argv[1:]
sys.exit(run.main())
"""
# ONE expert layer (the second) told apart by a marker in the weights: a kind
# of layer is traced once a program, so the fault cannot count calls.
_MARK_SECOND_EXPERT_LAYER = """
init = lfm2.init_params
def marked(config, key):
    p, n = init(config, key), [0]
    for lp in p["layers"]:
        if "router" in lp:
            lp["marked"] = jnp.asarray(n[0] == 1, jnp.float32)
            n[0] += 1
    return p
lfm2.init_params = marked
"""
FAULTS = {
    # every slot's tail zeroed before every fourth decode dispatch (chunks of
    # 4 in the rehearsal: every 16th token step; every 32nd at the cell's
    # chunk of 8), where it lies (donated)
    "zeroed_tail": _HEAD + """
plain, calls = llm.LLMEngine._run_decode, [0]
zeroed = jax.jit(lambda t: t * 0, donate_argnums=0)
def damaged(self, *args):
    calls[0] += 1
    if self._steady and calls[0] % 4 == 0:
        (tail,) = self._slot_state
        self._slot_state = (zeroed(tail),)
    return plain(self, *args)
llm.LLMEngine._run_decode = damaged
""" + _TAIL,
    # the gate C left out: Mixer = conv(B * u) W_out
    "no_gate": _HEAD + """
plain = lfm2._conv_out
lfm2._conv_out = lambda mw, gate, y, c: plain(mw, jnp.ones_like(gate), y, c)
""" + _TAIL,
    # the convolution's taps reversed: the newest row under the oldest's tap
    "taps_reversed": _HEAD + """
class reversed_taps:
    prefill = staticmethod(lambda pre, w, *rest: causal_conv.prefill(
        pre, w[::-1], *rest))
    decode = staticmethod(lambda pre, w, *rest: causal_conv.decode(
        pre, w[::-1], *rest))
lfm2.causal_conv = reversed_taps
""" + _TAIL,
    # q_norm left out: the queries go to the rotation as they left W_q
    "no_q_norm": _HEAD + """
plain_attention, plain_norm = lfm2._attention, lfm2.rms_norm
def unnormed_queries(mw, a, pool, al, ctx, c, kernel):
    # the query heads' norm alone: the keys have fewer heads
    q_heads = (c.num_attention_heads, c.head_dim)
    lfm2.rms_norm = lambda x, g, eps: (
        x if x.ndim == 4 and x.shape[-2:] == q_heads else plain_norm(x, g, eps))
    try:
        return plain_attention(mw, a, pool, al, ctx, c, kernel)
    finally:
        lfm2.rms_norm = plain_norm
lfm2._attention = unnormed_queries
""" + _TAIL,
    # the rotation left out: queries and keys carry no position
    "no_rotation": _HEAD + """
lfm2.rope = lambda x, positions, **kw: x
""" + _TAIL,
    # the expert bias added to the WEIGHTS: a pick weighs s + b, not s
    "bias_weighs": _HEAD + """
def weighing(h, w_router, bias, *, topk, scale, score, renormalise):
    s = jax.nn.sigmoid(jnp.einsum(
        "nd,de->ne", h.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST)) + bias.astype(jnp.float32)
    picked, idx = jax.lax.top_k(s, topk)
    picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), scale * picked
moe.route_topk = weighing
""" + _TAIL,
    # ONE expert layer's picks (the second's) cut to three: the weakest pick
    # weighs nothing and the three left are normalised over themselves
    "three_picks": _HEAD + _MARK_SECOND_EXPERT_LAYER + """
plain_layer, plain_route = lfm2.expert_layer, moe.route_topk
def cut(lp, x, valid, c):
    def route(h, w_router, bias, **kw):
        idx, w = plain_route(h, w_router, bias, **kw)
        kept = jnp.where(jnp.arange(w.shape[1]) < w.shape[1] - 1, w, 0.0)
        kept = kw["scale"] * kept / jnp.sum(kept, axis=-1, keepdims=True)
        return idx, jnp.where(lp["marked"] > 0, kept, w)
    moe.route_topk = route
    try:
        return plain_layer(lp, x, valid, c)
    finally:
        moe.route_topk = plain_route
lfm2.expert_layer = cut
""" + _TAIL,
}


def test_with_the_tail_zeroed_every_16th_step_the_cell_is_not_correct():
    last, detail = _rehearse(0, launcher=FAULTS["zeroed_tail"])
    assert detail["correct_parts"]["streams_complete"] is True
    assert detail["correct_parts"]["reference_sample"] is False
    assert last["correct"] is False
    # the sound float32 rehearsal reads 0.0 against the limit of 0.002
    assert detail["check"]["worst_gap"] > 5 * detail["check"]["tolerance"]


@pytest.mark.parametrize("fault", sorted(set(FAULTS) - {"zeroed_tail"}))
def test_each_lfm2_launcher_plants_the_fault_it_says(fault, monkeypatch):
    """On the program as it is named today: with the launcher's patch the
    tiny model's logits after a prefill and a decode chunk move by far more
    than float32's rounding. (``zeroed_tail`` patches the engine, not the
    model: the rehearsal above holds that launcher.)"""
    import jax
    import numpy as np

    from ray_tpu.models import lfm2
    from ray_tpu.models.generate import PagedGenerator
    from ray_tpu.ops import moe

    for mod, name in ((lfm2, "_conv_out"), (lfm2, "causal_conv"),
                      (lfm2, "rms_norm"), (lfm2, "rope"),
                      (lfm2, "_attention"), (lfm2, "expert_layer"),
                      (lfm2, "init_params"), (moe, "route_topk")):
        monkeypatch.setattr(mod, name, getattr(mod, name))   # put back after

    def last_rows():
        lfm2._layer_fn.cache_clear()            # traced anew, patched or not
        cfg = lfm2.tiny()
        params = lfm2.init_params(cfg, jax.random.key(3))
        gen = PagedGenerator(params, cfg, slots=1, num_blocks=8,
                             block_tokens=16, max_len=64,
                             attention_kernel="gather")
        pool, state, last, keys = gen.init_state()
        padded = np.arange(1, 65, dtype=np.int32)[None]
        dev = gen.prefill_fn(64)(params, pool, state, last, keys,
                                 np.asarray([1, 2, 3, 4], np.int32), padded,
                                 0, 40, 0, 0)[:4]
        out = gen.decode_fn(4)(params, *dev,
                               np.asarray([[1, 2, 3, 4]], np.int32),
                               np.asarray([40], np.int32), np.ones(1, bool),
                               np.ones(1, bool), np.zeros(1, np.float32))
        return np.asarray(out[3][0])

    whole = last_rows()
    exec(FAULTS[fault].split("from benchmark import run")[0], {})
    moved = np.abs(last_rows() - whole).max()
    lfm2._layer_fn.cache_clear()
    assert moved > 0.01, (fault, moved)


def test_the_lfm2_files_name_no_other_architecture():
    """The counts and the reference state this configuration from its dict
    alone and import nothing of the program: no kernel, no grouped product,
    no tail."""
    for file in (COUNTS, REFERENCE):
        with open(os.path.join(ROOT, file)) as f:
            text = f.read()
        assert "import ray_tpu" not in text and "from ray_tpu" not in text
        for module in ("ops.causal_conv", "ops.moe", "ragged_dot", "pallas"):
            assert module not in text, (file, module)
