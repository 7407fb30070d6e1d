"""The configuration ``mimo-v2-flash`` and its cell: its ``counts`` against
numbers worked by hand, the cut against ``published`` and the catalog's row,
the program's tree, pool and rings against the counts, the new reader by
hand and where there is nothing to read, the lists the cell joins, and
``--rehearse`` runs of the cell (a window of 16 under contexts of 24-72):
traced, untraced, and with the sink left out, which has to come out not
correct."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.manifest import Manifest, config_count, load_function

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "mimo-v2-flash.swa-decode"
CONFIG = "mimo-v2-flash"
COUNTS = "benchmark/reduce/mimo_v2_counts.py"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

# By hand, from the published widths (hidden 4096, 64 query heads of 192 with
# V heads of 128, 4 KV heads in a full layer and 8 in a window layer, dense
# FFN 16384, experts 2048, vocabulary slice 19072):
# W_q 4096*64*192 = 50,331,648; W_o 64*128*4096 = 33,554,432
# full:   W_k 4096*4*192 = 3,145,728; W_v 4096*4*128 = 2,097,152 -> 89,128,960
# window: W_k 4096*8*192 = 6,291,456; W_v 4096*8*128 = 4,194,304 -> 94,371,840
# the dense FFN 3*4096*16384                                    -> 201,326,592
# an expert 3*4096*2048                                         -> 25,165,824
# the router 4096*256                                           -> 1,048,576
# layer 0 (full, dense) 89,128,960 + 201,326,592                -> 290,455,552
# a window expert layer 94,371,840 + 1,048,576 + 8*25,165,824   -> 296,747,008
# the full expert layer 89,128,960 + 1,048,576 + 8*25,165,824   -> 291,504,128
# embedding and head 2*19072*4096                               -> 156,237,824
FULL_ATTN, WINDOW_ATTN = 89_128_960, 94_371_840
DENSE_FFN, EXPERT, ROUTER = 201_326_592, 25_165_824, 1_048_576
TOTAL = 2_221_932_544
# rows: a full layer 4 x (192 + 128) x 2 B, a window layer 8 x 320 x 2 B
FULL_ROW, WINDOW_ROW = 2_560, 5_120
# Keys of the source that say a SHAPE, a count or a rule the layer applies:
# each has to be in ``published`` whatever the catalog later prunes.
SHAPE_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
    "swa_num_attention_heads", "swa_num_key_value_heads", "head_dim",
    "v_head_dim", "swa_head_dim", "swa_v_head_dim", "sliding_window",
    "hybrid_layer_pattern", "moe_layer_freq", "n_routed_experts",
    "n_shared_experts", "num_experts_per_tok", "norm_topk_prob",
    "scoring_func", "topk_method", "n_group", "topk_group",
    "routed_scaling_factor", "rope_theta", "swa_rope_theta",
    "partial_rotary_factor", "attention_value_scale",
    "add_swa_attention_sink_bias", "add_full_attention_sink_bias",
    "attention_bias", "layernorm_epsilon", "max_position_embeddings",
    "tie_word_embeddings", "hidden_act")
# The widths, which no cut may touch.
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "num_attention_heads", "num_key_value_heads",
          "swa_num_key_value_heads", "head_dim", "v_head_dim", "swa_head_dim",
          "swa_v_head_dim", "sliding_window", "num_experts_per_tok",
          "rope_theta", "swa_rope_theta", "partial_rotary_factor",
          "attention_value_scale")


@pytest.fixture(scope="module")
def mimo_config():
    return Manifest(ROOT).load_config(CONFIG)


def test_mimo_counts_by_hand(mimo_config):
    c = mimo_config
    assert FULL_ATTN == 4096 * (64 * 320 + 4 * 320)
    assert WINDOW_ATTN == 4096 * (64 * 320 + 8 * 320)
    assert TOTAL == ((FULL_ATTN + DENSE_FFN) + 4 * (WINDOW_ATTN + ROUTER + 8 * EXPERT)
                     + (FULL_ATTN + ROUTER + 8 * EXPERT)
                     + (WINDOW_ATTN + ROUTER + 8 * EXPERT) + 2 * 19072 * 4096)
    count = lambda name: load_function(ROOT, f"{COUNTS}:{name}")  # noqa: E731
    assert count("attention_params")(c, False) == FULL_ATTN
    assert count("attention_params")(c, True) == WINDOW_ATTN
    assert count("dense_ffn_params")(c) == DENSE_FFN
    assert count("expert_params")(c) == EXPERT
    assert count("router_params")(c) == ROUTER
    assert count("param_count")(c) == TOTAL            # the issue's 2,222M
    assert (count("window_layers")(c), count("full_layers")(c)) == (5, 2)
    assert config_count(ROOT, c, "expert_layers") == 6
    # the whole model from its published keys: the card's 309B, 15.4B active
    pub = dict(c["published"], held={"first": 0, "count": 256, "of": 256})
    assert count("param_count")(pub) == 308_778_369_024
    assert round((count("params_per_token")(pub) + 152576 * 4096) / 1e9, 1) == 15.4
    # a token uses 8 * 8/256 = 0.25 held experts an expert layer; the
    # embedding is a lookup
    assert config_count(ROOT, c, "params_per_token") == (
        2 * FULL_ATTN + 5 * WINDOW_ATTN + DENSE_FFN
        + 6 * (ROUTER + 0.25 * EXPERT) + 19072 * 4096)
    # the pool: TWO full layers x 2,560 B; the rings: five window layers x
    # (128 + a block of 64) rows x 5,120 B
    assert (count("kv_row_bytes")(c, False), count("kv_row_bytes")(c, True)) == (
        FULL_ROW, WINDOW_ROW)
    assert config_count(ROOT, c, "kv_bytes_per_context_token") == 2 * FULL_ROW == 5_120
    rows = count("ring_rows")(c)
    assert rows == 128 + c["window_block_tokens"]
    assert config_count(ROOT, c, "state_bytes_per_slot") == 5 * rows * WINDOW_ROW
    assert config_count(ROOT, c, "expert_weight_bytes") == 2 * EXPERT
    # what a decode step HAS to read is not linear in the context: under the
    # window every layer reads it all, past it the window layers stop
    read = count("attention_bytes_read")
    assert read(c, [100]) == 100 * (5 * WINDOW_ROW + 2 * FULL_ROW)
    assert read(c, [2050]) == 128 * 5 * WINDOW_ROW + 2050 * 2 * FULL_ROW
    assert read(c, [4088]) - read(c, [3088]) == 1000 * 2 * FULL_ROW
    assert read(c, [100, 2050]) == read(c, [100]) + read(c, [2050])
    # a prefill's attention: the causal triangle in two layers, the band of
    # 128 keys in five, 64 query heads, (192 + 128) wide, 2 FLOPs a pair
    flops = count("attention_prefill_flops")
    assert flops(c, 1) == 2 * 7 * 64 * 320
    assert flops(c, 128) == 2 * 7 * (128 * 129 // 2) * 64 * 320
    band = 128 * 129 // 2 + (2048 - 128) * 128
    assert flops(c, 2048) == 2 * (2 * (2048 * 2049 // 2) + 5 * band) * 64 * 320
    # past the window a window layer's work grows with the prompt, not its square
    assert flops(c, 4096) - flops(c, 2048) < 4 * flops(c, 2048)


def test_the_mimo_program_holds_what_the_counts_say(mimo_config):
    """The program's own tree at the cell's sizes (shapes only), and what its
    engine would report as ``state_bytes`` and hold as a pool."""
    import jax

    from benchmark.drivers import common

    traffic = Manifest(ROOT).load_traffic("swa-decode")
    eng = traffic["engine"]
    slots, blocks = eng["slots"], eng["system_config"]["serve_kv_pool_blocks"]
    cfg = common.model_config(mimo_config, rehearse=False)
    init = common.resolve(mimo_config["init"])
    tree = jax.eval_shape(lambda k: init(cfg, k), jax.random.key(0))
    # the matrices, then two gains a layer and the final norm's, five window
    # layers' sinks and six routers' selection biases
    rest = 7 * 2 * 4096 + 4096 + 5 * 64 + 6 * 256
    assert sum(x.size for x in jax.tree.leaves(tree)) == TOTAL + rest
    assert len(tree["layers"]) == 7
    assert ["ffn" in lp for lp in tree["layers"]] == [True] + [False] * 6
    assert ["sink" in lp for lp in tree["layers"]] == [False, True, True, True,
                                                       True, False, True]
    assert tree["layers"][1]["router"].shape == (4096, 256)       # all 256
    assert tree["layers"][1]["experts"]["w_down"].shape == (8, 2048, 4096)
    assert tree["layers"][1]["w_k"].shape == (4096, 8 * 192)
    assert tree["layers"][5]["w_k"].shape == (4096, 4 * 192)
    assert tree["layers"][5]["w_v"].shape == (4096, 4 * 128)
    per_slot = config_count(ROOT, mimo_config, "state_bytes_per_slot")
    rb = mimo_config["window_block_tokens"]
    state = jax.eval_shape(lambda: cfg.paged_family().init_slot_state(cfg, slots))
    assert sum(x.size * x.dtype.itemsize for x in state) == slots * per_slot
    assert [x.shape for x in state] == [
        (5, slots, 128 // rb + 1, rb, 8 * 192), (5, slots, 128 // rb + 1, rb, 8 * 128)]
    pool = jax.eval_shape(lambda: cfg.paged_family().init_pool(cfg, blocks, 16))
    assert [x.shape for x in pool] == [(2, blocks, 16, 4 * 192),
                                       (2, blocks, 16, 4 * 128)]
    assert sum(x.size * x.dtype.itemsize for x in pool) == blocks * 16 * 2 * FULL_ROW
    # every slot at its longest reservation, and the trash block
    longest = -(-(2560 + 1528 + eng["chunk"]) // 16)
    assert blocks == slots * longest + 1
    for key in SHAPE_KEYS:
        if hasattr(cfg, key):
            got = getattr(cfg, key)
            got = list(got) if isinstance(got, tuple) else got
            assert got == {"n_routed_experts": 256}.get(key, mimo_config[key]), key
    assert cfg.held == (0, 8) and cfg.max_seq_len == 4096
    assert cfg.window_block_tokens == rb and cfg.rotary_dim == 64
    d = cfg.paged_family().describe(cfg)
    assert d["window_ring_bytes_per_slot"] == per_slot
    assert (d["window_layers"], d["full_layers"], d["kv_heads_window"],
            d["kv_heads_full"], d["ring_rows"], d["expert_layers"],
            d["dense_layers"]) == (5, 2, 8, 4, 128 + rb, 6, 1)


def test_the_mimo_file_states_the_cut_the_floors_and_every_published_width(
        mimo_config):
    c, pub = mimo_config, mimo_config["published"]
    cut = {"num_hidden_layers": 7, "hybrid_layer_pattern": [0, 1, 1, 1, 1, 0, 1],
           "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1], "n_routed_experts": 8,
           "vocab_size": 19072, "max_position_embeddings": 4096}
    assert sorted(c["reduced"]) == sorted(cut) == sorted(c["reduced_why"])
    for key, value in pub.items():
        assert c[key] == cut.get(key, value), key
    # the cut lists are the first seven PUBLISHED entries: the leading full,
    # dense layer and a whole period of six (five window : one full)
    assert pub["hybrid_layer_pattern"][:7] == cut["hybrid_layer_pattern"]
    assert pub["moe_layer_freq"][:7] == cut["moe_layer_freq"]
    assert len(pub["hybrid_layer_pattern"]) == len(pub["moe_layer_freq"]) == 48
    assert (pub["hybrid_layer_pattern"].count(0), sum(pub["moe_layer_freq"])) == (9, 47)
    assert pub["hybrid_layer_pattern"][1:7] == pub["hybrid_layer_pattern"][7:13]
    assert "OVER-represented" in c["reduced_why"]["hybrid_layer_pattern"]
    # the floors: a whole period and four layers after the leading dense
    # one, at least 8 held experts, at least an eighth of the vocabulary
    assert sum(c["moe_layer_freq"]) >= 4
    assert c["held"] == {**c["held"], "first": 0, "count": 8, "of": 256}
    assert c["n_routed_experts"] == c["held"]["count"] >= 8
    assert pub["n_routed_experts"] == c["held"]["of"]
    assert c["vocab_size"] * 8 == pub["vocab_size"] and c["vocab_size"] % 128 == 0
    assert c["context_tokens"] == c["max_position_embeddings"]
    # no width is among the cuts, and every shape key is published
    assert not set(c["reduced"]) & set(WIDTHS)
    for key in SHAPE_KEYS:
        assert key in pub, key
    for key in WIDTHS:
        assert c[key] == pub[key], key
    for key in ("value_scale", "sink", "rotary", "window_edges",
                "softmax_scale", "attention_chunk_size", "no_qk_norm",
                "head_map", "router", "init", "stored_dtype", "left_out",
                "window_block_tokens", "context_tokens"):
        assert key in c["assumed"], key
    stands = c["deployment"]["stands_for"]
    assert "one of 32 chips" in stands and "128 chips" in stands
    assert {"reckoned", "compiled"} <= set(c["deployment"]["memory"])
    entry = Manifest(ROOT).configs[CONFIG]
    assert entry["source"] == c["source"] and entry["reduced"] == c["reduced"]
    assert 1 <= len(entry["why"]) <= 200


def test_mimo_published_agrees_with_the_catalog_where_both_speak(mimo_config):
    """Every key present in BOTH ``published`` and the catalog's row agrees,
    and the row still is this model. Not equality of the two dicts: the
    catalog's keepers prune keys (ROADMAP M9)."""
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = next(r for r in rows if r["source_url"] == mimo_config["source"])
    pub = mimo_config["published"]
    both = set(pub) & set(row["config"])
    assert len(both) >= 30
    for key in both:
        assert pub[key] == row["config"][key], key
    assert row["config"].get("model_type", "mimo_v2_flash") == "mimo_v2_flash"


def test_the_mimo_rehearsal_overlay_is_the_tiny_models_sizes(mimo_config):
    from benchmark.drivers import common
    from benchmark.run import _merge

    merged = _merge(mimo_config, mimo_config["rehearse"])
    tiny = common.model_config(merged, rehearse=True)
    for key in SHAPE_KEYS:
        if hasattr(tiny, key) and key not in ("max_position_embeddings",
                                              "n_routed_experts"):
            got = getattr(tiny, key)
            assert (list(got) if isinstance(got, tuple) else got) == merged[key], key
    assert tiny.max_seq_len == merged["context_tokens"] == 128
    assert tiny.held == (0, merged["n_routed_experts"]) == (0, 4)
    assert tiny.n_routed_experts == merged["held"]["of"] == 32
    assert tiny.window_block_tokens == merged["window_block_tokens"] == 8
    # the rehearsal passes the window: prompts of 24-48, a window of 16
    traffic = Manifest(ROOT).load_traffic("swa-decode")["rehearse"]
    assert traffic["prompt_tokens"]["lo"] > merged["sliding_window"] == 16


def _run(config, **extra):
    return {"counters": {"before": {}, "after": {}, "polled": []},
            "config": config, "root": ROOT, "trace": None, "chunk": 8,
            "traffic": Manifest(ROOT).load_traffic("swa-decode"),
            "records": [], "t_open": 0.0, "t_close": 1.0, **extra}


def test_the_prefill_reader_finds_nothing_where_there_is_nothing_to_read(
        mimo_config):
    """An untraced run, a configuration with no such count (every other one:
    the parent commit's too), a trace with no prefill kernel in a whole
    call: the metric is left out and nothing raises."""
    man = Manifest(ROOT)
    reader = man.reader("prefill_attn_roofline")
    assert reader(_run(mimo_config)) is None
    for other in ("gpt2-medium", "trinity-large-preview"):
        assert reader(_run(man.load_config(other), trace={"devices": {}})) is None
    empty = {"devices": {"/device:TPU:0": {"XLA Ops": [["x:fusion:f32[1]", 0, 5]],
                                           "XLA Modules": []}}, "host": []}
    assert reader(_run(mimo_config, trace=empty, trace_host_t0=0.0,
                       peaks={"bf16_flops_per_s": 197e12})) is None


def test_the_prefill_reader_by_hand(mimo_config, monkeypatch):
    """Three whole prefill calls and four requests: the call that ends at
    10.5 s is the prompt whose first token came at 10.6 (not the one at
    10.2, whose prefill ran before the trace), the next the one at 11.3,
    the last has no first token after it and counts nothing; against 2 ms
    of the two kernels inside the calls."""
    from benchmark.reduce import trace as tr

    reader = load_function(ROOT, "benchmark/readers/prefill_attn.py:prefill_attn_roofline")
    with open(Manifest(ROOT).metric_file("prefill_attn_roofline")) as f:
        spec = json.load(f)
    flops = load_function(ROOT, f"{COUNTS}:attention_prefill_flops")
    calls = {"dev0": [(int(0.4e9), int(0.5e9)), (int(1.1e9), int(1.2e9)),
                      (int(2.0e9), int(2.1e9))]}
    seen = {}
    monkeypatch.setattr(tr, "whole_events", lambda trace, pattern: (
        seen.setdefault("step", pattern), calls)[1])
    monkeypatch.setattr(tr, "op_seconds", lambda trace, pattern, inside=None: (
        seen.setdefault("pattern", pattern),
        {"seconds": 2e-3 if inside is calls else 0.0, "count": 14})[1])
    recs = [{"prompt_tokens": 3000, "times": [10.2, 10.9]},
            {"prompt_tokens": 700, "times": [10.6]},
            {"prompt_tokens": 2500, "times": [11.3, 11.4]},
            {"prompt_tokens": 999, "times": []}]
    run = _run(mimo_config, trace={"stub": True}, trace_host_t0=10.0,
               peaks={"bf16_flops_per_s": 197e12}, records=recs)
    need = flops(mimo_config, 700) + flops(mimo_config, 2500)
    assert reader(run, spec) == pytest.approx(100.0 * need / 197e12 / 2e-3)
    assert seen == {"step": "^jit_paged_prefill",
                    "pattern": "^(window|paged)_prefill_attn:"}


def test_the_new_metric_is_a_file_on_a_new_reader():
    man = Manifest(ROOT)
    with open(man.metric_file("prefill_attn_roofline")) as f:
        spec = json.load(f)
    entry = man.per_layer["prefill_attn_roofline"]
    assert spec["reader"] == "benchmark/readers/prefill_attn.py:prefill_attn_roofline"
    assert entry["layer"] == spec["layer"] and entry["workloads"] == [CELL]
    assert (entry["moves"], entry["unit"], entry["source"]) == (
        "serve_out_tok_s", "%", "device_trace")
    # the program's kernels carry the names the pattern looks for
    import re

    from ray_tpu.ops import paged_attention as pa
    src = open(pa.__file__).read()
    assert '"_decode_attn" if T == 1 else "_prefill_attn"' in src
    for name in ("window_prefill_attn:custom-call:bf16[1,64,4096,128]",
                 "paged_prefill_attn:custom-call:bf16[1,64,2048,128]"):
        assert re.search(spec["pattern"], name)
    assert not re.search(spec["pattern"], "window_decode_attn:custom-call:bf16[256,64,1,128]")


def test_the_mimo_cell_joins_the_lists_the_issue_names():
    """Membership only: every per-layer list Trinity's cell is on, the one
    new metric, and the cell's traffic as ISSUE 49's table has it (or the
    fallback ``sizes_taken`` names)."""
    man = Manifest(ROOT)
    names = {m["name"] for m in man.metrics_of(CELL, "per_layer")}
    trinity = {m["name"] for m in man.metrics_of(
        "trinity-large-preview.window-decode", "per_layer")}
    assert trinity <= names and names - trinity == {"prefill_attn_roofline"}
    assert {"window_attn_ms_per_step.batch", "full_attn_ms_per_step.batch",
            "windowed_attn_roofline", "window_capped_share",
            "state_cache_share", "moe_ffn_ms_per_step.batch",
            "expert_layer_ffn_roofline", "expert_layer_tokens_per_expert",
            "moe_load_imbalance", "expert_rows_overflow_share",
            "prefill_dev_share.batch", "step_host_share",
            "device_idle_share.batch", "gap_admit_ms.batch"} <= names
    assert not {"paged_attn_roofline", "shared_expert_ms_per_step.batch",
                "mla_attn_roofline"} & names
    assert {"serve_out_tok_s", "setup_s"} <= {
        m["name"] for m in man.metrics_of(CELL, "end_to_end")}
    assert man.cells[CELL] == {**man.cells[CELL], "chips": 1,
                               "config": CONFIG, "traffic": "swa-decode"}
    assert len(man.cells) == 12 and len(man.configs) == 9
    assert sum(c["chips"] == 4 for c in man.cells.values()) == 1
    for entry in (man.doc["configs"] + man.doc["workloads"]
                  + man.doc["end_to_end"] + man.doc["per_layer"]):
        for key in ("why", "layer", "source"):
            text = entry.get(key, "x")
            assert 1 <= len(text) <= 200 and text.isprintable(), (entry["name"], key)
    traffic = man.load_traffic("swa-decode")
    assert traffic["driver"] == "serve_closed"
    eng = traffic["engine"]
    slots = eng["slots"]
    assert slots in (256, 192, 128) and "sizes_taken" in eng
    assert (traffic["clients"], eng["chunk"]) == (slots * 5 // 4, 8)
    # the waiting clients alone must not meet the shed rule (waiting - free
    # slots >= max_queue): ISSUE 49's 64 did, at 320 clients on 256 slots
    assert eng["max_queue"] > traffic["clients"] - slots
    assert eng["system_config"] == {"serve_kv_pool_blocks": slots * 256 + 1,
                                    "serve_kv_block_tokens": 16,
                                    "serve_llm_prefill_tokens": 4096}
    assert traffic["prompt_tokens"] == {"dist": "uniform", "lo": 512, "hi": 2560}
    assert traffic["output_tokens"]["lo"] == 512
    assert traffic["output_tokens"]["hi"] in (1528, 1016)
    assert (traffic["block_requests"], traffic["sub_block_requests"]) == (
        traffic["clients"], traffic["clients"] // 10)


def _env():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_ENABLE_COMPILATION_CACHE"] = "0"
    return env


def _rehearse(trace: int, launcher=None):
    args = ["--workload", CELL, "--seed", str(2 ** 31 + 49), "--seconds", "8",
            "--trace", str(trace), "--rehearse"]
    cmd = ([sys.executable, "benchmark/run.py"] + args if launcher is None
           else [sys.executable, "-c", launcher] + args)
    proc = subprocess.run(cmd, cwd=ROOT, env=_env(), capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    return json.loads(lines[-1]), json.loads(lines[-2])["detail"]


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_mimo_cell(trace):
    last, detail = _rehearse(trace)
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert last["device"]["platform"] == "cpu"            # never a chip result
    assert detail["check"]["checked"] >= 1 and detail["compiles_in_window"] == 0
    open_, close = (detail["realised"][f"engine_at_{k}"] for k in ("open", "close"))
    steps = close["state_slot_steps_total"] - open_["state_slot_steps_total"]
    capped = (close["window_capped_slot_steps_total"]
              - open_["window_capped_slot_steps_total"])
    assert capped > 0.9 * steps > 0                # the rehearsal passes the window
    assert close["state_bytes"] == open_["state_bytes"] > 0     # the rings stand
    assert close["prefix_lookups_refused_total"] == close["state_resets_total"] > 0
    assert close["kv_hit_tokens"] == 0 and close["moe_picks_total"] > 0
    if trace:
        # the counters' metrics need no device trace: a rehearsal reads them
        for name in ("window_capped_share", "state_cache_share",
                     "kv_blocks_peak_share", "pool_blocked_share",
                     "expert_layer_tokens_per_expert", "moe_load_imbalance",
                     "dispatch_ahead_share", "replica_warmup_s"):
            assert name in last["metrics"], sorted(last["metrics"])
        assert last["metrics"]["window_capped_share"]["value"] > 90
        assert 0 < last["metrics"]["state_cache_share"]["value"] < 100
        assert not {"window_attn_ms_per_step.batch", "windowed_attn_roofline",
                    "prefill_attn_roofline"} & set(last["metrics"])   # no device trace
    else:
        assert {"setup_s", "serve_out_tok_s"} <= set(last["metrics"])


# The same command, started through a wrapper that plants ONE fault in the
# program from outside it (the program has no option for any of them). For
# the chip, at the cell's sizes: ``python3 -c "from
# benchmark.tests.test_mimo_v2_cell import FAULTS as F; exec(F['no_sink'])"
# --workload mimo-v2-flash.swa-decode --seed N --seconds 45 --trace 0``
# (readings: ``check.why`` in benchmark/traffic/swa-decode.json).
_HEAD = """
import sys
sys.path.insert(0, ".")
import jax, jax.numpy as jnp
from ray_tpu.models import mimo_v2
"""
_TAIL = """
from benchmark import run
sys.argv = ["benchmark/run.py"] + sys.argv[1:]
sys.exit(run.main())
"""
FAULTS = {
    # the sink left out of the window layers' softmax
    "no_sink": _HEAD + """
def sinkless(plain):
    return lambda *a, sinks=None, **kw: plain(*a, **kw)
mimo_v2.paged_attention = sinkless(mimo_v2.paged_attention)
mimo_v2.paged_attention_reference = sinkless(mimo_v2.paged_attention_reference)
""" + _TAIL,
    # v left unscaled: the program's config object alone (the reference
    # reads attention_value_scale from the configuration's file)
    "v_unscaled": _HEAD + """
for factory in ("flash_share", "tiny"):
    plain = getattr(mimo_v2, factory)
    setattr(mimo_v2, factory, lambda plain=plain, **kw: plain(
        **kw).replace(attention_value_scale=1.0))
""" + _TAIL,
    # full attention in the window layers where the whole context is at
    # hand, the prefill (a ring holds no row behind the window to attend in
    # decode): every prompt position past the window sees every key before it
    "window_ignored": _HEAD + """
def unwindowed(plain):
    def call(q, *rest, **kw):
        if q.shape[1] > 1:
            kw["window"] = None
        return plain(q, *rest, **kw)
    return call
mimo_v2.paged_attention = unwindowed(mimo_v2.paged_attention)
mimo_v2.paged_attention_reference = unwindowed(mimo_v2.paged_attention_reference)
""" + _TAIL,
    # all of a head's dimensions rotated, not the first third
    "all_dims_rotated": _HEAD + """
mimo_v2.MimoV2Config.rotary_dim = property(lambda c: c.head_dim)
""" + _TAIL,
    # the window layers given the full layers' rotary base
    "window_base_from_full": _HEAD + """
mimo_v2.MimoV2Config.rope_base = lambda c, layer: c.rope_theta
""" + _TAIL,
    # a window layer's query head h reads KV head h // (heads / KV heads of a
    # FULL layer): under its own map it then finds the rows of head j // 2
    "window_kv_map_from_full": _HEAD + """
plain = mimo_v2._project_kv
def mapped(lw, a, layer, c):
    k, v = plain(lw, a, layer, c)
    if c.is_window(layer):
        times = c.swa_num_key_value_heads // c.num_key_value_heads
        heads = jnp.arange(k.shape[2]) // times
        k, v = k[:, :, heads], v[:, :, heads]
    return k, v
mimo_v2._project_kv = mapped
""" + _TAIL,
}


def test_with_the_sink_left_out_the_cell_is_not_correct():
    last, detail = _rehearse(0, launcher=FAULTS["no_sink"])
    assert detail["correct_parts"]["streams_complete"] is True
    assert detail["correct_parts"]["reference_sample"] is False
    assert last["correct"] is False
    # the sound float32 rehearsal reads 0.0 against the limit of 0.002
    assert detail["check"]["worst_gap"] > 5 * detail["check"]["tolerance"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_mimo_launcher_plants_the_fault_it_says(fault, monkeypatch):
    """On the program as it is named today: with the launcher's patch the
    tiny model's logits after a 40-token prefill (2.5 windows) and a decode
    chunk move by far more than float32's rounding."""
    import jax
    import numpy as np

    from ray_tpu.models import mimo_v2
    from ray_tpu.models.generate import PagedGenerator

    for name in ("paged_attention", "paged_attention_reference", "_project_kv",
                 "flash_share", "tiny"):
        monkeypatch.setattr(mimo_v2, name, getattr(mimo_v2, name))   # put back after
    for name in ("rotary_dim", "rope_base"):
        monkeypatch.setattr(mimo_v2.MimoV2Config, name,
                            mimo_v2.MimoV2Config.__dict__[name])

    def last_rows(kernel):
        cfg = mimo_v2.tiny()
        params = mimo_v2.init_params(cfg, jax.random.key(3))
        gen = PagedGenerator(params, cfg, slots=1, num_blocks=8,
                             block_tokens=16, max_len=64,
                             attention_kernel=kernel)
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

    kernels = (("gather", "interpret") if fault in ("window_ignored", "no_sink")
               else ("gather",))
    whole = {k: last_rows(k) for k in kernels}
    exec(FAULTS[fault].split("from benchmark import run")[0], {})
    for k in kernels:
        moved = np.abs(last_rows(k) - whole[k]).max()
        assert moved > 0.01, (fault, k, moved)


def test_the_mimo_files_name_no_other_architecture():
    """The counts, the reference and the new reader state this configuration
    from its dict alone and import nothing of the program; the reader names
    no architecture."""
    for file in (COUNTS, "benchmark/reference/mimo_v2_plain.py",
                 "benchmark/readers/prefill_attn.py"):
        with open(os.path.join(ROOT, file)) as f:
            text = f.read()
        assert "import ray_tpu" not in text and "from ray_tpu" not in text
    with open(os.path.join(ROOT, "benchmark/readers/prefill_attn.py")) as f:
        reader = f.read().lower()
    assert not any(word in reader for word in ("mimo", "swa", "sliding", "sink"))
