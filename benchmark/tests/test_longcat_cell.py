"""The configuration ``longcat-flash-omni`` and its cell: its ``counts``
against numbers worked by hand, its readers on a program that lacks the
counters, and one ``--rehearse`` run of the cell, traced and untraced."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.manifest import Manifest, config_count, load_function

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "longcat-flash-omni.moe-decode"

# By hand, from the published widths (hidden 6144, 64 heads of 128 + 64 | 128,
# q_lora_rank 1536, kv_lora_rank 512, ffn 12288, expert ffn 2048, router 768):
# one MLA sublayer: W_qa 6144*1536 = 9,437,184; W_qb 1536*64*192 = 18,874,368;
#   W_kva 6144*576 = 3,538,944; W_kvb 512*64*256 = 8,388,608;
#   W_o 8192*6144 = 50,331,648                               -> 90,570,752
# one dense gated FFN: 3*6144*12288                           -> 226,492,416
# the router: 6144*768                                        -> 4,718,592
# a layer outside its experts: 2*90,570,752 + 2*226,492,416 + 4,718,592
#                                                             -> 638,844,928
# one expert: 3*6144*2048 = 37,748,736; picks that land on a held expert at
#   uniform routing: 12 * 512/768 * 16/512 = 0.25 a token a layer
#                                                             -> 9,437,184
# four layers: 4 * 648,282,112 = 2,593,128,448; the head's slice 16384*6144 =
#   100,663,296                                               -> 2,693,791,744
MLA, DENSE_FFN, ROUTER, EXPERT = 90_570_752, 226_492_416, 4_718_592, 37_748_736


@pytest.fixture(scope="module")
def config():
    return Manifest(ROOT).load_config("longcat-flash-omni")


def test_counts_by_hand(config):
    assert MLA == (6144 * 1536 + 1536 * 64 * 192 + 6144 * 576
                   + 512 * 64 * 256 + 64 * 128 * 6144)
    layer = 2 * MLA + 2 * DENSE_FFN + ROUTER
    assert layer == 638_844_928
    by_hand = 4 * (layer + 0.25 * EXPERT) + 16384 * 6144
    assert by_hand == 2_693_791_744
    assert config_count(ROOT, config, "params_per_token") == by_hand
    # 8 attention sublayers x (512 latent + 64 rotary key) x 2 B
    assert config_count(ROOT, config, "kv_bytes_per_context_token") == 9_216
    assert config_count(ROOT, config, "expert_weight_bytes") == 75_497_472
    counts = load_function(ROOT, "benchmark/reduce/longcat_counts.py:mla_params")
    assert counts(config) == MLA


def test_the_file_states_the_cut_and_every_published_width(config):
    pub = config["published"]
    cut = {"num_layers": 4, "n_routed_experts": 16, "vocab_size": 16384,
           "max_position_embeddings": 1024}
    assert sorted(config["reduced"]) == sorted(cut)
    for key, value in pub.items():
        assert config[key] == cut.get(key, value), key
    assert config["held"]["of"] == pub["n_routed_experts"] == 512
    assert config["held"]["count"] == config["n_routed_experts"]
    assert config["context_tokens"] == config["max_position_embeddings"]


def test_the_rehearsal_overlay_is_the_tiny_models_sizes(config):
    from benchmark.drivers import common
    from benchmark.run import _merge

    merged = _merge(config, config["rehearse"])
    tiny = common.model_config(merged, rehearse=True)
    for key in ("vocab_size", "hidden_size", "ffn_hidden_size", "num_layers",
                "expert_ffn_hidden_size", "num_attention_heads", "moe_topk",
                "kv_lora_rank", "q_lora_rank", "qk_rope_head_dim",
                "qk_nope_head_dim", "v_head_dim", "zero_expert_num"):
        assert getattr(tiny, key) == merged[key], key
    assert tiny.held == (merged["held"]["first"], merged["held"]["count"])
    assert tiny.n_routed_experts == merged["held"]["of"]
    assert tiny.max_seq_len == merged["context_tokens"]


def test_readers_find_nothing_on_a_program_without_the_counters(config):
    """The parent commit has no ``moe_*`` counters: the new readers leave
    their metrics out and do not raise."""
    man = Manifest(ROOT)
    run = {"counters": {"before": {"steps_total": 1.0},
                        "after": {"steps_total": 9.0}, "polled": []},
           "config": config, "root": ROOT, "trace": None, "chunk": 8,
           "t_open": 0.0, "t_close": 1.0}
    for name in ("moe_zero_pick_share", "moe_held_tokens_per_expert",
                 "moe_load_imbalance", "moe_ffn_roofline",
                 "moe_ffn_ms_per_step.batch", "mla_attn_ms_per_step.batch",
                 "mla_attn_roofline"):
        assert man.reader(name)(run) is None, name


def test_counter_readers_by_hand(config):
    man = Manifest(ROOT)
    before = {k: 0.0 for k in ("moe_picks_total", "moe_picks_zero_total",
                               "moe_picks_held_total", "moe_steps_total",
                               "moe_held_pairs_max_total",
                               "moe_experts_hit_total")}
    # 10 token steps of 100 tokens: 10 * 100 * 12 picks * 4 layers = 48,000
    after = {"moe_picks_total": 48_000.0, "moe_picks_zero_total": 16_000.0,
             "moe_picks_held_total": 1_280.0, "moe_steps_total": 10.0,
             "moe_held_pairs_max_total": 200.0, "moe_experts_hit_total": 560.0}
    run = {"counters": {"before": before, "after": after, "polled": []},
           "config": config, "root": ROOT, "trace": None, "chunk": 8,
           "t_open": 0.0, "t_close": 1.0}
    assert man.reader("moe_zero_pick_share")(run) == pytest.approx(100 / 3)
    # 1280 pairs / (10 steps x 4 layers x 16 experts) = 2 a step and expert
    assert man.reader("moe_held_tokens_per_expert")(run) == 2.0
    # busiest 200 / 40 layer-steps = 5 pairs against the mean 2
    assert man.reader("moe_load_imbalance")(run) == 2.5
    moe = load_function(ROOT, "benchmark/readers/moe.py:experts_hit_per_layer_step")
    assert moe(run) == 14.0


def _env():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_ENABLE_COMPILATION_CACHE"] = "0"
    return env


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell(trace):
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2 ** 31 + 27), "--seconds", "8", "--trace", str(trace),
         "--rehearse"], cwd=ROOT, env=_env(), capture_output=True, text=True,
        timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert last["device"]["platform"] == "cpu"            # never a chip result
    detail = json.loads(proc.stdout.strip().splitlines()[-2])["detail"]
    assert detail["check"]["checked"] >= 1 and detail["compiles_in_window"] == 0
    if trace:
        # the counters' metrics need no device trace: a rehearsal reads them
        for name in ("moe_zero_pick_share", "moe_held_tokens_per_expert",
                     "moe_load_imbalance", "kv_blocks_peak_share",
                     "step_host_share", "replica_warmup_s"):
            assert name in last["metrics"], sorted(last["metrics"])
        assert 5 < last["metrics"]["moe_zero_pick_share"]["value"] < 70
    else:
        assert {"setup_s", "serve_out_tok_s"} <= set(last["metrics"])
