"""The configuration ``nemotron-3-nano-30b-a3b`` and its cell: its ``counts``
against numbers worked by hand, the cut against ``published`` and the
catalog's row (by agreement on the keys both have), the program's tree
against the counts, the readers on a program that lacks the counters, the
lists the cell joins (membership, not position), and ``--rehearse`` runs of
the cell: traced, untraced, and with one mixer layer skipped, which has to
come out not correct."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.manifest import Manifest, config_count, load_function

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG = "nemotron-3-nano-30b-a3b"
CELL = "nemotron-3-nano-30b-a3b.reason-decode"
COUNTS = "benchmark/reduce/nemotron_h_counts.py"
REFERENCE = "benchmark/reference/nemotron_h_plain.py"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CUT_PATTERN = "MEMEM*EMEMEM*"

# By hand, from the published widths (hidden 2688; Mamba-2: 64 heads of 64 =
# 4096 channels, state 128, 8 groups, convolution 4; 32 query heads over 2 KV
# heads of 128; experts of 1856, a shared expert of 3712, a router of 128;
# half the vocabulary, 65536):
# the convolution's channels: 4096 + 2*8*128 = 6,144
# the in-projection's width: 4096 (z) + 6144 (xBC) + 64 (dt) = 10,304
# a mixer: W_in 2688*10304 = 27,697,152; convolution and bias 5*6144 =
#   30,720; W_out 4096*2688 = 11,010,048; gated norm 4,096; A_log, D,
#   dt_bias 64 each                                       -> 38,742,208
# attention: W_q, W_o 2688*4096 each; W_k, W_v 2688*256   -> 23,396,352
# an expert: 2*2688*1856 = 9,977,856 (TWO matrices); the shared expert
#   2*2688*3712 = 19,955,712; the router 2688*128 + its bias 128 = 344,192
# an expert layer with 64 held: 64*9,977,856 + 19,955,712 + 344,192
#                                                         -> 658,882,688
# 6 mixers + 2 attention + 5 expert layers + 13 layer norms of 2688
#   = 232,453,248 + 46,792,704 + 3,294,413,440 + 34,944 = 3,573,694,336
# embedding and head 2*65536*2688 = 352,321,536; final norm 2,688
#                                                         -> 3,926,018,560
MIXER, ATTENTION, EXPERT, EXPERT_LAYER = (38_742_208, 23_396_352, 9_977_856,
                                          658_882_688)
TOTAL = 3_926_018_560
# Keys of the source that say a SHAPE or a constant the layer applies: each
# has to be in ``published`` whatever the catalog later prunes.
SHAPE_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers",
    "hybrid_override_pattern", "num_attention_heads", "num_key_value_heads",
    "head_dim", "mamba_num_heads", "mamba_head_dim", "n_groups",
    "ssm_state_size", "conv_kernel", "chunk_size", "n_routed_experts",
    "num_experts_per_tok", "moe_intermediate_size",
    "moe_shared_expert_intermediate_size", "n_shared_experts",
    "routed_scaling_factor", "norm_topk_prob", "mlp_hidden_act",
    "layer_norm_epsilon", "max_position_embeddings", "time_step_min",
    "time_step_max", "time_step_floor", "tie_word_embeddings", "use_conv_bias")
CUT = {"num_hidden_layers": 13, "hybrid_override_pattern": CUT_PATTERN,
       "n_routed_experts": 64, "vocab_size": 65536,
       "max_position_embeddings": 2176}


@pytest.fixture(scope="module")
def nemotron_config():
    return Manifest(ROOT).load_config(CONFIG)


def test_nemotron_counts_by_hand(nemotron_config):
    c = nemotron_config
    assert MIXER == 2688 * 10304 + 5 * 6144 + 4096 * 2688 + 4096 + 3 * 64
    assert ATTENTION == 2 * 2688 * 4096 + 2 * 2688 * 256
    assert EXPERT == 2 * 2688 * 1856
    assert EXPERT_LAYER == 64 * EXPERT + 2 * 2688 * 3712 + 2688 * 128 + 128
    count = lambda name: load_function(ROOT, f"{COUNTS}:{name}")  # noqa: E731
    assert (count("mixer_layers")(c), count("expert_layers")(c),
            count("attention_layers")(c)) == (6, 5, 2)
    assert count("d_inner")(c) == 4096 and count("conv_channels")(c) == 6144
    assert count("mixer_params")(c) == MIXER
    assert count("attention_params")(c) == ATTENTION
    assert count("expert_params")(c) == EXPERT
    assert count("expert_layer_params")(c) == EXPERT_LAYER
    assert TOTAL == (6 * MIXER + 2 * ATTENTION + 5 * EXPERT_LAYER + 13 * 2688
                     + 2 * 65536 * 2688 + 2688)        # the issue's 3,926.0M
    assert count("param_count")(c) == TOTAL
    # HERE: 3 of a token's 6 picks land on this chip's half of the experts
    per_token = (6 * (2688 * 10304 + 4096 * 2688) + 2 * ATTENTION
                 + 5 * (2688 * 128 + 2 * 2688 * 3712 + 3 * EXPERT)
                 + 65536 * 2688)
    assert config_count(ROOT, c, "params_per_token") == per_token == 706_363_392
    # 2 attention layers x (K and V) x 2 KV heads x 128 x 2 B
    assert config_count(ROOT, c, "kv_bytes_per_context_token") == 2_048
    # 6 mixer layers x 64 x 64 x 128 x 4 B, + 6 x 3 x 6144 x 2 B of tail
    assert config_count(ROOT, c, "recurrent_bytes_per_slot") == 12_582_912
    assert config_count(ROOT, c, "state_bytes_per_slot") == 12_804_096
    assert config_count(ROOT, c, "expert_weight_bytes") == 19_955_712
    assert config_count(ROOT, c, "expert_layers") == 5
    # a slot's state weighs as much as 6,252 tokens of its own K/V
    assert 12_804_096 // 2_048 == 6_252
    # the published model, by the same functions: the card's 31.6B
    pub = dict(c["published"], held={"of": 128})
    assert count("param_count")(pub) == 31_577_940_288
    assert (count("mixer_layers")(pub), count("expert_layers")(pub),
            count("attention_layers")(pub)) == (23, 23, 6)


def test_the_nemotron_program_holds_what_the_counts_say(nemotron_config):
    """The program's own tree at the cell's sizes (shapes only), and what its
    engine would report as ``state_bytes`` for 128 slots and hold as a pool."""
    import jax

    from benchmark.drivers import common

    traffic = Manifest(ROOT).load_traffic("reason-decode")["engine"]
    slots = traffic["slots"]
    blocks = traffic["system_config"]["serve_kv_pool_blocks"]
    cfg = common.model_config(nemotron_config, rehearse=False)
    init = common.resolve(nemotron_config["init"])
    tree = jax.eval_shape(lambda k: init(cfg, k), jax.random.key(0))
    # the routed experts are STORED 1,920 wide (15 lane tiles), the 64
    # columns of w_up and rows of w_down past the published 1,856 zero
    padding = 5 * 64 * 2 * 2688 * (1920 - 1856)
    assert sum(x.size for x in jax.tree.leaves(tree)) == TOTAL + padding
    assert cfg.expert_width_stored == 1920 and padding == 110_100_480
    assert len(tree["layers"]) == 13
    kinds = ["".join(sorted(k for k in ("w_in", "w_kv", "router") if k in lw))
             for lw in tree["layers"]]
    assert kinds == [{"M": "w_in", "E": "router", "*": "w_kv"}[k]
                     for k in CUT_PATTERN]
    expert = tree["layers"][1]["experts"]
    assert expert["w_up"].shape == (64, 2688, 1920)       # no gate: F, not 2F
    assert expert["w_down"].shape == (64, 1920, 2688)
    assert tree["layers"][5]["w_kv"].shape == (4, 128, 2688)
    assert str(tree["layers"][1]["router"].dtype) == "float32"
    state = jax.eval_shape(lambda: cfg.paged_family().init_slot_state(cfg, slots))
    assert sum(x.size * x.dtype.itemsize for x in state) == slots * 12_804_096
    assert str(state[0].dtype) == "float32"
    assert state[0].shape == (6, slots, 128, 4096)        # the mixers alone
    pool = jax.eval_shape(lambda: cfg.paged_family().init_pool(cfg, blocks, 16))
    assert sum(x.size * x.dtype.itemsize for x in pool) == blocks * 16 * 2_048
    assert pool[0].shape == (2, blocks, 16, 2 * 128)      # the attention layers
    # every slot at its longest reservation, and the trash block
    longest = -(-(1024 + 1024 + traffic["chunk"]) // 16)
    assert blocks == slots * longest + 1 == 16513
    # every width and constant the program runs is the file's; the file's
    # n_routed_experts is the experts HELD (what the readers divide by), the
    # program's the router's outputs
    for key in set(SHAPE_KEYS) - {"n_routed_experts"}:
        if hasattr(cfg, key):
            assert getattr(cfg, key) == nemotron_config[key], key
    assert cfg.held == (0, nemotron_config["n_routed_experts"]) == (0, 64)
    assert cfg.n_routed_experts == nemotron_config["held"]["of"] == 128
    assert cfg.max_seq_len == nemotron_config["context_tokens"] == 2176


def test_the_nemotron_file_states_the_cut_the_floors_and_every_published_width(
        nemotron_config):
    c, pub = nemotron_config, nemotron_config["published"]
    assert sorted(c["reduced"]) == sorted(CUT) == sorted(c["reduced_why"])
    for key, value in pub.items():
        assert c[key] == CUT.get(key, value), key
    # the floors: a whole repeating block and more than four layers; at
    # least 8 experts; at least an eighth of the vocabulary
    assert pub["hybrid_override_pattern"].startswith(CUT_PATTERN)
    assert CUT_PATTERN == "MEMEM*" + "EMEMEM*" and len(CUT_PATTERN) == 13 >= 4
    assert pub["hybrid_override_pattern"] == (
        "MEMEM*" + "EMEMEM*" * 4 + "EMEMEMEM*" + "EMEMEMEME")
    assert c["n_routed_experts"] >= 8 and 8 * c["vocab_size"] >= pub["vocab_size"]
    assert c["held"] == {**c["held"], "first": 0, "count": 64, "of": 128}
    assert c["context_tokens"] == c["max_position_embeddings"]
    # no width, head count, group count, state size, constant or the picks a
    # token is among the cuts
    assert not set(c["reduced"]) & (set(SHAPE_KEYS) - set(CUT))
    for key in SHAPE_KEYS:
        assert key in pub, key
    for key in ("no_rotary", "d_inner", "dt_unclamped", "gated_norm", "router",
                "relu2", "float32_state", "init", "stored_dtype",
                "context_tokens"):
        assert key in c["assumed"], key
    stands = c["deployment"]["stands_for"]
    assert "2 chips a layer x 4 pipeline stages" in stands and "2x their share" in stands
    assert {"reckoned", "compiled"} <= set(c["deployment"]["memory"])
    entry = Manifest(ROOT).configs[CONFIG]
    assert entry["source"] == c["source"] and entry["reduced"] == c["reduced"]
    assert 1 <= len(entry["why"]) <= 200


def test_nemotron_published_agrees_with_the_catalog_where_both_speak(
        nemotron_config):
    """Every key present in BOTH ``published`` and the catalog's row agrees,
    and the row still is this model. Not equality of the two dicts: the
    catalog prunes keys that say nothing of shape, and ``published`` is the
    source's file, not the catalog's copy of it (PERF.md section 7)."""
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = next(r for r in rows if r["source_url"] == nemotron_config["source"])
    pub = nemotron_config["published"]
    both = set(pub) & set(row["config"])
    assert len(both) >= 20
    for key in both:
        assert pub[key] == row["config"][key], key
    assert row["config"].get("model_type", "nemotron_h") == "nemotron_h"


def test_the_nemotron_rehearsal_overlay_is_the_tiny_models_sizes(nemotron_config):
    from benchmark.drivers import common
    from benchmark.run import _merge

    merged = _merge(nemotron_config, nemotron_config["rehearse"])
    tiny = common.model_config(merged, rehearse=True)
    for key in SHAPE_KEYS:
        if hasattr(tiny, key) and key not in ("max_position_embeddings",
                                              "n_routed_experts"):
            assert getattr(tiny, key) == merged[key], key
    # the file's n_routed_experts is the experts HELD (what the readers
    # divide by); the program's is the router's outputs
    assert tiny.held == (0, merged["n_routed_experts"]) == (0, 4)
    assert tiny.n_routed_experts == merged["held"]["of"] == 8
    assert tiny.max_seq_len == merged["context_tokens"] == 128


def _run(config, before, after, polled=()):
    return {"counters": {"before": before, "after": after,
                         "polled": list(polled)},
            "config": config, "root": ROOT, "trace": None, "chunk": 8,
            "traffic": Manifest(ROOT).load_traffic("reason-decode"),
            "t_open": 0.0, "t_close": 1.0}


NEW_METRICS = ("expert_capacity_ffn_ms_per_step.batch",
               "expert_capacity_ffn_roofline")
STATE_AND_EXPERT_METRICS = NEW_METRICS + (
    "ssd_state_ms_per_step.batch", "ssd_state_roofline", "state_cache_share",
    "expert_layer_tokens_per_expert", "moe_load_imbalance",
    "paged_attn_roofline")


def test_nemotron_readers_find_nothing_where_there_is_nothing_to_read(
        nemotron_config):
    """A program without the state or the expert counters (the parent commit
    has no such configuration; GPT-2 has neither counter), and an untraced
    run: every metric the cell joins is left out and nothing raises."""
    man = Manifest(ROOT)
    poll = {"t": 0.5, "slots_busy": 3.0, "slots_total": 4.0,
            "kv_blocks_active": 10.0}
    for config in (nemotron_config, man.load_config("gpt2-medium")):
        run = _run(config, {"steps_total": 1.0}, {"steps_total": 9.0}, [poll])
        for name in STATE_AND_EXPERT_METRICS:
            assert man.reader(name)(run) is None, name


def test_nemotron_counter_readers_by_hand(nemotron_config):
    man = Manifest(ROOT)
    # 10 decode calls of 8 token steps, 126 of 128 slots active in each: a
    # token step offers 5 expert layers 126 x 6 picks, half of them held
    steps = 10 * 8
    before = {k: 0.0 for k in (
        "steps_total", "state_slot_steps_total", "moe_steps_total",
        "moe_picks_held_total", "moe_held_pairs_max_total",
        "moe_experts_hit_total")}
    after = {"steps_total": 10.0, "state_slot_steps_total": steps * 126.0,
             "moe_steps_total": float(steps),
             "moe_picks_held_total": steps * 5 * 126 * 3.0,
             "moe_held_pairs_max_total": steps * 5 * 12.0,
             "moe_experts_hit_total": steps * 5 * 63.8}
    poll = {"t": 0.5, "slots_busy": 126.0, "slots_total": 128.0,
            "kv_blocks_active": 9600.0, "state_bytes": 128 * 12_804_096.0}
    run = _run(nemotron_config, before, after, [poll, dict(poll, t=2.0)])
    active = load_function(ROOT, "benchmark/readers/state.py:active_slots_per_step")
    assert active(run) == 126.0
    state, kv = 126 * 12_804_096, 9600 * 16 * 2_048
    assert man.reader("state_cache_share")(run) == pytest.approx(
        100.0 * state / (state + kv))
    assert 80 < man.reader("state_cache_share")(run) < 90
    # 126 x 3 held picks over 64 held experts: 5.9 tokens an expert a step
    assert man.reader("expert_layer_tokens_per_expert")(run) == pytest.approx(
        126 * 3 / 64)
    assert man.reader("moe_load_imbalance")(run) == pytest.approx(
        12 * 64 / (126 * 3))
    for name in ("ssd_state_roofline", "expert_capacity_ffn_roofline",
                 "paged_attn_roofline"):
        assert man.reader(name)(run) is None, name          # no trace


def test_the_two_new_metrics_are_files_on_readers_that_were_there():
    """The decode program takes the routed experts in the capacity form
    (``ops/moe.py:held_capacity``), whose products are one XLA fusion an
    expert layer and no ``ragged-dot``: the two metrics that read them are
    new FILES on the readers of ``moe_ffn_ms_per_step.batch`` and
    ``expert_layer_ffn_roofline``, with a pattern of their own."""
    man = Manifest(ROOT)
    for name, reader in (
            (NEW_METRICS[0], "benchmark/readers/device.py:op_ms_per_step"),
            (NEW_METRICS[1], "benchmark/readers/expert_layers.py:ffn_roofline")):
        with open(man.metric_file(name)) as f:
            spec = json.load(f)
        assert spec["reader"] == reader
        # [held experts, capacity rows, hidden_size]: the cell's own sizes
        assert spec["pattern"] == "^fusion:fusion:f32\\[64,64,2688\\]$"
        assert spec["step_pattern"] == "^jit_paged_decode"
        assert man.per_layer[name]["layer"] == spec["layer"] == man.per_layer[
            "expert_layer_ffn_roofline"]["layer"]
        assert man.per_layer[name]["workloads"] == [CELL]
    from ray_tpu.ops import moe
    assert moe.held_capacity(128, 6, (0, 64), 128) == 64


def test_the_nemotron_cell_joins_the_lists_the_issue_names():
    """Membership only: the next PR appends cells, configurations and
    metrics, and joins this cell to further lists, without this test's
    leave."""
    man = Manifest(ROOT)
    assert man.check() == []
    names = {m["name"] for m in man.metrics_of(CELL, "per_layer")}
    falcon = {m["name"] for m in man.metrics_of("falcon-h1-34b.ssm-decode",
                                                "per_layer")}
    assert falcon <= names                  # every list ssm-decode is on
    assert set(STATE_AND_EXPERT_METRICS) <= names
    assert {"decode_step_ms.batch", "prefill_dev_share.batch",
            "device_idle_share.batch", "hbm_peak_share.batch",
            "kv_blocks_peak_share", "pool_blocked_share",
            "dispatch_ahead_share", "replica_warmup_s",
            "warmup_lower_s"} <= names
    # NOT the grouped product's two metrics (the decode program takes the
    # capacity form: NEW_METRICS read that), NOT the row bound's metric (with
    # half the experts held the bound is None at every shape), NOT the shared
    # expert's (its pattern is another cell's shape), NOT another family's
    assert not {"moe_ffn_ms_per_step.batch", "expert_layer_ffn_roofline",
                "expert_rows_overflow_share", "shared_expert_ms_per_step.batch",
                "gdn_state_roofline", "mla_attn_roofline",
                "windowed_attn_roofline", "moe_ffn_roofline",
                "moe_held_tokens_per_expert"} & names
    assert {"serve_out_tok_s", "setup_s"} <= {
        m["name"] for m in man.metrics_of(CELL, "end_to_end")}
    assert man.cells[CELL] == {**man.cells[CELL], "chips": 1,
                               "config": CONFIG, "traffic": "reason-decode"}
    assert CONFIG in man.configs
    for entry in (man.doc["configs"] + man.doc["workloads"]
                  + man.doc["end_to_end"] + man.doc["per_layer"]):
        for key in ("why", "layer", "source"):
            text = entry.get(key, "x")
            assert 1 <= len(text) <= 200 and text.isprintable(), (entry["name"], key)
    traffic = man.load_traffic("reason-decode")
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
    args = ["--workload", CELL, "--seed", str(2 ** 31 + 46), "--seconds", "12",
            "--trace", str(trace), "--rehearse"]
    cmd = ([sys.executable, "benchmark/run.py"] + args if launcher is None
           else [sys.executable, "-c", launcher] + args)
    proc = subprocess.run(cmd, cwd=ROOT, env=_env(), capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    return json.loads(lines[-1]), json.loads(lines[-2])["detail"]


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_nemotron_cell(trace):
    last, detail = _rehearse(trace)
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert last["device"]["platform"] == "cpu"            # never a chip result
    assert detail["check"]["checked"] >= 1 and detail["compiles_in_window"] == 0
    open_, close = (detail["realised"][f"engine_at_{k}"] for k in ("open", "close"))
    # the state AND the experts, in one program's counters
    assert close["state_slot_steps_total"] > open_["state_slot_steps_total"]
    assert close["moe_picks_held_total"] > open_["moe_picks_held_total"]
    assert close["moe_picks_zero_total"] == 0
    assert close["state_bytes"] == open_["state_bytes"] > 0
    assert close["prefix_lookups_refused_total"] == close["state_resets_total"] > 0
    assert close["kv_hit_tokens"] == 0
    if trace:
        # the counters' metrics need no device trace: a rehearsal reads them
        for name in ("state_cache_share", "expert_layer_tokens_per_expert",
                     "moe_load_imbalance", "kv_blocks_peak_share",
                     "pool_blocked_share", "dispatch_ahead_share",
                     "replica_warmup_s"):
            assert name in last["metrics"], sorted(last["metrics"])
        assert 0 < last["metrics"]["state_cache_share"]["value"] < 100
        # 4 slots x top-3, half the 8 experts held: 1.5 tokens an expert at most
        assert 0.5 < last["metrics"]["expert_layer_tokens_per_expert"]["value"] <= 1.5
        assert not {"ssd_state_roofline", "expert_capacity_ffn_roofline",
                    "paged_attn_roofline"} & set(last["metrics"])  # no device trace
    else:
        assert {"setup_s", "serve_out_tok_s"} <= set(last["metrics"])


# The same command, started through a wrapper that plants ONE fault in the
# program from outside it (the program has no option for any of them). For
# the chip, at the cell's sizes: ``python3 -c "from
# benchmark.tests.test_nemotron_h_cell import FAULTS as F;
# exec(F['zeroed_state'])" --workload nemotron-3-nano-30b-a3b.reason-decode
# --seed N --seconds 45 --trace 0`` (readings: ``check.why`` in
# benchmark/traffic/reason-decode.json).
_HEAD = """
import sys
sys.path.insert(0, ".")
import jax, jax.numpy as jnp
from ray_tpu.models import nemotron_h
from ray_tpu.ops import moe
from ray_tpu.serve import llm
"""
_TAIL = """
from benchmark import run
sys.argv = ["benchmark/run.py"] + sys.argv[1:]
sys.exit(run.main())
"""
FAULTS = {
    # every slot's state-space state zeroed before every fourth decode
    # dispatch (chunks of 4 in the rehearsal: every 16th token step; every
    # 32nd at the cell's chunk of 8), where it lies (donated): a second 1.6 GB
    # array need not exist beside the engine
    "zeroed_state": _HEAD + """
plain, calls = llm.LLMEngine._run_decode, [0]
zeroed = jax.jit(lambda S: S * 0.0, donate_argnums=0)
def damaged(self, *args):
    calls[0] += 1
    if self._steady and calls[0] % 4 == 0:
        S, tail = self._slot_state
        self._slot_state = (zeroed(S), tail)
    return plain(self, *args)
llm.LLMEngine._run_decode = damaged
""" + _TAIL,
    # the activation left un-squared: relu where relu^2 stands, in the routed
    # experts and the shared expert alike
    "activation_not_squared": _HEAD + """
moe._expert_hidden = lambda p, F, form: jax.nn.relu(p)
def unsquared(fp, x, dtype):
    h = jnp.einsum("...d,df->...f", x, fp["w_up"],
                   preferred_element_type=jnp.float32)
    return nemotron_h._mm("...f,fd->...d", jax.nn.relu(h).astype(dtype),
                          fp["w_down"], dtype)
nemotron_h.relu2_ffn = unsquared
""" + _TAIL,
    # ONE expert layer's shared expert (the second's) left out: its
    # down-projection gives nothing
    "no_shared_expert": _HEAD + """
plain, calls = nemotron_h.expert_layer, [0]
def unshared(lp, x, valid, c):
    # traced once a program for the whole kind: told apart by a marker
    gone = lp["drop_shared"]
    shared = dict(lp["shared"], w_down=lp["shared"]["w_down"] * (1 - gone))
    return plain(dict(lp, shared=shared), x, valid, c)
nemotron_h.expert_layer = unshared
init = nemotron_h.init_params
def marked(config, key):
    p, n = init(config, key), [0]
    for lp in p["layers"]:
        if "shared" in lp:
            lp["drop_shared"] = jnp.asarray(n[0] == 1, lp["shared"]["w_down"].dtype)
            n[0] += 1
    return p
nemotron_h.init_params = marked
""" + _TAIL,
    # the query-to-KV-head map shifted by one group: query head h reads KV
    # head (h // R + 1) mod KV
    "kv_map_shifted": _HEAD + """
plain = nemotron_h._paged_attend
def shifted(q, k_pool, *rest, **kw):
    r = q.shape[2] // (k_pool.shape[3] // q.shape[3])
    return jnp.roll(plain(jnp.roll(q, r, axis=2), k_pool, *rest, **kw), -r, axis=2)
nemotron_h._paged_attend = shifted
""" + _TAIL,
    # ONE mixer layer (the third) skipped: it adds nothing to the stream, in
    # prefill and decode alike; its state is still written
    "mixer_layer_skipped": _HEAD + """
def skipping(plain):
    def call(lw, u, state, ml, *rest):
        f, state = plain(lw, u, state, ml, *rest)
        return f * (ml != 2).astype(f.dtype), state
    return call
nemotron_h._mixer_prefill = skipping(nemotron_h._mixer_prefill)
nemotron_h._mixer_decode = skipping(nemotron_h._mixer_decode)
""" + _TAIL,
}


# Not a fault: a reading. The routed experts' ``w_down`` zeroed in the WEIGHTS
# (the program and the reference read the same tree, so both lose the routed
# part and agree on it): what is left of the check's worst gap is what does
# NOT come from router picks that change hands between the program's
# bfloat16 stream and the reference's float32 one (``check.why``).
DIAGNOSTICS = {
    "routed_silenced": _HEAD + """
init = nemotron_h.init_params
def silenced(config, key):
    p = init(config, key)
    for lp in p["layers"]:
        if "experts" in lp:
            lp["experts"]["w_down"] = jnp.zeros_like(lp["experts"]["w_down"])
    return p
nemotron_h.init_params = silenced
""" + _TAIL,
}


def test_with_a_mixer_layer_skipped_the_cell_is_not_correct():
    last, detail = _rehearse(0, launcher=FAULTS["mixer_layer_skipped"])
    assert detail["correct_parts"]["streams_complete"] is True
    assert detail["correct_parts"]["reference_sample"] is False
    assert last["correct"] is False
    # the sound float32 rehearsal reads 0.0 against the limit of 0.002
    assert detail["check"]["worst_gap"] > 5 * detail["check"]["tolerance"]


@pytest.mark.parametrize("fault", sorted(set(FAULTS) - {"zeroed_state"}))
def test_each_nemotron_launcher_plants_the_fault_it_says(fault, monkeypatch):
    """On the program as it is named today: with the launcher's patch the
    tiny model's logits after a prefill and a decode chunk move by far more
    than float32's rounding. (``zeroed_state`` patches the engine, not the
    model: the rehearsal of Falcon-H1's cell holds that launcher's form.)"""
    import jax
    import numpy as np

    from ray_tpu.models import nemotron_h
    from ray_tpu.models.generate import PagedGenerator
    from ray_tpu.ops import moe

    for mod, name in ((nemotron_h, "_paged_attend"), (nemotron_h, "relu2_ffn"),
                      (nemotron_h, "expert_layer"), (nemotron_h, "init_params"),
                      (nemotron_h, "_mixer_prefill"),
                      (nemotron_h, "_mixer_decode"), (moe, "_expert_hidden")):
        monkeypatch.setattr(mod, name, getattr(mod, name))   # put back after
    cfg = nemotron_h.tiny()

    def last_rows():
        nemotron_h._layer_fn.cache_clear()      # traced anew, patched or not
        params = nemotron_h.init_params(cfg, jax.random.key(3))
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
    nemotron_h._layer_fn.cache_clear()
    assert moved > 0.01, (fault, moved)


def test_the_nemotron_files_name_no_other_architecture():
    """The counts and the reference state this configuration from its dict
    alone and import nothing of the program: not the state kernel's module,
    not the grouped product's, no kernel."""
    for file in (COUNTS, REFERENCE):
        with open(os.path.join(ROOT, file)) as f:
            text = f.read()
        assert "import ray_tpu" not in text and "from ray_tpu" not in text
        for module in ("ops.ssd", "ops.moe", "ragged_dot", "pallas"):
            assert module not in text, (file, module)
