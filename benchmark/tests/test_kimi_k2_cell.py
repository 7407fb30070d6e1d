"""The configuration ``kimi-k2.5`` and its cell: its ``counts`` against numbers
worked by hand, the cut against ``published`` and the floors, the program's
own tree against the counts, its readers on a program or configuration that
lacks what they read, and ``--rehearse`` runs of the cell: traced, untraced,
and with the shared expert left out, which has to come out not correct."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.manifest import Manifest, config_count, load_function

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "kimi-k2.5.agent-decode"
COUNTS = "benchmark/reduce/kimi_k2_counts.py"

# By hand, from the published widths (hidden 7168, 64 heads of 128 + 64 | 128,
# q_lora_rank 1536, kv_lora_rank 512, dense 18432, expert 2048, router 384):
# the MLA sublayer: W_qa 7168*1536 = 11,010,048; W_qb 1536*64*192 =
#   18,874,368; W_kva 7168*576 = 4,128,768; W_kvb 512*64*256 = 8,388,608;
#   W_o 8192*7168 = 58,720,256                                -> 101,122,048
# one expert (and the shared expert): 3*7168*2048              -> 44,040,192
# the router: 7168*384                                         -> 2,752,512
# an expert layer outside its routed experts                   -> 147,914,752
#   with its 12 held experts (528,482,304)                     -> 676,397,056
# the dense layer: 101,122,048 + 3*7168*18432 (396,361,728)    -> 497,483,776
# embedding and head slices: 2*20480*7168                      -> 293,601,280
# dense + 6 expert layers + vocabulary                         -> 4,849,467,392
# a token's picks on a held expert at uniform routing: 8 * 12/384 = 0.25
MLA, EXPERT, ROUTER, DENSE = 101_122_048, 44_040_192, 2_752_512, 497_483_776


@pytest.fixture(scope="module")
def kimi_config():
    return Manifest(ROOT).load_config("kimi-k2.5")


def test_kimi_counts_by_hand(kimi_config):
    c = kimi_config
    assert MLA == (7168 * 1536 + 1536 * 64 * 192 + 7168 * 576
                   + 512 * 64 * 256 + 64 * 128 * 7168)
    count = lambda name: load_function(ROOT, f"{COUNTS}:{name}")  # noqa: E731
    assert count("mla_params")(c) == MLA
    assert count("expert_params")(c) == count("shared_expert_params")(c) == EXPERT
    assert count("dense_layer_params")(c) == DENSE == MLA + 3 * 7168 * 18432
    outside = MLA + ROUTER + EXPERT
    assert count("expert_layer_params_outside_routed")(c) == outside == 147_914_752
    total = DENSE + 6 * (outside + 12 * EXPERT) + 2 * 20480 * 7168
    assert total == 4_849_467_392                 # the issue's 4,849M
    assert count("param_count")(c) == total
    assert config_count(ROOT, c, "params_per_token") == (
        DENSE + 6 * (outside + 0.25 * EXPERT) + 20480 * 7168)
    # 7 layers x (512 latent + 64 rotary key) x 2 B: the dense layer attends too
    assert config_count(ROOT, c, "kv_bytes_per_context_token") == 8_064
    assert config_count(ROOT, c, "expert_weight_bytes") == 88_080_384
    assert config_count(ROOT, c, "expert_layers") == 6


def test_the_kimi_program_holds_what_the_counts_say(kimi_config):
    """The program's own tree at the cell's sizes (shapes only): the counts'
    matrices plus the norm gains and the selection biases."""
    import jax

    from benchmark.drivers import common

    cfg = common.model_config(kimi_config, rehearse=False)
    init = common.resolve(kimi_config["init"])
    tree = jax.eval_shape(lambda k: init(cfg, k), jax.random.key(0))
    gains = 7 * (1536 + 512 + 2 * 7168) + 7168 + 6 * 384
    assert sum(x.size for x in jax.tree.leaves(tree)) == 4_849_467_392 + gains
    pool = jax.eval_shape(lambda: cfg.paged_family().init_pool(cfg, 16993, 16))
    assert [tuple(x.shape) for x in pool] == [(7, 16993, 16, 640)]
    assert sum(x.size * x.dtype.itemsize for x in pool) == 16993 * 16 * 8_960
    assert cfg.paged_family().describe(cfg) == {
        "expert_layers": 6, "dense_layers": 1,
        "shared_expert_params": EXPERT}


def test_the_kimi_file_states_the_cut_the_floors_and_every_published_width(kimi_config):
    c, pub = kimi_config, kimi_config["published"]
    cut = {"num_hidden_layers": 7, "n_routed_experts": 12, "vocab_size": 20480,
           "max_position_embeddings": 3072}
    assert sorted(c["reduced"]) == sorted(cut) == sorted(c["reduced_why"])
    for key, value in pub.items():
        assert c[key] == cut.get(key, value), key
    assert c["rope_scaling"] == pub["rope_scaling"]          # YaRN whole
    assert c["held"]["of"] == pub["n_routed_experts"] == 384
    assert c["held"]["count"] == c["n_routed_experts"] == 384 // 32
    assert c["context_tokens"] == c["max_position_embeddings"]
    # the floors: four expert layers after the dense one, eight experts, an
    # eighth of the vocabulary
    assert c["num_hidden_layers"] - c["first_k_dense_replace"] >= 4
    assert c["n_routed_experts"] >= 8
    assert c["vocab_size"] * 8 >= pub["vocab_size"]
    for key in ("rotary_pairing", "e_score_correction_bias", "stored_dtype",
                "init", "vision_tower", "context_tokens"):
        assert key in c["assumed"], key
    assert "32 chips" in c["deployment"]["stands_for"]
    assert "13.94 GB" in c["deployment"]["memory"]["compiled"]
    entry = Manifest(ROOT).configs["kimi-k2.5"]
    assert entry["source"] == c["source"] and entry["reduced"] == c["reduced"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):          # the builder's machine has it
        with open(catalog) as f:
            rows = [json.loads(line) for line in f]
        [row] = [r for r in rows if r["name"] == "Kimi-K2.5"]
        assert row["config"] == pub and row["source_url"] == c["source"]


def test_the_kimi_rehearsal_overlay_is_the_tiny_models_sizes(kimi_config):
    from benchmark.drivers import common
    from benchmark.run import _merge

    merged = _merge(kimi_config, kimi_config["rehearse"])
    tiny = common.model_config(merged, rehearse=True)
    for key in ("vocab_size", "hidden_size", "intermediate_size",
                "moe_intermediate_size", "num_hidden_layers",
                "first_k_dense_replace", "num_attention_heads",
                "num_experts_per_tok", "kv_lora_rank", "q_lora_rank",
                "qk_rope_head_dim", "qk_nope_head_dim", "v_head_dim",
                "n_shared_experts", "routed_scaling_factor", "rope_theta"):
        assert getattr(tiny, key) == merged[key], key
    assert tiny.held == (merged["held"]["first"], merged["held"]["count"])
    assert tiny.n_routed_experts == merged["held"]["of"]
    assert tiny.max_seq_len == merged["context_tokens"]
    scaling = {k: v for k, v in merged["rope_scaling"].items() if k != "type"}
    assert dict(tiny.rope_scaling) == scaling
    full = common.model_config(kimi_config, rehearse=False)
    for key in ("vocab_size", "hidden_size", "intermediate_size",
                "moe_intermediate_size", "num_hidden_layers",
                "num_experts_per_tok", "routed_scaling_factor", "rope_theta"):
        assert getattr(full, key) == kimi_config[key], key
    assert dict(full.rope_scaling) == {
        k: v for k, v in kimi_config["rope_scaling"].items() if k != "type"}
    assert full.held == (0, 12) and full.n_routed_experts == 384


NEW_METRICS = ("expert_layer_tokens_per_expert", "expert_layer_ffn_roofline",
               "shared_expert_ms_per_step.batch")
COUNTERS = ("moe_picks_total", "moe_picks_zero_total", "moe_picks_held_total",
            "moe_steps_total", "moe_held_pairs_max_total",
            "moe_experts_hit_total")


def _run(config, before, after):
    return {"counters": {"before": before, "after": after, "polled": []},
            "config": config, "root": ROOT, "trace": None, "chunk": 8,
            "t_open": 0.0, "t_close": 1.0}


def test_kimi_readers_find_nothing_where_there_is_nothing_to_read(kimi_config):
    """The parent commit's programs have no such family, and the cells that
    are there run configurations without ``counts.expert_layers`` (LongCat's
    has the counters and not the count; GPT-2's has neither): the new
    readers leave their metrics out and do not raise."""
    man = Manifest(ROOT)
    full = {k: 100.0 for k in COUNTERS}
    for config, after in ((kimi_config, {"steps_total": 9.0}),
                          (man.load_config("longcat-flash-omni"), full),
                          (man.load_config("gpt2-medium"), {"steps_total": 9.0})):
        run = _run(config, {k: 0.0 for k in after}, after)
        for name in NEW_METRICS:
            assert man.reader(name)(run) is None, name


def test_kimi_counter_readers_by_hand(kimi_config):
    man = Manifest(ROOT)
    before = {k: 0.0 for k in COUNTERS}
    # 10 token steps of 96 tokens: 10 * 96 * 8 picks * 6 EXPERT layers = 46,080
    after = {"moe_picks_total": 46_080.0, "moe_picks_zero_total": 0.0,
             "moe_picks_held_total": 1_440.0, "moe_steps_total": 10.0,
             "moe_held_pairs_max_total": 300.0, "moe_experts_hit_total": 624.0}
    run = _run(kimi_config, before, after)
    # 1440 pairs / (10 steps x 6 expert layers x 12 experts) = 2 a step
    assert man.reader("expert_layer_tokens_per_expert")(run) == 2.0
    hit = load_function(
        ROOT, "benchmark/readers/expert_layers.py:experts_hit_per_layer_step")
    assert hit(run) == 10.4                       # of 12, an expert layer
    # the shipped reader that this cell shares: busiest 300 / 60 layer-steps
    # = 5 pairs against the mean 2
    assert man.reader("moe_load_imbalance")(run) == 2.5
    assert man.reader("expert_layer_ffn_roofline")(run) is None   # no trace


def test_the_new_reader_file_names_no_architecture():
    with open(os.path.join(ROOT, "benchmark", "readers", "expert_layers.py")) as f:
        text = f.read().lower()
    assert not any(w in text for w in ("kimi", "deepseek", "gpt", "longcat", "olmo"))


def test_the_cell_joins_the_lists_the_issue_names():
    man = Manifest(ROOT)
    names = {m["name"] for m in man.metrics_of(CELL, "per_layer")}
    three = {"gpt2-medium.decode-batch", "longcat-flash-omni.moe-decode",
             "olmo-hybrid-7b.hybrid-decode"}
    shared = {m["name"] for m in man.doc["per_layer"]
              if three <= set(m.get("workloads", ()))}
    assert len(shared) >= 15 and shared <= names
    assert {"prefill_dev_share.batch", "mla_attn_ms_per_step.batch",
            "mla_attn_roofline", "moe_ffn_ms_per_step.batch",
            "moe_load_imbalance", *NEW_METRICS} <= names
    assert not names & {"moe_held_tokens_per_expert", "moe_ffn_roofline",
                        "moe_zero_pick_share", "paged_attn_roofline"}
    assert {m["name"] for m in man.metrics_of(CELL, "end_to_end")} == {
        "serve_out_tok_s", "setup_s"}
    assert man.cells[CELL]["chips"] == 1
    # the driver's rule of form for every line of prose in the manifest
    # (PR 33's first check was refused over a configuration's `why` of 218)
    for entry in (man.doc["configs"] + man.doc["workloads"]
                  + man.doc["end_to_end"] + man.doc["per_layer"]):
        for key in ("why", "layer", "source"):
            text = entry.get(key, "x")
            assert 1 <= len(text) <= 200 and text.isprintable(), (entry["name"], key)


def _env():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_ENABLE_COMPILATION_CACHE"] = "0"
    return env


def _rehearse(trace: int, launcher=None):
    args = ["--workload", CELL, "--seed", str(2 ** 31 + 33), "--seconds", "8",
            "--trace", str(trace), "--rehearse"]
    cmd = ([sys.executable, "benchmark/run.py"] + args if launcher is None
           else [sys.executable, "-c", launcher] + args)
    proc = subprocess.run(cmd, cwd=ROOT, env=_env(), capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    return json.loads(lines[-1]), json.loads(lines[-2])["detail"]


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_kimi_cell(trace):
    last, detail = _rehearse(trace)
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert last["device"]["platform"] == "cpu"            # never a chip result
    assert detail["check"]["checked"] >= 1 and detail["compiles_in_window"] == 0
    open_, close = (detail["realised"][f"engine_at_{k}"] for k in ("open", "close"))
    assert close["moe_picks_held_total"] > open_["moe_picks_held_total"]
    assert close["moe_picks_zero_total"] == 0
    if trace:
        # the counters' metrics need no device trace: a rehearsal reads them
        for name in ("expert_layer_tokens_per_expert", "moe_load_imbalance",
                     "kv_blocks_peak_share", "step_host_share",
                     "dispatch_ahead_share", "replica_warmup_s"):
            assert name in last["metrics"], sorted(last["metrics"])
        assert 0.1 < last["metrics"]["expert_layer_tokens_per_expert"]["value"] < 2.5
    else:
        assert {"setup_s", "serve_out_tok_s"} <= set(last["metrics"])


# The planted faults of ISSUE 33, each the same command started through a
# wrapper that damages the PROGRAM from outside it (the program has no option
# for any of them; the reference is untouched). On the chip, at the cell's
# sizes: ``python3 -c "from benchmark.tests.test_kimi_k2_cell import FAULTS as
# F; exec(F['no_shared_expert'])" --workload kimi-k2.5.agent-decode --seed N
# --seconds 45 --trace 0`` (readings: ``check.why`` in
# benchmark/traffic/agent-decode.json).
_HEAD = """
import sys
sys.path.insert(0, ".")
import jax, jax.numpy as jnp
from ray_tpu.models import kimi_k2
from ray_tpu.ops import layers, mla, moe
"""
_TAIL = """
from benchmark import run
sys.argv = ["benchmark/run.py"] + sys.argv[1:]
sys.exit(run.main())
"""
FAULTS = {
    # the shared expert adds nothing, in prefill and decode alike
    "no_shared_expert": _HEAD + """
plain = kimi_k2.gated_ffn
def gated(fp, x, dtype):
    out = plain(fp, x, dtype)
    return out * 0 if x.ndim == 2 else out       # the shared expert's call is flat
kimi_k2.gated_ffn = gated
""" + _TAIL,
    # picked weights scale * s, not renormalised over the picks
    "not_renormalised": _HEAD + """
plain = moe.route_topk
moe.route_topk = lambda *a, **kw: plain(*a, **dict(kw, renormalise=False))
""" + _TAIL,
    # plain rotary frequencies in YaRN's place, the softmax scale as it is
    "plain_rotary": _HEAD + """
plain = layers.rope_frequencies
mla.rope_frequencies = lambda base, dim, scaling=None: plain(base, dim)
""" + _TAIL,
    # the dense first layer's FFN adds nothing
    "dense_ffn_skipped": _HEAD + """
plain = kimi_k2.gated_ffn
def gated(fp, x, dtype):
    out = plain(fp, x, dtype)
    return out * 0 if x.ndim == 3 else out       # the dense layer's call is [S, T, D]
kimi_k2.gated_ffn = gated
""" + _TAIL,
}


def test_without_the_shared_expert_the_cell_is_not_correct():
    last, detail = _rehearse(0, launcher=FAULTS["no_shared_expert"])
    assert detail["correct_parts"]["streams_complete"] is True
    assert detail["correct_parts"]["reference_sample"] is False
    assert last["correct"] is False
    # 32 positions of the tiny model read 1.3-1.9 (mean 0.44-0.58), by which
    # requests the rehearsal's window finished
    assert detail["check"]["worst_gap"] > 5 * detail["check"]["tolerance"]
    assert detail["check"]["mean_gap"] > detail["check"]["tolerance"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_launcher_plants_the_fault_it_says(fault, monkeypatch):
    """On the program as it is named today: with the launcher's patch the
    tiny model's last-row logits move, and by more than float32's rounding."""
    import jax
    import numpy as np

    from ray_tpu.models import kimi_k2
    from ray_tpu.models.generate import PagedGenerator
    from ray_tpu.ops import mla, moe

    for mod, name in ((kimi_k2, "gated_ffn"), (moe, "route_topk"),
                      (mla, "rope_frequencies")):
        monkeypatch.setattr(mod, name, getattr(mod, name))   # put back after
    cfg = kimi_k2.tiny()
    params = kimi_k2.init_params(cfg, jax.random.key(3))

    def last_row():
        gen = PagedGenerator(params, cfg, slots=1, num_blocks=8,
                             block_tokens=16, max_len=64,
                             attention_kernel="gather")
        pool, state, last, keys = gen.init_state()
        padded = np.arange(1, 65, dtype=np.int32)[None]
        out = gen.prefill_fn(64)(params, pool, state, last, keys,
                                 np.asarray([1, 2, 3, 4], np.int32), padded,
                                 0, 40, 0, 0)
        return np.asarray(out[2][0])

    whole = last_row()
    exec(FAULTS[fault].split("from benchmark import run")[0], {})
    assert np.abs(last_row() - whole).max() > 0.01, fault
