"""The configuration ``trinity-large-preview`` and its cell: its ``counts``
against numbers worked by hand, the cut against ``published`` and the
catalog's row, the program's tree, pool and rings against the counts, its
readers on a program that lacks the counters, the lists the cell joins, and
``--rehearse`` runs of the cell (a window of 16 under contexts of 24-72):
traced, untraced, and with the window ignored, which has to come out not
correct."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.manifest import Manifest, config_count, load_function

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "trinity-large-preview.window-decode"
CONFIG = "trinity-large-preview"
COUNTS = "benchmark/reduce/afmoe_counts.py"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

# By hand, from the published widths (hidden 3072, 48 query heads over 8 KV
# heads of 128, dense FFN 12288, experts 3072, vocabulary slice 25088):
# attention: W_q, W_gate, W_o 3072*6144 = 18,874,368 each; W_k, W_v
#   3072*1024 = 3,145,728 each                              -> 62,914,560
# the dense FFN 3*3072*12288                                -> 113,246,208
# an expert 3*3072*3072 (the shared one the same)           -> 28,311,552
# the router 3072*256                                       -> 786,432
# the dense layer 62,914,560 + 113,246,208                  -> 176,160,768
# an expert layer outside its routed experts                -> 92,012,544
#   with 16 held experts                                    -> 544,997,376
# embedding and head 2*25088*3072                           -> 154,140,672
ATTENTION, DENSE_FFN, EXPERT, ROUTER = 62_914_560, 113_246_208, 28_311_552, 786_432
DENSE_LAYER, OUTSIDE, EXPERT_LAYER = 176_160_768, 92_012_544, 544_997_376
TOTAL = 2_510_290_944
# a ring: (4096/128 + 1) x 128 = 4,224 rows of (K and V) 2 x 8 x 128 x 2 B
RING_ROWS, ROW = 4_224, 4_096
# Keys of the source that say a SHAPE, a count or a rule the layer applies:
# each has to be in ``published`` whatever the catalog later prunes.
SHAPE_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "num_dense_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "sliding_window", "layer_types",
    "global_attn_every_n_layers", "num_experts", "num_experts_per_tok",
    "num_shared_experts", "route_norm", "route_scale", "score_func",
    "rope_theta", "rope_scaling", "rms_norm_eps", "mup_enabled",
    "max_position_embeddings", "tie_word_embeddings", "hidden_act")
# The widths, which no cut may touch.
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "num_attention_heads", "num_key_value_heads", "head_dim",
          "sliding_window", "num_experts_per_tok", "num_shared_experts",
          "route_scale", "rope_theta")


@pytest.fixture(scope="module")
def trinity_config():
    return Manifest(ROOT).load_config(CONFIG)


def test_trinity_counts_by_hand(trinity_config):
    c = trinity_config
    assert ATTENTION == 3 * 3072 * 6144 + 2 * 3072 * 1024
    assert DENSE_LAYER == ATTENTION + DENSE_FFN
    assert OUTSIDE == ATTENTION + ROUTER + EXPERT
    assert EXPERT_LAYER == OUTSIDE + 16 * EXPERT
    assert TOTAL == DENSE_LAYER + 4 * EXPERT_LAYER + 2 * 25088 * 3072
    count = lambda name: load_function(ROOT, f"{COUNTS}:{name}")  # noqa: E731
    assert count("attention_params")(c) == ATTENTION
    assert count("expert_params")(c) == EXPERT == count("shared_expert_params")(c)
    assert count("dense_layer_params")(c) == DENSE_LAYER
    assert count("expert_layer_params_outside_routed")(c) == OUTSIDE
    assert count("param_count")(c) == TOTAL        # the issue's 2,509.9M + 0.4M
    assert (count("window_layers")(c), count("full_layers")(c)) == (4, 1)
    assert config_count(ROOT, c, "expert_layers") == 4
    # a token uses 4 * 16/256 = 0.25 held experts an expert layer; the
    # embedding is a lookup
    assert config_count(ROOT, c, "params_per_token") == (
        DENSE_LAYER + 4 * (OUTSIDE + 0.25 * EXPERT) + 25088 * 3072)
    # ONE full layer x (K and V) x 8 KV heads x 128 x 2 B: the pool's row
    assert config_count(ROOT, c, "kv_bytes_per_context_token") == ROW
    # four window layers x 4,224 rows x 4,096 B: the rings a slot
    assert count("ring_rows")(c) == RING_ROWS
    assert config_count(ROOT, c, "state_bytes_per_slot") == 4 * RING_ROWS * ROW == 69_206_016
    assert config_count(ROOT, c, "expert_weight_bytes") == 2 * EXPERT
    # what a decode step HAS to read is not linear in the context: under the
    # window every layer reads it all, past it the window layers stop
    read = count("attention_bytes_read")
    assert read(c, [100]) == 5 * 100 * ROW
    assert read(c, [4096]) == 5 * 4096 * ROW
    assert read(c, [5200]) == (4 * 4096 + 5200) * ROW
    assert read(c, [8192]) - read(c, [7192]) == 1000 * ROW       # the full layer's
    assert read(c, [100, 5200]) == read(c, [100]) + read(c, [5200])
    # ... where paged_attn_roofline's count would put 5 x 4,096 B a token
    assert 5 * 5200 * ROW / read(c, [5200]) > 1.2


def test_the_trinity_program_holds_what_the_counts_say(trinity_config):
    """The program's own tree at the cell's sizes (shapes only), and what its
    engine would report as ``state_bytes`` for 64 slots and hold as a pool."""
    import jax

    from benchmark.drivers import common

    traffic = Manifest(ROOT).load_traffic("window-decode")
    eng = traffic["engine"]
    slots, blocks = eng["slots"], eng["system_config"]["serve_kv_pool_blocks"]
    cfg = common.model_config(trinity_config, rehearse=False)
    init = common.resolve(trinity_config["init"])
    tree = jax.eval_shape(lambda k: init(cfg, k), jax.random.key(0))
    # the matrices, then the gains (four stream norms and q's and k's a
    # layer, the final norm) and four routers' selection biases
    gains = 5 * (4 * 3072 + 2 * 128) + 3072 + 4 * 256
    assert sum(x.size for x in jax.tree.leaves(tree)) == TOTAL + gains
    assert len(tree["layers"]) == 5
    assert ["ffn" in lp for lp in tree["layers"]] == [True] + [False] * 4
    assert tree["layers"][1]["router"].shape == (3072, 256)       # all 256
    assert tree["layers"][1]["experts"]["w_down"].shape == (16, 3072, 3072)
    state = jax.eval_shape(lambda: cfg.paged_family().init_slot_state(cfg, slots))
    assert sum(x.size * x.dtype.itemsize for x in state) == slots * 69_206_016
    assert [x.shape for x in state] == [(4, slots, 33, 128, 1024)] * 2
    pool = jax.eval_shape(lambda: cfg.paged_family().init_pool(cfg, blocks, 16))
    assert [x.shape for x in pool] == [(1, blocks, 16, 1024)] * 2   # ONE layer
    assert sum(x.size * x.dtype.itemsize for x in pool) == blocks * 16 * ROW
    # every slot at its longest reservation, and the trash block
    longest = -(-(6144 + 1536 + eng["chunk"]) // 16)
    assert blocks == slots * longest + 1 == 30_785
    # a uniform pool of all five layers over the same reservations: 10 GB
    assert 5 * blocks * 16 * ROW > 10e9 > slots * 69_206_016 + blocks * 16 * ROW
    for key in SHAPE_KEYS:
        if hasattr(cfg, key):
            got = getattr(cfg, key)
            got = list(got) if isinstance(got, tuple) else got
            assert got == {"num_experts": 256}.get(key, trinity_config[key]), key
    assert cfg.held == (0, 16) and cfg.max_seq_len == 8192
    assert cfg.window_block_tokens == trinity_config["window_block_tokens"]
    d = cfg.paged_family().describe(cfg)
    assert d["window_ring_bytes_per_slot"] == 69_206_016
    assert (d["window_layers"], d["full_layers"], d["window_tokens"],
            d["expert_layers"], d["dense_layers"]) == (4, 1, 4096, 4, 1)


def test_the_trinity_file_states_the_cut_the_floors_and_every_published_width(
        trinity_config):
    c, pub = trinity_config, trinity_config["published"]
    kinds = ["sliding_attention"] * 3 + ["full_attention", "sliding_attention"]
    cut = {"num_hidden_layers": 5, "num_dense_layers": 1, "layer_types": kinds,
           "num_experts": 16, "vocab_size": 25088,
           "max_position_embeddings": 8192}
    assert sorted(c["reduced"]) == sorted(cut) == sorted(c["reduced_why"])
    for key, value in pub.items():
        assert c[key] == cut.get(key, value), key
    # the cut layer_types are the first five PUBLISHED entries: a whole period
    assert pub["layer_types"][:5] == kinds and len(pub["layer_types"]) == 60
    assert pub["layer_types"] == (kinds[:4] * 15)
    # the floors: a whole period and four layers after the leading dense
    # ones, at least 8 held experts, at least an eighth of the vocabulary
    assert c["num_hidden_layers"] - c["num_dense_layers"] >= 4
    assert c["num_hidden_layers"] >= pub["global_attn_every_n_layers"] + 1
    assert c["held"] == {**c["held"], "first": 0, "count": 16, "of": 256}
    assert c["num_experts"] == c["n_routed_experts"] == c["held"]["count"] >= 8
    assert pub["num_experts"] == c["held"]["of"]
    assert c["vocab_size"] * 8 >= pub["vocab_size"] and c["vocab_size"] % 128 == 0
    assert c["context_tokens"] == c["max_position_embeddings"]
    # no width is among the cuts, and every shape key is published
    assert not set(c["reduced"]) & set(WIDTHS)
    for key in SHAPE_KEYS:
        assert key in pub, key
    for key in WIDTHS:
        assert c[key] == pub[key], key
    for key in ("mup_embedding_scale", "attention_gate", "qk_norm",
                "full_layers_unrotated", "window_edges", "sandwich_norm",
                "rotary_pairing", "router_bias", "init", "stored_dtype",
                "window_block_tokens", "context_tokens"):
        assert key in c["assumed"], key
    stands = c["deployment"]["stands_for"]
    assert "one of 16 chips" in stands and "96 chips" in stands
    assert {"reckoned", "compiled"} <= set(c["deployment"]["memory"])
    entry = Manifest(ROOT).configs[CONFIG]
    assert entry["source"] == c["source"] and entry["reduced"] == c["reduced"]
    assert 1 <= len(entry["why"]) <= 200


def test_trinity_published_agrees_with_the_catalog_where_both_speak(trinity_config):
    """Every key present in BOTH ``published`` and the catalog's row agrees,
    and the row still is this model. Not equality of the two dicts: the
    catalog's keepers prune keys (ROADMAP M9)."""
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = next(r for r in rows if r["source_url"] == trinity_config["source"])
    pub = trinity_config["published"]
    both = set(pub) & set(row["config"])
    assert len(both) >= 20
    for key in both:
        assert pub[key] == row["config"][key], key
    assert row["config"].get("model_type", "afmoe") == "afmoe"


def test_the_trinity_rehearsal_overlay_is_the_tiny_models_sizes(trinity_config):
    from benchmark.drivers import common
    from benchmark.run import _merge

    merged = _merge(trinity_config, trinity_config["rehearse"])
    tiny = common.model_config(merged, rehearse=True)
    for key in SHAPE_KEYS:
        if hasattr(tiny, key) and key not in ("max_position_embeddings",
                                              "num_experts"):
            got = getattr(tiny, key)
            assert (list(got) if isinstance(got, tuple) else got) == merged[key], key
    assert tiny.max_seq_len == merged["context_tokens"] == 128
    assert tiny.held == (0, merged["num_experts"]) == (0, 4)
    assert tiny.num_experts == merged["held"]["of"] == 32
    assert tiny.window_block_tokens == merged["window_block_tokens"] == 8
    # the rehearsal passes the window: prompts of 24-48, a window of 16
    traffic = Manifest(ROOT).load_traffic("window-decode")["rehearse"]
    assert traffic["prompt_tokens"]["lo"] > merged["sliding_window"] == 16


NEW_METRICS = ("window_attn_ms_per_step.batch", "full_attn_ms_per_step.batch",
               "windowed_attn_roofline", "window_capped_share")


def _run(config, before, after, polled=()):
    return {"counters": {"before": before, "after": after,
                         "polled": list(polled)},
            "config": config, "root": ROOT, "trace": None, "chunk": 8,
            "traffic": Manifest(ROOT).load_traffic("window-decode"),
            "records": [], "t_open": 0.0, "t_close": 1.0}


def test_trinity_readers_find_nothing_where_there_is_nothing_to_read(trinity_config):
    """The parent commit has no window kernel in its trace, no
    ``window_capped_slot_steps_total`` and no such configuration; an untraced
    run has no trace at all: the new metrics are left out and nothing
    raises."""
    man = Manifest(ROOT)
    poll = {"t": 0.5, "slots_busy": 3.0, "slots_total": 4.0,
            "kv_blocks_active": 10.0}
    for config in (trinity_config, man.load_config("gpt2-medium")):
        run = _run(config, {"steps_total": 1.0, "state_slot_steps_total": 0.0},
                   {"steps_total": 9.0, "state_slot_steps_total": 64.0}, [poll])
        for name in NEW_METRICS + ("state_cache_share",):
            assert man.reader(name)(run) is None, name
    # a traced run of a configuration with no such count: nothing, no raise
    traced = dict(_run(man.load_config("falcon-h1-34b"), {}, {}), trace={})
    assert man.reader("windowed_attn_roofline")(traced) is None


def test_trinity_counter_readers_by_hand(trinity_config):
    man = Manifest(ROOT)
    # 10 decode calls of 8 token steps, 62 of 64 slots active, 45 past the window
    before = {"steps_total": 0.0, "state_slot_steps_total": 0.0,
              "window_capped_slot_steps_total": 0.0}
    after = {"steps_total": 10.0, "state_slot_steps_total": 4960.0,
             "window_capped_slot_steps_total": 3600.0}
    poll = {"t": 0.5, "slots_busy": 62.0, "slots_total": 64.0,
            "kv_blocks_active": 20000.0, "state_bytes": 64 * 69_206_016.0}
    run = _run(trinity_config, before, after, [poll, dict(poll, t=2.0)])
    assert man.reader("window_capped_share")(run) == pytest.approx(
        100.0 * 3600 / 4960)
    rings, pool = 62 * 69_206_016, 20000 * 16 * ROW
    assert man.reader("state_cache_share")(run) == pytest.approx(
        100.0 * rings / (rings + pool))
    assert man.reader("windowed_attn_roofline")(run) is None        # no trace


def test_the_roofline_reader_by_hand(trinity_config, monkeypatch):
    """Two tokens delivered inside the traced interval, at contexts 5,002
    and 101, against 10 us of the two kernels: the share the reader gives
    is the count's bytes over the peak over that time."""
    from benchmark.reduce import trace as tr

    reader = load_function(ROOT, "benchmark/readers/window.py:windowed_attn_roofline")
    with open(Manifest(ROOT).metric_file("windowed_attn_roofline")) as f:
        spec = json.load(f)
    seen = {}
    monkeypatch.setattr(tr, "window", lambda trace: (1e9, 3e9))
    monkeypatch.setattr(tr, "op_seconds", lambda trace, pattern: (
        seen.setdefault("pattern", pattern), {"seconds": 1e-5, "count": 2})[1])
    run = dict(_run(trinity_config, {}, {}), trace={"stub": True},
               trace_host_t0=100.0, peaks={"hbm_bytes_per_s": 819e9},
               records=[{"prompt_tokens": 5000, "times": [100.5, 101.5, 103.5]},
                        {"prompt_tokens": 100, "times": [102.0]}])
    need = ((4 * 4096 + 5002) + 5 * 101) * ROW       # tokens at 101.5 and 102.0
    assert reader(run, spec) == pytest.approx(100.0 * need / 819e9 / 1e-5)
    assert seen["pattern"] == "^(window|paged)_decode_attn:"


def test_the_new_metrics_are_files_and_two_ride_a_reader_that_was_there():
    man = Manifest(ROOT)
    want = {"window_attn_ms_per_step.batch": ("benchmark/readers/device.py:op_ms_per_step", "^window_decode_attn:"),
            "full_attn_ms_per_step.batch": ("benchmark/readers/device.py:op_ms_per_step", "^paged_decode_attn:"),
            "windowed_attn_roofline": ("benchmark/readers/window.py:windowed_attn_roofline", "^(window|paged)_decode_attn:"),
            "window_capped_share": ("benchmark/readers/spans.py:counter_ratio", None)}
    for name, (reader, pattern) in want.items():
        with open(man.metric_file(name)) as f:
            spec = json.load(f)
        assert spec["reader"] == reader and spec.get("pattern") == pattern
        assert man.per_layer[name]["layer"] == spec["layer"]
        assert CELL in man.per_layer[name]["workloads"]
        assert man.per_layer[name]["moves"] == "serve_out_tok_s"
    # the program's kernels carry the names the patterns look for
    import re

    from ray_tpu.ops import paged_attention as pa
    src = open(pa.__file__).read()
    assert '"paged" if window is None else "window"' in src
    assert re.search("^window_decode_attn:", "window_decode_attn:custom-call:bf16[64,48,1,128]")


def test_the_trinity_cell_joins_the_lists_the_issue_names():
    """Membership only: the next PR appends cells, configurations and
    metrics, and joins this cell to further lists, without this test's
    leave."""
    man = Manifest(ROOT)
    names = {m["name"] for m in man.metrics_of(CELL, "per_layer")}
    assert set(NEW_METRICS) <= names
    assert {"decode_step_ms.batch", "tpot_p50_ms.batch", "decode_batch_mean.batch",
            "device_idle_share.batch", "hbm_peak_share.batch",
            "kv_blocks_peak_share", "pool_blocked_share",
            "prefill_dev_share.batch", "state_cache_share",
            "moe_ffn_ms_per_step.batch", "expert_layer_tokens_per_expert",
            "expert_layer_ffn_roofline", "moe_load_imbalance",
            "step_gap_ms.batch", "gap_deliver_ms.batch", "gap_admit_ms.batch",
            "admit_budget_stop_share", "step_host_share",
            "dispatch_ahead_share", "replica_warmup_s",
            "warmup_lower_s"} <= names
    # NOT paged_attn_roofline: its count is linear in the context, which a
    # window layer is not. NOT the shared expert's metric: its pattern is
    # another cell's shape. NOT the ten of PR 35 that
    # benchmark/tests/test_step_accounting.py holds by equality (ROADMAP M9)
    assert not {"paged_attn_roofline", "shared_expert_ms_per_step.batch",
                "step_handoff_share.batch", "step_host_cpu_share",
                "slots_active_share.batch", "admit_starved_share",
                "warmup_backend_s", "mla_attn_roofline",
                "gdn_state_roofline", "ssd_state_roofline"} & names
    assert {"serve_out_tok_s", "setup_s"} <= {
        m["name"] for m in man.metrics_of(CELL, "end_to_end")}
    assert man.cells[CELL] == {**man.cells[CELL], "chips": 1,
                               "config": CONFIG, "traffic": "window-decode"}
    for entry in (man.doc["configs"] + man.doc["workloads"]
                  + man.doc["end_to_end"] + man.doc["per_layer"]):
        for key in ("why", "layer", "source"):
            text = entry.get(key, "x")
            assert 1 <= len(text) <= 200 and text.isprintable(), (entry["name"], key)
    traffic = man.load_traffic("window-decode")
    assert traffic["driver"] == "serve_closed"
    eng = traffic["engine"]
    assert (traffic["clients"], eng["slots"], eng["chunk"], eng["max_queue"]) == (
        80, 64, 8, 64)
    assert eng["system_config"] == {"serve_kv_pool_blocks": 30785,
                                    "serve_kv_block_tokens": 16,
                                    "serve_llm_prefill_tokens": 8192}
    assert traffic["prompt_tokens"] == {"dist": "uniform", "lo": 3072, "hi": 6144}
    assert traffic["output_tokens"] == {"dist": "uniform", "lo": 512, "hi": 1536}
    assert (traffic["block_requests"], traffic["sub_block_requests"]) == (80, 8)


def _env():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_ENABLE_COMPILATION_CACHE"] = "0"
    return env


def _rehearse(trace: int, launcher=None):
    args = ["--workload", CELL, "--seed", str(2 ** 31 + 42), "--seconds", "8",
            "--trace", str(trace), "--rehearse"]
    cmd = ([sys.executable, "benchmark/run.py"] + args if launcher is None
           else [sys.executable, "-c", launcher] + args)
    proc = subprocess.run(cmd, cwd=ROOT, env=_env(), capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    return json.loads(lines[-1]), json.loads(lines[-2])["detail"]


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_trinity_cell(trace):
    last, detail = _rehearse(trace)
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert last["device"]["platform"] == "cpu"            # never a chip result
    assert detail["check"]["checked"] >= 1 and detail["compiles_in_window"] == 0
    open_, close = (detail["realised"][f"engine_at_{k}"] for k in ("open", "close"))
    assert close["state_slot_steps_total"] > open_["state_slot_steps_total"]
    # the rehearsal passes the window: nearly every slot-step is capped
    capped = (close["window_capped_slot_steps_total"]
              - open_["window_capped_slot_steps_total"])
    assert capped > 0.9 * (close["state_slot_steps_total"]
                           - open_["state_slot_steps_total"])
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
        assert not {"window_attn_ms_per_step.batch", "full_attn_ms_per_step.batch",
                    "windowed_attn_roofline"} & set(last["metrics"])   # no device trace
    else:
        assert {"setup_s", "serve_out_tok_s"} <= set(last["metrics"])


# The same command, started through a wrapper that plants ONE fault in the
# program from outside it (the program has no option for any of them). For
# the chip, at the cell's sizes: ``python3 -c "from
# benchmark.tests.test_trinity_cell import FAULTS as F;
# exec(F['window_ignored'])" --workload trinity-large-preview.window-decode
# --seed N --seconds 45 --trace 0`` (readings: ``check.why`` in
# benchmark/traffic/window-decode.json).
_HEAD = """
import sys
sys.path.insert(0, ".")
import jax, jax.numpy as jnp
from ray_tpu.models import afmoe
"""
_TAIL = """
from benchmark import run
sys.argv = ["benchmark/run.py"] + sys.argv[1:]
sys.exit(run.main())
"""
FAULTS = {
    # full attention in the sliding layers where the whole context is at
    # hand, the prefill (a ring holds no row behind the window to attend in
    # decode): every prompt position past the window sees every key before it
    "window_ignored": _HEAD + """
def unwindowed(plain):
    def call(q, *rest, **kw):
        if q.shape[1] > 1:
            kw["window"] = None
        return plain(q, *rest, **kw)
    return call
afmoe.paged_attention = unwindowed(afmoe.paged_attention)
afmoe.paged_attention_reference = unwindowed(afmoe.paged_attention_reference)
""" + _TAIL,
    # rotation applied in the full layer too
    "rope_in_the_full_layer": _HEAD + """
afmoe._rotates = lambda c, layer: True
""" + _TAIL,
    # the gate left out: a gate of zeros is sigmoid = 1/2 everywhere, which
    # the norm after the sublayer takes out again
    "no_gate": _HEAD + """
plain = afmoe._attention
def ungated(lw, *rest):
    return plain(dict(lw, w_g=jnp.zeros_like(lw["w_g"])), *rest)
afmoe._attention = ungated
""" + _TAIL,
    # the shared expert left out: its down-projection gives nothing
    "no_shared_expert": _HEAD + """
plain = afmoe.expert_layer
def unshared(lp, x, valid, c):
    shared = dict(lp["shared"], w_down=jnp.zeros_like(lp["shared"]["w_down"]))
    return plain(dict(lp, shared=shared), x, valid, c)
afmoe.expert_layer = unshared
""" + _TAIL,
    # the norm AFTER each sublayer left out. A layer calls rms_norm six
    # times, in this order: the input's, q's, k's, the attention output's,
    # the feed-forward input's, the feed-forward output's; a forward pass's
    # last call is the final norm
    "no_post_norms": _HEAD + """
norm, forward, calls = afmoe.rms_norm, afmoe._forward, [0]
def skipping(x, g, eps):
    n = calls[0] % 6
    calls[0] += 1
    return x if n in (3, 5) else norm(x, g, eps)
def counted(*a, **kw):
    calls[0] = 0
    return forward(*a, **kw)
afmoe.rms_norm, afmoe._forward = skipping, counted
""" + _TAIL,
}

def test_with_the_window_ignored_the_cell_is_not_correct():
    last, detail = _rehearse(0, launcher=FAULTS["window_ignored"])
    assert detail["correct_parts"]["streams_complete"] is True
    assert detail["correct_parts"]["reference_sample"] is False
    assert last["correct"] is False
    # the sound float32 rehearsal reads 0.0 against the limit of 0.002
    assert detail["check"]["worst_gap"] > 5 * detail["check"]["tolerance"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_trinity_launcher_plants_the_fault_it_says(fault, monkeypatch):
    """On the program as it is named today: with the launcher's patch the
    tiny model's logits after a 40-token prefill (2.5 windows) and a decode
    chunk move by far more than float32's rounding."""
    import jax
    import numpy as np

    from ray_tpu.models import afmoe
    from ray_tpu.models.generate import PagedGenerator

    for name in ("paged_attention", "paged_attention_reference", "_rotates",
                 "_attention",
                 "expert_layer", "rms_norm", "_forward"):
        monkeypatch.setattr(afmoe, name, getattr(afmoe, name))   # put back after
    cfg = afmoe.tiny()
    params = afmoe.init_params(cfg, jax.random.key(3))

    def last_rows(kernel):
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

    kernels = ("gather", "interpret") if fault == "window_ignored" else ("gather",)
    whole = {k: last_rows(k) for k in kernels}
    exec(FAULTS[fault].split("from benchmark import run")[0], {})
    for k in kernels:
        moved = np.abs(last_rows(k) - whole[k]).max()
        assert moved > 0.01, (fault, k, moved)


def test_the_trinity_files_name_no_other_architecture():
    """The counts, the reference and the new reader state this configuration
    from its dict alone and import nothing of the program; the reader names
    no architecture."""
    for file in (COUNTS, "benchmark/reference/afmoe_plain.py",
                 "benchmark/readers/window.py"):
        with open(os.path.join(ROOT, file)) as f:
            text = f.read()
        assert "import ray_tpu" not in text and "from ray_tpu" not in text
    with open(os.path.join(ROOT, "benchmark/readers/window.py")) as f:
        reader = f.read().lower()
    assert not any(word in reader for word in ("afmoe", "trinity", "sliding"))
