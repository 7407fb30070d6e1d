"""The configuration ``glm-5`` and its cell: its ``counts`` against numbers
worked by hand, the cut against ``published`` and the floors, the program's
own tree against the counts, the new metrics' readers on a program or
configuration that lacks what they read, the planted faults' launchers
(``FAULTS``: what the chip runs plant, one a run; tier-1's
``tests/test_glm_dsa.py`` holds each to the reference at a small size) and
``--rehearse`` runs of the cell: traced, untraced, and with the selection
ignored, which has to come out not correct."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.manifest import Manifest, config_count, load_function

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "glm-5.sparse-decode"
COUNTS = "benchmark/reduce/glm_dsa_counts.py"

# By hand, from the published widths (hidden 6144, 64 heads of 192 + 64 | 256,
# q_lora_rank 2048, kv_lora_rank 512, dense 12288, expert 2048, router 256,
# indexer 32 heads of 128):
# the MLA sublayer: W_qa 6144*2048 = 12,582,912; W_qb 2048*64*256 =
#   33,554,432; W_kva 6144*576 = 3,538,944; W_kvb 512*64*448 = 14,680,064;
#   W_o 16384*6144 = 100,663,296                              -> 165,019,648
# its indexer: W_qb 2048*32*128 = 8,388,608; W_k 6144*128 = 786,432;
#   W_w 6144*32 = 196,608                                     -> 9,371,648
# one expert (and the shared expert): 3*6144*2048             -> 37,748,736
# the router: 6144*256                                        -> 1,572,864
# the dense layer: 174,391,296 + 3*6144*12288 (226,492,416)   -> 400,883,712
# an expert layer outside its routed experts                  -> 213,712,896
#   with its 8 held experts (301,989,888)                     -> 515,702,784
# embedding and head slices: 2*19456*6144                     -> 239,075,328
# dense + 4 expert layers + vocabulary                        -> 2,702,770,176
# a token's picks on a held expert at uniform routing: 8 * 8/256 = 0.25
MLA, INDEXER, EXPERT, ROUTER = 165_019_648, 9_371_648, 37_748_736, 1_572_864
DENSE = 400_883_712


@pytest.fixture(scope="module")
def glm_config():
    return Manifest(ROOT).load_config("glm-5")


def test_glm_counts_by_hand(glm_config):
    c = glm_config
    assert MLA == (6144 * 2048 + 2048 * 64 * 256 + 6144 * 576
                   + 512 * 64 * 448 + 64 * 256 * 6144)
    assert INDEXER == 2048 * 32 * 128 + 6144 * 128 + 6144 * 32
    count = lambda name: load_function(ROOT, f"{COUNTS}:{name}")  # noqa: E731
    assert count("mla_params")(c) == MLA
    assert count("indexer_params")(c) == INDEXER
    assert count("expert_params")(c) == count("shared_expert_params")(c) == EXPERT
    assert count("dense_layer_params")(c) == DENSE == MLA + INDEXER + 3 * 6144 * 12288
    outside = MLA + INDEXER + ROUTER + EXPERT
    assert count("expert_layer_params_outside_routed")(c) == outside == 213_712_896
    total = DENSE + 4 * (outside + 8 * EXPERT) + 2 * 19456 * 6144
    assert total == 2_702_770_176                 # the issue's 2,703M
    assert count("param_count")(c) == total
    assert config_count(ROOT, c, "params_per_token") == (
        DENSE + 4 * (outside + 0.25 * EXPERT) + 19456 * 6144)
    # what the pools HOLD: 5 layers x ((512 + 64) + 128) numbers x 2 B
    assert config_count(ROOT, c, "kv_bytes_per_context_token") == 5 * 1_408
    assert config_count(ROOT, c, "expert_weight_bytes") == 75_497_472
    assert config_count(ROOT, c, "expert_layers") == 4
    # what a step HAD to read: under index_topk every row and no index key;
    # past it 2,048 rows of 1,152 B and every index key of 256 B, a layer
    read = count("attention_bytes_read")
    assert read(c, [2048]) == 5 * 2048 * 1_152
    assert read(c, [2049]) == 5 * (2048 * 1_152 + 2049 * 256)
    assert read(c, [100, 5200]) == 5 * (100 * 1_152 + 2048 * 1_152 + 5200 * 256)
    # the card's 744B-A40B from the published keys whole (no MTP layer)
    pub = dict(c["published"])
    whole = 3 * DENSE + 75 * (outside + 256 * EXPERT) + 2 * 154_880 * 6144
    assert count("param_count")(pub) == whole == 743_910_014_976
    active = 3 * DENSE + 75 * (outside + 8 * EXPERT) + 154_880 * 6144
    assert count("params_per_token")(pub) == active == 40_831_942_656


def test_the_glm_program_holds_what_the_counts_say(glm_config):
    """The program's own tree at the cell's sizes (shapes only): the counts'
    matrices plus the norm gains, the indexer's LayerNorm and the selection
    biases; the pool's TWO arrays."""
    import jax

    from benchmark.drivers import common

    cfg = common.model_config(glm_config, rehearse=False)
    init = common.resolve(glm_config["init"])
    tree = jax.eval_shape(lambda k: init(cfg, k), jax.random.key(0))
    gains = 5 * (2048 + 512 + 2 * 6144 + 2 * 128) + 6144 + 4 * 256
    assert sum(x.size for x in jax.tree.leaves(tree)) == 2_702_770_176 + gains
    blocks = Manifest(ROOT).load_traffic("sparse-decode")["engine"][
        "system_config"]["serve_kv_pool_blocks"]
    assert blocks == 48 * 457 + 1                 # ceil((6,144 + 1,152 + 8) / 16)
    pool = jax.eval_shape(lambda: cfg.paged_family().init_pool(cfg, blocks, 16))
    assert [tuple(x.shape) for x in pool] == [(5, 21937, 16, 640),
                                              (5, 21937, 16, 128)]
    # 1,536 B a token a layer as stored: 2.70 GB
    assert sum(x.size * x.dtype.itemsize for x in pool) == 21937 * 16 * 5 * 1_536
    assert cfg.paged_family().describe(cfg) == {
        "expert_layers": 4, "dense_layers": 1, "index_heads": 32,
        "index_topk": 2048, "index_key_bytes_per_token": 256,
        "shared_expert_params": EXPERT}
    assert cfg.paged_family().unsupported == ("prefix_cache",)


def test_the_glm_file_states_the_cut_the_floors_and_every_published_width(glm_config):
    c, pub = glm_config, glm_config["published"]
    cut = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
           "n_routed_experts": 8, "vocab_size": 19456,
           "max_position_embeddings": 8192, "num_nextn_predict_layers": 0}
    assert sorted(c["reduced"]) == sorted(cut) == sorted(c["reduced_why"])
    for key, value in pub.items():
        assert c[key] == cut.get(key, value), key
    # every width, the heads, the indexer and the router's picks as published
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "kv_lora_rank", "q_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "index_n_heads", "index_head_dim", "index_topk",
                "num_experts_per_tok", "n_shared_experts"):
        assert key in pub and key not in c["reduced"], key
    assert (c["index_n_heads"], c["index_head_dim"], c["index_topk"]) == (
        32, 128, 2048)
    assert c["rope_parameters"] == pub["rope_parameters"]
    assert c["held"]["of"] == pub["n_routed_experts"] == 256
    assert c["held"]["count"] == c["n_routed_experts"] == 256 // 32
    assert c["context_tokens"] == c["max_position_embeddings"]
    # the floors: four expert layers behind the dense one, eight experts, an
    # eighth of the vocabulary in whole lane tiles
    assert c["num_hidden_layers"] - c["first_k_dense_replace"] >= 4
    assert c["first_k_dense_replace"] >= 1 and c["n_routed_experts"] >= 8
    assert c["vocab_size"] * 8 >= pub["vocab_size"] and c["vocab_size"] % 128 == 0
    assert (c["vocab_size"] - 128) * 8 < pub["vocab_size"]
    for key in ("indexer_form", "indexer_rotary", "rotary_pairing",
                "indexer_kernel_choices", "ties", "index_key_dtype",
                "stored_dtype", "e_score_correction_bias", "init", "left_out",
                "context_tokens"):
        assert key in c["assumed"], key
    assert "32 chips" in c["deployment"]["stands_for"]
    assert "11.53 GB" in c["deployment"]["memory"]["compiled"]
    entry = Manifest(ROOT).configs["glm-5"]
    assert entry["source"] == c["source"] and entry["reduced"] == c["reduced"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):          # the builder's machine has it
        with open(catalog) as f:
            rows = [json.loads(line) for line in f]
        [row] = [r for r in rows if r["name"] == "GLM-5"]
        assert row["source_url"] == c["source"]
        # agreement, not equality (benchmark/FALCON_H1.md): the catalog's
        # keepers prune their copy
        for key in set(row["config"]) & set(pub):
            assert row["config"][key] == pub[key], key
        assert {"index_n_heads", "index_head_dim", "index_topk",
                "first_k_dense_replace", "kv_lora_rank"} <= set(pub)


def test_the_glm_rehearsal_overlay_is_the_tiny_models_sizes(glm_config):
    from benchmark.drivers import common
    from benchmark.run import _merge

    merged = _merge(glm_config, glm_config["rehearse"])
    tiny = common.model_config(merged, rehearse=True)
    for key in ("vocab_size", "hidden_size", "intermediate_size",
                "moe_intermediate_size", "num_hidden_layers",
                "first_k_dense_replace", "num_attention_heads",
                "num_experts_per_tok", "kv_lora_rank", "q_lora_rank",
                "qk_rope_head_dim", "qk_nope_head_dim", "v_head_dim",
                "index_n_heads", "index_head_dim", "index_topk",
                "n_shared_experts", "routed_scaling_factor"):
        assert getattr(tiny, key) == merged[key], key
    assert tiny.rope_theta == merged["rope_parameters"]["rope_theta"]
    assert tiny.held == (merged["held"]["first"], merged["held"]["count"])
    assert tiny.n_routed_experts == merged["held"]["of"]
    assert tiny.max_seq_len == merged["context_tokens"]
    full = common.model_config(glm_config, rehearse=False)
    for key in ("vocab_size", "hidden_size", "intermediate_size",
                "moe_intermediate_size", "num_hidden_layers",
                "first_k_dense_replace", "num_experts_per_tok",
                "routed_scaling_factor", "index_topk"):
        assert getattr(full, key) == glm_config[key], key
    assert full.rope_theta == glm_config["rope_parameters"]["rope_theta"]
    assert full.held == (0, 8) and full.n_routed_experts == 256


NEW_METRICS = ("dsa_index_ms_per_step.batch", "dsa_select_ms_per_step.batch",
               "dsa_gather_ms_per_step.batch", "sparse_attn_roofline",
               "dsa_kept_share", "dsa_capped_share",
               "dsa_index_ms_per_prefill.batch",
               "dsa_select_ms_per_prefill.batch",
               "mla_attn_ms_per_prefill.batch")
COUNTERS = ("dsa_selected_rows_total", "dsa_context_rows_total",
            "dsa_capped_slot_steps_total", "dsa_slot_steps_total")


def _run(config, before, after):
    return {"counters": {"before": before, "after": after, "polled": []},
            "config": config, "root": ROOT, "trace": None, "chunk": 8,
            "t_open": 0.0, "t_close": 1.0, "records": []}


def test_glm_readers_find_nothing_where_there_is_nothing_to_read(glm_config):
    """The parent commit's programs have no such family and no such counter,
    and an untraced run no trace: the new metrics are left out and nothing
    raises."""
    man = Manifest(ROOT)
    for config in (glm_config, man.load_config("kimi-k2.5"),
                   man.load_config("gpt2-medium")):
        run = _run(config, {"steps_total": 0.0}, {"steps_total": 9.0})
        for name in NEW_METRICS:
            assert man.reader(name)(run) is None, name


def test_glm_counter_readers_by_hand(glm_config):
    man = Manifest(ROOT)
    before = {k: 0.0 for k in COUNTERS}
    # 10 token steps of 64 live slots: 8 of them under index_topk at 1,000
    # rows, 56 past it at 5,200
    after = {"dsa_selected_rows_total": 10.0 * (8 * 1000 + 56 * 2048),
             "dsa_context_rows_total": 10.0 * (8 * 1000 + 56 * 5200),
             "dsa_capped_slot_steps_total": 560.0,
             "dsa_slot_steps_total": 640.0}
    run = _run(glm_config, before, after)
    assert man.reader("dsa_capped_share")(run) == 87.5
    assert man.reader("dsa_kept_share")(run) == pytest.approx(
        100.0 * 122_688 / 299_200)
    assert man.reader("sparse_attn_roofline")(run) is None       # no trace


def test_the_cell_joins_the_lists_the_issue_names():
    man = Manifest(ROOT)
    names = {m["name"] for m in man.metrics_of(CELL, "per_layer")}
    kimi = {m["name"] for m in man.metrics_of("kimi-k2.5.agent-decode",
                                              "per_layer")}
    # every list Kimi's cell is on, but: mla_attn_roofline (its reader
    # multiplies every context token by kv_bytes_per_context_token; a step
    # that reads 2,048 rows of 5,200 would read over 105%), the shared
    # expert's pattern of [96, 2048] outputs (this cell has 48 slots, and
    # its query latent is 2,048 wide too), and the ten that
    # test_step_accounting.py pins to the cells of their day
    left = {"mla_attn_roofline", "shared_expert_ms_per_step.batch",
            "step_handoff_share.batch", "step_host_cpu_share",
            "prefill_dispatch_share", "prefill_dispatch_cpu_share",
            "slots_active_share.batch", "admit_starved_share",
            "submit_path_ms.batch", "return_tail_p50_ms.batch",
            "return_tail_p90_ms.batch", "warmup_backend_s"}
    assert names == (kimi - left) | set(NEW_METRICS)
    for name in NEW_METRICS:
        entry = man.per_layer[name]
        assert entry["workloads"] == [CELL] and entry["moves"] == "serve_out_tok_s"
    assert {m["name"] for m in man.metrics_of(CELL, "end_to_end")} == {
        "serve_out_tok_s", "setup_s"}
    assert man.cells[CELL]["chips"] == 1
    assert sum(1 for w in man.doc["workloads"] if w["chips"] == 4) == 1
    for entry in (man.doc["configs"] + man.doc["workloads"]
                  + man.doc["end_to_end"] + man.doc["per_layer"]):
        for key in ("why", "layer", "source"):
            text = entry.get(key, "x")
            assert 1 <= len(text) <= 200 and text.isprintable(), (entry["name"], key)


def test_the_glm_files_name_no_other_architecture_and_import_no_program():
    for file in (COUNTS, "benchmark/reference/glm_dsa_plain.py"):
        with open(os.path.join(ROOT, file)) as f:
            text = f.read()
        assert "import ray_tpu" not in text and "from ray_tpu" not in text


def _env():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_ENABLE_COMPILATION_CACHE"] = "0"
    return env


def _rehearse(trace: int, launcher=None):
    args = ["--workload", CELL, "--seed", str(2 ** 31 + 53), "--seconds", "8",
            "--trace", str(trace), "--rehearse"]
    cmd = ([sys.executable, "benchmark/run.py"] + args if launcher is None
           else [sys.executable, "-c", launcher] + args)
    proc = subprocess.run(cmd, cwd=ROOT, env=_env(), capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    return json.loads(lines[-1]), json.loads(lines[-2])["detail"]


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_glm_cell(trace):
    last, detail = _rehearse(trace)
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert last["device"]["platform"] == "cpu"            # never a chip result
    assert detail["check"]["checked"] >= 1 and detail["compiles_in_window"] == 0
    open_, close = (detail["realised"][f"engine_at_{k}"] for k in ("open", "close"))
    assert close["dsa_slot_steps_total"] > open_["dsa_slot_steps_total"]
    assert close["prefix_lookups_refused_total"] > 0 and close["kv_hit_tokens"] == 0
    if trace:
        # the counters' metrics need no device trace: a rehearsal reads them
        for name in ("dsa_kept_share", "dsa_capped_share",
                     "expert_layer_tokens_per_expert", "moe_load_imbalance",
                     "kv_blocks_peak_share", "step_host_share"):
            assert name in last["metrics"], sorted(last["metrics"])
        # contexts of 24-72 tokens against index_topk 12: every step selects
        assert last["metrics"]["dsa_capped_share"]["value"] == 100.0
        assert 12 < last["metrics"]["dsa_kept_share"]["value"] < 50
    else:
        assert {"setup_s", "serve_out_tok_s"} <= set(last["metrics"])


# The planted faults of ISSUE 53, each the same command started through a
# wrapper that damages the PROGRAM from outside it (the program has no option
# for any of them; the reference is untouched). On the chip, at the cell's
# sizes: ``python3 -c "from benchmark.tests.test_glm_5_cell import FAULTS as
# F; exec(F['selection_ignored'])" --workload glm-5.sparse-decode --seed N
# --seconds 45 --trace 0`` (readings: ``check.why`` in
# benchmark/traffic/sparse-decode.json).
_HEAD = """
import sys
sys.path.insert(0, ".")
import jax, jax.numpy as jnp
from ray_tpu.models import glm_dsa
from ray_tpu.ops import sparse_select
"""
_TAIL = """
from benchmark import run
sys.argv = ["benchmark/run.py"] + sys.argv[1:]
sys.exit(run.main())
"""
FAULTS = {
    # the selection ignored: every visible row attended
    "selection_ignored": _HEAD + """
def every_visible_row(q, w, keys, q_pos, *, k, kernel, dtype):
    return (jnp.arange(keys.shape[1])[None, None, :]
            <= q_pos[..., None]).astype(dtype)
glm_dsa.keep_bits = every_visible_row
""" + _TAIL,
    # the index_topk SMALLEST scores chosen
    "smallest_chosen": _HEAD + """
plain = sparse_select.index_scores
sparse_select.index_scores = lambda q, w, keys: -plain(q, w, keys)
""" + _TAIL,
    # the ReLU left out of the index scores
    "no_relu": _HEAD + """
sparse_select.index_scores = lambda q, w, keys: jnp.einsum(
    "bqhd,bnd,bqh->bqn", q.astype(jnp.float32), keys.astype(jnp.float32), w)
""" + _TAIL,
    # the head weights w left out: a plain sum over the index heads
    "no_head_weights": _HEAD + """
plain = sparse_select.index_scores
sparse_select.index_scores = lambda q, w, keys: plain(q, jnp.ones_like(w), keys)
""" + _TAIL,
    # the indexer's keys left unrotated (position 0's rotation is none)
    "keys_unrotated": _HEAD + """
plain = glm_dsa.index_keys
glm_dsa.index_keys = lambda ip, a, positions, c: plain(
    ip, a, jnp.zeros_like(positions), c)
""" + _TAIL,
    # the selection of a prompt's LAST row used for all its rows
    "last_rows_selection": _HEAD + """
plain = glm_dsa.keep_bits
def last_row_for_all(*a, **kw):
    keep = plain(*a, **kw)
    return jnp.broadcast_to(keep[:, -1:], keep.shape)
glm_dsa.keep_bits = last_row_for_all
""" + _TAIL,
    # the index keys of the step's own tokens not written before they are
    # scored: the cells hold what they held (zeros in a fresh block)
    "own_key_unwritten": _HEAD + """
plain = glm_dsa.select
def stale(cq, *, index, sub, tables, positions, **kw):
    bt = index.shape[2]
    pos = jnp.minimum(positions, tables.shape[1] * bt - 1)
    blk = tables[jnp.arange(tables.shape[0])[:, None], pos // bt]
    return plain(cq, index=index.at[sub, blk, pos % bt].set(0), sub=sub,
                 tables=tables, positions=positions, **kw)
glm_dsa.select = stale
""" + _TAIL,
}


def test_with_the_selection_ignored_the_cell_is_not_correct():
    last, detail = _rehearse(0, launcher=FAULTS["selection_ignored"])
    assert detail["correct_parts"]["streams_complete"] is True
    assert detail["correct_parts"]["reference_sample"] is False
    assert last["correct"] is False
    # the sound float32 rehearsal reads ~0.0 against the limit of 0.002
    assert detail["check"]["worst_gap"] > 5 * detail["check"]["tolerance"]
