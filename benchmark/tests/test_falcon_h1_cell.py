"""The configuration ``falcon-h1-34b`` and its cell: its ``counts`` against
numbers worked by hand, the cut against ``published`` and the catalog's row,
the program's tree against the counts, its readers on a program that lacks
the counters, the lists the cell joins, and ``--rehearse`` runs of the cell:
traced, untraced, and with the state-space state zeroed every 16th decode
step, which has to come out not correct."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.manifest import Manifest, config_count, load_function

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "falcon-h1-34b.ssm-decode"
COUNTS = "benchmark/reduce/falcon_h1_counts.py"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

# By hand, from the published widths (hidden 5120, feed-forward 21504, 20
# query heads over 4 KV heads of 128; Mamba-2: 32 heads of 128 = 4096
# channels, state 256, 2 groups, convolution 4; vocabulary 261120):
# the convolution's channels: 4096 + 2*2*256 = 5,120
# the in-projection's width: 4096 (z) + 5120 (xBC) + 32 (dt) = 9,248
# a mixer: W_in 5120*9248 = 47,349,760; convolution and bias 5*5120 =
#   25,600; W_out 4096*5120 = 20,971,520; gated norm 4,096; A_log, D,
#   dt_bias 32 each                                       -> 68,351,072
# attention: W_q, W_o 5120*2560 each; W_k, W_v 5120*512   -> 31,457,280
# a feed-forward: 3*5120*21504                            -> 330,301,440
# a layer's two norms: 2*5120 = 10,240
# a layer                                                 -> 430,120,032
# six of them 2,580,720,192; embedding and head 2*261120*5120 =
#   2,673,868,800; final norm 5,120                       -> 5,254,594,112
MIXER, ATTENTION, FFN, LAYER = 68_351_072, 31_457_280, 330_301_440, 430_120_032
TOTAL = 5_254_594_112
# Keys of the source that say a SHAPE (or a multiplier the layer applies):
# each has to be in ``published`` whatever the catalog later prunes.
SHAPE_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim", "mamba_d_ssm",
    "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups",
    "mamba_d_conv", "mamba_chunk_size", "mamba_expand", "mamba_conv_bias",
    "mamba_rms_norm", "mamba_norm_before_gate", "mamba_use_mlp",
    "max_position_embeddings", "rope_theta", "rope_scaling", "rms_norm_eps",
    "tie_word_embeddings", "embedding_multiplier", "lm_head_multiplier",
    "attention_in_multiplier", "attention_out_multiplier", "key_multiplier",
    "ssm_in_multiplier", "ssm_out_multiplier", "ssm_multipliers",
    "mlp_multipliers")


@pytest.fixture(scope="module")
def falcon_config():
    return Manifest(ROOT).load_config("falcon-h1-34b")


def test_falcon_counts_by_hand(falcon_config):
    c = falcon_config
    assert MIXER == (5120 * 9248 + 5 * 5120 + 4096 * 5120 + 4096 + 3 * 32)
    assert ATTENTION == 2 * 5120 * 2560 + 2 * 5120 * 512
    assert LAYER == MIXER + ATTENTION + FFN + 2 * 5120
    count = lambda name: load_function(ROOT, f"{COUNTS}:{name}")  # noqa: E731
    assert count("conv_channels")(c) == 5_120
    assert count("mixer_params")(c) == MIXER
    assert count("attention_params")(c) == ATTENTION
    assert count("ffn_params")(c) == FFN
    assert count("layer_params")(c) == LAYER
    assert TOTAL == 6 * LAYER + 2 * 261120 * 5120 + 5120   # the issue's 5,254.6M
    assert count("param_count")(c) == TOTAL
    # the issue's 3,917.7M: the embedding is a lookup
    assert config_count(ROOT, c, "params_per_token") == TOTAL - 261120 * 5120
    assert config_count(ROOT, c, "params_per_token") == 3_917_659_712
    # 6 layers x (K and V) x 4 KV heads x 128 x 2 B: the row is the KV heads'
    assert config_count(ROOT, c, "kv_bytes_per_context_token") == 12_288
    # 6 layers x 32 x 128 x 256 x 4 B, + 6 x 3 x 5120 x 2 B of tail
    assert config_count(ROOT, c, "recurrent_bytes_per_slot") == 25_165_824
    assert config_count(ROOT, c, "state_bytes_per_slot") == 25_350_144
    # a slot's state weighs as much as two thousand tokens of its own K/V
    assert 25_350_144 // 12_288 == 2_063 and 25_165_824 // 12_288 == 2_048


def test_the_falcon_program_holds_what_the_counts_say(falcon_config):
    """The program's own tree at the cell's sizes (shapes only), and what its
    engine would report as ``state_bytes`` for 64 slots and hold as a pool."""
    import jax

    from benchmark.drivers import common

    traffic = Manifest(ROOT).load_traffic("ssm-decode")["engine"]
    slots = traffic["slots"]
    blocks = traffic["system_config"]["serve_kv_pool_blocks"]
    cfg = common.model_config(falcon_config, rehearse=False)
    init = common.resolve(falcon_config["init"])
    tree = jax.eval_shape(lambda k: init(cfg, k), jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(tree)) == TOTAL
    assert len(tree["layers"]) == 6
    state = jax.eval_shape(lambda: cfg.paged_family().init_slot_state(cfg, slots))
    assert sum(x.size * x.dtype.itemsize for x in state) == slots * 25_350_144
    assert str(state[0].dtype) == "float32"
    assert state[0].size == 6 * slots * 1_048_576       # in whatever folding
    pool = jax.eval_shape(lambda: cfg.paged_family().init_pool(cfg, blocks, 16))
    assert sum(x.size * x.dtype.itemsize for x in pool) == blocks * 16 * 12_288
    assert pool[0].shape[-1] == 4 * 128                 # KV heads, not 2,560
    # every slot at its longest reservation, and the trash block
    longest = -(-(448 + 512 + traffic["chunk"]) // 16)
    assert blocks == slots * longest + 1 == 3905
    # every multiplier and width the program runs is the file's
    for key in SHAPE_KEYS:
        if hasattr(cfg, key):
            got = getattr(cfg, key)
            got = list(got) if isinstance(got, tuple) else got
            want = 1024 if key == "max_position_embeddings" else falcon_config[key]
            assert got == want, key
    assert cfg.max_seq_len == falcon_config["context_tokens"] == 1024


def test_the_falcon_file_states_the_cut_the_floors_and_every_published_width(
        falcon_config):
    c, pub = falcon_config, falcon_config["published"]
    cut = {"num_hidden_layers": 6, "max_position_embeddings": 1024}
    assert sorted(c["reduced"]) == sorted(cut) == sorted(c["reduced_why"])
    for key, value in pub.items():
        assert c[key] == cut.get(key, value), key
    # the floors: at least one period (a layer here) and at least four layers
    assert c["num_hidden_layers"] >= 4 and pub["num_hidden_layers"] == 72
    assert c["context_tokens"] == c["max_position_embeddings"]
    # no width, head count, group count, state size, multiplier or the
    # vocabulary is among the cuts
    assert not set(c["reduced"]) & (set(SHAPE_KEYS) - set(cut))
    for key in SHAPE_KEYS:
        assert key in pub, key
    for key in ("float32_state", "mamba_use_mlp", "dt_unclamped", "gated_norm",
                "mup_vector", "rotary", "init", "stored_dtype",
                "context_tokens"):
        assert key in c["assumed"], key
    assert "twelve" in c["deployment"]["stands_for"].lower()
    assert "12x its share" in c["deployment"]["stands_for"]
    assert {"reckoned", "compiled"} <= set(c["deployment"]["memory"])
    entry = Manifest(ROOT).configs["falcon-h1-34b"]
    assert entry["source"] == c["source"] and entry["reduced"] == c["reduced"]
    assert 1 <= len(entry["why"]) <= 200


def test_published_agrees_with_the_catalog_where_both_speak(falcon_config):
    """Every key present in BOTH ``published`` and the catalog's row agrees,
    and the row still is this model. Not equality of the two dicts: the
    catalog prunes keys that say nothing of shape (it dropped two from
    another configuration's row after that configuration's test was written),
    and ``published`` is the source's file, not the catalog's copy of it."""
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = next(r for r in rows if r["source_url"] == falcon_config["source"])
    pub = falcon_config["published"]
    both = set(pub) & set(row["config"])
    assert len(both) >= 20
    for key in both:
        assert pub[key] == row["config"][key], key
    assert row["config"].get("model_type", "falcon_h1") == "falcon_h1"


def test_the_falcon_rehearsal_overlay_is_the_tiny_models_sizes(falcon_config):
    from benchmark.drivers import common
    from benchmark.run import _merge

    merged = _merge(falcon_config, falcon_config["rehearse"])
    tiny = common.model_config(merged, rehearse=True)
    for key in SHAPE_KEYS:
        if hasattr(tiny, key) and key != "max_position_embeddings":
            got = getattr(tiny, key)
            assert (list(got) if isinstance(got, tuple) else got) == merged[key], key
    assert tiny.max_seq_len == merged["context_tokens"] == 128
    assert tiny.num_hidden_layers == merged["num_hidden_layers"]


NEW_METRICS = ("ssd_state_ms_per_step.batch", "ssd_state_roofline")


def _run(config, before, after, polled=()):
    return {"counters": {"before": before, "after": after,
                         "polled": list(polled)},
            "config": config, "root": ROOT, "trace": None, "chunk": 8,
            "traffic": Manifest(ROOT).load_traffic("ssm-decode"),
            "t_open": 0.0, "t_close": 1.0}


def test_falcon_readers_find_nothing_where_there_is_nothing_to_read(falcon_config):
    """The parent commit has no ``ssd_decode`` in its trace and no such
    configuration; an untraced run has no trace at all: the two new metrics
    are left out and nothing raises."""
    man = Manifest(ROOT)
    poll = {"t": 0.5, "slots_busy": 3.0, "slots_total": 4.0,
            "kv_blocks_active": 10.0}
    for config in (falcon_config, man.load_config("gpt2-medium")):
        run = _run(config, {"steps_total": 1.0}, {"steps_total": 9.0}, [poll])
        for name in NEW_METRICS + ("state_cache_share",):
            assert man.reader(name)(run) is None, name


def test_falcon_counter_readers_by_hand(falcon_config):
    man = Manifest(ROOT)
    # 10 decode calls of 8 token steps, 62 of 64 slots active in each
    before = {"steps_total": 0.0, "state_slot_steps_total": 0.0}
    after = {"steps_total": 10.0, "state_slot_steps_total": 4960.0}
    poll = {"t": 0.5, "slots_busy": 62.0, "slots_total": 64.0,
            "kv_blocks_active": 2400.0, "state_bytes": 64 * 25_350_144.0}
    run = _run(falcon_config, before, after, [poll, dict(poll, t=2.0)])
    active = load_function(ROOT, "benchmark/readers/state.py:active_slots_per_step")
    assert active(run) == 62.0
    state, kv = 62 * 25_350_144, 2400 * 16 * 12_288
    assert man.reader("state_cache_share")(run) == pytest.approx(
        100.0 * state / (state + kv))
    assert 75 < man.reader("state_cache_share")(run) < 85
    assert man.reader("ssd_state_roofline")(run) is None        # no trace


def test_the_two_new_metrics_are_files_on_readers_that_were_there():
    man = Manifest(ROOT)
    for name, reader in (
            ("ssd_state_ms_per_step.batch", "benchmark/readers/device.py:op_ms_per_step"),
            ("ssd_state_roofline", "benchmark/readers/state.py:state_update_roofline")):
        with open(man.metric_file(name)) as f:
            spec = json.load(f)
        assert spec["reader"] == reader
        assert spec["pattern"] == "^ssd_decode:"
        assert spec["step_pattern"] == "^jit_paged_decode"
        # a layer of its own, named for the module the kernel lives in
        assert man.per_layer[name]["layer"] == spec["layer"]
        assert spec["layer"].endswith("ops/ssd.py")
        assert CELL in man.per_layer[name]["workloads"]


def test_the_falcon_cell_joins_the_lists_the_issue_names():
    """Membership only: the next PR appends cells, configurations and
    metrics, and joins this cell to further lists, without this test's
    leave."""
    man = Manifest(ROOT)
    names = {m["name"] for m in man.metrics_of(CELL, "per_layer")}
    assert set(NEW_METRICS) <= names
    assert {"paged_attn_roofline", "state_cache_share", "decode_step_ms.batch",
            "kv_blocks_peak_share", "pool_blocked_share",
            "hbm_peak_share.batch", "prefill_dev_share.batch",
            "dispatch_ahead_share", "replica_warmup_s",
            "warmup_lower_s"} <= names
    # the delta rule's kernel is not on this configuration's path
    assert not {"gdn_state_ms_per_step.batch", "gdn_state_roofline"} & names
    assert {"serve_out_tok_s", "setup_s"} <= {
        m["name"] for m in man.metrics_of(CELL, "end_to_end")}
    assert man.cells[CELL] == {**man.cells[CELL], "chips": 1,
                               "config": "falcon-h1-34b",
                               "traffic": "ssm-decode"}
    assert "falcon-h1-34b" in man.configs
    for entry in (man.doc["configs"] + man.doc["workloads"]
                  + man.doc["end_to_end"] + man.doc["per_layer"]):
        for key in ("why", "layer", "source"):
            text = entry.get(key, "x")
            assert 1 <= len(text) <= 200 and text.isprintable(), (entry["name"], key)
    traffic = man.load_traffic("ssm-decode")
    assert traffic["driver"] == "serve_closed"
    assert (traffic["clients"], traffic["engine"]["slots"]) == (80, 64)
    assert traffic["prompt_tokens"] == {"dist": "uniform", "lo": 64, "hi": 448}
    assert traffic["output_tokens"] == {"dist": "uniform", "lo": 128, "hi": 512}


def _env():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_ENABLE_COMPILATION_CACHE"] = "0"
    return env


def _rehearse(trace: int, launcher=None):
    args = ["--workload", CELL, "--seed", str(2 ** 31 + 40), "--seconds", "8",
            "--trace", str(trace), "--rehearse"]
    cmd = ([sys.executable, "benchmark/run.py"] + args if launcher is None
           else [sys.executable, "-c", launcher] + args)
    proc = subprocess.run(cmd, cwd=ROOT, env=_env(), capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    return json.loads(lines[-1]), json.loads(lines[-2])["detail"]


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_falcon_cell(trace):
    last, detail = _rehearse(trace)
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert last["device"]["platform"] == "cpu"            # never a chip result
    assert detail["check"]["checked"] >= 1 and detail["compiles_in_window"] == 0
    open_, close = (detail["realised"][f"engine_at_{k}"] for k in ("open", "close"))
    assert close["state_slot_steps_total"] > open_["state_slot_steps_total"]
    assert close["prefix_lookups_refused_total"] == close["state_resets_total"] > 0
    assert close["kv_hit_tokens"] == 0
    if trace:
        # the counters' metrics need no device trace: a rehearsal reads them
        for name in ("state_cache_share", "kv_blocks_peak_share",
                     "pool_blocked_share", "dispatch_ahead_share",
                     "replica_warmup_s"):
            assert name in last["metrics"], sorted(last["metrics"])
        assert 0 < last["metrics"]["state_cache_share"]["value"] < 100
        assert not set(NEW_METRICS) & set(last["metrics"])    # no device trace
    else:
        assert {"setup_s", "serve_out_tok_s"} <= set(last["metrics"])


# The same command, started through a wrapper that plants ONE fault in the
# program from outside it (the program has no option for any of them). For
# the chip, at the cell's sizes: ``python3 -c "from
# benchmark.tests.test_falcon_h1_cell import FAULTS as F;
# exec(F['zeroed_state'])" --workload falcon-h1-34b.ssm-decode --seed N
# --seconds 45 --trace 0`` (readings: ``check.why`` in
# benchmark/traffic/ssm-decode.json).
_HEAD = """
import sys
sys.path.insert(0, ".")
import jax, jax.numpy as jnp
from ray_tpu.models import falcon_h1
from ray_tpu.ops import ssd
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
    # array would not fit beside the engine
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
    # one layer's attention branch (the third's) adds nothing, in prefill
    # and decode alike; its K/V rows are still written
    "no_attention_branch": _HEAD + """
plain = falcon_h1._attention
def branch(lw, u, pool, layer, ctx, c, kernel):
    o, pool = plain(lw, u, pool, layer, ctx, c, kernel)
    return o * (layer != 2).astype(o.dtype), pool
falcon_h1._attention = branch
""" + _TAIL,
    # the query-to-KV-head map shifted by one group: query head h reads KV
    # head (h // R + 1) mod KV
    "kv_map_shifted": _HEAD + """
plain = falcon_h1._paged_attend
def shifted(q, k_pool, *rest, **kw):
    r = q.shape[2] // (k_pool.shape[3] // q.shape[3])
    return jnp.roll(plain(jnp.roll(q, r, axis=2), k_pool, *rest, **kw), -r, axis=2)
falcon_h1._paged_attend = shifted
""" + _TAIL,
    # the state kept at bfloat16's precision between token steps: what
    # prefill writes and what every decode step leaves is rounded to 8 bits
    # of mantissa where it lies (a step's arithmetic stays float32). NOT
    # expected to be seen by the limit (as olmo-hybrid-7b's was not): held
    # structurally, tests/test_v5e_compile.py
    "bf16_state": _HEAD + """
round16 = lambda a: jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
def rounded(plain):
    def call(state, x, dt, A, B, C, active, layer, **kw):
        state, y = plain(state, x, dt, A, B, C, active, layer, **kw)
        row = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=True)
        return jax.lax.dynamic_update_slice_in_dim(
            state, round16(row), layer, 0), y
    return call
ssd.ssd_decode = rounded(ssd.ssd_decode)
ssd.ssd_decode_reference = rounded(ssd.ssd_decode_reference)
fold = ssd.fold_state
ssd.fold_state = lambda s: round16(fold(s))
""" + _TAIL,
}


def test_a_state_zeroed_every_16th_step_is_not_correct():
    last, detail = _rehearse(0, launcher=FAULTS["zeroed_state"])
    assert detail["correct_parts"]["streams_complete"] is True
    assert detail["correct_parts"]["reference_sample"] is False
    assert last["correct"] is False
    # the sound float32 rehearsal reads 0.0 against the limit of 0.002
    assert detail["check"]["worst_gap"] > 5 * detail["check"]["tolerance"]


@pytest.mark.parametrize("fault", ["no_attention_branch", "kv_map_shifted",
                                   "bf16_state"])
def test_each_falcon_launcher_plants_the_fault_it_says(fault, monkeypatch):
    """On the program as it is named today: with the launcher's patch the
    tiny model's logits after a prefill and a decode chunk move, by far more
    than float32's rounding for the two faults the limit must see, and by a
    bfloat16 state's worth for the one it is not expected to."""
    import jax
    import numpy as np

    from ray_tpu.models import falcon_h1
    from ray_tpu.models.generate import PagedGenerator
    from ray_tpu.ops import ssd

    for mod, name in ((falcon_h1, "_attention"), (falcon_h1, "_paged_attend"),
                      (ssd, "ssd_decode"), (ssd, "ssd_decode_reference"),
                      (ssd, "fold_state")):
        monkeypatch.setattr(mod, name, getattr(mod, name))   # put back after
    cfg = falcon_h1.tiny(num_hidden_layers=3)
    params = falcon_h1.init_params(cfg, jax.random.key(3))

    def last_rows():
        falcon_h1._layer_fn.cache_clear()       # traced anew, patched or not
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
    falcon_h1._layer_fn.cache_clear()
    if fault == "bf16_state":
        assert 1e-5 < moved < 0.05, moved
    else:
        assert moved > 0.01, (fault, moved)


def test_the_falcon_files_name_no_other_architecture():
    """The counts and the reference state this configuration from its dict
    alone and import nothing of the program."""
    for file in (COUNTS, "benchmark/reference/falcon_h1_plain.py"):
        with open(os.path.join(ROOT, file)) as f:
            text = f.read()
        assert "import ray_tpu" not in text and "from ray_tpu" not in text
        assert "ops.ssd" not in text and "ops/ssd" not in text
