"""The configuration ``olmo-hybrid-7b`` and its cell: its ``counts`` against
numbers worked by hand, the cut against ``published``, its readers on a
program that lacks the counters, and ``--rehearse`` runs of the cell: traced,
untraced, and with the recurrent state zeroed every 16th decode step, which
has to come out not correct."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.manifest import Manifest, config_count, load_function

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "olmo-hybrid-7b.hybrid-decode"
COUNTS = "benchmark/reduce/olmo_hybrid_counts.py"

# By hand, from the published widths (hidden 3840, feed-forward 11008, 30
# heads of 128; linear attention 30 key heads of 96, 30 value heads of 192,
# convolution 4; vocabulary 100352):
# the convolution's channels: 2*30*96 + 30*192 = 11,520
# a linear mixer: W_qkv 3840*11520 = 44,236,800; W_g, W_o 3840*5760 =
#   22,118,400 each; w_a, w_b 3840*30 = 115,200 each; convolution 4*11520 =
#   46,080; A_log, dt_bias 30 each; output norm 192      -> 88,750,332
# a full mixer: 4*3840^2 = 58,982,400; q and k norms 2*3840 -> 58,990,080
# a feed-forward: 3*3840*11008                            -> 126,812,160
# a layer's two output norms: 2*3840 = 7,680
# a linear layer 215,570,172; a full layer 185,809,920
# 12 + 4 of them: 2,586,842,064 + 743,239,680            -> 3,330,081,744
# embedding and head 2*100352*3840 = 770,703,360; final norm 3,840
#                                                         -> 4,100,788,944
LINEAR_MIXER, FULL_MIXER, FFN = 88_750_332, 58_990_080, 126_812_160


@pytest.fixture(scope="module")
def olmo_config():
    return Manifest(ROOT).load_config("olmo-hybrid-7b")


def test_olmo_counts_by_hand(olmo_config):
    c = olmo_config
    assert LINEAR_MIXER == (3840 * 11520 + 2 * 3840 * 5760 + 2 * 3840 * 30
                            + 4 * 11520 + 2 * 30 + 192)
    count = lambda name: load_function(ROOT, f"{COUNTS}:{name}")  # noqa: E731
    assert count("conv_channels")(c) == 11_520
    assert count("linear_mixer_params")(c) == LINEAR_MIXER
    assert count("full_mixer_params")(c) == FULL_MIXER
    assert count("layer_params")(c, "linear_attention") == 215_570_172
    assert count("layer_params")(c, "full_attention") == 185_809_920
    total = 12 * 215_570_172 + 4 * 185_809_920 + 2 * 100352 * 3840 + 3840
    assert total == 4_100_788_944            # the issue's 4,100.8M
    assert count("param_count")(c) == total
    assert config_count(ROOT, c, "params_per_token") == total - 100352 * 3840
    # 4 full layers x (K and V) x 3840 x 2 B
    assert config_count(ROOT, c, "kv_bytes_per_context_token") == 61_440
    # 12 linear layers x (30 x 192 x 96 x 4 B + 3 x 11520 x 2 B)
    assert config_count(ROOT, c, "recurrent_bytes_per_slot") == 12 * 2_211_840
    assert config_count(ROOT, c, "state_bytes_per_slot") == 27_371_520
    # a slot's state weighs as much as 445 tokens of K/V
    assert 27_371_520 // 61_440 == 445


def test_the_program_holds_what_the_counts_say(olmo_config):
    """The program's own tree at the cell's sizes (shapes only), and what its
    engine would report as ``state_bytes`` for 48 slots."""
    import jax

    from benchmark.drivers import common

    cfg = common.model_config(olmo_config, rehearse=False)
    init = common.resolve(olmo_config["init"])
    tree = jax.eval_shape(lambda k: init(cfg, k), jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(tree)) == 4_100_788_944
    state = jax.eval_shape(lambda: cfg.paged_family().init_slot_state(cfg, 48))
    assert sum(x.size * x.dtype.itemsize for x in state) == 48 * 27_371_520
    pool = jax.eval_shape(lambda: cfg.paged_family().init_pool(cfg, 3265, 16))
    assert sum(x.size * x.dtype.itemsize for x in pool) == 3265 * 16 * 61_440


def test_the_olmo_file_states_the_cut_and_every_published_width(olmo_config):
    c, pub = olmo_config, olmo_config["published"]
    cut = {"num_hidden_layers": 16, "layer_types": pub["layer_types"][:16],
           "max_position_embeddings": 2048}
    assert sorted(c["reduced"]) == sorted(cut)
    assert sorted(c["reduced_why"]) == sorted(cut)
    for key, value in pub.items():
        assert c[key] == cut.get(key, value), key
    # depth only: four whole periods in the published 3:1
    assert c["layer_types"] == (["linear_attention"] * 3
                                + ["full_attention"]) * 4
    assert len(pub["layer_types"]) == pub["num_hidden_layers"] == 32
    assert c["context_tokens"] == c["max_position_embeddings"]
    assert c["rope_parameters"] == {"rope_theta": None}
    for key in ("residual_form", "qk_norm", "no_rotary_embedding",
                "float32_state", "init", "stored_dtype", "context_tokens"):
        assert key in c["assumed"], key
    entry = Manifest(ROOT).configs["olmo-hybrid-7b"]
    assert entry["source"] == c["source"] and entry["reduced"] == c["reduced"]


def test_the_olmo_rehearsal_overlay_is_the_tiny_models_sizes(olmo_config):
    from benchmark.drivers import common
    from benchmark.run import _merge

    merged = _merge(olmo_config, olmo_config["rehearse"])
    tiny = common.model_config(merged, rehearse=True)
    for key in ("vocab_size", "hidden_size", "intermediate_size",
                "num_hidden_layers", "num_attention_heads",
                "num_key_value_heads", "linear_num_key_heads",
                "linear_num_value_heads", "linear_key_head_dim",
                "linear_value_head_dim", "linear_conv_kernel_dim"):
        assert getattr(tiny, key) == merged[key], key
    assert list(tiny.layer_types) == merged["layer_types"]
    assert tiny.max_seq_len == merged["context_tokens"]
    full = common.model_config(olmo_config, rehearse=False)
    for key in ("vocab_size", "hidden_size", "intermediate_size",
                "num_hidden_layers", "num_attention_heads",
                "linear_key_head_dim", "linear_value_head_dim"):
        assert getattr(full, key) == olmo_config[key], key
    assert list(full.layer_types) == olmo_config["layer_types"]


NEW_METRICS = ("gdn_state_ms_per_step.batch", "gdn_state_roofline",
               "prefill_dev_share.batch", "state_cache_share")


def _run(config, before, after, polled=()):
    return {"counters": {"before": before, "after": after,
                         "polled": list(polled)},
            "config": config, "root": ROOT, "trace": None, "chunk": 8,
            "traffic": Manifest(ROOT).load_traffic("hybrid-decode"),
            "t_open": 0.0, "t_close": 1.0}


def test_olmo_readers_find_nothing_on_a_program_without_the_counters(olmo_config):
    """The parent commit has no ``state_*`` counters, and the cells that are
    there run configurations without a slot state: the new readers leave
    their metrics out and do not raise."""
    man = Manifest(ROOT)
    poll = {"t": 0.5, "slots_busy": 3.0, "slots_total": 4.0,
            "kv_blocks_active": 10.0}
    for config in (olmo_config, man.load_config("gpt2-medium")):
        run = _run(config, {"steps_total": 1.0}, {"steps_total": 9.0}, [poll])
        for name in NEW_METRICS:
            assert man.reader(name)(run) is None, name


def test_olmo_counter_readers_by_hand(olmo_config):
    man = Manifest(ROOT)
    # 10 decode calls of 8 token steps, 45 of 48 slots active in each
    before = {"steps_total": 0.0, "state_slot_steps_total": 0.0}
    after = {"steps_total": 10.0, "state_slot_steps_total": 3600.0}
    poll = {"t": 0.5, "slots_busy": 45.0, "slots_total": 48.0,
            "kv_blocks_active": 3200.0, "state_bytes": 48 * 27_371_520.0}
    run = _run(olmo_config, before, after, [poll, dict(poll, t=2.0)])
    active = load_function(ROOT, "benchmark/readers/state.py:active_slots_per_step")
    assert active(run) == 45.0
    state, kv = 45 * 27_371_520, 3200 * 16 * 61_440
    assert man.reader("state_cache_share")(run) == pytest.approx(
        100.0 * state / (state + kv))
    assert man.reader("gdn_state_roofline")(run) is None        # no trace


def test_no_new_reader_names_an_architecture():
    with open(os.path.join(ROOT, "benchmark", "readers", "state.py")) as f:
        text = f.read().lower()
    assert not any(w in text for w in ("olmo", "gdn", "delta", "gpt", "longcat"))


def _env():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_ENABLE_COMPILATION_CACHE"] = "0"
    return env


def _rehearse(trace: int, launcher=None):
    args = ["--workload", CELL, "--seed", str(2 ** 31 + 31), "--seconds", "8",
            "--trace", str(trace), "--rehearse"]
    cmd = ([sys.executable, "benchmark/run.py"] + args if launcher is None
           else [sys.executable, "-c", launcher] + args)
    proc = subprocess.run(cmd, cwd=ROOT, env=_env(), capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    return json.loads(lines[-1]), json.loads(lines[-2])["detail"]


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_olmo_cell(trace):
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
                     "dispatch_ahead_share", "replica_warmup_s"):
            assert name in last["metrics"], sorted(last["metrics"])
        assert 0 < last["metrics"]["state_cache_share"]["value"] < 100
    else:
        assert {"setup_s", "serve_out_tok_s"} <= set(last["metrics"])


# The same command, started through a wrapper that zeroes the recurrent
# state of every slot before every fourth decode dispatch (chunks of 4: every
# 16th token step; every 32nd at the cell's chunk of 8). The test steers the
# program from outside it: the program has no option for this. The state is
# zeroed where it lies (donated): at the cell's sizes a second 1.27 GB array
# would not fit beside the engine.
_ZEROING = """
import sys
sys.path.insert(0, ".")
import jax
from ray_tpu.serve import llm
plain, calls = llm.LLMEngine._run_decode, [0]
zeroed = jax.jit(lambda S: S * 0.0, donate_argnums=0)
def damaged(self, *args):
    calls[0] += 1
    if self._steady and calls[0] % 4 == 0:
        S, tail = self._slot_state
        self._slot_state = (zeroed(S), tail)
    return plain(self, *args)
llm.LLMEngine._run_decode = damaged
from benchmark import run
sys.argv = ["benchmark/run.py"] + sys.argv[1:]
sys.exit(run.main())
"""

# The same command with the recurrent state kept at bfloat16's precision
# between token steps: what prefill writes and what every decode step leaves
# is rounded to 8 bits of mantissa where it lies (the arithmetic of a step
# stays float32). For the chip, at the cell's sizes, where it reads what
# ``check.logit_tolerance`` can see of the state's precision (both readings:
# ``check.why`` in benchmark/traffic/hybrid-decode.json): the float32
# rehearsal's sampled tokens seldom sit that near a tie.
_BF16_STATE = """
import sys
sys.path.insert(0, ".")
import jax
from ray_tpu.ops import gated_delta as gd
round16 = lambda a: jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
def rounded(plain):
    def call(state, q, k, v, alpha, beta, active, layer, **kw):
        state, o = plain(state, q, k, v, alpha, beta, active, layer, **kw)
        row = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=True)
        return jax.lax.dynamic_update_slice_in_dim(
            state, round16(row), layer, 0), o
    return call
gd.gdn_decode = rounded(gd.gdn_decode)
gd.gdn_decode_reference = rounded(gd.gdn_decode_reference)
fold = gd.fold_state
gd.fold_state = lambda s: round16(fold(s))
from benchmark import run
sys.argv = ["benchmark/run.py"] + sys.argv[1:]
sys.exit(run.main())
"""


def test_a_state_zeroed_every_16th_step_is_not_correct():
    last, detail = _rehearse(0, launcher=_ZEROING)
    assert detail["correct_parts"]["streams_complete"] is True
    assert detail["correct_parts"]["reference_sample"] is False
    assert last["correct"] is False
    assert detail["check"]["worst_gap"] > 10 * detail["check"]["tolerance"]


def test_the_bfloat16_launcher_rounds_the_state_it_says(monkeypatch):
    """``_BF16_STATE`` plants what its comment says, on the program as it is
    named today: the layer's states come back at bfloat16's precision, the
    other layers' bit for bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import gated_delta as gd

    for name in ("gdn_decode", "gdn_decode_reference", "fold_state"):
        monkeypatch.setattr(gd, name, getattr(gd, name))    # put back after
    exec(_BF16_STATE.split("from benchmark import run")[0], {})
    L, S, H, dk, dv = 2, 3, 4, 8, 16
    ks = jax.random.split(jax.random.key(31), 6)
    state = jax.random.normal(ks[0], (L, S, dk, H * dv), jnp.float32)
    q, k = (jax.random.normal(x, (S, H, dk)) for x in ks[1:3])
    v = jax.random.normal(ks[3], (S, H, dv))
    alpha, beta = (jax.random.uniform(x, (S, H)) for x in ks[4:6])
    active = jnp.array([True, False, True])
    new, _ = gd.gdn_decode_reference(state, q, k, v, alpha, beta, active, 1)
    new, old = np.asarray(new), np.asarray(state)
    as16 = np.asarray(jnp.asarray(new[1]).astype(jnp.bfloat16)
                      .astype(jnp.float32))
    assert (new[1] == as16).all() and (new[1][0] != old[1][0]).any()
    assert (new[0] == old[0]).all()
    folded = np.asarray(gd.fold_state(jax.random.normal(ks[0], (H, dk, dv))))
    assert (folded == np.asarray(jnp.asarray(folded).astype(jnp.bfloat16)
                                 .astype(jnp.float32))).all()
