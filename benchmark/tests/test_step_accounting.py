"""ISSUE 35's metrics: the engine's account of its window (time between
steps, the step's CPU against its wall time, slots filled against slots
offered, why admission stopped) and the closed loop's way around the engine.
Every one is a file under ``metrics/`` read by a reader that was there: each
resolves through the manifest, is worked by hand on a small run, reads
nothing on a program that lacks the counter or the ring, and is printed by a
traced rehearsal of the cells that list it."""

import collections
import json
import os
import subprocess
import sys

import pytest

from benchmark.manifest import Manifest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MS = 1_000_000

BATCH = ["gpt2-medium.decode-batch", "longcat-flash-omni.moe-decode",
         "olmo-hybrid-7b.hybrid-decode", "kimi-k2.5.agent-decode"]
CHAT = ["gpt2-medium.prefix-chat", "gpt2-medium.chat-unshared"]
# metric -> (the cells that list it, the end-to-end metric it moves)
NEW = {
    "step_handoff_share.batch": (BATCH, "serve_out_tok_s"),
    "step_handoff_share.chat": (CHAT, "tpot_p90_ms"),
    "step_host_cpu_share": (BATCH, "serve_out_tok_s"),
    "prefill_dispatch_share": (BATCH, "serve_out_tok_s"),
    "prefill_dispatch_cpu_share": (BATCH, "serve_out_tok_s"),
    "slots_active_share.batch": (BATCH, "serve_out_tok_s"),
    "admit_starved_share": (BATCH, "serve_out_tok_s"),
    "submit_path_ms.batch": (BATCH, "serve_out_tok_s"),
    "return_tail_p50_ms.batch": (BATCH, "serve_out_tok_s"),
    "return_tail_p90_ms.batch": (BATCH, "serve_out_tok_s"),
    "warmup_backend_s": (BATCH + CHAT, "setup_s"),
}
COUNTERS = ("step_host_s", "step_host_cpu_s", "step_device_wait_s",
            "step_handoff_s", "step_driver_switches_total",
            "prefill_dispatch_s", "prefill_dispatch_cpu_s",
            "slot_steps_total", "slot_steps_offered_total",
            "admit_stopped_queue_empty_total", "admit_stopped_no_slot_total",
            "admit_stopped_budget_total", "admit_stopped_no_blocks_total",
            "admit_starved_total")

Span = collections.namedtuple(
    "Span", "name start_ns end_ns span_id parent_id trace_id attrs")


@pytest.fixture(scope="module")
def man():
    return Manifest(ROOT)


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_new_metric_resolves_through_the_manifest(man, name):
    cells, moves = NEW[name]
    entry = man.per_layer[name]
    assert sorted(entry["workloads"]) == sorted(cells)
    assert entry["moves"] == moves
    with open(man.metric_file(name)) as f:
        spec = json.load(f)
    # A reader that an earlier PR wrote, in a file this PR does not touch.
    assert spec["reader"].split(":")[0] == "benchmark/readers/spans.py"
    assert spec["reader"].split(":")[1] in (
        "counter_share", "counter_ratio", "between", "attr_sum")
    assert callable(man.reader(name))
    for cell in cells:
        assert entry in man.metrics_of(cell, "per_layer")


# A window of 10 s on a closed cell of 4 slots, chunk 8. Over it the engine
# spent 6.0 s of host time inside steps (4.5 s of it on a CPU; 2.0 s inside
# the prefills' jitted calls, 0.5 s of that on a CPU), 3.0 s in device_get
# and 1.0 s between steps: 10 s. 100 steps dispatched a decode, 20 of them
# with a slot free and nobody waiting; 2,800 of 3,200 slot steps were filled.
#   r1: handle 1,000 ms, submit 1,030, engine done 1,400, iterator ends 1,410
#   r2: handle 2,000 ms, submit 2,020, engine done 2,600, iterator ends 2,650
BEFORE = {"step_host_s": 1.0, "step_host_cpu_s": 0.5, "step_device_wait_s": 2.0,
          "step_handoff_s": 0.25, "prefill_dispatch_s": 0.5,
          "prefill_dispatch_cpu_s": 0.125, "slot_steps_total": 400.0,
          "slot_steps_offered_total": 800.0, "admit_starved_total": 5.0,
          "steps_total": 25.0}
GROWTH = {"step_host_s": 6.0, "step_host_cpu_s": 4.5, "step_device_wait_s": 3.0,
          "step_handoff_s": 1.0, "prefill_dispatch_s": 2.0,
          "prefill_dispatch_cpu_s": 0.5, "slot_steps_total": 2800.0,
          "slot_steps_offered_total": 3200.0, "admit_starved_total": 20.0,
          "steps_total": 100.0}


def _request(tid, handle, submit, admitted, done, ended):
    return [
        Span("llm.admission_wait", submit * MS, admitted * MS, tid + "a", tid, tid, None),
        Span("llm.request", submit * MS, done * MS, tid + "r", tid, tid, None),
        Span("serve.request", handle * MS, ended * MS, tid, None, tid, None)]


SPANS = (
    [Span("llm.warmup.program", 100 * MS, 130 * MS, "w1", "w", "eng",
          {"program": "paged_prefill", "trace_s": 0.01, "lower_s": 0.012, "backend_s": 0.005}),
     Span("llm.warmup.program", 130 * MS, 160 * MS, "w2", "w", "eng",
          {"program": "paged_decode", "trace_s": 0.004, "lower_s": 0.003, "backend_s": 0.020})]
    + _request("r1", 1000, 1030, 1040, 1400, 1410)
    + _request("r2", 2000, 2020, 2025, 2600, 2650))
RUN = {"spans": SPANS, "t_open": 0.5, "t_close": 10.5,
       "counters": {"before": BEFORE,
                    "after": {k: BEFORE[k] + GROWTH[k] for k in BEFORE}}}
BY_HAND = {
    "step_handoff_share.batch": 10.0, "step_handoff_share.chat": 10.0,
    "step_host_cpu_share": 75.0, "prefill_dispatch_share": 100.0 / 3,
    "prefill_dispatch_cpu_share": 25.0, "slots_active_share.batch": 87.5,
    "admit_starved_share": 20.0, "submit_path_ms.batch": 25.0,
    "return_tail_p50_ms.batch": 30.0, "return_tail_p90_ms.batch": 46.0,
    "warmup_backend_s": 0.025,
}


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_metrics_by_hand(man, name):
    assert man.reader(name)(RUN) == pytest.approx(BY_HAND[name])


def test_the_three_time_counters_account_for_the_window():
    assert sum(GROWTH[k] for k in ("step_host_s", "step_device_wait_s",
                                   "step_handoff_s")) == RUN["t_close"] - RUN["t_open"]


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_program_without_the_counters_or_the_ring_reads_nothing(
        man, name, monkeypatch):
    """The parent commit has to stay measurable: its stats() lacks the new
    counters, so each counter metric is left out of its line; its spans are
    read as far as it records them, and a program with no ring reads None."""
    old = ("steps_total", "step_host_s", "step_device_wait_s")
    parent = {"spans": [], "t_open": 0.5, "t_close": 10.5,
              "counters": {"before": {k: BEFORE[k] for k in old},
                           "after": {k: BEFORE[k] + GROWTH[k] for k in old}}}
    assert man.reader(name)(parent) is None
    import ray_tpu.util.tracing as tracing

    monkeypatch.delattr(tracing, "recorded")
    del parent["spans"]
    assert man.reader(name)(parent) is None


@pytest.mark.parametrize("cell", ["gpt2-medium.decode-batch",
                                  "gpt2-medium.chat-unshared"])
def test_traced_rehearsal_prints_every_new_metric_of_the_cell(cell):
    env = dict(os.environ, JAX_ENABLE_COMPILATION_CACHE="0")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         str(2 ** 31 + 35), "--seconds", "6", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["failed"] == 0
    want = {n for n, (cells, _) in NEW.items() if cell in cells}
    assert len(want) == (10 if cell in BATCH else 2)
    assert want <= set(last["metrics"]), sorted(want - set(last["metrics"]))
    got = {n: last["metrics"][n]["value"] for n in want}
    for name, value in got.items():
        if last["metrics"][name]["unit"] == "%":
            assert 0.0 <= value <= 100.0, (name, value)
        else:
            assert value > 0.0, (name, value)
    m = last["metrics"]
    assert (m["warmup_backend_s"]["value"] + m["warmup_lower_s"]["value"]
            <= m["replica_warmup_s"]["value"])
    if cell not in BATCH:
        return
    # Every run's detail line, traced or not, carries the counters; on a
    # closed cell the engine is never empty and the three add up.
    realised = json.loads(lines[-2])["detail"]["realised"]
    opened, closed = realised["engine_at_open"], realised["engine_at_close"]
    assert set(COUNTERS) <= set(opened) and set(COUNTERS) <= set(closed)
    d = {k: closed[k] - opened[k] for k in COUNTERS}
    window = json.loads(lines[-2])["detail"]["window_s"]
    assert (d["step_host_s"] + d["step_device_wait_s"] + d["step_handoff_s"]
            == pytest.approx(window, rel=0.05))
    assert 0 < d["step_host_cpu_s"] <= d["step_host_s"]
    assert 0 < d["prefill_dispatch_cpu_s"] <= d["prefill_dispatch_s"] <= d["step_host_s"]
    assert 0 < d["slot_steps_total"] <= d["slot_steps_offered_total"]
    assert got["slots_active_share.batch"] == pytest.approx(
        100.0 * d["slot_steps_total"] / d["slot_steps_offered_total"])
