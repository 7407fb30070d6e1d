"""The span readers: on a span list small enough to work by hand, and in one
traced ``--rehearse`` run of each serve driver, which has to print every
metric that reads the program's spans and step counters."""

import collections
import json
import os
import subprocess
import sys

import pytest

from benchmark.readers import spans as sp

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MS = 1_000_000

# The ring's record, as the program defines it (attribute access only).
Span = collections.namedtuple(
    "Span", "name start_ns end_ns span_id parent_id trace_id attrs")


def _step(sid, start, marks, end, **attrs):
    """An ``llm.step`` and its phases from boundary times in ms."""
    names = ("retire", "admit", "operands", "dispatch", "device_wait",
             "deliver", "observe")
    edges = [start] + list(marks) + [end]
    out = [Span("llm.step", start * MS, end * MS, sid, None, "eng", attrs)]
    for name, a, b in zip(names, edges, edges[1:]):
        out.append(Span(f"llm.step.{name}", int(a * MS), int(b * MS),
                        f"{sid}.{name}", sid, "eng", None))
    return out


def _request(tid, submit, admit, prefilled, first, item, done):
    return [
        Span("llm.admission_wait", submit * MS, admit * MS, tid + "a", tid, tid, None),
        Span("kv.alloc", admit * MS, (admit + 1) * MS, tid + "k", tid + "p", tid, None),
        Span("llm.prefill", admit * MS, prefilled * MS, tid + "p", tid, tid, None),
        Span("llm.first_chunk", prefilled * MS, first * MS, tid + "c", tid, tid, None),
        Span("serve.first_item", item * MS, item * MS, tid + "i", tid, tid, None),
        Span("serve.request", (submit - 2) * MS, done * MS, tid, None, tid, None),
    ]


# A window of [1 s, 2 s). Three requests; r3 is submitted after it closes.
#   r1: handle 1098, submit 1100, admitted 1140, prefilled 1200, first tokens
#       1300, first item 1304   -> queue 40, prefill 60, chunk 100, return 4
#   r2: handle 1498, submit 1500, admitted 1520, prefilled 1600, first tokens
#       1800, first item 1810   -> queue 20, prefill 80, chunk 200, return 10
# Steps (ms): each is start, then the starts of admit, operands, dispatch,
# device_wait, deliver, observe, then the end.
#   s1 1000 | 1001 1002 1003 1010 1110 1112 | 1113
#   s2 1115 | 1116 1136 1138 1148 1248 1250 | 1251   (admits r1's neighbour)
#   s3 1251 | 1252 1253 1254 1260 1360 1362 | 1363   then the engine runs dry
#   s4 1900 | 1901 1902 1903 1905 1990 1992 | 1993
# Gaps: s1->s2: from 1110 to 1148 = 38 (deliver 5, admit 21, the rest 12);
#       s2->s3: from 1248 to 1260 = 12 (deliver 3, admit 2, the rest 7);
#       s3->s4 is left out: s3 left nothing in flight.
# Counters over the window: 50 steps dispatched a decode, 42 of them stopped
# admitting on the budget; 0.1 s of host time in steps against 0.7 s of
# device_get; the pool blocked a free slot for 0.25 s.
SPANS = (
    [Span("llm.warmup", 100 * MS, 160 * MS, "w", None, "eng", {"programs": 2}),
     Span("llm.warmup.program", 100 * MS, 130 * MS, "w1", "w", "eng",
          {"program": "paged_prefill", "trace_s": 0.010, "lower_s": 0.012, "backend_s": 0.005}),
     Span("llm.warmup.program", 130 * MS, 160 * MS, "w2", "w", "eng",
          {"program": "paged_decode", "trace_s": 0.004, "lower_s": 0.003, "backend_s": 0.020})]
    + _step("s1", 1000, (1001, 1002, 1003, 1010, 1110, 1112), 1113, inflight_after=3)
    + _step("s2", 1115, (1116, 1136, 1138, 1148, 1248, 1250), 1251, inflight_after=3)
    + _step("s3", 1251, (1252, 1253, 1254, 1260, 1360, 1362), 1363, inflight_after=0)
    + _step("s4", 1900, (1901, 1902, 1903, 1905, 1990, 1992), 1993, inflight_after=1)
    + _request("r1", 1100, 1140, 1200, 1300, 1304, 1900)
    + _request("r2", 1500, 1520, 1600, 1800, 1810, 1990)
    + _request("r3", 2100, 2110, 2150, 2200, 2201, 2300))
RUN = {"spans": SPANS, "t_open": 1.0, "t_close": 2.0,
       "counters": {"before": {"admit_blocked_pool_s": 2.0, "steps_total": 10.0,
                               "admit_stopped_budget_total": 4.0,
                               "step_host_s": 1.0, "step_device_wait_s": 5.0},
                    "after": {"admit_blocked_pool_s": 2.25, "steps_total": 60.0,
                              "admit_stopped_budget_total": 46.0,
                              "step_host_s": 1.1, "step_device_wait_s": 5.7}}}


@pytest.mark.parametrize("reader,spec,want", [
    ("duration", {"span": "llm.admission_wait", "stat": "p50", "unit": "ms"}, 30.0),
    ("duration", {"span": "llm.admission_wait", "stat": "p90", "unit": "ms"}, 38.0),
    ("duration", {"span": "llm.admission_wait", "stat": "mean", "unit": "ms"}, 30.0),
    ("duration", {"span": "llm.prefill", "stat": "median", "unit": "ms"}, 70.0),
    ("duration", {"span": "kv.alloc", "stat": "median", "unit": "ms"}, 1.0),
    ("duration", {"span": "llm.first_chunk", "stat": "median", "unit": "ms"}, 150.0),
    ("duration", {"span": "llm.warmup", "stat": "sum", "unit": "s", "window": False}, 0.06),
    ("duration", {"span": "llm.warmup", "stat": "sum", "unit": "s"}, None),
    ("duration", {"span": "no.such.span", "stat": "median", "unit": "ms"}, None),
    ("between", {"from": ["serve.request", "start"], "to": ["llm.admission_wait", "start"],
                 "stat": "median", "unit": "ms"}, 2.0),
    ("between", {"from": ["llm.first_chunk", "end"], "to": ["serve.first_item", "start"],
                 "stat": "median", "unit": "ms"}, 7.0),
    ("attr_sum", {"span": "llm.warmup.program", "attrs": ["trace_s", "lower_s"],
                  "unit": "s", "window": False}, 0.029),
    ("step_gap", {"part": "all", "stat": "mean", "unit": "ms"}, 25.0),
    ("step_gap", {"part": "all", "stat": "median", "unit": "ms"}, 25.0),
    ("step_gap", {"part": "all", "stat": "p90", "unit": "ms"}, 35.4),
    ("step_gap", {"part": "deliver", "stat": "mean", "unit": "ms"}, 4.0),
    ("step_gap", {"part": "admit", "stat": "mean", "unit": "ms"}, 11.5),
    ("counter_share", {"counter": "admit_blocked_pool_s"}, 25.0),
    ("counter_share", {"counter": "not_counted_by_this_program"}, None),
    ("counter_ratio", {"counter": "admit_stopped_budget_total",
                       "over": ["steps_total"]}, 84.0),
    ("counter_ratio", {"counter": "step_host_s",
                       "over": ["step_host_s", "step_device_wait_s"]}, 12.5),
    ("counter_ratio", {"counter": "step_host_s", "over": ["not_counted"]}, None),
    ("counter_ratio", {"counter": "steps_total", "over": ["not_counted"]}, None),
])
def test_readers_on_a_hand_built_span_list(reader, spec, want):
    got = getattr(sp, reader)(RUN, spec)
    assert got == (want if want is None else pytest.approx(want))


def test_a_program_without_the_ring_gives_nothing_to_read(monkeypatch):
    """The parent commit records no span: every reader returns None."""
    import ray_tpu.util.tracing as tracing

    monkeypatch.delattr(tracing, "recorded")
    run = {"t_open": 1.0, "t_close": 2.0, "trace": None,
           "counters": {"before": {}, "after": {}}}
    assert sp.duration(run, {"span": "llm.prefill", "stat": "median", "unit": "ms"}) is None
    assert sp.between(run, {"from": ["a", "start"], "to": ["b", "end"],
                            "stat": "median", "unit": "ms"}) is None
    assert sp.attr_sum(run, {"span": "llm.warmup.program", "attrs": ["trace_s"],
                             "unit": "s"}) is None
    assert sp.step_gap(run, {"part": "all", "stat": "median", "unit": "ms"}) is None
    assert sp.counter_share(run, {"counter": "admit_blocked_pool_s"}) is None
    assert sp.counter_ratio(run, {"counter": "step_host_s",
                                  "over": ["step_host_s"]}) is None


NEW = {"handle_to_engine_ms", "engine_queue_p50_ms", "engine_queue_p90_ms",
       "prefill_host_ms", "kv_alloc_ms", "first_chunk_ms", "return_path_ms",
       "step_gap_ms.chat", "gap_deliver_ms.chat", "gap_admit_ms.chat",
       "step_gap_ms.batch", "gap_deliver_ms.batch", "gap_admit_ms.batch",
       "pool_blocked_share", "admit_budget_stop_share", "step_host_share",
       "replica_warmup_s", "warmup_lower_s"}


@pytest.mark.parametrize("cell", ["gpt2-medium.decode-batch",
                                  "gpt2-medium.prefix-chat"])
def test_traced_rehearsal_prints_every_span_and_counter_metric(cell):
    env = dict(os.environ, JAX_ENABLE_COMPILATION_CACHE="0")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         str(2 ** 31 + 29), "--seconds", "6", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        want = {m["name"] for m in json.load(f)["per_layer"]
                if m["name"] in NEW and cell in m["workloads"]}
    assert len(want) >= 8 and want <= set(last["metrics"]), \
        sorted(want - set(last["metrics"]))
    for name in want:
        value = last["metrics"][name]["value"]
        assert isinstance(value, float) and value >= 0.0, (name, value)
