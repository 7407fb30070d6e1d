"""ISSUE 51's metrics: a token's way back to its client, read from the stream
account the program keeps at its four hand-overs (counters beside the
engine's, attrs on ``llm.request``, ``serve.replica_stream`` and
``serve.request``). Each is a file under ``metrics/`` on the one new reader
file, ``readers/stream.py`` (or on ``spans.py:counter_share``, as it was):
each resolves through the manifest, is worked by hand on a small ring and
counter pair, and reads nothing on a program that lacks the counters or the
attrs."""

import collections
import json
import os

import pytest

from benchmark.manifest import Manifest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MS = 1_000_000
US = 1_000

CLOSED = ["gpt2-medium.decode-batch", "longcat-flash-omni.moe-decode",
          "olmo-hybrid-7b.hybrid-decode", "kimi-k2.5.agent-decode",
          "falcon-h1-34b.ssm-decode", "trinity-large-preview.window-decode",
          "nemotron-3-nano-30b-a3b.reason-decode", "mimo-v2-flash.swa-decode"]
CHAT = ["gpt2-medium.prefix-chat", "gpt2-medium.chat-unshared"]
# stem -> (reader, worked by hand on RUN below, in the metric's unit)
STEMS = {
    "stream_pickup_lag_ms": ("stream.py:counter_mean", 2.5),
    "stream_publish_us_per_item": ("stream.py:counter_mean", 50.0),
    "stream_producer_cpu_share": ("spans.py:counter_share", 6.0),
    "stream_take_lag_ms": ("stream.py:attr_ratio", 0.25),
    "stream_client_hold_ms": ("stream.py:attr_ratio", 1.5),
    "stream_consumer_cpu_share": ("stream.py:attr_share", 0.8),
    "stream_cpu_us_per_item": ("stream.py:attr_ratio", 1125.0),
}
NEW = {stem + suffix: (cells, moves, reader, value)
       for stem, (reader, value) in STEMS.items()
       for suffix, cells, moves in ((".batch", CLOSED, "serve_out_tok_s"),
                                    (".chat", CHAT, "tpot_p90_ms"))}
COUNTERS = ("stream_pickups_total", "stream_pickup_lag_s",
            "stream_items_total", "stream_source_wait_s", "stream_publish_s",
            "stream_producer_cpu_s")

Span = collections.namedtuple(
    "Span", "name start_ns end_ns span_id parent_id trace_id attrs")


@pytest.fixture(scope="module")
def man():
    return Manifest(ROOT)


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_stream_metric_resolves_through_the_manifest(man, name):
    cells, moves, reader, _ = NEW[name]
    entry = man.per_layer[name]
    assert set(cells) <= set(entry["workloads"])
    assert entry["moves"] == moves and entry["better"] == "lower"
    with open(man.metric_file(name)) as f:
        spec = json.load(f)
    assert spec["reader"] == "benchmark/readers/" + reader
    assert callable(man.reader(name))
    for cell in cells:
        assert entry in man.metrics_of(cell, "per_layer")


# A window of 10 s. Over it the consumers took a non-empty req.tokens 400
# times, 1.0 s after their delivery in all (2.5 ms a take); the replica
# yielded 3,200 items and was suspended in yield for 0.16 s (50 us an item);
# the streams that ended burnt 0.6 s of CPU (6% of the window).
#   r1 starts in the window: 100 items; take lag 20 ms, client hold 200 ms,
#      the caller's thread 50 ms of CPU, the runner thread 60 ms
#   r2 starts in the window: 60 items; take lag 20 ms, client hold 40 ms,
#      30 ms and 40 ms of CPU; its runner also drove steps (not its CPU)
#   r0 started before the window: read by no span metric
# take lag 40 ms / 160 items = 0.25 ms; hold 240 / 160 = 1.5 ms; the callers'
# CPU 80 ms of 10 s = 0.8%; both sides' CPU 180 ms / 160 = 1,125 us an item.
BEFORE = {"stream_pickups_total": 100.0, "stream_pickup_lag_s": 0.5,
          "stream_items_total": 800.0, "stream_source_wait_s": 3.0,
          "stream_publish_s": 0.04, "stream_producer_cpu_s": 0.2}
GROWTH = {"stream_pickups_total": 400.0, "stream_pickup_lag_s": 1.0,
          "stream_items_total": 3200.0, "stream_source_wait_s": 30.0,
          "stream_publish_s": 0.16, "stream_producer_cpu_s": 0.6}


def _request(tid, start, end, items, take, hold, cpu, producer_cpu, drove=0):
    return [
        Span("serve.request", start * MS, end * MS, tid, None, tid,
             {"replica": "r", "items": items, "take_lag_ns": take * MS,
              "take_lag_max_ns": take * MS // 2, "client_hold_ns": hold * MS,
              "get_ns": 1 * MS, "end_wait_ns": 0, "cpu_ns": cpu * MS}),
        Span("serve.replica_stream", (start + 5) * MS, (end - 1) * MS,
             tid + "s", tid, tid,
             {"items": items, "source_wait_ns": 900 * MS,
              "publish_ns": 5 * MS, "publish_max_ns": 1 * MS,
              "cpu_ns": producer_cpu * MS, "drove_ns": drove * MS,
              "drove_cpu_ns": drove * MS // 4})]


SPANS = (_request("r0", 100, 1500, 500, 900, 900, 900, 900)
         + _request("r1", 1000, 4000, 100, 20, 200, 50, 60)
         + _request("r2", 2000, 5000, 60, 20, 40, 30, 40, drove=800))
RUN = {"spans": SPANS, "t_open": 0.5, "t_close": 10.5,
       "counters": {"before": BEFORE,
                    "after": {k: BEFORE[k] + GROWTH[k] for k in BEFORE}}}


@pytest.mark.parametrize("name", sorted(NEW))
def test_stream_metrics_by_hand(man, name):
    assert man.reader(name)(RUN) == pytest.approx(NEW[name][3])


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_program_without_the_stream_account_reads_nothing(
        man, name, monkeypatch):
    """The parent commit has to stay measurable: its stats() lacks the six
    counters and its spans the attrs (``serve.request`` holds ``replica``
    alone, there is no ``serve.replica_stream``), so every metric is left
    out of its line; a program with no ring reads None too."""
    old = ("steps_total", "step_host_s")
    spans = [Span("serve.request", 1000 * MS, 4000 * MS, "r1", None, "r1",
                  {"replica": "r"}),
             Span("llm.request", 1030 * MS, 3990 * MS, "r1r", "r1", "r1",
                  {"tokens": 100, "slot": 0})]
    parent = {"spans": spans, "t_open": 0.5, "t_close": 10.5,
              "counters": {"before": dict.fromkeys(old, 1.0),
                           "after": dict.fromkeys(old, 2.0)}}
    assert man.reader(name)(parent) is None
    import ray_tpu.util.tracing as tracing

    monkeypatch.delattr(tracing, "recorded")
    del parent["spans"]
    assert man.reader(name)(parent) is None


def test_a_span_that_lacks_an_attr_is_left_out_not_read_as_zero(man):
    """A request whose items carried no published stamp (a runtime that kept
    none) leaves ``take_lag_ns`` and ``client_hold_ns`` out: it counts in
    neither sum, and where every span lacks it the metric reads nothing."""
    bare = [s._replace(attrs={k: v for k, v in s.attrs.items()
                              if k not in ("take_lag_ns", "client_hold_ns")})
            for s in SPANS]
    run = dict(RUN, spans=bare)
    assert man.reader("stream_take_lag_ms.batch")(run) is None
    assert man.reader("stream_cpu_us_per_item.batch")(run) == \
        pytest.approx(1125.0)
