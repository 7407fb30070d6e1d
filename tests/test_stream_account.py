"""A token's way back to its client has an account of its own (ISSUE 51):
stamps at the four hand-overs between ``LLMEngine._deliver`` and the handle's
iterator, summed into counters (the engine's ``stats()``, the replica's
``get_metrics``) and into attrs of three per-request spans (``llm.request``,
``serve.replica_stream``, ``serve.request``). Never a span per token."""

import threading
import time
from types import SimpleNamespace

import jax
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.core.config import Config, set_config
from ray_tpu.core.runtime import get_runtime
from ray_tpu.models import transformer
from ray_tpu.serve.controller import get_or_create_controller
from ray_tpu.serve.llm import llm_deployment
from ray_tpu.serve.replica import ReplicaActor
from ray_tpu.util import tracing

STREAM_COUNTERS = ("stream_pickups_total", "stream_pickup_lag_s",
                   "stream_items_total", "stream_source_wait_s",
                   "stream_publish_s", "stream_producer_cpu_s")


@serve.deployment(name="Slow", max_concurrency=4)
class Slow:
    """A producer that is late by itself: ``gap_s`` before every item."""

    def __call__(self, payload):
        for i in range(int(payload["n"])):
            time.sleep(float(payload["gap_s"]))
            yield i


@pytest.fixture(scope="module")
def served():
    ray_tpu.init(resources={"CPU": 4, "TPU": 8})
    cfg = transformer.tiny(max_seq_len=64)
    LM = llm_deployment(
        cfg, lambda: transformer.init_params(cfg, jax.random.key(0)),
        name="LM", slots=2, chunk=4)
    lm = serve.run(LM.bind(), name="lm")
    slow = serve.run(Slow.bind(), name="slow", route_prefix="/slow")
    _, table = ray_tpu.get(get_or_create_controller().get_snapshot.remote())
    replicas = {k: v["replicas"][0] for k, v in table.items()}
    yield SimpleNamespace(lm=lm, slow=slow, replicas=replicas)
    set_config(Config())
    serve.shutdown()
    ray_tpu.shutdown()


def _metrics(served, name):
    return ray_tpu.get(served.replicas[name].get_metrics.remote())


def _stream(handle, payload, pause_s=0.0):
    """One streamed request under a span of its own: its items and, by
    name, the spans of its trace."""
    t0 = tracing.now_ns()
    with tracing.span("client") as (trace_id, _sid):
        items = []
        for item in handle.options(stream=True).remote(payload):
            items.append(item)
            if pause_s:
                time.sleep(pause_s)
    spans = {}
    for s in tracing.recorded(t0):
        if s.trace_id == trace_id:
            spans.setdefault(s.name, []).append(s)
    return items, spans


def test_items_agree_on_the_three_spans_and_every_lag_fits_its_request(served):
    before = _metrics(served, "LM")
    items, spans = _stream(served.lm, {"prompt_ids": [7, 3, 11],
                                       "max_new_tokens": 12})
    after = _metrics(served, "LM")
    assert len(items) == 12
    [llm], [stream], [outer] = (spans["llm.request"],
                                spans["serve.replica_stream"],
                                spans["serve.request"])
    assert llm.attrs["tokens"] == stream.attrs["items"] \
        == outer.attrs["items"] == 12
    # One span a request at each hand-over, none a token.
    assert {n: len(v) for n, v in spans.items() if n.startswith("serve.")} \
        == {"serve.request": 1, "serve.router_pick": 1,
            "serve.replica_queue": 1, "serve.replica_stream": 1,
            "serve.first_item": 1}
    # Hop 1: three chunks of four tokens, each taken once, after its stamp.
    a = llm.attrs
    assert 1 <= a["pickups"] <= 3
    assert 0 <= a["pickup_lag_max_ns"] <= a["pickup_lag_ns"] \
        <= llm.end_ns - llm.start_ns + (outer.end_ns - llm.end_ns)
    # Hop 2: next() and yield tile the stream's span; its CPU is its own.
    s = stream.attrs
    assert 0 <= s["publish_max_ns"] <= s["publish_ns"]
    assert 0 < s["source_wait_ns"]
    assert s["source_wait_ns"] + s["publish_ns"] <= \
        stream.end_ns - stream.start_ns
    assert 0 <= s["drove_cpu_ns"] <= s["drove_ns"] <= s["source_wait_ns"]
    assert 0 <= s["cpu_ns"] <= stream.end_ns - stream.start_ns
    assert outer.start_ns <= stream.start_ns <= stream.end_ns <= outer.end_ns
    assert stream.parent_id == spans["serve.replica_queue"][0].parent_id
    # Hops 3 and 4: no instant of the request is counted twice.
    o = outer.attrs
    assert 0 <= o["get_ns"] <= o["take_lag_ns"]
    assert 0 <= o["take_lag_max_ns"] <= o["take_lag_ns"]
    assert o["client_hold_ns"] >= 0 and o["end_wait_ns"] >= 0
    assert o["take_lag_ns"] + o["client_hold_ns"] + o["end_wait_ns"] <= \
        outer.end_ns - outer.start_ns
    assert 0 < o["cpu_ns"] <= outer.end_ns - outer.start_ns
    # The counters tell the same story, from where the work happened.
    d = {k: after[k] - before[k] for k in STREAM_COUNTERS}
    assert d["stream_items_total"] == 12
    assert d["stream_pickups_total"] == a["pickups"]
    assert d["stream_pickup_lag_s"] == pytest.approx(a["pickup_lag_ns"] / 1e9)
    assert d["stream_source_wait_s"] == pytest.approx(
        s["source_wait_ns"] / 1e9)
    assert d["stream_publish_s"] == pytest.approx(s["publish_ns"] / 1e9)
    assert d["stream_producer_cpu_s"] == pytest.approx(s["cpu_ns"] / 1e9)


def test_a_caller_that_sleeps_between_items_shows_in_client_hold_alone(served):
    n, pause = 8, 0.02
    _, spans = _stream(served.lm, {"prompt_ids": [7, 3, 12],
                                   "max_new_tokens": n}, pause_s=pause)
    o = spans["serve.request"][0].attrs
    assert o["items"] == n
    # The in-process producer runs ahead of its consumer: every item but the
    # first lies published for most of the caller's pause.
    assert o["client_hold_ns"] >= 0.8 * (n - 1) * pause * 1e9
    assert o["take_lag_ns"] < 0.25 * o["client_hold_ns"]
    assert o["take_lag_ns"] + o["client_hold_ns"] + o["end_wait_ns"] <= \
        spans["serve.request"][0].end_ns - spans["serve.request"][0].start_ns


def test_a_producer_that_sleeps_between_items_shows_in_source_wait_alone(
        served):
    n, gap = 6, 0.02
    items, spans = _stream(served.slow, {"n": n, "gap_s": gap})
    assert items == list(range(n))
    s = spans["serve.replica_stream"][0].attrs
    assert s["items"] == n and s["drove_ns"] == s["drove_cpu_ns"] == 0
    assert s["source_wait_ns"] >= n * gap * 1e9
    assert s["publish_ns"] < 0.25 * s["source_wait_ns"]
    # A sleeping thread burns no CPU: the stream's is far under its wall.
    assert s["cpu_ns"] < 0.5 * s["source_wait_ns"]
    o = spans["serve.request"][0].attrs
    # The caller asked at once each time: it held nothing back.
    assert o["client_hold_ns"] < 0.25 * n * gap * 1e9


def test_the_counters_move_with_tracing_off_and_no_span_is_recorded(served):
    set_config(Config({"trace_enabled": False}))
    try:
        before = _metrics(served, "LM")
        t0 = tracing.now_ns()
        items = list(served.lm.options(stream=True).remote(
            {"prompt_ids": [7, 3, 13], "max_new_tokens": 8}))
        after = _metrics(served, "LM")
    finally:
        set_config(Config())
    assert len(items) == 8
    assert not [s.name for s in tracing.recorded(t0)
                if s.name.startswith(("serve.", "llm."))]
    d = {k: after[k] - before[k] for k in STREAM_COUNTERS}
    assert d["stream_items_total"] == 8
    assert 1 <= d["stream_pickups_total"] <= 2
    assert d["stream_pickup_lag_s"] >= 0 and d["stream_publish_s"] > 0
    assert d["stream_source_wait_s"] > 0 and d["stream_producer_cpu_s"] > 0


def test_a_running_stream_is_counted_before_it_ends(served):
    """``get_metrics`` adds the accounts of the streams that still run: a
    window's edge cuts a request of thousands of items where it is."""
    before = _metrics(served, "Slow")
    gen = iter(served.slow.options(stream=True).remote(
        {"n": 40, "gap_s": 0.01}))
    for _ in range(5):
        next(gen)
    mid = _metrics(served, "Slow")
    rest = list(gen)
    after = _metrics(served, "Slow")
    assert 5 <= mid["stream_items_total"] - before["stream_items_total"] < 40
    assert mid["stream_producer_cpu_s"] == before["stream_producer_cpu_s"]
    assert len(rest) == 35
    assert after["stream_items_total"] - before["stream_items_total"] == 40
    assert after["stream_producer_cpu_s"] > before["stream_producer_cpu_s"]


def test_an_abandoned_generator_closes_the_span_with_the_items_it_had():
    replica = ReplicaActor("Counting", lambda n: (i for i in range(n)),
                           (), {})
    tracing.set_context(tracing.new_root_context())
    try:
        t0 = tracing.now_ns()
        gen = replica.handle_request_streaming(
            "__call__", 10, _trace_submit_ts=tracing.wall_of(t0))
        assert [next(gen) for _ in range(3)] == [0, 1, 2]
        live = replica.get_metrics()
        gen.close()
        ended = replica.get_metrics()
    finally:
        tracing.set_context(None)
    [span] = [s for s in tracing.recorded(t0)
              if s.name == "serve.replica_stream"]
    assert span.attrs["items"] == 3
    assert live["stream_items_total"] == ended["stream_items_total"] == 3
    assert live["ongoing"] == 1 and ended["ongoing"] == 0
    assert not replica._streams_live


def test_a_plain_return_streams_as_one_item():
    replica = ReplicaActor("Plain", lambda x: x + 1, (), {})
    assert list(replica.handle_request_streaming("__call__", 1)) == [2]
    assert replica.get_metrics()["stream_items_total"] == 1


def test_the_published_stamp_is_kept_beside_the_item_and_goes_with_it(served):
    """In process the stamps live in the task's record beside the ids; at an
    owner (``CoreWorker``) beside ``state.items``, on the owner's clock, and
    ``release_generator`` drops both."""
    t0 = tracing.now_ns()
    gen = served.slow.options(stream=True).remote({"n": 3, "gap_s": 0.0})
    assert list(gen) == [0, 1, 2]
    inner = gen._gen
    state = get_runtime().tasks[inner._task_id]
    stamps = state.generator_published_ns
    assert len(stamps) == len(state.generator_items) == 3
    assert t0 <= stamps[0] <= stamps[1] <= stamps[2] <= tracing.now_ns()
    assert inner.last_published_ns == stamps[2]
    assert get_runtime().generator_item_published_ns(
        inner._task_id, 3) is None

    from ray_tpu.core.core_worker import CoreWorker, _OwnerService
    from ray_tpu.core.ids import ObjectID, TaskID

    dropped = []
    core = SimpleNamespace(
        _cache_lock=threading.Lock(), _generators={}, _cache={},
        _inline_owned={}, reference_counter=SimpleNamespace(
            set_owned=lambda oid: None,
            drop_owned_if_unreferenced=dropped.append))
    core._generator_state = lambda tid: CoreWorker._generator_state(core, tid)
    tid = inner._task_id
    assert isinstance(tid, TaskID)
    oid = ObjectID.for_task_return(tid, 0)
    t1 = tracing.now_ns()
    _OwnerService(core).report_generator_item(tid.binary(), 0, oid.binary())
    stamp = CoreWorker.generator_item_published_ns(core, tid, 0)
    assert t1 <= stamp <= tracing.now_ns()
    assert CoreWorker.generator_item_published_ns(core, tid, 1) is None
    CoreWorker.release_generator(core, tid)
    assert dropped == [oid]
    assert core._generators[tid].published_ns == {} == core._generators[tid].items
    assert CoreWorker.generator_item_published_ns(core, tid, 0) is None
