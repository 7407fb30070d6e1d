"""End-to-end request tracing tests (ISSUE 10).

A sampled serve LLM request must yield ONE connected trace — handle root,
router pick, replica queue wait, engine admission/prefill/first-chunk spans —
retrievable by trace id through ``gcs.trace``, ``ray_tpu.timeline`` and the
CLI tree, with the TTFT span decomposition matching the engine's measured
TTFT. Head-based sampling is decided once at the root and inherited;
export is batched (spans ≫ RPCs); compiled-DAG ticks trace only under an
already-sampled caller.

ISSUE 24: every span is an interval on one clock (``perf_counter_ns``),
lands first in a bounded in-memory ring (``tracing.recorded``), and leaves
for the GCS from an exporter thread, never from the engine's step thread;
the engine step is a span tree of its own (``llm.step`` + phases) and the
counts at its boundaries live in ``stats()``.
"""

import collections
import threading
import time

import jax
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.core.config import Config, set_config
from ray_tpu.core.runtime import get_runtime
from ray_tpu.dag import InputNode
from ray_tpu.models import transformer
from ray_tpu.serve.llm import LLMEngine, llm_deployment
from ray_tpu.util import tracing


def _span_events(events, name=None):
    spans = [e for e in events if e.get("kind") == "span"]
    if name is not None:
        spans = [e for e in spans if e.get("name") == name]
    return spans


def _ids(events):
    return {e.get("span_id") or e["task_id"] for e in events}


@pytest.fixture
def fresh_config():
    """Restore the default config after a test that overrides flags."""
    yield set_config
    set_config(Config())


class TestServeTraceE2E:
    def test_streamed_llm_request_yields_connected_trace(self,
                                                         ray_start_regular):
        """One streamed request through handle → router → replica → engine
        produces a single connected tree, retrievable by trace id, whose
        TTFT spans decompose the engine-measured TTFT."""
        cfg = transformer.tiny(max_seq_len=64)
        LM = llm_deployment(
            cfg, lambda: transformer.init_params(cfg, jax.random.key(0)),
            name="LM", slots=2, chunk=4)
        try:
            handle = serve.run(LM.bind())
            with tracing.span("client") as (trace_id, _client_span):
                gen = handle.options(stream=True).remote(
                    {"prompt_ids": [7, 3, 11], "max_new_tokens": 8})
                assert gen.trace_id == trace_id
                items = list(gen)
            assert items and items[-1]["finish_reason"] == "stop"
            ttft = items[-1]["ttft_s"]
            tracing.flush()

            events = get_runtime().gcs.trace(trace_id)
            names = {e["name"] for e in events}
            for expected in ("client", "serve.request", "serve.router_pick",
                            "serve.replica_queue", "llm.admission_wait",
                            "llm.prefill", "kv.alloc", "llm.first_chunk",
                            "serve.first_item", "llm.request"):
                assert expected in names, f"missing span {expected}: {names}"
            # One span a step, not one a request a step.
            assert "llm.decode_chunk" not in names

            # Connected: every event's parent resolves inside the trace
            # (only the client root has no parent).
            ids = _ids(events)
            orphans = [e["name"] for e in events
                       if e.get("parent_span_id")
                       and e["parent_span_id"] not in ids]
            assert not orphans, f"disconnected spans: {orphans}"
            roots = [e for e in events if not e.get("parent_span_id")]
            assert [e["name"] for e in roots] == ["client"]

            # The router's pick recorded the occupancy snapshot it acted on.
            pick = _span_events(events, "serve.router_pick")[0]
            assert pick["attrs"]["deployment"] == "LM"
            assert "replica" in pick["attrs"]

            # TTFT decomposition: queue-side waits + prefill + first decode
            # chunk account for the engine's measured TTFT.
            first = lambda n: min(  # noqa: E731
                _span_events(events, n), key=lambda e: e["time"])
            parts = (first("llm.admission_wait")["duration"]
                     + first("llm.prefill")["duration"]
                     + first("llm.first_chunk")["duration"])
            assert ttft > 0
            assert abs(parts - ttft) <= 0.10 * ttft + 0.015, \
                f"TTFT decomposition {parts:.4f}s vs measured {ttft:.4f}s"

            # The same spans, read back in-process on the span clock: real
            # intervals, in order, none shifted by when it was emitted.
            ring = {s.name: s for s in tracing.recorded()
                    if s.trace_id == trace_id}
            wait, prefill, chunk = (ring["llm.admission_wait"],
                                    ring["llm.prefill"],
                                    ring["llm.first_chunk"])
            assert ring["serve.request"].start_ns <= wait.start_ns
            assert wait.end_ns <= prefill.start_ns
            assert prefill.end_ns <= chunk.start_ns <= chunk.end_ns
            assert (chunk.end_ns - wait.start_ns) / 1e9 == \
                pytest.approx(ttft, abs=1e-6)
            alloc = ring["kv.alloc"]
            assert alloc.parent_id == prefill.span_id
            assert prefill.start_ns <= alloc.start_ns <= alloc.end_ns \
                <= prefill.end_ns
            assert chunk.end_ns <= ring["serve.first_item"].start_ns \
                <= ring["serve.request"].end_ns
            req = ring["llm.request"]
            assert req.start_ns == wait.start_ns
            assert req.attrs["tokens"] == 8
            assert req.attrs["finish_reason"] == "stop"
        finally:
            serve.shutdown()
        # The ring outlives the deployment: the process that held the chip
        # reads its spans back after the server is gone.
        assert {"llm.request", "llm.step", "llm.warmup"} <= {
            s.name for s in tracing.recorded()}

    def test_trace_reaches_timeline_and_cli_tree(self, ray_start_regular):
        """The same trace is retrievable through the timeline view (with
        flow events) and renders as the CLI span tree."""
        with tracing.span("request") as (trace_id, _sid):
            with tracing.span("inner"):
                pass
        tracing.flush()

        view = ray_tpu.timeline(trace_id=trace_id)
        assert {e["name"] for e in view if e["ph"] == "X"} == \
            {"request", "inner"}
        # Flow events pair up: one "s" (at the parent) and one "f" (at the
        # child) per resolved parent link.
        assert [e["ph"] for e in view if e["cat"] == "trace"] == ["s", "f"]

        from ray_tpu.scripts import format_trace_tree

        tree = format_trace_tree(get_runtime().gcs.trace(trace_id))
        assert "request" in tree
        assert "    inner" in tree  # nested under the root

    def test_timeline_feed_is_incremental(self, ray_start_regular):
        """Repeated timeline() polls reuse the per-caller cursor cache —
        entries accumulate, they are not rebuilt from a full-log copy."""
        with tracing.span("a"):
            pass
        tracing.flush()
        first = ray_tpu.timeline(client="t-incr")
        with tracing.span("b"):
            pass
        tracing.flush()
        second = ray_tpu.timeline(client="t-incr")
        assert len(second) == len(first) + 1
        assert second[-1]["name"] == "b"


class TestSampling:
    def test_rate_zero_propagates_but_emits_nothing(self, ray_start_regular,
                                                    fresh_config):
        set_config(Config({"trace_sample_rate": 0.0}))
        with tracing.span("root") as (trace_id, _sid):
            assert not tracing.is_sampled()
            with tracing.span("child"):
                # The child inherits the root's NEGATIVE decision — same
                # trace id, no fresh root, nothing emitted.
                assert tracing.current_context()[0] == trace_id
                assert not tracing.is_sampled()
        tracing.flush()
        assert _span_events(get_runtime().gcs.trace(trace_id)) == []

    def test_rate_one_emits_connected_spans(self, ray_start_regular):
        with tracing.span("root") as (trace_id, root_sid):
            assert tracing.is_sampled()
            with tracing.span("child"):
                pass
        tracing.flush()
        events = get_runtime().gcs.trace(trace_id)
        child = _span_events(events, "child")[0]
        assert child["parent_span_id"] == root_sid

    def test_unsampled_root_suppresses_actor_task_events(
            self, ray_start_regular, fresh_config):
        """Actor tasks submitted under an unsampled root emit no
        trace-linked task events (the untraced hot path)."""

        @ray_tpu.remote
        class A:
            def f(self):
                return 1

        a = A.remote()
        set_config(Config({"trace_sample_rate": 0.0}))
        with tracing.span("root") as (trace_id, _sid):
            assert ray_tpu.get(a.f.remote()) == 1
        tracing.flush()
        assert get_runtime().gcs.trace(trace_id) == []

    def test_gate_off_costs_no_context(self, ray_start_regular, fresh_config):
        set_config(Config({"trace_enabled": False}))
        assert tracing.new_root_context() is None
        with tracing.span("root") as (trace_id, _sid):
            assert not tracing.is_sampled()
        tracing.flush()
        assert get_runtime().gcs.trace(trace_id) == []


class TestDagTracing:
    def test_tick_spans_under_sampled_caller(self, ray_start_regular):
        @ray_tpu.remote
        class Doubler:
            def apply(self, x):
                return x * 2

        d = Doubler.remote()
        compiled = d.apply.bind(InputNode()).experimental_compile()
        try:
            # Untraced executes (no ambient context) emit nothing — the
            # µs-scale tick path stays span-free.
            assert compiled.execute(3).get(timeout=30) == 6
            tracing.flush()
            base = len(_span_events(
                get_runtime().gcs.task_events(), "dag.tick"))

            with tracing.span("driver") as (trace_id, _sid):
                assert compiled.execute(5).get(timeout=30) == 10
            tracing.flush()

            events = get_runtime().gcs.trace(trace_id)
            ticks = _span_events(events, "dag.tick")
            stages = _span_events(events, "dag.stage:apply")
            assert len(ticks) == 1 and len(stages) == 1
            # Stage spans parent to their tick; the tick to the caller.
            assert stages[0]["parent_span_id"] == ticks[0]["task_id"]
            all_ticks = _span_events(
                get_runtime().gcs.task_events(), "dag.tick")
            assert len(all_ticks) == base + 1
        finally:
            compiled.teardown()


class TestBatchedExport:
    def test_spans_ship_in_batches_not_per_rpc(self, ray_start_regular,
                                               monkeypatch):
        gcs = get_runtime().gcs
        calls = {"batches": 0, "events": 0}
        real = gcs.record_task_events

        def counting(events):
            calls["batches"] += 1
            calls["events"] += len(events)
            return real(events)

        monkeypatch.setattr(gcs, "record_task_events", counting)
        tracing.flush()  # start from an empty buffer
        n = 300
        ctx = tracing.new_root_context()
        assert ctx is not None and ctx[2]
        for _ in range(n):
            t = tracing.now_ns()
            tracing.emit("bulk", ctx, start=t, end=t + 1_000_000)
        tracing.flush()
        assert calls["events"] >= n
        # 300 spans ride a few batched record_task_events calls — far fewer
        # RPCs than spans (the exporter thread may take a batch of its own).
        assert calls["batches"] <= n // 32


class TestRing:
    def test_bounded_and_drops_oldest(self, monkeypatch):
        monkeypatch.setattr(tracing, "_RING", collections.deque(maxlen=8))
        ctx = ("ring-trace", None, True)
        for i in range(20):
            tracing.emit(f"s{i}", ctx, start=i, end=i + 1)
        kept = tracing.recorded()
        assert [s.name for s in kept] == [f"s{i}" for i in range(12, 20)]
        assert [s.name for s in tracing.recorded(since_ns=18)] == \
            ["s18", "s19"]
        tracing.flush()     # no runtime here: the export is dropped, quietly
        assert len(tracing.recorded()) == 8

    def test_wall_time_is_derived_from_the_one_anchor(self):
        t = tracing.now_ns()
        assert tracing.ns_of_wall(tracing.wall_of(t)) == \
            pytest.approx(t, abs=1000)
        event = tracing._event_of(
            tracing.Span("x", t, t + 2_000_000, "a", None, "b", None), "n")
        assert event["duration"] == pytest.approx(0.002)
        assert event["time"] == pytest.approx(tracing.wall_of(t) + 0.002)

    def test_exporter_thread_exits_when_idle(self, ray_start_regular,
                                             monkeypatch):
        monkeypatch.setattr(tracing, "EXPORT_INTERVAL_S", 0.02)
        with tracing.span("wake") as (trace_id, _sid):
            pass
        deadline = tracing.now_ns() + 5_000_000_000
        while tracing.now_ns() < deadline and (
                tracing._exporter_alive
                or not get_runtime().gcs.trace(trace_id)):
            threading.Event().wait(0.01)
        # Shipped with no flush() call, and the thread is gone again.
        assert _span_events(get_runtime().gcs.trace(trace_id), "wake")
        assert not tracing._exporter_alive


    def test_full_pending_queue_counts_what_it_drops(self, monkeypatch):
        monkeypatch.setattr(tracing, "_PENDING", collections.deque(maxlen=4))
        monkeypatch.setattr(tracing, "PENDING_MAX", 4)
        monkeypatch.setattr(tracing, "_exporter_alive", True)  # no thread
        monkeypatch.setattr(tracing, "_dropped", 0)
        shipped = []
        monkeypatch.setattr(tracing, "_ship",
                            lambda batch, runtime: shipped.extend(batch))
        ctx = ("drop-trace", None, True)
        for i in range(10):
            tracing.emit(f"d{i}", ctx, start=i, end=i + 1)
        tracing.emit("ring-only", ctx, start=10, end=11, export=False)
        assert tracing._dropped == 6
        tracing.flush()
        assert [e["name"] for e in shipped] == ["d6", "d7", "d8", "d9"]
        assert tracing._dropped == 0    # said once, with the batch

    def test_forked_child_starts_without_the_parents_exporter(
            self, monkeypatch):
        monkeypatch.setattr(tracing, "_PENDING", collections.deque(maxlen=4))
        monkeypatch.setattr(tracing, "_exporter_alive", True)
        monkeypatch.setattr(tracing, "_EXPORT_LOCK", tracing._EXPORT_LOCK)
        monkeypatch.setattr(tracing, "_START_LOCK", tracing._START_LOCK)
        tracing._PENDING.append(tracing.Span("p", 0, 1, "a", None, "t", None))
        held = tracing._START_LOCK
        with held:                       # forked while another thread held it
            tracing._after_fork_in_child()
        assert not tracing._exporter_alive and not tracing._PENDING
        assert tracing._START_LOCK is not held

    def test_replica_queue_span_never_starts_in_the_future(self):
        """The submit stamp is another process's wall clock."""
        import types

        from ray_tpu.serve.replica import ReplicaActor

        prev = tracing.current_context()
        tracing.set_context(("skew-trace", "root", True))
        try:
            t0 = tracing.now_ns()
            ReplicaActor._trace_queue_wait(
                types.SimpleNamespace(deployment_name="d"),
                {"_trace_submit_ts": tracing.wall_of(t0) + 5.0})
        finally:
            tracing.set_context(prev)
        [s] = [s for s in tracing.recorded(t0) if s.trace_id == "skew-trace"]
        assert s.name == "serve.replica_queue"
        assert s.start_ns == s.end_ns <= tracing.now_ns()


@pytest.fixture(scope="module")
def tiny_model():
    cfg = transformer.tiny(max_seq_len=64)
    return cfg, transformer.init_params(cfg, jax.random.key(0))


def _engine(tiny_model, pool_blocks, name):
    cfg, params = tiny_model
    eng = LLMEngine(params, cfg, prompt_buckets=(16,), chunk=4, slots=2,
                         max_queue=0, name=name, block_tokens=8,
                         pool_blocks=pool_blocks)
    eng.warmup()
    return eng


def _two_requests(eng):
    """Two requests queued before the first step; each pins 3 blocks."""
    reqs = [eng.submit([7, 3, 11 + i], max_new_tokens=16) for i in range(2)]
    return [list(eng.drive(r)) for r in reqs]


class TestEngineStepTrace:
    def test_one_step_span_per_step_with_phases_that_tile_it(
            self, tiny_model):
        eng = _engine(tiny_model, 65, "steps")
        t0 = tracing.now_ns()
        before = eng.stats()
        outs = _two_requests(eng)
        assert [len(o) for o in outs] == [16, 16]
        spans = [s for s in tracing.recorded(t0)
                 if s.trace_id == eng.trace_id]
        steps = [s for s in spans if s.name == "llm.step"]
        stats = eng.stats()
        decoded = [s for s in steps if s.attrs["batch"]]
        assert len(decoded) == stats["steps_total"] - before["steps_total"]
        assert sum(s.attrs["admitted"] for s in steps) == 2
        assert sum(s.attrs["tokens"] for s in steps) == 32
        assert not any(s.name == "llm.decode_chunk"
                       for s in tracing.recorded(t0))
        seven = ["retire", "admit", "operands", "dispatch", "device_wait",
                 "deliver", "observe"]
        fetched = 0
        for step in steps:
            kids = [s for s in spans if s.parent_id == step.span_id]
            names = [k.name[len("llm.step."):] for k in kids]
            # The seven phases keep their names and their order. A step
            # that dispatched with a chunk pending has all of them; the
            # first chunk of a busy stretch has nothing to fetch yet, and
            # the draining step after the last one has nothing to dispatch.
            assert names == [p for p in seven if p in names]
            assert names[:3] == seven[:3] and names[-1] == "observe"
            assert ("dispatch" in names) == bool(step.attrs["batch"])
            assert ("device_wait" in names) == ("deliver" in names)
            assert step.attrs["ahead"] == (
                "dispatch" in names and "device_wait" in names)
            if "deliver" not in names:
                assert step.attrs["tokens"] == 0
            fetched += "device_wait" in names
            if step.attrs["ahead"]:
                assert names == seven
            assert kids[0].start_ns == step.start_ns
            assert kids[-1].end_ns == step.end_ns
            total = sum(k.end_ns - k.start_ns for k in kids)
            assert total == pytest.approx(step.end_ns - step.start_ns,
                                          rel=0.02)
            assert step.attrs["admit_stopped"] in (
                "queue_empty", "no_slot", "budget", "no_blocks")
            assert step.attrs["driver"] == threading.current_thread().name
        # Every chunk dispatched is fetched, one step later. Two requests
        # overlap here, so the look-ahead engaged: in every dispatching
        # step but the first of each busy stretch.
        assert fetched == len(decoded)
        ahead = stats["steps_ahead_total"] - before["steps_ahead_total"]
        assert ahead == sum(s.attrs["ahead"] for s in steps)
        assert 0 < ahead <= len(decoded) - 1
        # Steps do not overlap; what lies between two is the driver's own.
        for a, b in zip(steps, steps[1:]):
            assert a.end_ns <= b.start_ns
        in_steps = sum(s.end_ns - s.start_ns for s in steps) / 1e9
        assert (stats["step_host_s"] + stats["step_device_wait_s"]
                - before["step_host_s"] - before["step_device_wait_s"]
                ) == pytest.approx(in_steps, rel=1e-6)

    def test_warmup_span_has_one_child_per_program(self, tiny_model):
        t0 = tracing.now_ns()
        eng = _engine(tiny_model, 65, "warm")
        spans = [s for s in tracing.recorded(t0)
                 if s.trace_id == eng.trace_id]
        [warm] = [s for s in spans if s.name == "llm.warmup"]
        programs = [s for s in spans if s.name == "llm.warmup.program"]
        assert all(p.parent_id == warm.span_id for p in programs)
        assert [p.attrs["program"] for p in programs] == [
            "paged_prefill", "paged_decode", "copy_block"]
        assert programs[0].attrs["bucket"] == 16
        assert warm.attrs["programs"] == 3
        # JAX reported the time it spent tracing and lowering each one.
        assert all(p.attrs["trace_s"] > 0 and p.attrs["lower_s"] > 0
                   for p in programs[:2])
        # Nested jits report their tracing inside their caller's: a program's
        # seconds are counted once, so they fit inside its span.
        for p in programs:
            took = (p.end_ns - p.start_ns) / 1e9
            assert max(p.attrs["trace_s"], p.attrs["lower_s"],
                       p.attrs["backend_s"]) <= took
        assert warm.start_ns <= programs[0].start_ns
        assert programs[-1].end_ns <= warm.end_ns

    @pytest.mark.parametrize("pool_blocks,blocked", [(5, True), (65, False)])
    def test_pool_blocked_time_is_counted_where_admission_stops(
            self, tiny_model, pool_blocks, blocked):
        """A pool that holds one request's blocks but not two: the second
        waits with a slot free, and the engine counts that time."""
        eng = _engine(tiny_model, pool_blocks, f"pool{pool_blocks}")
        t0 = tracing.now_ns()
        outs = _two_requests(eng)
        assert [len(o) for o in outs] == [16, 16]
        st = eng.stats()
        stops = [s.attrs["admit_stopped"] for s in tracing.recorded(t0)
                 if s.name == "llm.step" and s.trace_id == eng.trace_id]
        if blocked:
            assert st["admit_blocked_pool_s"] > 0
            assert "no_blocks" in stops
            assert st["admit_blocked_pool_s"] <= (
                st["step_host_s"] + st["step_device_wait_s"]) * 1.5
        else:
            assert st["admit_blocked_pool_s"] == 0
            assert "no_blocks" not in stops

    def test_budget_stops_are_counted_per_step(self, tiny_model):
        """A prefill budget of one prompt a step: with two queued, the first
        step admits one and stops on the budget, the second finds the queue
        empty. ``admit_budget_stop_share`` reads the two counters."""
        eng = _engine(tiny_model, 65, "budget")
        eng.prefill_budget = 16
        t0 = tracing.now_ns()
        before = eng.stats()
        outs = _two_requests(eng)
        assert [len(o) for o in outs] == [16, 16]
        st = eng.stats()
        stops = [s.attrs["admit_stopped"] for s in tracing.recorded(t0)
                 if s.name == "llm.step" and s.trace_id == eng.trace_id]
        assert stops[:2] == ["budget", "queue_empty"]
        assert st["admit_stopped_budget_total"] \
            - before["admit_stopped_budget_total"] == stops.count("budget") == 1
        assert st["steps_total"] - before["steps_total"] >= 4

    def test_gate_off_leaves_ring_empty_and_counters_counting(
            self, tiny_model, fresh_config):
        eng = _engine(tiny_model, 65, "gated")
        set_config(Config({"trace_enabled": False}))
        t0 = tracing.now_ns()
        before = eng.stats()
        outs = _two_requests(eng)
        assert [len(o) for o in outs] == [16, 16]
        assert tracing.recorded(t0) == []
        st = eng.stats()
        assert st["steps_total"] - before["steps_total"] >= 4
        assert 0 < st["steps_ahead_total"] - before["steps_ahead_total"] \
            <= st["steps_total"] - before["steps_total"] - 1
        assert st["admit_stopped_budget_total"] == \
            before["admit_stopped_budget_total"]
        assert st["step_device_wait_s"] > before["step_device_wait_s"]
        assert st["step_host_s"] > before["step_host_s"]

    def test_step_thread_never_exports(self, ray_start_regular, tiny_model,
                                       monkeypatch):
        """The GCS export runs on the exporter thread or in an explicit
        flush(), never on the thread that steps the engine."""
        shippers = []
        real = tracing._ship

        def recording_ship(batch, runtime):
            shippers.append(threading.current_thread().name)
            return real(batch, runtime)

        monkeypatch.setattr(tracing, "_ship", recording_ship)
        monkeypatch.setattr(tracing, "EXPORT_INTERVAL_S", 0.01)
        eng = _engine(tiny_model, 65, "noexport")
        out = []

        def drive():
            with tracing.span("caller"):
                out.extend(eng.generate([7, 3, 11], max_new_tokens=32))

        th = threading.Thread(target=drive, name="engine-driver")
        th.start()
        th.join(timeout=120)
        assert not th.is_alive() and len(out) == 32
        tracing.flush()
        assert shippers and "engine-driver" not in shippers
        # What the step thread emitted for the request did reach the GCS ...
        shipped = get_runtime().gcs.task_events()
        assert _span_events(shipped, "llm.request")
        # ... and the step tree stayed in the ring: eight spans a step are
        # read in-process, whatever the sample rate, and never exported.
        assert not _span_events(shipped, "llm.step")
        assert not _span_events(get_runtime().gcs.trace(eng.trace_id),
                                "llm.step.dispatch")
        steps = [s for s in tracing.recorded()
                 if s.name == "llm.step" and s.trace_id == eng.trace_id]
        assert steps and all(s.attrs["driver"] == "engine-driver"
                             for s in steps if s.attrs["batch"])


# ISSUE 35: the engine accounts for every second it holds work. All of it is
# counters in stats() (tracing off too) and attrs on spans already emitted.

TIME3 = ("step_host_s", "step_device_wait_s", "step_handoff_s")
STOPS = ("queue_empty", "no_slot", "budget", "no_blocks")


def _delta(after, before):
    return {k: after[k] - before[k] for k in after if k in before}


def _steps_of(eng, t0):
    return [s for s in tracing.recorded(t0)
            if s.name == "llm.step" and s.trace_id == eng.trace_id]


def _closed_loop(eng, clients, requests_each, think_s=0.0, step_sleep_s=0.0):
    """``clients`` threads, each sending its next request when the last one
    ended: after ``think_s`` (a client on its way back through a router),
    and consuming with ``step_sleep_s`` between tokens (a consumer that is
    slow to come back for its next step). Returns the counters' growth."""
    before = eng.stats()
    errors = []

    def client(k):
        try:
            for i in range(requests_each):
                req = eng.submit([7, 3, 11 + k, 5 + i], max_new_tokens=16)
                n = 0
                for _ in eng.drive(req):
                    n += 1
                    if step_sleep_s:
                        time.sleep(step_sleep_s)
                assert n == 16
                if think_s:
                    time.sleep(think_s)
        except BaseException as e:  # noqa: BLE001 — reported by the test
            errors.append(e)

    threads = [threading.Thread(target=client, args=(k,), name=f"client-{k}")
               for k in range(clients)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=120)
    assert not errors and not any(th.is_alive() for th in threads), errors
    return _delta(eng.stats(), before)


class TestStepAccounting:
    @pytest.mark.parametrize("traced", [True, False])
    def test_host_wait_and_handoff_add_up_to_the_wall_time(
            self, tiny_model, fresh_config, traced):
        """An engine that is never empty from its first step to its last:
        the time inside steps and the time between them is all of it, with
        tracing off too; CPU never exceeds wall."""
        eng = _engine(tiny_model, 65, f"whole{int(traced)}")
        if not traced:
            set_config(Config({"trace_enabled": False}))
        reqs = [eng.submit([7, 3, 11 + i], max_new_tokens=48)
                for i in range(2)]
        before = eng.stats()
        t0 = time.perf_counter_ns()
        outs = [list(eng.drive(r)) for r in reqs]
        wall = (time.perf_counter_ns() - t0) / 1e9
        d = _delta(eng.stats(), before)
        assert [len(o) for o in outs] == [48, 48]
        assert (len(tracing.recorded(t0)) > 0) == traced
        assert sum(d[k] for k in TIME3) == pytest.approx(wall, rel=0.05)
        assert d["step_handoff_s"] > 0
        assert 0 < d["step_host_cpu_s"] <= d["step_host_s"]
        assert (0 < d["prefill_dispatch_cpu_s"] <= d["prefill_dispatch_s"]
                <= d["step_host_s"])
        assert 0 < d["slot_steps_total"] <= d["slot_steps_offered_total"]
        assert d["slot_steps_offered_total"] == d["steps_total"] * 2 * 4
        assert d["step_driver_switches_total"] == 0     # one thread drove
        assert all(isinstance(v, float) for v in eng.stats().values())

    def test_slot_steps_are_the_rows_of_every_dispatch(self, tiny_model):
        eng = _engine(tiny_model, 65, "rows")
        t0 = tracing.now_ns()
        d = _closed_loop(eng, clients=3, requests_each=2)
        steps = _steps_of(eng, t0)
        assert d["slot_steps_total"] == 4 * sum(s.attrs["batch"] for s in steps)
        assert d["slot_steps_offered_total"] == 4 * 2 * sum(
            1 for s in steps if s.attrs["batch"])
        assert d["slot_steps_total"] == 3 * 2 * 16      # every token step
        # Three threads took turns: the step names its driver, the counter
        # counts the changes, and what lay between two steps rides the later.
        drivers = [s.attrs["driver"] for s in steps]
        assert d["step_driver_switches_total"] == sum(
            a != b for a, b in zip(drivers, drivers[1:]))
        assert d["step_handoff_s"] == pytest.approx(
            sum(s.attrs["handoff_ns"] for s in steps) / 1e9, rel=1e-6)
        for a, b in zip(steps, steps[1:]):
            assert b.attrs["handoff_ns"] in (0, b.start_ns - a.end_ns)
        # A phase's CPU is on its span and fits inside its wall time.
        kids = [s for s in tracing.recorded(t0)
                if s.name.startswith("llm.step.")
                and s.trace_id == eng.trace_id]
        assert len(kids) >= 3 * len(steps)
        host_cpu = sum(k.attrs["cpu_ns"] for k in kids
                       if k.name != "llm.step.device_wait")
        assert d["step_host_cpu_s"] == pytest.approx(host_cpu / 1e9, rel=1e-6)

    @pytest.mark.parametrize("outcome,pool_blocks,budget,requests", [
        ("queue_empty", 65, None, 2), ("no_slot", 65, None, 3),
        ("budget", 65, 16, 2), ("no_blocks", 5, None, 2)])
    def test_every_admission_ends_in_one_counted_outcome(
            self, tiny_model, outcome, pool_blocks, budget, requests):
        eng = _engine(tiny_model, pool_blocks, f"stop-{outcome}")
        if budget:
            eng.prefill_budget = budget
        before = eng.stats()
        t0 = tracing.now_ns()
        reqs = [eng.submit([7, 3, 11 + i], max_new_tokens=16)
                for i in range(requests)]
        assert [len(list(eng.drive(r))) for r in reqs] == [16] * requests
        d = _delta(eng.stats(), before)
        stops = [s.attrs["admit_stopped"] for s in _steps_of(eng, t0)]
        for name in STOPS:
            assert d[f"admit_stopped_{name}_total"] == stops.count(name)
        assert d[f"admit_stopped_{outcome}_total"] > 0
        assert sum(d[f"admit_stopped_{n}_total"] for n in STOPS) == len(stops)

    def test_a_planted_starvation_shows_in_the_new_counters(self, tiny_model):
        """Twelve requests twice on two slots. Sound: four clients that come
        straight back, so somebody always waits. Starved: the clients are
        elsewhere (one is left, away 50 ms before it resubmits) and the
        consumer sleeps 5 ms after every token. Decodes go out half empty
        with nobody waiting, the engine holds work with no thread stepping
        it, and the ratio of the two in-step counters cannot say so: it is
        a share of the time inside steps."""
        eng = _engine(tiny_model, 65, "starved")
        sound = _closed_loop(eng, clients=4, requests_each=3)
        starved = _closed_loop(eng, clients=1, requests_each=12,
                               think_s=0.05, step_sleep_s=0.005)

        def occupancy(d):       # slots_active_share's reading
            return d["slot_steps_total"] / d["slot_steps_offered_total"]

        def starved_share(d):   # admit_starved_share's reading
            return d["admit_starved_total"] / d["steps_total"]

        def host_share(d):      # step_host_share's reading
            return d["step_host_s"] / (d["step_host_s"]
                                       + d["step_device_wait_s"])

        def handoff_share(d):
            return d["step_handoff_s"] / sum(d[k] for k in TIME3)

        assert sound["slot_steps_total"] == starved["slot_steps_total"]
        assert occupancy(starved) == 0.5 < 0.7 < occupancy(sound)
        assert starved_share(starved) == 1.0 > 0.5 > starved_share(sound)
        # Three of a request's four chunks are followed by four sleeps with
        # the request still held: 60 ms a request against microseconds.
        assert starved["step_handoff_s"] > max(0.3, 5 * sound["step_handoff_s"])
        assert (abs(host_share(starved) - host_share(sound)) < 0.35
                < handoff_share(starved) - handoff_share(sound))
        for d in (sound, starved):
            assert d["step_host_cpu_s"] <= d["step_host_s"]

    def test_handoff_is_not_counted_while_the_engine_holds_nothing(
            self, tiny_model):
        eng = _engine(tiny_model, 65, "idle")
        before = eng.stats()
        assert len(eng.generate([7, 3, 11], max_new_tokens=8)) == 8
        time.sleep(0.2)                 # nothing held: nobody's hand-off
        t0 = tracing.now_ns()
        assert len(eng.generate([7, 3, 12], max_new_tokens=8)) == 8
        d = _delta(eng.stats(), before)
        assert d["step_handoff_s"] < 0.1
        assert _steps_of(eng, t0)[0].attrs["handoff_ns"] == 0
        assert d["admit_starved_total"] > 0     # one stream on two slots

    @pytest.mark.parametrize("others", [0, 1])
    def test_handoff_is_not_counted_after_the_last_request_is_cancelled(
            self, tiny_model, others):
        """A client that goes away (its generator closed) frees its slot
        without a step running. If it was the last request held, the idle
        stretch that follows is nobody's hand-off, however much later the
        next request comes; if another is still held, the clock runs on."""
        eng = _engine(tiny_model, 65, f"gone{others}")
        before = eng.stats()
        rest = [eng.submit([7, 3, 13], max_new_tokens=16)
                for _ in range(others)]
        gen = eng.drive(eng.submit([7, 3, 11], max_new_tokens=64))
        assert next(gen) is not None        # steps ran: the engine holds it
        assert eng._held_since_ns is not None
        gen.close()                         # drive()'s finally cancels it
        assert (eng._held_since_ns is not None) == bool(others)
        time.sleep(0.2)
        t0 = tracing.now_ns()
        for r in rest:
            assert len(list(eng.drive(r))) == 16
        assert len(eng.generate([7, 3, 12], max_new_tokens=8)) == 8
        d = _delta(eng.stats(), before)
        first = _steps_of(eng, t0)[0].attrs["handoff_ns"]
        if others:
            assert first >= 0.2e9 and d["step_handoff_s"] >= 0.2
        else:
            assert first == 0 and d["step_handoff_s"] < 0.1

    def test_stepspans_summarises_the_ring_of_an_untraced_run(
            self, tiny_model):
        """The operator's reader of ``cpu_ns`` and ``handoff_ns``
        (``python -m ray_tpu.devtools.stepspans``) agrees with stats()."""
        from ray_tpu.devtools import stepspans
        from ray_tpu.serve.replica import ReplicaActor
        eng = _engine(tiny_model, 65, "ringsum")
        # Through a replica's streaming method, so that the ring also holds
        # the way back: ``serve.replica_stream`` beside ``llm.request``.
        replica = ReplicaActor(
            "ringsum", lambda i: eng.stream([7, 3, 11 + i], max_new_tokens=8),
            (), {})
        t0 = tracing.now_ns()
        before = eng.stats()
        for i in range(2):
            with tracing.span("serve.request"):
                assert len(list(replica.handle_request_streaming(
                    "__call__", i, _trace_submit_ts=tracing.wall_of(
                        tracing.now_ns())))) == 8
        d = _delta(eng.stats(), before)
        out = stepspans.summarise(tracing.recorded(t0))
        assert out["decode_steps"] == d["steps_total"] > 0
        assert set(out["phases"]) >= {"admit", "dispatch", "device_wait"}
        for p in out["phases"].values():
            assert 0 <= p["cpu_ms_a_step"] <= p["wall_ms_a_step"] + 0.05
        pf = out["prefill"]
        assert pf["calls"] == 2
        assert 0 < pf["dispatch_cpu_ms"] <= pf["dispatch_ms"] <= pf["prefill_ms"]
        assert pf["dispatch_ms_p10_p50_p90"][1] > 0
        assert out["requests"] == 2
        assert out["submit_ms"]["p50"] >= 0 and out["tail_ms"]["max"] >= 0
        assert sum(b["handoff_s"] for b in out["steps_by_bucket_s"].values()
                   ) == pytest.approx(d["step_handoff_s"], rel=1e-6)
        by_finish = out["requests_by_bucket_s_of_engine_finish"].values()
        assert sum(b["n"] for b in by_finish) == 2
        # One consumer drove every step: no driver ever changed, and its
        # stream's span is mostly steps it ran for the engine.
        assert sum(b["driver_switches"]
                   for b in out["steps_by_bucket_s"].values()) == \
            d["step_driver_switches_total"] == 0
        for b in by_finish:
            # The hops beside the tail, in the same buckets: the pick-ups
            # and the publishes as stats() counted them; this caller went
            # through no handle, so its take and hold have no items.
            assert 0 <= b["pickup_lag_ms"]["mean"] <= b["pickup_lag_ms"]["max"]
            assert 0 < b["publish_us_per_item"]["mean"] \
                <= b["publish_us_per_item"]["max"]
            assert b["take_lag_ms"]["mean"] is None
            assert b["client_hold_ms"]["mean"] is None
            assert 0 < b["drove_share"] <= 100
            assert 0 < b["producer_cpu_share"] <= 100
            assert b["consumer_cpu_share"] == 0
        assert sum(b["pickup_lag_ms"]["mean"] is not None
                   for b in by_finish) >= 1
        assert d["stream_pickups_total"] >= 2 and d["stream_pickup_lag_s"] >= 0
        empty = stepspans.summarise([])
        assert empty["spans"] == empty["decode_steps"] == empty["requests"] == 0

    def test_the_prefill_call_is_a_span_inside_llm_prefill(self, tiny_model):
        eng = _engine(tiny_model, 65, "pfspan")
        t0 = tracing.now_ns()
        before = eng.stats()
        with tracing.span("caller") as (trace_id, _sid):
            assert len(eng.generate([7, 3, 11], max_new_tokens=8)) == 8
        mine = [s for s in tracing.recorded(t0) if s.trace_id == trace_id]
        [prefill] = [s for s in mine if s.name == "llm.prefill"]
        [call] = [s for s in mine if s.name == "llm.prefill.dispatch"]
        [alloc] = [s for s in mine if s.name == "kv.alloc"]
        assert call.parent_id == alloc.parent_id == prefill.span_id
        assert prefill.start_ns <= alloc.end_ns <= call.start_ns
        assert call.end_ns <= prefill.end_ns
        assert 0 < call.attrs["cpu_ns"] <= call.end_ns - call.start_ns
        d = _delta(eng.stats(), before)
        assert d["prefill_dispatch_s"] == pytest.approx(
            (call.end_ns - call.start_ns) / 1e9, rel=1e-6)
        assert d["prefill_dispatch_cpu_s"] == pytest.approx(
            call.attrs["cpu_ns"] / 1e9, rel=1e-6)
