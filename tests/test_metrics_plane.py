"""Cluster metrics plane tests — exporter → GCS aggregation → dashboard.

Covers the per-process export pipeline (reference: ``_private/
metrics_agent.py`` → Prometheus scrape), the built-in task lifecycle phase
histograms, the bisect histogram + label escaping, and the cursor'd
task-event reads.
"""

import time
from unittest import mock

import pytest

import ray_tpu
from ray_tpu.util import metrics as um


# ====================== metrics module units ======================


def test_histogram_bisect_bucketing():
    h = um.Histogram("t_hist_bisect", boundaries=[1.0, 5.0, 10.0])
    for v in (0.5, 1.0, 1.5, 5.0, 7.0, 11.0, 1e9):
        h.observe(v)
    snap = h._snapshot()
    assert snap["type"] == "histogram" and snap["bounds"] == [1.0, 5.0, 10.0]
    [(tags, (buckets, total_sum, count))] = snap["samples"]
    # value <= bound semantics: 0.5,1.0 | 1.5,5.0 | 7.0 | 11.0,1e9 (+Inf)
    assert buckets == [2, 2, 1, 2]
    assert count == 7
    lines = h._prom_lines()
    # cumulative counts in the exposition
    assert any(line.endswith(" 2") and 'le="1.0"' in line for line in lines)
    assert any(line.endswith(" 5") and 'le="10.0"' in line for line in lines)
    assert any(line.endswith(" 7") and 'le="+Inf"' in line for line in lines)


def test_histogram_rejects_unsorted_bounds():
    with pytest.raises(ValueError):
        um.Histogram("t_hist_bad", boundaries=[5.0, 1.0])


def test_label_value_escaping():
    g = um.Gauge("t_gauge_escape", tag_keys=("path",))
    g.set(1.0, {"path": 'a\\b"c\nd'})
    [line] = [ln for ln in g._prom_lines() if not ln.startswith("#")]
    assert 'path="a\\\\b\\"c\\nd"' in line
    # and the escaped form survives the aggregator's merged rendering
    agg = um.MetricsAggregator()
    agg.report("n1", "driver", 1, [g._snapshot()])
    assert 'path="a\\\\b\\"c\\nd"' in agg.prometheus_text()


def test_aggregator_merges_processes_with_identity_labels():
    c = um.Counter("t_agg_counter", tag_keys=("op",))
    c.inc(3, {"op": "x"})
    snap = [c._snapshot()]
    agg = um.MetricsAggregator()
    agg.report("node-a", "worker", 11, snap)
    agg.report("node-b", "node_daemon", 22, snap)
    text = agg.prometheus_text()
    assert text.count("# TYPE t_agg_counter counter") == 1
    assert 'component="worker"' in text and 'component="node_daemon"' in text
    assert 'node_id="node-a"' in text and 'pid="22"' in text
    summ = agg.summary()
    assert len(summ["processes"]) == 2
    [row] = [m for m in summ["metrics"] if m["name"] == "t_agg_counter"]
    assert row["series"] == 2 and row["total"] == 6.0


def test_aggregator_staleness_eviction():
    g = um.Gauge("t_agg_stale")
    g.set(1.0)
    agg = um.MetricsAggregator()
    now = time.time()
    agg.report("dead-node", "worker", 1, [g._snapshot()], now=now - 3600)
    agg.report("live-node", "worker", 2, [g._snapshot()], now=now)
    text = agg.prometheus_text(now=now)
    assert "live-node" in text and "dead-node" not in text
    assert len(agg.summary(now=now)["processes"]) == 1


def test_collector_hooks_run_before_snapshot():
    g = um.Gauge("t_collected")
    unregister = um.register_collector(lambda: g.set(42.0))
    try:
        snap = um.snapshot_registry()
        [m] = [m for m in snap if m["name"] == "t_collected"]
        assert m["samples"] == [((), 42.0)]
    finally:
        unregister()


# ====================== exporter units ======================


def test_exporter_survives_gcs_outage():
    """Reports raising (GCS down/restarting) are swallowed and the next
    tick re-registers the full snapshot — no crash, no thread death."""
    from ray_tpu.core.config import Config, set_config
    from ray_tpu.core.metrics_export import MetricsExporter
    from ray_tpu.core.rpc import RpcConnectionError

    set_config(Config({"metrics_export_interval_s": 0.05}))
    try:
        got = []
        calls = {"n": 0}

        def report(node_id, component, pid, snapshot):
            calls["n"] += 1
            if calls["n"] <= 2:
                raise RpcConnectionError("gcs restarting")
            got.append((node_id, component, pid, snapshot))

        exp = MetricsExporter(report, node_id="n1",
                              component="worker").start()
        try:
            deadline = time.time() + 10
            while not got and time.time() < deadline:
                time.sleep(0.02)
            assert got, "exporter never recovered after failed reports"
            node_id, component, pid, snapshot = got[0]
            assert (node_id, component) == ("n1", "worker")
            assert isinstance(snapshot, list)
        finally:
            exp.stop()
    finally:
        set_config(Config())


def test_exporter_disabled_by_knob():
    from ray_tpu.core.config import Config, set_config
    from ray_tpu.core.metrics_export import MetricsExporter, metrics_enabled

    set_config(Config({"metrics_export_enabled": False}))
    try:
        assert not metrics_enabled()
        exp = MetricsExporter(lambda *a: (_ for _ in ()).throw(
            AssertionError("must not report")), "n", "driver").start()
        assert exp._thread is None
        exp.stop()
    finally:
        set_config(Config())


# ====================== cursor'd task events ======================


def test_task_events_since_cursor():
    from ray_tpu.core.gcs import GlobalControlStore

    store = GlobalControlStore()
    for i in range(10):
        store.record_task_event({"task_id": f"t{i}"})
    cur, evs = store.task_events_since(0, limit=4)
    assert [e["task_id"] for e in evs] == ["t0", "t1", "t2", "t3"]
    assert cur == 4
    cur, evs = store.task_events_since(cur, limit=100)
    assert len(evs) == 6 and cur == 10
    # caught up: nothing new
    cur2, evs2 = store.task_events_since(cur)
    assert evs2 == [] and cur2 == 10
    # None tails from the end
    cur3, tail = store.task_events_since(None, limit=3)
    assert [e["task_id"] for e in tail] == ["t7", "t8", "t9"] and cur3 == 10
    # a cursor past the end (GCS restarted with a fresh log) clamps
    cur4, evs4 = store.task_events_since(99999)
    assert evs4 == [] and cur4 == 10
    # legacy full read unchanged
    assert len(store.task_events()) == 10


def test_task_events_since_survives_truncation():
    from ray_tpu.core.gcs import GlobalControlStore

    store = GlobalControlStore()
    store._task_events = [{"task_id": f"t{i}"} for i in range(100)]
    store._task_event_base = 0
    # force the 100k truncation path with a small synthetic log
    with store._lock:
        drop = 50
        del store._task_events[:drop]
        store._task_event_base += drop
    cur, evs = store.task_events_since(10, limit=5)
    # events below the base were truncated away; read resumes at the base
    assert [e["task_id"] for e in evs] == ["t50", "t51", "t52", "t53", "t54"]
    assert cur == 55


# ====================== tracing satellite ======================


def test_span_duration_uses_monotonic_clock():
    from ray_tpu.util import tracing

    class _GcsSink:
        def __init__(self):
            self.events = []

        def record_task_event(self, e):
            self.events.append(e)

    class _Rt:
        gcs = _GcsSink()

    rt = _Rt()
    # Freeze the WALL clock: with time.time pinned, only a monotonic-based
    # duration can come out positive, and only a timestamp derived from the
    # span clock's one anchor (not read at emit) can come out right.
    before = tracing.wall_of(tracing.now_ns())
    with mock.patch.object(tracing.time, "time", return_value=1234.0):
        with tracing.span("probe", runtime=rt):
            time.sleep(0.05)
    [event] = rt.gcs.events
    assert before + 0.04 <= event["time"] <= tracing.wall_of(tracing.now_ns())
    assert event["duration"] >= 0.04


# ====================== in-process pipeline ======================


def test_phase_histograms_and_summary_in_process(ray_start_regular):
    @ray_tpu.remote
    def work(x):
        return x + 1

    assert ray_tpu.get([work.remote(i) for i in range(4)]) == [1, 2, 3, 4]
    from ray_tpu.core.runtime import get_runtime

    rt = get_runtime()
    rt._metrics_exporter.flush()
    text = rt.gcs.metrics_text()
    assert "ray_tpu_task_phase_s_bucket" in text
    for phase in ("queued", "args_fetch", "execute", "total"):
        assert f'phase="{phase}"' in text
    assert 'component="driver"' in text
    # task events carry the phase stamps too
    evs = [e for e in rt.gcs.task_events() if e.get("phases")]
    assert evs and "execute" in evs[-1]["phases"]
    summ = rt.gcs.metrics_summary()
    assert summ["processes"] and summ["metrics"]


# ====================== multiprocess cluster pipeline ======================


def test_cluster_metrics_merged_exposition_and_dashboard():
    """Acceptance: dashboard /metrics returns the merged exposition with
    ≥2 distinct components and populated task phase histograms after a
    multi-process workload; the exporter pipeline survives a GCS restart."""
    import os

    import httpx

    from ray_tpu.core import runtime as runtime_mod
    from ray_tpu.core.cluster import Cluster, connect
    from ray_tpu.core.config import Config, set_config
    from ray_tpu.dashboard import start_dashboard

    os.environ["RAY_TPU_METRICS_EXPORT_INTERVAL_S"] = "0.3"
    set_config(Config())  # driver adopts the fast cadence too
    cluster = Cluster(num_nodes=2, resources_per_node={"CPU": 1})
    try:
        core = connect(cluster.gcs_address)
        try:
            @ray_tpu.remote
            def work(x):
                return x * 2

            assert ray_tpu.get([work.remote(i) for i in range(6)],
                               timeout=120) == [0, 2, 4, 6, 8, 10]
            dash = start_dashboard(port=18931)
            try:
                deadline = time.time() + 60
                text = ""
                while time.time() < deadline:
                    text = httpx.get(f"{dash.url}/metrics", timeout=30).text
                    comps = {seg.split('"')[0]
                             for seg in text.split('component="')[1:]}
                    if ({"worker", "node_daemon"} <= comps
                            and "ray_tpu_task_phase_s_bucket" in text):
                        break
                    time.sleep(0.5)
                assert {"worker", "node_daemon"} <= comps, text[:2000]
                assert "ray_tpu_task_phase_s_bucket" in text
                assert 'phase="execute"' in text
                # one TYPE header per metric despite many reporting processes
                assert text.count("# TYPE ray_tpu_task_phase_s ") == 1

                summ = httpx.get(f"{dash.url}/api/metrics_summary",
                                 timeout=30).json()
                comps = {p["component"] for p in summ["processes"]}
                assert {"worker", "node_daemon", "gcs"} <= comps
                daemon_nodes = {p["node_id"] for p in summ["processes"]
                                if p["component"] == "node_daemon"}
                assert len(daemon_nodes) == 2
                page = httpx.get(f"{dash.url}/", timeout=30).text
                assert "renderMetrics" in page

                # GCS restart: exporters keep notifying and re-register on
                # the fresh aggregator — series reappear, nothing crashes.
                cluster.kill_gcs()
                cluster.restart_gcs()
                deadline = time.time() + 60
                comps = set()
                while time.time() < deadline:
                    try:
                        summ = core.gcs.metrics_summary()
                    except Exception:  # noqa: BLE001 — GCS still rebinding
                        time.sleep(0.5)
                        continue
                    comps = {p["component"] for p in summ["processes"]}
                    if "node_daemon" in comps:
                        break
                    time.sleep(0.5)
                assert "node_daemon" in comps, comps
            finally:
                dash.stop()
        finally:
            core.shutdown()
            runtime_mod._global_runtime = None
    finally:
        cluster.shutdown()
        os.environ.pop("RAY_TPU_METRICS_EXPORT_INTERVAL_S", None)
        set_config(Config())
