"""Tests for util (ActorPool, Queue, metrics), accelerators, state API, CLI —
modeled on the reference's ``python/ray/tests/test_actor_pool.py``,
``test_queue.py``, ``test_metrics.py``, and state-API tests.
"""

import json
import os
import subprocess
import sys

import pytest

import ray_tpu
from ray_tpu.util.actor_pool import ActorPool
from ray_tpu.util.queue import Empty, Full, Queue
from ray_tpu.util import metrics as rt_metrics


class TestActorPool:
    def test_map_ordered(self, ray_start_regular):
        @ray_tpu.remote
        class Worker:
            def double(self, x):
                return x * 2

        pool = ActorPool([Worker.remote() for _ in range(2)])
        out = list(pool.map(lambda a, v: a.double.remote(v), range(8)))
        assert out == [x * 2 for x in range(8)]

    def test_map_unordered_complete(self, ray_start_regular):
        import time as _t

        @ray_tpu.remote
        class Worker:
            def work(self, x):
                _t.sleep(0.01 * (x % 3))
                return x

        pool = ActorPool([Worker.remote() for _ in range(3)])
        out = list(pool.map_unordered(lambda a, v: a.work.remote(v), range(9)))
        assert sorted(out) == list(range(9))

    def test_submit_more_than_actors(self, ray_start_regular):
        @ray_tpu.remote
        class Worker:
            def f(self, x):
                return x + 1

        pool = ActorPool([Worker.remote()])
        for i in range(5):
            pool.submit(lambda a, v: a.f.remote(v), i)
        results = [pool.get_next() for _ in range(5)]
        assert results == [1, 2, 3, 4, 5]


class TestQueue:
    def test_fifo_and_batch(self, ray_start_regular):
        q = Queue()
        for i in range(5):
            q.put(i)
        assert q.qsize() == 5
        assert [q.get() for _ in range(5)] == list(range(5))
        q.put_nowait_batch([10, 11, 12])
        assert q.get_nowait_batch(3) == [10, 11, 12]
        q.shutdown()

    def test_empty_and_full(self, ray_start_regular):
        q = Queue(maxsize=2)
        with pytest.raises(Empty):
            q.get_nowait()
        q.put(1)
        q.put(2)
        with pytest.raises(Full):
            q.put_nowait(3)
        assert q.full()
        q.shutdown()

    def test_cross_actor_queue(self, ray_start_regular):
        q = Queue()

        @ray_tpu.remote
        def producer(q, n):
            for i in range(n):
                q.put(i)
            return True

        assert ray_tpu.get(producer.remote(q, 4))
        assert [q.get(timeout=5) for _ in range(4)] == [0, 1, 2, 3]
        q.shutdown()


class TestMetrics:
    def test_counter_gauge_histogram(self):
        c = rt_metrics.Counter("test_requests", tag_keys=("route",))
        c.inc(1.0, {"route": "/a"})
        c.inc(2.0, {"route": "/a"})
        assert c.get({"route": "/a"}) == 3.0
        with pytest.raises(ValueError):
            c.inc(0)

        g = rt_metrics.Gauge("test_inflight")
        g.set(7)
        assert g.get() == 7.0

        h = rt_metrics.Histogram("test_latency", boundaries=[0.1, 1.0])
        h.observe(0.05)
        h.observe(0.5)
        h.observe(5.0)
        text = rt_metrics.prometheus_text()
        assert 'test_requests{route="/a"} 3.0' in text
        assert "test_latency_bucket" in text
        assert 'le="+Inf"} 3' in text

    def test_invalid_tags_rejected(self):
        g = rt_metrics.Gauge("test_tagged", tag_keys=("k",))
        with pytest.raises(ValueError):
            g.set(1.0, {"other": "x"})


class TestAccelerators:
    def test_resources_from_env(self, monkeypatch):
        from ray_tpu.accelerators import tpu as acc

        monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-16")
        monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0,1,2,3")
        monkeypatch.setenv("TPU_WORKER_ID", "0")
        info = acc.detect_tpu()
        assert info is not None
        # jax may report the real attached chip count; env fallback says 4
        assert info.chips_on_host >= 1
        res = acc.tpu_resources(
            acc.TpuInfo(
                chips_on_host=4, accelerator_type="v5litepod-16", generation="V5E",
                pod_name=None, worker_id=0, hosts_in_slice=4,
            )
        )
        assert res["TPU"] == 4.0
        assert res["TPU-V5E"] == 4.0
        assert res["TPU-v5litepod-16-head"] == 1.0

    def test_non_head_worker_has_no_head_resource(self):
        from ray_tpu.accelerators import tpu as acc

        res = acc.tpu_resources(
            acc.TpuInfo(
                chips_on_host=4, accelerator_type="v5litepod-16", generation="V5E",
                pod_name=None, worker_id=2, hosts_in_slice=4,
            )
        )
        assert "TPU-v5litepod-16-head" not in res

    def test_generation_parsing(self):
        from ray_tpu.accelerators.tpu import _generation_from_type

        assert _generation_from_type("v5litepod-16") == "V5E"
        assert _generation_from_type("v4-8") == "V4"
        assert _generation_from_type("v5p-128") == "V5P"


    @staticmethod
    def _stub_devices(monkeypatch, kind, n=1):
        import jax

        class Chip:
            platform = "tpu"
            device_kind = kind

        monkeypatch.setattr(jax, "devices", lambda *a: [Chip()] * n)

    @pytest.mark.parametrize("kind,marker", [
        ("TPU v5 lite", "TPU-V5E"),   # what a v5e reports
        ("TPU v6 lite", "TPU-V6E"),
        ("TPU v4", "TPU-V4"),
        ("TPU v5", "TPU-V5P"),
        ("TPU v5p", "TPU-V5P"),
    ])
    def test_generation_from_device_kind(self, monkeypatch, kind, marker):
        """The marker a live JAX client yields is the one the env path
        yields for the same hardware (v5litepod-* -> TPU-V5E)."""
        from ray_tpu.accelerators import tpu as acc

        self._stub_devices(monkeypatch, kind, n=4)
        res = acc.tpu_resources()
        assert res["TPU"] == 4.0 and res[marker] == 4.0

    def test_unknown_device_kind_gets_no_marker(self, monkeypatch):
        import logging

        from ray_tpu.accelerators import tpu as acc

        seen = []
        handler = logging.Handler()
        handler.emit = lambda record: seen.append(record.getMessage())
        log = logging.getLogger("ray_tpu.accelerators")
        log.addHandler(handler)
        try:
            self._stub_devices(monkeypatch, "TPU v99 mega")
            res = acc.tpu_resources()
        finally:
            log.removeHandler(handler)
        assert res == {"TPU": 1.0}
        assert any("unknown TPU device_kind 'TPU v99 mega'" in m
                   for m in seen)

    def test_failed_platform_probe_propagates(self, monkeypatch):
        """A JAX that cannot bring up its platform must stop ``init`` —
        not leave a node that quietly has no TPU resource."""
        import jax

        from ray_tpu.accelerators import tpu as acc

        def boom(*a):
            raise RuntimeError("Unable to initialize backend 'tpu'")

        monkeypatch.setattr(jax, "devices", boom)
        with pytest.raises(RuntimeError, match="backend 'tpu'"):
            acc.detect_tpu()
        with pytest.raises(RuntimeError, match="backend 'tpu'"):
            ray_tpu.init(num_cpus=1)
        ray_tpu.shutdown()


class TestCompileCache:
    """``enable_compile_cache``: the environment's directory wins untouched;
    otherwise one fixed checkout path, the same from every process."""

    ENV = "JAX_COMPILATION_CACHE_DIR"

    def test_honours_the_variable(self, monkeypatch):
        import jax

        from ray_tpu.util.compile_cache import enable_compile_cache

        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv(self.ENV, "/placed/from/outside")
        assert enable_compile_cache() == "/placed/from/outside"
        assert os.environ[self.ENV] == "/placed/from/outside"
        assert jax.config.jax_compilation_cache_dir == before  # set nothing

    def test_fixed_checkout_path_in_every_process(self, monkeypatch):
        import jax

        from ray_tpu.util.compile_cache import enable_compile_cache

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.delenv(self.ENV, raising=False)
        try:
            first = enable_compile_cache()
            assert first == os.path.join(repo, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == first
            # Exported, so spawned workers inherit it ...
            assert os.environ[self.ENV] == first
            monkeypatch.delenv(self.ENV)
            assert enable_compile_cache() == first
            # ... and a process that inherits nothing, started elsewhere,
            # still lands on the same directory (no cwd, pid or time in it).
            env = {k: v for k, v in os.environ.items() if k != self.ENV}
            env["PYTHONPATH"] = repo
            child = subprocess.run(
                [sys.executable, "-c",
                 "from ray_tpu.util.compile_cache import enable_compile_cache"
                 "; print(enable_compile_cache())"],
                env=env, cwd="/", capture_output=True, text=True, timeout=60)
            assert child.returncode == 0, child.stderr
            assert child.stdout.strip() == first
        finally:
            jax.config.update("jax_compilation_cache_dir", before)


class TestStateApi:
    def test_lists_and_summaries(self, ray_start_cluster):
        from ray_tpu.util import state

        @ray_tpu.remote
        def f(x):
            return x

        @ray_tpu.remote
        class A:
            def ping(self):
                return 1

        a = A.remote()
        ray_tpu.get([f.remote(i) for i in range(3)] + [a.ping.remote()])

        nodes = state.list_nodes()
        assert len(nodes) == 4 and all(n["state"] == "ALIVE" for n in nodes)
        actors = state.list_actors()
        assert any(x["class_name"] == "A" for x in actors)
        tasks = state.list_tasks()
        assert any(t["name"].endswith("f") for t in tasks)
        assert state.summarize_tasks().get("FINISHED", 0) >= 3
        summary = state.cluster_summary()
        assert summary["alive_nodes"] == 4


class TestCli:
    def test_status_and_list(self):
        import os

        env = {**__import__("os").environ, "JAX_PLATFORMS": "cpu"}
        out = subprocess.run(
            [sys.executable, "-m", "ray_tpu.scripts", "--num-cpus", "2", "status"],
            capture_output=True, text=True, timeout=120, cwd="/root/repo", env=env,
        )
        assert out.returncode == 0, out.stderr
        data = json.loads(out.stdout[out.stdout.index("{"):])
        assert data["alive_nodes"] >= 1


def test_cross_process_trace_propagation():
    """Spans propagate submit -> execute across PROCESS boundaries: a task
    tree submitted under a driver span shares one trace_id, parent links
    form the chain, and worker-side events reach the timeline through the
    batched task-event pipeline (tracing_helper.py + task_event_buffer.cc
    analogs)."""
    import time

    import ray_tpu
    from ray_tpu.core import runtime as runtime_mod
    from ray_tpu.core.cluster import Cluster, connect
    from ray_tpu.util import tracing

    cluster = Cluster(num_nodes=1, resources_per_node={"CPU": 2})
    try:
        core = connect(cluster.gcs_address)
        try:
            @ray_tpu.remote
            def child():
                return "leaf"

            @ray_tpu.remote
            def parent_task():
                return ray_tpu.get(child.remote(), timeout=120)

            with tracing.span("root", runtime=core) as (trace_id, root_span):
                assert ray_tpu.get(parent_task.remote(),
                                   timeout=240) == "leaf"
            # worker event buffers flush once a second
            def by_suffix():
                out = {}
                for e in ray_tpu.timeline():
                    for want in ("root", "parent_task", "child"):
                        if e["name"] == want or e["name"].endswith(want):
                            out[want] = e
                return out

            deadline = time.time() + 15
            while time.time() < deadline:
                named = by_suffix()
                if {"root", "parent_task", "child"} <= set(named):
                    break
                time.sleep(0.5)
            named = by_suffix()
            assert {"root", "parent_task", "child"} <= set(named), named.keys()
            p = named["parent_task"]["args"]
            c = named["child"]["args"]
            assert p["trace_id"] == trace_id
            assert c["trace_id"] == trace_id
            assert p["parent_span_id"] == root_span
            # child's parent is parent_task's span (the task id prefix)
            assert c["parent_span_id"] is not None
            assert c["parent_span_id"] != root_span
        finally:
            core.shutdown()
            runtime_mod._global_runtime = None
    finally:
        cluster.shutdown()
