"""Per-process metrics exporter + built-in framework metrics.

The process-local half of the cluster metrics plane (the reference's
per-node metrics agent, ``_private/metrics_agent.py`` + ``src/ray/stats/``):
a :class:`MetricsExporter` thread snapshots this process's ``util.metrics``
registry every ``metrics_export_interval_s`` and ships it to the GCS as a
coalescable one-way notify; the GCS's :class:`~ray_tpu.util.metrics.
MetricsAggregator` merges the cluster's reports into the dashboard's
``/metrics`` exposition.

This module also owns the BUILT-IN metric instances wired at the framework's
hot paths (created lazily so unused components cost nothing):

- ``ray_tpu_task_phase_s{phase}`` — task lifecycle histogram split into
  submit→start (``queued``), dependency fetch (``args_fetch``), user-code
  runtime (``execute``) and submit→finish (``total``).
- ``ray_tpu_tasks_total{state}`` — finished/failed task counter.
- ``ray_tpu_serve_request_latency_s{deployment}`` / ``ray_tpu_serve_batch_size``
  — Serve data-plane histograms.
- ``ray_tpu_rpc_*`` / ``ray_tpu_object_pull_*`` / ``ray_tpu_collective_*`` —
  gauges mirrored from the existing ad-hoc stats dicts by collector hooks,
  off the hot path (only at export ticks).

Every ``observe`` at a hot path is gated on :func:`metrics_enabled` so
``metrics_export_enabled=0`` reduces instrumentation to one flag check.
"""

from __future__ import annotations

import os
import threading
from typing import Callable, Dict, List, Optional

from ray_tpu.core.config import config
from ray_tpu.util import flightrec
from ray_tpu.util import metrics as um
from ray_tpu.utils.logging import get_logger, log_swallowed

logger = get_logger("metrics")

# Latency-style histogram bounds (seconds): 100us .. 60s, exponential.
_LATENCY_BOUNDS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                   0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
                   60.0)
_BATCH_BOUNDS = (1, 2, 4, 8, 16, 32, 64, 128, 256)

_metrics_lock = threading.Lock()
_metric_cache: Dict[str, um.Metric] = {}


def metrics_enabled() -> bool:
    """Gate for every built-in hot-path observation."""
    try:
        return bool(config().metrics_export_enabled)
    except Exception:  # noqa: BLE001 — config unavailable mid-teardown
        return False


def _metric(cls, name: str, desc: str = "", **kwargs) -> um.Metric:
    """Process-wide singleton per metric name (a second instance of the
    same name would duplicate series in the exposition)."""
    with _metrics_lock:
        m = _metric_cache.get(name)
        if m is None:
            m = cls(name, desc, **kwargs)
            _metric_cache[name] = m
        return m


def gauge(name: str, desc: str = "", tag_keys=()) -> um.Gauge:
    """Cached process-wide Gauge — for collectors mirroring ad-hoc stats."""
    return _metric(um.Gauge, name, desc, tag_keys=tag_keys)


def counter(name: str, desc: str = "", tag_keys=()) -> um.Counter:
    """Cached process-wide Counter — for collectors mirroring monotonic
    ad-hoc totals (inc by positive delta only)."""
    return _metric(um.Counter, name, desc, tag_keys=tag_keys)


def mirror_stats_gauge(name: str, desc: str, stats: Dict[str, float]) -> None:
    """Mirror an ad-hoc stats dict into one gauge with a ``counter`` tag per
    key — the shared shape of every stats-dict collector."""
    g = gauge(name, desc, tag_keys=("counter",))
    for key, val in stats.items():
        g.set(float(val), {"counter": key})


def gang_placement_hist() -> um.Histogram:
    """Gang placement latency, reserve→commit, tagged by planner path
    (``gang`` atomic block reservation vs ``2pc`` legacy per-bundle)."""
    return _metric(
        um.Histogram, "ray_tpu_gang_placement_s",
        "Placement-group gang placement latency (reserve to commit)",
        boundaries=_LATENCY_BOUNDS, tag_keys=("path",))


def gang_preemptions_total() -> um.Counter:
    return _metric(um.Counter, "ray_tpu_gang_preemptions_total",
                   "Gangs revoked to make room for higher gang_priority "
                   "capacity (serve SLO pressure)")


def task_phase_hist() -> um.Histogram:
    return _metric(
        um.Histogram, "ray_tpu_task_phase_s",
        "Task lifecycle phase durations (queued/args_fetch/execute/total)",
        boundaries=_LATENCY_BOUNDS, tag_keys=("phase",))


def tasks_total() -> um.Counter:
    return _metric(um.Counter, "ray_tpu_tasks_total",
                   "Tasks executed, by terminal state",
                   tag_keys=("state",))


def serve_request_hist() -> um.Histogram:
    return _metric(
        um.Histogram, "ray_tpu_serve_request_latency_s",
        "Serve replica request latency", boundaries=_LATENCY_BOUNDS,
        tag_keys=("deployment",))


def serve_ttft_hist() -> um.Histogram:
    return _metric(
        um.Histogram, "ray_tpu_serve_ttft_s",
        "LLM serving time-to-first-token (request submit to first token), "
        "phase-split: total | queued | prefill | decode",
        boundaries=_LATENCY_BOUNDS, tag_keys=("deployment", "phase"))


def jit_compiles_total() -> um.Counter:
    return _metric(um.Counter, "ray_tpu_jit_compiles_total",
                   "XLA compilations observed by jitcheck, by the "
                   "file:line that constructed the jitted callable",
                   tag_keys=("site",))


def jit_compile_seconds_total() -> um.Counter:
    return _metric(um.Counter, "ray_tpu_jit_compile_seconds_total",
                   "Cumulative XLA backend-compile wall seconds observed "
                   "by jitcheck, by construction site",
                   tag_keys=("site",))


def serve_tokens_total() -> um.Counter:
    return _metric(um.Counter, "ray_tpu_serve_tokens_total",
                   "LLM serving decoded tokens delivered to requests",
                   tag_keys=("deployment",))


def serve_kv_hit_tokens_total() -> um.Counter:
    return _metric(um.Counter, "ray_tpu_serve_kv_hit_tokens_total",
                   "Prompt tokens served from the paged KV prefix cache "
                   "(prefill FLOPs avoided)",
                   tag_keys=("deployment",))


def serve_shed_total() -> um.Counter:
    return _metric(um.Counter, "ray_tpu_serve_shed_total",
                   "Requests shed by serve admission control, by class "
                   "(saturated=admission queues over limit, quota=tenant "
                   "over its per-tenant cap)",
                   tag_keys=("deployment", "reason"))


def observe_shed(deployment: str, reason: str) -> None:
    """Count one shed request (router/handle/engine Saturated raises)."""
    flightrec.record("serve", deployment, f"shed {reason}")
    if metrics_enabled():
        serve_shed_total().inc(1, {"deployment": deployment,
                                   "reason": reason})


def cluster_histogram(name: str, tags: Dict[str, str]) -> Optional[dict]:
    """Cluster-merged cumulative histogram from the GCS aggregator —
    ``{"bounds", "buckets", "sum", "count"}`` summed across every live
    process's series matching ``tags`` (see
    :meth:`~ray_tpu.util.metrics.MetricsAggregator.histogram_merged`).

    The read path the serve controller's SLO loop uses for the
    ``ray_tpu_serve_ttft_s`` override: a direct aggregator call on the
    in-process runtime, one ``metrics_histogram`` RPC on a multiprocess
    cluster. None when the runtime is down, the metric has no live
    samples, or the deployment hasn't reported yet — callers must treat
    the signal as absent, never as zero."""
    try:
        from ray_tpu.core.runtime import get_runtime

        return get_runtime().gcs.metrics_histogram(name, dict(tags))
    except Exception:  # noqa: BLE001 — rollup is advisory: no runtime /
        return None    # GCS mid-restart / pre-PR-13 server without the RPC


def serve_kv_block_occupancy() -> um.Gauge:
    return _metric(um.Gauge, "ray_tpu_serve_kv_block_occupancy",
                   "Paged KV pool blocks by state "
                   "(active=pinned, cached=prefix-reusable, free)",
                   tag_keys=("deployment", "state"))


def dag_tick_hist() -> um.Histogram:
    return _metric(
        um.Histogram, "ray_tpu_dag_tick_s",
        "Compiled-DAG tick latency (execute write to result fetch)",
        boundaries=_LATENCY_BOUNDS)


def serve_batch_hist() -> um.Histogram:
    return _metric(um.Histogram, "ray_tpu_serve_batch_size",
                   "Serve @batch flush sizes", boundaries=_BATCH_BOUNDS)


def rl_env_steps_total() -> um.Counter:
    return _metric(um.Counter, "ray_tpu_rl_env_steps_total",
                   "Environment steps consumed by RL training")


def rl_learner_idle_hist() -> um.Histogram:
    return _metric(
        um.Histogram, "ray_tpu_rl_learner_idle_s",
        "Time the RL learner waits for a sample batch per consume "
        "(sum/total-time is the sampling-bound fraction)",
        boundaries=_LATENCY_BOUNDS)


def rl_inference_batch_hist() -> um.Histogram:
    return _metric(um.Histogram, "ray_tpu_rl_inference_batch_size",
                   "InferenceActor forward-batch sizes (requests per flush)",
                   boundaries=_BATCH_BOUNDS)


# Precomputed tag keys for the per-task hot path (one merge/validate/sort
# per phase name per process instead of per task execution).
_phase_keys: Dict[str, tuple] = {}
_state_keys: Dict[str, tuple] = {}


def observe_task_phases(phases: Dict[str, float],
                        ok: bool = True) -> None:
    """Record one task execution's phase durations (worker execute loops
    call this with whatever phases they could stamp)."""
    if not metrics_enabled():
        return
    h = task_phase_hist()
    for phase, dur in phases.items():
        if dur is not None and dur >= 0:
            key = _phase_keys.get(phase)
            if key is None:
                key = _phase_keys[phase] = h.tag_key({"phase": phase})
            h.observe_key(dur, key)
    state = "FINISHED" if ok else "FAILED"
    skey = _state_keys.get(state)
    if skey is None:
        skey = _state_keys[state] = tasks_total().tag_key({"state": state})
    tasks_total().inc_key(1, skey)


# ---------------------------------------------------------------------------
# Default collectors: mirror existing ad-hoc stats into gauges at export time
# ---------------------------------------------------------------------------

_default_collectors_installed = False


def _collect_rpc_send_stats() -> None:
    from ray_tpu.core import rpc

    mirror_stats_gauge(
        "ray_tpu_rpc_send",
        "RPC frame-send counters (frames/syscalls/bytes/batches + "
        "frames_per_syscall)", rpc.send_stats())


def _collect_pull_stats() -> None:
    from ray_tpu.core import object_transfer

    mirror_stats_gauge(
        "ray_tpu_object_pull",
        "Object-plane pull counters (bytes/chunks/reassigned "
        "ranges/failed sources)", object_transfer.pull_stats())


def _collect_collective_stats() -> None:
    try:
        from ray_tpu.parallel import collectives
    except Exception:  # noqa: BLE001 — optional dependency surface
        return
    groups = collectives.all_group_stats()
    if not groups:
        return
    g = _metric(um.Gauge, "ray_tpu_collective_bytes",
                "Per-group collective byte counters by traffic kind",
                tag_keys=("group", "counter"))
    for name, st in groups.items():
        for key, val in st.items():
            g.set(float(val), {"group": name, "counter": key})


def ensure_default_collectors() -> None:
    """Install the process-wide collectors exactly once."""
    global _default_collectors_installed
    with _metrics_lock:
        if _default_collectors_installed:
            return
        _default_collectors_installed = True
    um.register_collector(_collect_rpc_send_stats)
    um.register_collector(_collect_pull_stats)
    um.register_collector(_collect_collective_stats)


# ---------------------------------------------------------------------------
# The exporter thread
# ---------------------------------------------------------------------------


class MetricsExporter:
    """Ships this process's registry to the GCS every export interval.

    ``report`` is ``callable(node_id, component, pid, snapshot)`` — an RPC
    notify for remote processes, a direct aggregator call for the GCS/
    in-process runtime. Failures are swallowed and retried next tick, so a
    GCS restart just costs a few missed reports: the next successful tick
    re-registers the full snapshot (reports are stateless).
    """

    def __init__(self, report: Callable[[str, str, int, list], None],
                 node_id: str, component: str,
                 collectors: Optional[List[Callable[[], None]]] = None):
        self._report = report
        self._node_id = node_id
        self._component = component
        self._collectors = list(collectors or [])
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "MetricsExporter":
        if not metrics_enabled():
            return self
        ensure_default_collectors()
        self._thread = threading.Thread(
            target=self._loop, name=f"metrics-export-{self._component}",
            daemon=True)
        self._thread.start()
        return self

    @staticmethod
    def _interval() -> float:
        try:
            return max(0.05, float(config().metrics_export_interval_s))
        except Exception:  # noqa: BLE001 — config unavailable mid-teardown
            return 10.0

    def _loop(self) -> None:
        # First flush immediately: a short-lived process (autoscaled worker,
        # early crash) must appear in the exposition without surviving a
        # full interval. Then re-read the interval every tick — daemons
        # adopt the cluster config AFTER their exporter starts, and tests
        # shrink the cadence via env.
        self.flush()
        while not self._stop.wait(self._interval()):
            self.flush()

    def flush(self) -> None:
        """One export tick (also called directly by the dashboard's
        /metrics handler so the serving process's own series are fresh)."""
        if not metrics_enabled():
            return
        try:
            for fn in self._collectors:
                try:
                    fn()
                except Exception:  # noqa: BLE001 — a collector must never
                    log_swallowed(logger, "metrics collector")  # kill the tick
            snapshot = um.snapshot_registry()
            self._report(self._node_id, self._component, os.getpid(),
                         snapshot)
        except Exception:  # noqa: BLE001 — GCS down/restarting: retry next tick
            log_swallowed(logger, "metrics export tick")

    def stop(self) -> None:
        """Join the exporter thread (with timeout) rather than abandoning
        it as a daemon: an abandoned exporter holds its GCS client and one
        report slot per restart cycle. Idempotent."""
        self._stop.set()
        thread, self._thread = self._thread, None
        if thread is not None:
            thread.join(timeout=2.0)
            if thread.is_alive():
                # Mid-flush on an unresponsive GCS: the RPC timeout will
                # reap it; don't race a second flush from this thread.
                logger.warning("metrics exporter did not stop in 2s "
                               "(flush in flight); skipping final flush")
                return
            # Final flush: ship the last partial interval's observations
            # (runs on the caller, after the loop thread is parked/joined).
            self.flush()
