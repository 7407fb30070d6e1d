"""Replica actor — hosts one copy of a deployment's callable.

Analog of the reference's ``python/ray/serve/_private/replica.py`` (1,165
lines): wraps the user's class/function, counts ongoing requests (the router's
pow-2 signal), applies ``user_config`` via ``reconfigure``, exposes a health
check, and supports sync functions, async coroutines, and (async) generators
for streaming responses.
"""

from __future__ import annotations

import contextvars
import inspect
import threading
import time
from typing import Any, Callable, Dict, Iterator, Optional

from ray_tpu.util import tracing


class _Stream:
    """One streamed request's account of its way out of the replica, kept
    by the runner thread that produces it (``handle_request_streaming``)."""

    __slots__ = ("items", "source_wait_ns", "publish_ns", "publish_max_ns",
                 "drove_ns", "drove_cpu_ns")

    def __init__(self):
        self.items = 0
        # Inside next(): waiting for the callable's generator.
        self.source_wait_ns = 0
        # Suspended in yield (the sum, and the longest single one): the
        # runtime's store.put and broadcast and its way back for the next.
        self.publish_ns = 0
        self.publish_max_ns = 0
        # See note_driven.
        self.drove_ns = 0
        self.drove_cpu_ns = 0


# The stream the current thread is producing, None outside one.
_STREAM: contextvars.ContextVar[Optional[_Stream]] = contextvars.ContextVar(
    "ray_tpu_serve_stream", default=None)


def note_driven(wall_ns: int, cpu_ns: int) -> None:
    """For code under a streamed request that ran work of OTHER requests on
    the request's thread (the engine's elected driver runs a step for every
    slot): that work's wall and CPU time, which lies inside the stream's
    ``source_wait_ns`` and its thread's CPU clock. The stream's CPU is given
    less it, so the streams and the engine's ``step_host_cpu_s`` never count
    one microsecond twice. A no-op outside a stream."""
    acct = _STREAM.get()
    if acct is not None:
        acct.drove_ns += wall_ns
        acct.drove_cpu_ns += cpu_ns


def _drain_async(agen) -> Iterator:
    """An async generator's items, one event loop for the stream."""
    import asyncio

    loop = asyncio.new_event_loop()
    try:
        while True:
            try:
                yield loop.run_until_complete(agen.__anext__())
            except StopAsyncIteration:
                return
    finally:
        loop.close()


class ReplicaActor:
    def __init__(
        self,
        deployment_name: str,
        serialized_callable: Callable,
        init_args: tuple,
        init_kwargs: dict,
        user_config: Optional[Dict] = None,
    ):
        self.deployment_name = deployment_name
        self._is_function = not inspect.isclass(serialized_callable)
        if self._is_function:
            self._callable = serialized_callable
        else:
            self._callable = serialized_callable(*init_args, **init_kwargs)
        self._ongoing = 0
        self._total = 0
        self._lock = threading.Lock()
        # Cumulative account of the streams' way out (get_metrics; kept in
        # ns): the ended streams' sums, and the accounts of those running,
        # which their threads write without a lock.
        self._streams_ended = dict.fromkeys(
            ("stream_items_total", "stream_source_wait_s", "stream_publish_s",
             "stream_producer_cpu_s"), 0)
        self._streams_live: set = set()
        if user_config is not None:
            self.reconfigure(user_config)

    # -- control plane -------------------------------------------------------
    def reconfigure(self, user_config: Dict) -> bool:
        if not self._is_function and hasattr(self._callable, "reconfigure"):
            self._callable.reconfigure(user_config)
        return True

    def check_health(self) -> bool:
        if not self._is_function and hasattr(self._callable, "check_health"):
            self._callable.check_health()
        return True

    def get_metrics(self) -> Dict[str, float]:
        """``ongoing``/``total`` (the router drain probe's keys) merged with
        the hosted callable's ``get_engine_stats`` (slot occupancy, queue
        depth — KV-occupancy-aware routing), when it exposes one."""
        with self._lock:
            metrics = {"ongoing": float(self._ongoing),
                       "total": float(self._total)}
            streams = dict(self._streams_ended)
            for acct in self._streams_live:
                streams["stream_items_total"] += acct.items
                streams["stream_source_wait_s"] += acct.source_wait_ns
                streams["stream_publish_s"] += acct.publish_ns
        # What the streamed requests' runner threads spent: items yielded,
        # time inside next() of the callable's generator (waiting for the
        # engine), time suspended in yield (the runtime's store.put and
        # notify_all, and its way back for the next item), and, once a
        # stream has ENDED, its thread's CPU time less the engine steps it
        # drove. They count with tracing off too.
        metrics.update({k: v / 1e9 if k.endswith("_s") else float(v)
                        for k, v in streams.items()})
        if not self._is_function and hasattr(self._callable,
                                             "get_engine_stats"):
            try:
                stats = self._callable.get_engine_stats() or {}
                for k, v in stats.items():
                    metrics.setdefault(k, float(v))
            except Exception:  # noqa: BLE001 — a sick engine must not
                from ray_tpu.utils.logging import (get_logger,  # break the
                                                   log_swallowed)  # probe

                log_swallowed(get_logger("serve_replica"),
                              "get_engine_stats")
        return metrics

    def get_state(self) -> Dict[str, Any]:
        """Model ids + load metrics in ONE control-plane RPC — what the
        controller's periodic poll distributes to routers as
        ``replica_load``."""
        return {"model_ids": self.multiplexed_model_ids(),
                "metrics": self.get_metrics()}

    def multiplexed_model_ids(self) -> list:
        """Model ids loaded in this replica (multiplex.py registry)."""
        from ray_tpu.serve.multiplex import loaded_model_ids

        return loaded_model_ids()

    # -- data plane ----------------------------------------------------------

    def _trace_queue_wait(self, kwargs) -> Optional[tuple]:
        """Emit the handle-submit → replica-pickup span and return the
        request's context (the parent of the replica's spans for it). The
        handle injects ``_trace_submit_ts`` only into SAMPLED requests, so
        untraced calls pay one dict-pop here, nothing else, and get None."""
        submit_ts = kwargs.pop("_trace_submit_ts", None)
        if submit_ts is None:
            return None
        ctx = tracing.current_context()
        if ctx is not None:
            # The submit stamp is another process's wall clock: under skew
            # it can land after now, and the span then has no length.
            end = tracing.now_ns()
            tracing.emit("serve.replica_queue", ctx,
                         start=min(tracing.ns_of_wall(submit_ts), end),
                         end=end,
                         attrs={"deployment": self.deployment_name})
        return ctx

    def handle_request(self, method_name: str, *args, **kwargs):
        from ray_tpu.serve import multiplex

        self._trace_queue_wait(kwargs)
        model_id = kwargs.pop("_multiplexed_model_id", "")
        token = multiplex.set_current_model_id(model_id)
        with self._lock:
            self._ongoing += 1
            self._total += 1
        started = time.monotonic()
        try:
            target = self._resolve_method(method_name)
            result = target(*args, **kwargs)
            if inspect.iscoroutine(result):
                import asyncio

                result = asyncio.run(result)
            if inspect.isgenerator(result):
                # materialize sync generators; streaming goes through
                # handle_request_streaming
                return list(result)
            return result
        finally:
            multiplex.reset_current_model_id(token)
            with self._lock:
                self._ongoing -= 1
            self._observe_latency(time.monotonic() - started)

    def _observe_latency(self, elapsed_s: float) -> None:
        """Per-deployment request latency histogram (metrics plane)."""
        from ray_tpu.core.metrics_export import (metrics_enabled,
                                                 serve_request_hist)

        if metrics_enabled():
            serve_request_hist().observe(
                elapsed_s, {"deployment": self.deployment_name})

    def dag_call(self, value):
        """Single-arg data-plane entry for PRECOMPILED pipeline DAGs
        (serve.run_pipeline(compiled=True)): the replica parks in a
        resident compiled-DAG loop reading this method's input from a
        mutable channel instead of taking per-request actor RPCs. Keeps
        the same ongoing/total bookkeeping and latency histogram as
        handle_request so autoscaling metrics and dashboards stay
        truthful."""
        import asyncio

        with self._lock:
            self._ongoing += 1
            self._total += 1
        started = time.monotonic()
        try:
            result = self._resolve_method("__call__")(value)
            if inspect.iscoroutine(result):
                result = asyncio.run(result)
            return result
        finally:
            with self._lock:
                self._ongoing -= 1
            self._observe_latency(time.monotonic() - started)

    def handle_request_streaming(self, method_name: str, *args, **kwargs):
        """Generator method: yields items (streamed via ObjectRefGenerator)."""
        from ray_tpu.serve import multiplex

        ctx = self._trace_queue_wait(kwargs)
        model_id = kwargs.pop("_multiplexed_model_id", "")
        token = multiplex.set_current_model_id(model_id)
        # The stream's own account: a clock reading either side of every
        # yield, the thread's CPU clock at its two ends; one span a request.
        acct = _Stream()
        stream_token = _STREAM.set(acct)
        with self._lock:
            self._ongoing += 1
            self._total += 1
            self._streams_live.add(acct)
        cpu0 = time.thread_time_ns()
        start = t = tracing.now_ns()
        try:
            target = self._resolve_method(method_name)
            result = target(*args, **kwargs)
            if inspect.isasyncgen(result):
                result = _drain_async(result)
            elif not inspect.isgenerator(result):
                result = iter((result,))
            while True:
                try:
                    item = next(result)
                except StopIteration:
                    break
                waited = tracing.now_ns()
                acct.source_wait_ns += waited - t
                acct.items += 1
                yield item
                t = tracing.now_ns()
                acct.publish_ns += t - waited
                acct.publish_max_ns = max(acct.publish_max_ns, t - waited)
        finally:
            end = tracing.now_ns()
            cpu_ns = time.thread_time_ns() - cpu0 - acct.drove_cpu_ns
            _STREAM.reset(stream_token)
            multiplex.reset_current_model_id(token)
            with self._lock:
                self._ongoing -= 1
                self._streams_live.discard(acct)
                ended = self._streams_ended
                ended["stream_items_total"] += acct.items
                ended["stream_source_wait_s"] += acct.source_wait_ns
                ended["stream_publish_s"] += acct.publish_ns
                ended["stream_producer_cpu_s"] += cpu_ns
            if ctx is not None:
                tracing.emit(
                    "serve.replica_stream", ctx, start=start, end=end,
                    attrs={"items": acct.items,
                           "source_wait_ns": acct.source_wait_ns,
                           "publish_ns": acct.publish_ns,
                           "publish_max_ns": acct.publish_max_ns,
                           "cpu_ns": cpu_ns, "drove_ns": acct.drove_ns,
                           "drove_cpu_ns": acct.drove_cpu_ns})

    def _resolve_method(self, method_name: str) -> Callable:
        if self._is_function:
            return self._callable
        if method_name == "__call__":
            return self._callable
        return getattr(self._callable, method_name)
