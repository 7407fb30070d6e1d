"""Cross-process request tracing — span context rides inside the TaskSpec.

Analog of the reference's OpenTelemetry task tracing
(``python/ray/util/tracing/tracing_helper.py`` — context inject/extract
:169-175, propagated inside the TaskSpec) without the otel dependency:
a (trace_id, span_id, sampled) triple flows submit→execute across
processes, instrumented code paths (serve data plane, compiled-DAG ticks,
traced RPCs, user :func:`span` blocks) emit span events into the GCS
task-event stream (the ``task_event_buffer.cc`` → ``gcs_task_manager.cc``
pipeline), and ``ray_tpu.timeline()`` / ``gcs.trace(trace_id)`` /
``ray-tpu trace`` render the assembled trace.

Cost model: with ``trace_enabled=0`` every potential span costs one flag
check (the ``metrics_export_enabled`` pattern). With tracing on, head-based
sampling (``trace_sample_rate``) is decided ONCE where the trace root is
stamped and the decision is carried in the context — children of an
unsampled root emit nothing instead of starting fresh roots, so a trace is
either fully collected or not at all.

A span is an interval on ONE clock, ``time.perf_counter_ns()`` of its
process (:func:`now_ns`), stamped where the work starts and where it ends.
Every span first lands in a bounded in-memory ring (:func:`recorded` reads
it back in-process: the process that holds the chip is the only one that can
lay its spans on a device trace). Export to the GCS is a side channel: a
short-lived exporter thread drains the ring's pending queue in batches
(workers hand the batch to their task-event buffer: one
``record_task_events`` notify per flush), so an emitter, the engine's step
thread above all, never runs an RPC and never waits on a lock an RPC holds.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import os
import random
import threading
import time
import uuid
from typing import Callable, Iterator, List, Optional, Tuple

# contextvars, not threading.local: async actor methods run as tasks on a
# shared event loop, where thread-locals leak between interleaved
# coroutines — each asyncio task gets its own contextvars copy.
_CTX: contextvars.ContextVar[Optional[Tuple[str, str, bool]]] = \
    contextvars.ContextVar("ray_tpu_trace_ctx", default=None)


# Cached ``config`` accessor: these run per-request on the serve hot path,
# where a sys.modules lookup per call is measurable.
_config_fn: Optional[Callable] = None


def _cfg() -> Callable:
    global _config_fn
    if _config_fn is None:
        from ray_tpu.core.config import config as _config

        _config_fn = _config
    return _config_fn


def trace_enabled() -> bool:
    """Master gate — the one flag check every potential span costs when
    tracing is off."""
    try:
        config = _cfg()
        return bool(config().trace_enabled)
    except Exception:  # noqa: BLE001 — config unavailable mid-teardown
        return False


def current_context() -> Optional[Tuple[str, str, bool]]:
    """(trace_id, span_id, sampled) active in this context, or None."""
    return _CTX.get()


def set_context(ctx: Optional[Tuple[str, str, bool]]) -> None:
    _CTX.set(ctx)


def is_sampled() -> bool:
    """True iff a context is active AND its root sampled this trace."""
    ctx = _CTX.get()
    return bool(ctx is not None and ctx[2])


# Dedicated PRNG for span ids: uuid4 costs ~1.5µs of os.urandom per id and
# a traced serve request mints half a dozen — a seeded Mersenne generator is
# ~10x cheaper and ids need uniqueness, not cryptographic strength. The pid
# check reseeds forked children so parent and child streams diverge.
_rand = random.Random(uuid.uuid4().int)
_rand_pid = os.getpid()


def _new_id() -> str:
    global _rand_pid
    pid = os.getpid()
    if pid != _rand_pid:
        _rand_pid = pid
        _rand.seed(uuid.uuid4().int ^ pid)
    return f"{_rand.getrandbits(64):016x}"


def new_span_id() -> str:
    """A fresh span id — for callers that pre-allocate a span's identity
    (install it as the parent of nested work) and emit() it at finish."""
    return _new_id()


def _decide_sampled() -> bool:
    """Head-based sampling decision — made exactly once, at a trace root."""
    try:
        config = _cfg()
        rate = float(config().trace_sample_rate)
    except Exception:  # noqa: BLE001 — config unavailable mid-teardown
        rate = 1.0
    if rate >= 1.0:
        return True
    if rate <= 0.0:
        return False
    return _rand.random() < rate


def new_root_context() -> Optional[Tuple[str, str, bool]]:
    """Stamp a fresh trace root: None when tracing is gated off, else a
    (trace_id, root_span_id, sampled) triple with the sampling decision
    baked in. The caller owns installing/restoring it via set_context."""
    if not trace_enabled():
        return None
    return (_new_id(), _new_id(), _decide_sampled())


def child_context(ctx: Tuple[str, str, bool], span_id: str) -> Tuple[str, str, bool]:
    """Context for work nested under ``span_id`` of ``ctx``'s trace."""
    return (ctx[0], span_id, ctx[2])


_get_runtime: Optional[Callable] = None


def _node_id() -> str:
    """The runtime's node id when one is attached (timeline ``pid`` lanes
    then group spans by node like task events); the pid otherwise. Resolved
    once per exported batch, on the exporting thread — never per span."""
    global _get_runtime
    try:
        if _get_runtime is None:
            from ray_tpu.core.runtime import get_runtime

            _get_runtime = get_runtime
        rt = _get_runtime()
        nid = (getattr(rt, "current_node_id", None)
               or getattr(rt, "head_node_id", None))
        if nid is not None:
            return nid.hex() if hasattr(nid, "hex") else str(nid)
    except Exception:  # noqa: BLE001 — no runtime yet / mid-teardown
        from ray_tpu.utils.logging import get_logger, log_swallowed

        log_swallowed(get_logger("tracing"), "span node id")
    return f"pid-{os.getpid()}"


# ====================== the span clock ======================

# One clock for every span of a process: ``time.perf_counter_ns()``, the clock
# the engine's ``submitted_at``/``ttft_s`` and a profiler session's host times
# are on. One wall-clock anchor, taken here at import, turns it into the
# ``time`` field of the exported task event, so a span's wall time is DERIVED
# (never a second ``time.time()`` reading at emit) and two processes of one
# host agree to within the anchors' reading jitter.
_ANCHOR_NS = time.perf_counter_ns()
_ANCHOR_WALL = time.time()

now_ns = time.perf_counter_ns


def wall_of(ns: int) -> float:
    """Wall-clock seconds of a ``perf_counter_ns`` reading of this process."""
    return _ANCHOR_WALL + (ns - _ANCHOR_NS) / 1e9


def ns_of_wall(wall: float) -> int:
    """This process's ``perf_counter_ns`` at wall time ``wall`` — how a
    timestamp stamped by another process (``wall_of`` there) lands on the
    local span clock."""
    return _ANCHOR_NS + int(round((wall - _ANCHOR_WALL) * 1e9))


# ====================== the in-memory ring ======================

Span = collections.namedtuple(
    "Span", "name start_ns end_ns span_id parent_id trace_id attrs")

# Every span of the process lands here first — a tuple append, no lock, no
# dict, no runtime lookup — so the hot paths (the engine's step thread) pay
# the same whether or not anything is exported. Bounded, oldest dropped.
RING_MAX = 1 << 16
_RING: "collections.deque[Span]" = collections.deque(maxlen=RING_MAX)


def recorded(since_ns: int = 0) -> List[Span]:
    """The ring's spans that START at or after ``since_ns``, oldest first:
    the in-process reader (a benchmark that holds the chip, a test). The
    ring outlives ``serve.shutdown()`` / ``ray_tpu.shutdown()``."""
    return [s for s in list(_RING) if s.start_ns >= since_ns]


# ====================== batched span export ======================

# Per-process sink override: worker processes point this at their
# _TaskEventBuffer.record so spans ride the existing batched
# record_task_events notify pipeline instead of per-span RPCs.
_SINK: Optional[Callable[[dict], None]] = None


def set_sink(sink: Optional[Callable[[dict], None]]) -> None:
    global _SINK
    _SINK = sink


# Spans awaiting export to the GCS task-event stream. Emitters only append;
# the exporter thread (or an explicit flush()) pops, builds the event dicts
# and ships them — so no emitter ever runs an RPC or waits on _EXPORT_LOCK.
PENDING_MAX = 4096
_PENDING: "collections.deque[Span]" = collections.deque(maxlen=PENDING_MAX)
EXPORT_INTERVAL_S = 0.5
_EXPORT_LOCK = threading.Lock()     # held across drain + ship (an RPC)
_START_LOCK = threading.Lock()      # guards the exporter's start/exit
_exporter_alive = False
# Spans pushed out of a full _PENDING since the last export (the ring still
# has them): the exporter logs the count with the batch it ships.
_dropped = 0


def _after_fork_in_child() -> None:
    """A forked child has the parent's flag and queue but not its exporter
    thread: start clean, so the child's first span starts its own exporter
    and the parent's pending spans are shipped once, by the parent."""
    global _exporter_alive, _dropped, _EXPORT_LOCK, _START_LOCK
    _exporter_alive = False
    _dropped = 0
    _EXPORT_LOCK = threading.Lock()
    _START_LOCK = threading.Lock()
    _PENDING.clear()


os.register_at_fork(after_in_child=_after_fork_in_child)


def _event_of(s: Span, node_id: str) -> dict:
    event = {
        "task_id": s.span_id,
        "name": s.name,
        "state": "FINISHED",
        "kind": "span",
        "time": wall_of(s.end_ns),
        "duration": (s.end_ns - s.start_ns) / 1e9,
        "trace_id": s.trace_id,
        "parent_span_id": s.parent_id,
        "node_id": node_id,
    }
    if s.attrs:
        event["attrs"] = s.attrs
    return event


def _drain(runtime=None) -> int:
    """Export everything pending, as one batch; returns how many spans."""
    with _EXPORT_LOCK:
        batch = []
        while True:
            try:
                batch.append(_PENDING.popleft())
            except IndexError:
                break
        if not batch:
            return 0
        global _dropped
        if _dropped:
            from ray_tpu.utils.logging import get_logger

            get_logger("tracing").warning(
                "%d spans left out of the GCS export: more than %d were "
                "pending (tracing.recorded() still has them)",
                _dropped, PENDING_MAX)
            _dropped = 0
        node_id = _node_id()    # once a batch, not once a span
        events = [_event_of(s, node_id) for s in batch]
        if _SINK is not None:
            try:
                for event in events:
                    _SINK(event)
            except Exception:  # noqa: BLE001 — tracing must never break work
                from ray_tpu.utils.logging import get_logger, log_swallowed

                log_swallowed(get_logger("tracing"), "span sink")
        else:
            _ship(events, runtime)
        return len(batch)


def _export_loop() -> None:
    global _exporter_alive
    while True:
        time.sleep(EXPORT_INTERVAL_S)
        if _drain():
            continue
        with _START_LOCK:
            # Idle: exit instead of parking a thread in every process that
            # ever traced; the next span starts a fresh exporter. The flag
            # drops BEFORE the last look at the queue: an emitter appends
            # and then reads the flag, so its span is either seen here or
            # finds the flag down and starts the next exporter.
            _exporter_alive = False
            if _PENDING:
                _exporter_alive = True
                continue
            return


def _wake_exporter() -> None:
    global _exporter_alive
    with _START_LOCK:
        if _exporter_alive:
            return
        _exporter_alive = True
        threading.Thread(target=_export_loop, name="trace-export",
                         daemon=True).start()


def _ship(batch: list, runtime) -> None:
    try:
        rt = runtime
        if rt is None:
            from ray_tpu.core.runtime import get_runtime

            rt = get_runtime()
        gcs = rt.gcs
        record_batch = getattr(gcs, "record_task_events", None)
        if record_batch is not None:
            record_batch(batch)
        else:
            for event in batch:
                gcs.record_task_event(event)
    except Exception:  # noqa: BLE001 — tracing must never break work
        from ray_tpu.utils.logging import get_logger, log_swallowed

        log_swallowed(get_logger("tracing"), "span export")


def _record(s: Span, runtime=None, export: bool = True) -> None:
    global _dropped
    _RING.append(s)
    if not export:
        return
    if runtime is not None:
        # Explicit-runtime emission (tests, pre-init drivers) delivers NOW —
        # the caller named the destination and may not live to flush later.
        _ship([_event_of(s, _node_id())], runtime)
        return
    if len(_PENDING) >= PENDING_MAX:
        _dropped += 1       # the deque drops its oldest on this append
    _PENDING.append(s)
    if not _exporter_alive:
        _wake_exporter()


def flush(runtime=None) -> None:
    """Ship any pending spans now, on this thread (runtime shutdown / test
    sync point); waits out an export the exporter thread has in flight."""
    _drain(runtime)


# ====================== span emission ======================

def emit(name: str, ctx: Optional[Tuple[str, str, bool]], *, start: int,
         end: int,
         parent_span_id: Optional[str] = None,
         span_id: Optional[str] = None,
         attrs: Optional[dict] = None,
         export: bool = True) -> Optional[str]:
    """Record one finished span under an EXPLICIT context — for code that
    tracks many concurrent requests on one thread (the LLM engine's slot
    loop, DAG stage loops), where the ambient contextvar belongs to a
    different request than the span being recorded.

    ``start``/``end`` are :func:`now_ns` readings taken where the work
    started and ended — never "now, at emit". ``ctx`` is a (trace_id,
    span_id, sampled) triple; the span parents to ``ctx``'s span unless
    ``parent_span_id`` overrides. ``export=False`` keeps the span in the ring
    alone (the engine's step tree: eight spans a step, read in-process and
    never worth a place in the GCS's task-event ring). Returns the new span
    id, or None when the trace is unsampled / ctx is absent."""
    if ctx is None or not ctx[2]:
        return None
    sid = span_id or _new_id()
    _record(Span(name, start, max(start, end), sid,
                 parent_span_id if parent_span_id is not None else ctx[1],
                 ctx[0], attrs), export=export)
    return sid


_annotation_cls = None


def annotation(name: str, **args):
    """A ``jax.profiler.TraceAnnotation`` for ``name``: the same interval,
    written into whatever profiler session is open, above the device lines.
    With no session open it costs one flag check inside the profiler, and
    ``args`` (the event's arguments in the trace) are not encoded."""
    global _annotation_cls
    if _annotation_cls is None:
        from jax.profiler import TraceAnnotation

        _annotation_cls = TraceAnnotation
    return _annotation_cls(name, **args)


@contextlib.contextmanager
def span(name: str, *, runtime=None,
         attrs: Optional[dict] = None) -> Iterator[Tuple[str, str]]:
    """Open a user span: child of the active context (a fresh trace root
    otherwise, with the head-based sampling decision made here). Tasks
    submitted inside inherit the span as parent, across process
    boundaries. The span event lands in the task-event stream — unless the
    root decided not to sample, in which case the context still propagates
    (children inherit the negative decision) but nothing is emitted."""
    parent = current_context()
    if parent is not None:
        trace_id, sampled = parent[0], parent[2]
    else:
        trace_id = _new_id()
        sampled = trace_enabled() and _decide_sampled()
    span_id = _new_id()
    set_context((trace_id, span_id, sampled))
    start = now_ns()
    try:
        yield (trace_id, span_id)
    finally:
        end = now_ns()
        set_context(parent)
        if sampled:
            try:
                _record(Span(name, start, end, span_id,
                             parent[1] if parent else None, trace_id, attrs),
                        runtime)
            except Exception:  # noqa: BLE001 — tracing must never break work
                from ray_tpu.utils.logging import get_logger, log_swallowed

                log_swallowed(get_logger("tracing"), "span finalize")


def context_for_spec() -> Optional[Tuple[str, str, bool]]:
    """What a submitting call should stamp into the TaskSpec."""
    return current_context()
