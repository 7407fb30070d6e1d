"""LLM serving — one continuous-batching engine over a paged KV cache, and
its Serve deployment.

The reference serves LLMs by embedding engines (vLLM) inside replicas;
TPU-native the engine is jitted XLA programs (``models/generate.py
PagedGenerator``: ``paged_prefill`` per prompt bucket, ``paged_decode`` per
chunk size) over a shared pool of ``serve_kv_block_tokens``-sized KV blocks.
S independent sequences address the pool through per-slot block tables, and
every decode dispatch advances ALL active slots at once — the matmuls run at
batch S instead of batch 1, which is the difference between feeding the MXU
and starving it. A host-side ``KVBlockManager`` keeps the blocks' refcounts
and the hash table that lets a prompt reuse the blocks of an earlier one.

Scheduling is iteration-level (the vLLM/Orca policy): each engine step

1. retires finished slots by COUNT (max_new_tokens dispatched, or no room
   for another chunk before ``max_len`` — ``length_cap``) and immediately
2. admits queued prompts into the free slots, bounded by a prefill token
   budget per step (``serve_llm_prefill_tokens``) so a burst of long
   prompts can't starve in-flight decode; an admission prefills only the
   suffix the prefix cache does not already hold, and a prompt the pool has
   no blocks for goes back to the head of the queue; then
3. dispatches ONE batched decode chunk, and only then
4. fetches the tokens of the chunk dispatched ONE STEP EARLIER and
   distributes each slot's tokens to its request's queue.

So one decode program is always queued behind the one that runs, and the
host's work of a step lies under it: no stop condition reads a token's value
(there is no stop token), and the device threads last tokens, keys and pool
from program to program itself.

There is no engine thread: the step loop is driven by whichever request
thread wins a non-blocking try-lock (``drive``), so an idle engine owns no
resources (leak-check clean) and a busy one is stepped exactly as fast as
its consumers read. Admission control sheds with :class:`~ray_tpu.serve.
errors.Saturated` once ``max_queue`` requests are already waiting for a
slot (beyond those the free slots will take over the next steps).

Prompts pad to a power-of-two bucket (one prefill compile per bucket, warmed
at replica start), first-token logits are read at the REAL last position,
and decode overwrites pad garbage before the causal mask could ever expose
it.
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time
import weakref
from typing import Callable, Dict, Iterable, List, Optional, Sequence

import jax
import numpy as np

from ray_tpu.devtools import jitcheck
from ray_tpu.models.generate import (KVBlockManager, NoFreeBlocks,
                                     PagedGenerator)
from ray_tpu.serve.errors import Saturated
from ray_tpu.serve.replica import note_driven
from ray_tpu.util import tracing
from ray_tpu.utils.logging import get_logger

logger = get_logger("serve.llm")


def _default_buckets(max_len: int) -> List[int]:
    buckets, b = [], 16
    while b < max_len:
        buckets.append(b)
        b *= 2
    buckets.append(max_len)
    return buckets


def _check_token_ids(prompt: np.ndarray, vocab: int, name: str) -> None:
    """Reject out-of-range token ids at admission. Under jit an out-of-range
    embedding gather fills with NaN, and with a SHARED paged pool that NaN
    outlives the offending request (it spills into the trash block and its
    sequence's cached blocks, poisoning masked reads of every later request
    on the pool) — so a bad id must never reach the device."""
    if int(prompt.min()) < 0 or int(prompt.max()) >= vocab:
        raise ValueError(
            f"engine {name}: prompt token ids must be in [0, {vocab})")


def _shed(name: str, depth: int, limit: int, what: str) -> Saturated:
    """Build the engine-queue-full :class:`Saturated` (and bump the shed
    counter): ``retry_after_s`` estimates the queue's drain time at one
    admitted-item service time per waiting request."""
    from ray_tpu.core.config import config as _get_config
    from ray_tpu.core.metrics_export import observe_shed

    observe_shed(name, "saturated")
    try:
        retry = depth * _get_config().serve_retry_after_item_s
    except Exception:  # noqa: BLE001 — hint is advisory, shed regardless
        retry = None
    return Saturated(
        f"engine {name}: {depth} requests {what} "
        f"(serve_admission_queue_limit={limit})",
        retry_after_s=retry)


class _Request:
    """One in-flight generation: its token queue, slot, and counters.

    ``decode_tokens``/``decode_seconds`` live HERE (not on the engine) so the
    per-request ``decode_tps`` the deployment streams is this request's own
    rate — the engine-level attributes these replaced were shared across
    concurrent streams and raced exactly like ``finish_reason`` once did.
    """

    __slots__ = (
        "prompt", "padded", "real_len", "bucket", "max_new", "temperature",
        "seed", "tokens", "cond", "slot", "emitted", "done", "cancelled",
        "error", "finish_reason", "decode_tokens", "decode_seconds",
        "submitted_at", "ttft_s", "trace_ctx", "queued_s", "prefill_s",
        "out_ids", "hit_tokens",
        "submitted_ns", "prefill_end_ns", "prefill_span",
        "scheduled", "retiring", "blocks",
        "delivered_ns", "pickups", "pickup_lag_ns", "pickup_lag_max_ns",
        "finished_ns",
    )

    def __init__(self, prompt, padded, real_len, bucket, max_new,
                 temperature, seed, cond):
        self.prompt = prompt
        self.padded = padded
        self.real_len = real_len
        self.bucket = bucket
        self.max_new = max_new
        self.temperature = temperature
        self.seed = seed
        self.tokens: collections.deque = collections.deque()
        self.cond = cond
        self.slot: Optional[int] = None
        self.emitted = 0
        # Tokens of the answer that dispatched chunks will deliver: the
        # scheduler's count, ahead of ``emitted`` by the chunk in flight.
        self.scheduled = 0
        # Set when the request is retired by count ("stop"/"length_cap"):
        # its slot is free, its blocks (``blocks``) stay pinned until its
        # last tokens are delivered.
        self.retiring: Optional[str] = None
        self.blocks: List[int] = []
        # The first hand-over of a token's way back: ``_deliver`` stamps
        # ``delivered_ns`` when it extends an EMPTY ``tokens`` (the oldest
        # untaken token's instant), ``drive`` counts each take of a
        # non-empty ``tokens`` and how long after that stamp it came.
        self.delivered_ns = 0
        self.pickups = 0
        self.pickup_lag_ns = 0
        self.pickup_lag_max_ns = 0
        # When a request finished by count: its ``llm.request`` span ends
        # there and is recorded by the consumer's last take, which it has
        # to count.
        self.finished_ns: Optional[int] = None
        self.done = False
        self.cancelled = False
        self.error: Optional[BaseException] = None
        self.finish_reason: Optional[str] = None
        self.decode_tokens = 0
        self.decode_seconds = 0.0
        # One reading, on the span clock: ``ttft_s``, the TTFT histogram
        # phases and the request's spans all count from this instant.
        self.submitted_ns = tracing.now_ns()
        self.submitted_at = self.submitted_ns / 1e9
        self.ttft_s: Optional[float] = None
        # TTFT decomposition (metrics phase labels): submit→admission and
        # the prefill dispatch, stamped by the scheduler.
        self.queued_s = 0.0
        self.prefill_s = 0.0
        self.prefill_end_ns = 0
        # Id of the request's ``llm.prefill`` span, allocated at admission
        # so that ``kv.alloc`` can parent to it before it is recorded.
        self.prefill_span: Optional[str] = None
        # Every delivered token id, in order — the engine registers the
        # finished prompt+output chain in the prefix cache at retire.
        self.out_ids: List[int] = []
        # Prompt tokens the prefix cache already held at admission.
        self.hit_tokens = 0
        # Captured at submit time on the request's own thread; engine spans
        # must use THIS explicit context (the step loop runs on whichever
        # thread won the driver election — its ambient context belongs to a
        # different request). None unless the trace sampled in, and with
        # tracing off: a task that was handed no context runs under one of
        # its own that says "sampled" (``Runtime._adopt_trace``).
        self.trace_ctx = (tracing.current_context()
                          if tracing.trace_enabled() and tracing.is_sampled()
                          else None)

    def decode_tps(self) -> float:
        if self.decode_seconds == 0:
            return 0.0
        return self.decode_tokens / self.decode_seconds


# What JAX reports, per program it builds, through ``jax.monitoring``: seconds
# spent tracing to a jaxpr, lowering to a module, and in the backend compiler
# or reading the persistent cache instead.
_COMPILE_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
}
_COMPILE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
_compile_tls = threading.local()    # .sink: the open program's tallies
_compile_listeners_lock = threading.Lock()
_compile_listeners_on = False


# Cached ``metrics_export`` module: ``_observe`` runs once a step on the
# driver's thread, where an import statement per call is a sys.modules lookup
# and a dozen attribute reads (tracing._cfg caches its accessor the same way).
_metrics_mod = None


def _metrics():
    global _metrics_mod
    if _metrics_mod is None:
        from ray_tpu.core import metrics_export

        _metrics_mod = metrics_export
    return _metrics_mod


def _install_compile_listeners() -> None:
    """Once per process, at the first traced warm-up. JAX compiles on the
    calling thread, so a thread-local sink attributes each event to the
    ``llm.warmup.program`` span open on that thread."""
    global _compile_listeners_on
    with _compile_listeners_lock:
        if _compile_listeners_on:
            return
        _compile_listeners_on = True
    import jax.monitoring as mon

    def on_duration(name: str, duration: float, **kw) -> None:
        sink = getattr(_compile_tls, "sink", None)
        key = _COMPILE_DURATIONS.get(name)
        if sink is not None and key is not None:
            # An event is reported as it ends. Kept as an interval: a nested
            # jit's tracing lies inside its caller's and must count once.
            end = time.perf_counter()
            sink[key].append((end - duration, end))

    def on_event(name: str, **kw) -> None:
        sink = getattr(_compile_tls, "sink", None)
        key = _COMPILE_EVENTS.get(name)
        if sink is not None and key is not None:
            sink[key] += 1

    mon.register_event_duration_secs_listener(on_duration)
    mon.register_event_listener(on_event)


def _union_s(intervals: List[tuple]) -> float:
    """Seconds covered by at least one of the ``(start, end)`` intervals."""
    total, reach = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > reach:
            total += b - max(a, reach)
            reach = b
    return total


class _WarmupTrace:
    """``llm.warmup`` around an engine's warm-up, with one
    ``llm.warmup.program`` child per program it builds: why a replica takes
    the time it takes to come up."""

    def __init__(self, engine: "LLMEngine"):
        self.on = tracing.trace_enabled()
        self.ctx = (engine.trace_id, tracing.new_span_id(), True)
        self.engine = engine.name
        self.programs = 0
        self.start = tracing.now_ns()
        if self.on:
            _install_compile_listeners()

    @contextlib.contextmanager
    def program(self, program: str, bucket: Optional[int] = None):
        if not self.on:
            yield
            return
        sink = {"trace_s": [], "lower_s": [], "backend_s": [],
                "cache_hits": 0, "cache_misses": 0}
        _compile_tls.sink = sink
        start = tracing.now_ns()
        try:
            yield
        finally:
            _compile_tls.sink = None
            self.programs += 1
            attrs = {k: _union_s(v) if isinstance(v, list) else v
                     for k, v in sink.items()}
            attrs["program"] = program
            if bucket is not None:
                attrs["bucket"] = bucket
            tracing.emit("llm.warmup.program", self.ctx, start=start,
                         end=tracing.now_ns(), attrs=attrs)

    def close(self) -> None:
        if self.on:
            tracing.emit("llm.warmup", (self.ctx[0], None, True),
                         span_id=self.ctx[1], start=self.start,
                         end=tracing.now_ns(),
                         attrs={"engine": self.engine,
                                "programs": self.programs})


class _StepTrace:
    """One engine step's clock. A ``perf_counter_ns`` stamp at every phase
    boundary, always, and beside it the thread's CPU time
    (``time.thread_time_ns``): the ``stats()`` counters are sums of them. A
    step runs on one thread and its phases tile it, so a phase's CPU is a
    difference of stamps, and wall less CPU is what the phase spent off the
    CPU (the GIL, a lock, a blocking runtime call). With tracing on the same
    intervals are open ``jax.profiler.TraceAnnotation``s while they run (an
    operator's profiler session shows them above the device lines) and
    become the ``llm.step`` span tree when the step ends.

    Phases tile the step: each ends where the next starts."""

    __slots__ = ("on", "marks", "end_ns", "end_cpu_ns", "attrs", "_open",
                 "prefill_ns", "prefill_cpu_ns")

    def __init__(self, on: bool):
        self.on = on
        self.marks: List[tuple] = []    # (phase, start_ns, cpu_ns), in order
        self.end_ns = self.end_cpu_ns = 0
        self.attrs: Dict = {}
        # Inside the jitted prefill calls of this step's admissions.
        self.prefill_ns = self.prefill_cpu_ns = 0
        self._open: List = []           # the step's annotation, the phase's
        if on:
            # One instant on both clocks, once a step: a profiler session
            # records when this annotation opened on ITS clock and, as the
            # event's argument, the span clock's reading of that instant.
            self._push("llm.step", span_ns=tracing.now_ns())

    def _push(self, name: str, **args) -> None:
        ann = tracing.annotation(name, **args)
        ann.__enter__()
        self._open.append(ann)

    def enter(self, phase: str) -> None:
        if len(self._open) > 1:
            self._open.pop().__exit__(None, None, None)
        self.marks.append((phase, tracing.now_ns(), time.thread_time_ns()))
        if self.on:
            self._push("llm.step." + phase)

    def close(self) -> None:
        # CPU before wall here, wall before CPU in enter(): the step's CPU
        # time can never read longer than its wall time.
        self.end_cpu_ns = time.thread_time_ns()
        self.end_ns = tracing.now_ns()
        while self._open:
            self._open.pop().__exit__(None, None, None)

    def phases(self) -> List[tuple]:
        """(phase, start_ns, end_ns, cpu_ns) of every phase entered."""
        nxt = self.marks[1:] + [(None, self.end_ns, self.end_cpu_ns)]
        return [(name, start, end, cpu_end - cpu)
                for (name, start, cpu), (_, end, cpu_end)
                in zip(self.marks, nxt)]


class _Chunk:
    """A dispatched decode chunk whose tokens are not on the host yet: its
    device arrays and the host's snapshot of whom they are for. Delivery
    goes by ``rows``, never by the engine's slot table at fetch time: a
    slot whose request ends with this chunk is given to the next request
    before this chunk's tokens are fetched."""

    __slots__ = ("arrays", "rows", "start_ns")

    def __init__(self, arrays, rows: List[tuple], start_ns: int):
        # (tokens, the family's count arrays: the decode call's and that
        # step's prefills'), still on the device.
        self.arrays = arrays
        # (slot, request, tokens it may still take) per active slot.
        self.rows = rows
        # When the device could start on it: its dispatch, or the fetch of
        # the chunk it was queued behind.
        self.start_ns = start_ns

    def take(self):
        """The device arrays, for the one ``device_get``; the record keeps
        none of them, so that nothing outlives the fetch (PERF.md §6,
        PR 29: a live reference to a fetched output cost the
        ``longcat-flash-omni`` cell 12 ms of device idle a step)."""
        arrays, self.arrays = self.arrays, None
        return arrays


class LLMEngine:
    """Continuous-batching engine over a PAGED KV cache with prefix reuse:
    S cache slots, caller-driven stepping.

    ``stream``/``generate`` serve one request each; concurrency comes from
    calling ``stream`` from many threads — their sequences SHARE the batched
    decode dispatches instead of queueing behind each other.

    The device half is a shared pool of ``serve_kv_block_tokens``-sized KV
    blocks (:class:`~ray_tpu.models.generate.PagedGenerator`) addressed
    through per-slot block tables, with a host-side :class:`~ray_tpu.models.
    generate.KVBlockManager` doing refcounts and hash-based prefix reuse:

    - admission looks the prompt up in the block-hash table and prefills
      ONLY the uncached suffix (``start_pos = hit_len``) — a shared system
      prompt or multi-turn history costs its prefill FLOPs once;
    - a hit on a retired sequence's partial tail block is copy-on-write:
      the block is duplicated into a private block before the divergent
      suffix writes into it, full-block hits share by refcount alone;
    - when a finished request's last tokens are delivered its
      prompt+output chain is registered so the NEXT turn of the
      conversation hits it;
    - pool exhaustion (after LRU-evicting unpinned cached blocks) requeues
      the request rather than failing it.

    **When a step's tokens are fetched.** A step schedules and dispatches
    chunk k+1 (retire by count, admit, operands, the decode call) BEFORE it
    fetches chunk k's tokens, delivers them and observes: the ``device_get``
    returns when k ends, with k+1 and its prefills already in the device's
    queue. A step that finds nothing to dispatch but a chunk pending fetches
    and delivers it; a step on an engine with nothing pending dispatches and
    returns. The depth is one and is not a setting.

    **When a retired request's blocks return to the pool.** Retirement by
    count frees the SLOT at schedule time, one step before the request's
    last tokens are on the host; its BLOCKS stay pinned until those tokens
    are delivered (the chain is registered with them), then ``finish_reason``
    and ``done`` follow and the ``llm.request`` span ends (its consumer's
    next take records it, with that take counted). Cancellation frees slot
    and blocks at once; tokens of a cancelled request still in flight are
    dropped at delivery.
    """

    def __init__(self, params, config, *,
                 max_len: Optional[int] = None,
                 prompt_buckets: Optional[Sequence[int]] = None,
                 chunk: int = 8,
                 slots: Optional[int] = None,
                 max_queue: Optional[int] = None,
                 name: str = "LLM",
                 block_tokens: Optional[int] = None,
                 pool_blocks: Optional[int] = None,
                 attention_kernel: Optional[str] = None):
        from ray_tpu.core.config import config as _get_config

        knobs = _get_config()
        self.config = config
        self.max_len = max_len or config.max_seq_len
        self.buckets = sorted(prompt_buckets or _default_buckets(self.max_len))
        self.chunk = chunk
        self.slots = int(slots if slots is not None else knobs.serve_llm_slots)
        self.max_queue = int(max_queue if max_queue is not None
                             else knobs.serve_admission_queue_limit)
        self.prefill_budget = int(knobs.serve_llm_prefill_tokens)
        self.name = name
        self.block_tokens = int(block_tokens if block_tokens is not None
                                else knobs.serve_kv_block_tokens)
        self.attention_kernel = str(
            attention_kernel if attention_kernel is not None
            else knobs.serve_paged_attention_kernel)

        self.blocks_per_seq = -(-self.max_len // self.block_tokens)
        num_blocks = int(pool_blocks if pool_blocks is not None
                         else knobs.serve_kv_pool_blocks)
        if not num_blocks:
            # Auto pool size: 2x a full slot set plus the trash block — half
            # the pool can idle as reusable prefix cache under full load.
            num_blocks = 2 * self.slots * self.blocks_per_seq + 1
        self._pg = PagedGenerator(params, config, slots=self.slots,
                                  num_blocks=num_blocks,
                                  block_tokens=self.block_tokens,
                                  max_len=self.max_len,
                                  attention_kernel=self.attention_kernel)
        self.kv = KVBlockManager(num_blocks, self.block_tokens)
        (self._pool, self._slot_state, self._last,
         self._keys) = self._pg.init_state()
        # A family that keeps a state a slot (``PagedFamily.init_slot_state``)
        # is served no prefix hit: rows at position p are usable only with
        # the state at p, which nothing keeps. What the state weighs and how
        # often it moved is counted always and reported (stats()) for such a
        # family alone.
        self._prefix_cache = "prefix_cache" not in self._pg.family.unsupported
        self._state_bytes = sum(a.nbytes for a in self._slot_state)
        self._state_counts = {"state_slot_steps_total": 0,
                              "state_resets_total": 0,
                              "prefix_lookups_refused_total": 0}
        # The family's per-call counts (None for GPT-2): the decode call's
        # and this step's prefills', fetched with the step's tokens.
        self._decode_aux = None
        self._prefill_aux: List = []
        # The chunk dispatched by the step before, unfetched: the loop runs
        # one decode program ahead of the tokens it has read (_step_inner).
        # On the engine because the driver changes between steps.
        self._pending: Optional[_Chunk] = None
        self._aux_totals = {
            key: 0 for n in self._pg.family.aux_counts
            for key in (n.decode, n.prefill) if key}
        self._slot_table = np.zeros((self.slots, self.blocks_per_seq),
                                    np.int32)
        self._slot_blocks: List[List[int]] = [[] for _ in range(self.slots)]
        self._hit_pending = 0  # hit tokens awaiting metric flush (step thread)

        # Lock order: _step_lock (try-acquired, never under others) →
        # _state_lock (request/slot bookkeeping; also every req.cond) →
        # _agg_lock. Device dispatches happen holding only _step_lock.
        self._step_lock = threading.Lock()
        self._state_lock = threading.Lock()
        self._agg_lock = threading.Lock()

        self._waiting: collections.deque = collections.deque()
        self._slot_req: List[Optional[_Request]] = [None] * self.slots
        self._slot_len = [0] * self.slots  # host mirror of device lengths
        self._active = np.zeros(self.slots, bool)
        self._greedy = np.ones(self.slots, bool)
        self._temps = np.zeros(self.slots, np.float32)

        # Aggregate decode counters (get_metrics / decode_tokens_per_sec);
        # the per-request truth lives on each _Request.
        self.decode_tokens = 0
        self.decode_seconds = 0.0
        self.finish_reason = "stop"  # convenience; races under concurrency

        # Flipped by warmup(): from then on every scheduler step runs under
        # jitcheck.steady_state() — zero new XLA compiles, zero implicit
        # device->host reads (enforced when jitcheck is installed).
        self._steady = False

        # The engine's own trace: ``llm.warmup`` and every ``llm.step`` are
        # roots of it (steps belong to no request).
        self.trace_id = tracing.new_span_id()
        # Cumulative counts at the step's boundaries (under _agg_lock;
        # written by the elected driver, read by stats()). The ``_s`` ones
        # are kept in ns here and given in seconds by stats(). What each
        # counts is said in stats(); each has a reader under
        # benchmark/metrics (PERF.md §3 pairs them).
        self._counts = dict.fromkeys((
            "steps_total", "steps_ahead_total", "step_driver_switches_total",
            "step_host_s", "step_host_cpu_s", "step_device_wait_s",
            "step_handoff_s", "prefill_dispatch_s", "prefill_dispatch_cpu_s",
            "slot_steps_total", "slot_steps_offered_total",
            "admit_stopped_budget_total", "admit_stopped_queue_empty_total",
            "admit_stopped_no_slot_total", "admit_stopped_no_blocks_total",
            "admit_starved_total", "admit_blocked_pool_s",
            "stream_pickups_total", "stream_pickup_lag_s",
            "prefill_rows_total", "prefill_pad_rows_total"), 0)
        # The end of the last step if the engine still held a request then
        # (_holds_request_locked), else None: the next step charges the
        # stretch up to its start to step_handoff_s. Written under
        # _state_lock: _cancel clears it when the last request held goes
        # between two steps. And the thread that ran that step (under
        # _step_lock).
        self._held_since_ns: Optional[int] = None
        self._last_driver: Optional[int] = None
        # Start of a step whose admission stopped on NoFreeBlocks with a
        # slot free and a request waiting; charged to admit_blocked_pool_s
        # when the next step starts. Step-thread-owned.
        self._blocked_since_ns: Optional[int] = None

    def set_params(self, params) -> None:
        """Serve new weights of the same shapes (a policy update between
        rollouts; no request may be in flight). The generator makes its
        working tree anew, and the pool goes: cached blocks hold K/V the old
        weights computed, and a prefix hit would splice them into the new
        weights' streams."""
        with self._step_lock:
            self._pg.set_params(params)
            self._reset_device_state()

    def _reset_device_state(self) -> None:
        """A fresh empty engine on the device: after warm-up, and after a
        failed dispatch took the in-flight requests' cache state with it."""
        # Let go of the old arrays first: a deployment fills the chip, and
        # the old and the new pool and slot state do not fit side by side.
        self._pool = self._slot_state = self._last = self._keys = None
        (self._pool, self._slot_state, self._last,
         self._keys) = self._pg.init_state()
        self._decode_aux, self._prefill_aux = None, []
        self._pending = None
        # Pool contents are gone — the prefix cache resets with it.
        self.kv = KVBlockManager(self.kv.num_blocks, self.block_tokens)
        self._slot_table[:] = 0
        self._slot_blocks = [[] for _ in range(self.slots)]

    # -- public single-request surface ---------------------------------------
    def warmup(self) -> None:
        """Compile prefill for every bucket + the decode chunk, then reset —
        TTFT never pays XLA compilation. One program per bucket and one per
        chunk size: greedy vs sampled is an operand, not a recompile."""
        wt = _WarmupTrace(self)
        with self._step_lock:
            zero_row = np.zeros(self.blocks_per_seq, np.int32)  # all trash
            for b in self.buckets:
                with wt.program("paged_prefill", b):
                    pf = self._pg.prefill_fn(b)
                    (self._pool, self._slot_state, self._last, self._keys,
                     _aux) = pf(
                        self._pg.params, self._pool, self._slot_state,
                        self._last, self._keys, zero_row,
                        np.zeros((1, b), np.int32), 0, b, 0, 0)
            with wt.program("paged_decode"):
                df = self._pg.decode_fn(self.chunk)
                (toks, self._pool, self._slot_state, self._last, self._keys,
                 _aux) = df(
                    self._pg.params, self._pool, self._slot_state, self._last,
                    self._keys, np.zeros((self.slots, self.blocks_per_seq),
                                         np.int32),
                    np.zeros(self.slots, np.int32),
                    np.zeros(self.slots, bool), self._greedy, self._temps)
                np.asarray(toks)
            with wt.program("copy_block"):
                cf = self._pg.copy_fn()
                self._pool = cf(self._pool, 0, 0)
            self._reset_device_state()
            self._steady = True
        wt.close()

    def _bucket_for(self, n: int) -> int:
        # One full decode chunk must fit after the prompt: decode always
        # advances in `chunk`-token dispatches, and a slot with no room for
        # one retires as length_cap before emitting anything.
        if n + self.chunk > self.max_len:
            raise ValueError(
                f"prompt of {n} tokens leaves no room for a {self.chunk}-token "
                f"decode chunk within max_len {self.max_len}")
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"prompt of {n} tokens exceeds max_len {self.max_len}")

    def _suffix_bucket(self, n: int) -> int:
        # The suffix prefill's compile bucket — unlike _bucket_for it needs
        # no decode-chunk headroom check (submit already validated the full
        # prompt against max_len).
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def stream(self, prompt_ids: Sequence[int], *, max_new_tokens: int = 32,
               temperature: float = 0.0, seed: int = 0,
               result: Optional[Dict] = None) -> Iterable[int]:
        """Yield generated token ids for ONE request, decoded in shared
        batched chunks with every other in-flight request.

        ``result``, if given, receives ``{"finish_reason", "decode_tps"}`` —
        per-request values; the engine-level ``finish_reason`` attribute is a
        single-stream convenience and races under concurrency.
        """
        if result is None:
            result = {}
        req = self.submit(prompt_ids, max_new_tokens=max_new_tokens,
                          temperature=temperature, seed=seed)

        def run():
            try:
                for tok in self.drive(req):
                    result["decode_tps"] = req.decode_tps()
                    yield tok
            finally:
                result["finish_reason"] = self.finish_reason = (
                    req.finish_reason or "stop")
                if req.ttft_s is not None:
                    result["ttft_s"] = req.ttft_s

        gen = run()
        # The request is submitted EAGERLY (Saturated raises at call time),
        # but an abandoned generator that was never started skips drive()'s
        # cancel-in-finally — close() doesn't enter an unstarted body. The
        # finalizer unqueues it at collection; _cancel is a no-op once done.
        weakref.finalize(gen, self._cancel, req)
        return gen

    def generate(self, prompt_ids: Sequence[int], **kw) -> List[int]:
        return list(self.stream(prompt_ids, **kw))

    # -- request lifecycle ----------------------------------------------------
    def submit(self, prompt_ids: Sequence[int], *, max_new_tokens: int = 32,
               temperature: float = 0.0, seed: int = 0) -> _Request:
        """Validate + enqueue; raises :class:`Saturated` when ``max_queue``
        requests are already waiting for a slot, beyond those the free slots
        will take (0 disables shedding)."""
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        real_len = int(prompt.shape[0])
        if real_len == 0:
            raise ValueError("empty prompt")
        _check_token_ids(prompt, self.config.vocab_size, self.name)
        bucket = self._bucket_for(real_len)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :real_len] = prompt
        req = _Request(prompt, padded, real_len, bucket, int(max_new_tokens),
                       float(temperature), int(seed),
                       threading.Condition(self._state_lock))
        if max_new_tokens <= 0:
            req.done = True
            req.finish_reason = "stop"
            return req
        with self._state_lock:
            # A waiting request that a free slot is there for waits for the
            # step's prefill budget, not for a slot: a burst onto idle slots
            # is admitted over a few steps and must not be shed.
            backlog = len(self._waiting) - self._slot_req.count(None)
            if self.max_queue and backlog >= self.max_queue:
                raise _shed(self.name, backlog, self.max_queue,
                            "already waiting for a slot")
            self._waiting.append(req)
        return req

    def drive(self, req: _Request) -> Iterable[int]:
        """Yield ``req``'s tokens, stepping the engine whenever this thread
        wins the step try-lock (otherwise another request's thread is the
        driver and this one just waits on its queue). Abandoning the
        generator cancels the request and frees its slot."""
        try:
            while True:
                with self._state_lock:
                    out = list(req.tokens)
                    req.tokens.clear()
                    done, err = req.done, req.error
                    if out:
                        self._count_pickup_locked(req)
                    if done:
                        self._emit_finished_locked(req)
                for tok in out:
                    yield tok
                if err is not None:
                    raise err
                if done:
                    return
                if self._step_lock.acquire(False):
                    try:
                        st = self._step()
                    finally:
                        self._step_lock.release()
                    # The step was every slot's, run on this request's
                    # thread: its time is not this stream's.
                    note_driven(st.end_ns - st.marks[0][1],
                                st.end_cpu_ns - st.marks[0][2])
                else:
                    with self._state_lock:
                        if not req.tokens and not req.done:
                            # Timed slice as a safety net only: the exiting
                            # driver hands off via _wake_inflight, and token
                            # arrival notifies directly.
                            # raylint: ignore[blocking-under-lock] — req.cond
                            # wraps _state_lock (Condition(self._state_lock)
                            # in submit), so wait() releases the held lock.
                            req.cond.wait(timeout=0.01)
        finally:
            self._cancel(req)
            # Driver handoff: this thread may have been the stepper — wake
            # every in-flight request so one of them re-elects immediately
            # instead of waiting out a poll slice.
            self._wake_inflight()

    def _count_pickup_locked(self, req: _Request) -> None:
        """The consumer took a non-empty ``tokens``: how long after
        ``_deliver`` made the oldest of them the request's. Under
        _state_lock (the take's own hold)."""
        lag = tracing.now_ns() - req.delivered_ns
        req.pickups += 1
        req.pickup_lag_ns += lag
        req.pickup_lag_max_ns = max(req.pickup_lag_max_ns, lag)
        with self._agg_lock:
            self._counts["stream_pickups_total"] += 1
            self._counts["stream_pickup_lag_s"] += lag

    def _wake_inflight(self) -> None:
        with self._state_lock:
            for r in self._slot_req:
                if r is not None:
                    r.cond.notify_all()
            for r in self._waiting:
                r.cond.notify_all()

    def _cancel(self, req: _Request) -> None:
        """No-op on a finished request; otherwise unqueue/mark-cancelled and
        free its slot for the next admission."""
        with self._state_lock:
            if req.done:
                # Finished by count, its last tokens never taken: the span
                # still has to be recorded.
                self._emit_finished_locked(req)
                return
            req.cancelled = True
            try:
                self._waiting.remove(req)
            except ValueError:
                pass
            slot = req.slot
            if slot is not None:
                self._free_slot_locked(slot)
            # Retired by count with its last chunk unfetched: the blocks it
            # still pins go now, and delivery drops its tokens.
            self._release_blocks_locked(req)
            req.done = True
            if req.finish_reason is None:
                req.finish_reason = "cancelled"
            self._emit_request_span(req)
            req.cond.notify_all()
            if not self._holds_request_locked():
                # The last request held went without a step: what follows
                # is an empty engine's time, nobody's hand-off.
                self._held_since_ns = None

    def _holds_request_locked(self) -> bool:
        """A request in a slot, waiting for one, or retired by count with
        its last chunk unfetched (its consumer still waits for tokens)."""
        due = self._pending
        return (bool(self._waiting)
                or any(r is not None for r in self._slot_req)
                or (due is not None
                    and any(not r.done for _, r, _ in due.rows)))

    def _emit_request_span(self, req: _Request,
                           end_ns: Optional[int] = None) -> None:
        """``llm.request``: submit to finish, whatever the finish was, with
        the consumer's takes so far (its prompt, hit and slot are on its
        ``llm.prefill``). A ring append: safe under _state_lock."""
        if req.trace_ctx is None:
            return
        tracing.emit("llm.request", req.trace_ctx,
                     start=req.submitted_ns,
                     end=tracing.now_ns() if end_ns is None else end_ns,
                     attrs={"tokens": req.emitted,
                            "finish_reason": req.finish_reason,
                            "pickups": req.pickups,
                            "pickup_lag_ns": req.pickup_lag_ns,
                            "pickup_lag_max_ns": req.pickup_lag_max_ns})

    def _emit_finished_locked(self, req: _Request) -> None:
        """Record the ``llm.request`` span of a request that finished by
        count (``_finish_locked`` left its end on it): by the consumer's
        take that found it done, or by ``_cancel`` where the consumer went
        without one. Under _state_lock; idempotent."""
        if req.finished_ns is not None:
            end_ns, req.finished_ns = req.finished_ns, None
            self._emit_request_span(req, end_ns)

    def _free_slot_locked(self, slot: int) -> None:
        """Unpin the slot's blocks and clear its bookkeeping. Under
        _state_lock; idempotent."""
        ids = self._slot_blocks[slot]
        if ids:
            self._slot_blocks[slot] = []
            self.kv.release(ids)
        # Always: a request retired by count took its blocks with it, and a
        # parked slot's writes must land in the trash block, not in theirs.
        self._slot_table[slot, :] = 0
        r = self._slot_req[slot]
        if r is not None:
            r.slot = None
        self._slot_req[slot] = None
        self._slot_len[slot] = 0
        self._active[slot] = False

    def _release_blocks_locked(self, req: _Request) -> None:
        """Unpin what a request retired by count still holds. Under
        _state_lock; idempotent."""
        ids, req.blocks = req.blocks, []
        if ids:
            self.kv.release(ids)

    def _on_retire_locked(self, req: _Request) -> None:
        """A request finished cleanly ("stop"/"length_cap") and its last
        tokens are on the host: publish its chain into the prefix cache.
        Under _state_lock, just before its blocks are released."""
        ids = req.blocks
        if not ids or not self._prefix_cache:
            return
        # Register the finished prompt+output chain (including a partial
        # tail entry) — the conversation's next turn extends exactly this
        # token sequence. Tokens past `emitted` (final-chunk spill) were
        # written to the pool but are NOT part of the chain, and
        # register_chain only publishes blocks fully covered by n_real.
        chain = [int(t) for t in req.prompt] + req.out_ids[:req.emitted]
        n_real = min(len(chain), len(ids) * self.block_tokens)
        self.kv.register_chain(chain, ids, n_real)

    def _retire_locked(self, req: _Request, reason: str) -> None:
        """Retire by count: every token the request may take is dispatched
        ("stop"), or its next chunk would cross ``max_len``
        ("length_cap"). The SLOT is free for the next admission from here;
        the BLOCKS stay pinned on the request until its last tokens are
        delivered (their chain is registered then: it needs the token
        values). Under _state_lock."""
        slot = req.slot
        req.retiring = reason
        req.blocks, self._slot_blocks[slot] = self._slot_blocks[slot], []
        self._free_slot_locked(slot)
        if req.emitted == req.scheduled:    # nothing of it is in flight
            self._finish_locked(req)

    def _finish_locked(self, req: _Request) -> None:
        """A retired request's last tokens are delivered: register its
        chain, return its blocks to the pool, tell its consumer."""
        self._on_retire_locked(req)
        self._release_blocks_locked(req)
        req.finish_reason = req.retiring
        req.done = True
        # ``llm.request`` ends HERE; the take that finds the request done
        # records it, with the pick-up of these last tokens counted.
        req.finished_ns = tracing.now_ns()
        req.cond.notify_all()

    def _fail_inflight(self, err: BaseException) -> None:
        """A device-dispatch failure poisons every in-flight request: their
        cache state is gone. Reset to a fresh empty engine."""
        with self._state_lock:
            victims = list(self._waiting) + [r for r in self._slot_req
                                             if r is not None]
            if self._pending is not None:
                # Requests retired by count whose last chunk was in flight
                # hold no slot any more.
                victims += [r for _, r, _ in self._pending.rows]
            self._waiting.clear()
            self._held_since_ns = None      # nothing is held any more
            for slot in range(self.slots):
                self._free_slot_locked(slot)
            for r in victims:
                if r.done:
                    continue
                r.blocks = []       # the manager they pin dies below
                r.error = err
                r.done = True
                if r.finish_reason is None:
                    r.finish_reason = "error"
                self._emit_request_span(r)
                r.cond.notify_all()
        self._reset_device_state()

    # -- the iteration-level scheduler ----------------------------------------
    def _step(self) -> _StepTrace:
        # Called holding _step_lock (the elected driver). Post-warmup the
        # step runs under the steady-state contract: any new XLA compile or
        # implicit device->host read is a violation (recorded when jitcheck
        # is installed; steady_state() is a no-op otherwise).
        st = _StepTrace(tracing.trace_enabled())
        st.enter("retire")
        held = self._held_since_ns
        st.attrs["handoff_ns"] = (st.marks[0][1] - held
                                  if held is not None else 0)
        if self._blocked_since_ns is not None:
            # The step before stopped admitting on an exhausted pool: the
            # whole stretch from its start to this one's is time a free slot
            # stood empty for want of blocks.
            with self._agg_lock:
                self._counts["admit_blocked_pool_s"] += (
                    st.marks[0][1] - self._blocked_since_ns)
            self._blocked_since_ns = None
        try:
            try:
                if self._steady:
                    with jitcheck.steady_state():
                        self._step_inner(st)
                else:
                    self._step_inner(st)
            except BaseException as err:
                self._fail_inflight(err)
                raise
        finally:
            st.close()      # whatever happened, no annotation stays open
        self._record_step(st)
        return st

    def _record_step(self, st: _StepTrace) -> None:
        """Fold one finished step into the counters and, traced, record it
        as ``llm.step`` with one child per phase. The stretch between one
        step's end and the next one's start (the driver yielding its own
        tokens, the election of the next driver) is no step's: it is
        counted as ``step_handoff_s`` when the engine held work through it,
        and rides the later step as ``handoff_ns``."""
        phases = st.phases()
        start = phases[0][1]
        wait = sum(e - b for name, b, e, _ in phases if name == "device_wait")
        cpu = sum(c for name, _, _, c in phases if name != "device_wait")
        a = st.attrs
        rows, stopped = a["batch"], a["admit_stopped"]
        me = threading.get_ident()
        with self._agg_lock:
            c = self._counts
            c["steps_total"] += 1 if rows else 0
            c["steps_ahead_total"] += a["ahead"]
            c["step_driver_switches_total"] += self._last_driver not in (
                None, me)
            c["admit_stopped_" + stopped + "_total"] += 1
            # A decode went out with a slot empty and nobody waiting for it.
            c["admit_starved_total"] += (
                stopped == "queue_empty" and 0 < rows < self.slots)
            c["step_device_wait_s"] += wait
            c["step_host_s"] += st.end_ns - start - wait
            c["step_host_cpu_s"] += cpu
            c["step_handoff_s"] += a["handoff_ns"]
            c["prefill_dispatch_s"] += st.prefill_ns
            c["prefill_dispatch_cpu_s"] += st.prefill_cpu_ns
            # Token steps the decode program advanced active slots by, and
            # those it had slots for.
            c["slot_steps_total"] += rows * self.chunk
            c["slot_steps_offered_total"] += (
                self.slots * self.chunk if rows else 0)
            self._state_counts["state_slot_steps_total"] += (
                a.get("state_slots", 0) * self.chunk)
        self._last_driver = me
        with self._state_lock:
            self._held_since_ns = (
                st.end_ns if self._holds_request_locked() else None)
        if not st.on:
            return
        ctx = (self.trace_id, None, True)
        a["engine"] = self.name
        a["driver"] = threading.current_thread().name
        # Ring-only: eight spans a step whatever ``trace_sample_rate`` says
        # would crowd the requests an operator did sample out of the GCS's
        # task-event ring. ``tracing.recorded()`` and a profiler session
        # (the annotations) are where a step is read.
        sid = tracing.emit("llm.step", ctx, start=start, end=st.end_ns,
                           attrs=a, export=False)
        for name, b, e, cpu_ns in phases:
            tracing.emit("llm.step." + name, ctx, start=b, end=e,
                         parent_span_id=sid, attrs={"cpu_ns": cpu_ns},
                         export=False)

    def _step_inner(self, st: _StepTrace) -> None:
        """Schedule and dispatch the next chunk, THEN fetch and deliver the
        one dispatched a step earlier (``_pending``). Nothing the next chunk
        needs depends on the values of the tokens in flight: a request
        stops on counts (``max_new``, ``max_len``), the decode program
        reads each slot's last token from device state, and pool, keys and
        last tokens are threaded from program to program as device arrays.
        So the host's work of a step (retire, admit, operands, deliver, the
        hand-off between drivers) lies under a running decode program. The
        look-ahead is one chunk, and not a setting: a second would add a
        whole chunk to every new request's wait for admission and hide
        nothing more."""
        st.attrs.update(batch=0, tokens=0, ahead=False)
        # 1. Retire, by count: a request all of whose tokens are dispatched
        #    ends as stop, a slot whose next chunk would cross max_len as
        #    length_cap BEFORE dispatch (no partial chunks — shapes stay
        #    static), and cancelled slots free immediately. The slot is
        #    free from here; a request whose last chunk is still in flight
        #    finishes when this step delivers it.
        with self._state_lock:
            for slot in range(self.slots):
                req = self._slot_req[slot]
                if req is None:
                    continue
                if req.cancelled:
                    self._free_slot_locked(slot)
                elif req.scheduled >= req.max_new:
                    self._retire_locked(req, "stop")
                elif self._slot_len[slot] + self.chunk > self.max_len:
                    self._retire_locked(req, "length_cap")

        # 2. Admit queued prompts into free slots under the prefill budget.
        #    The FIRST admission always goes through — the budget bounds how
        #    much prefill work piles into one step, never progress.
        st.enter("admit")
        admitted_tokens = 0
        admitted = 0
        while True:
            with self._state_lock:
                free = next((s for s in range(self.slots)
                             if self._slot_req[s] is None), None)
                if not self._waiting:
                    stopped = "queue_empty"
                    break
                if free is None:
                    stopped = "no_slot"
                    break
                nxt = self._waiting[0]
                # What this admission charges against the budget: the bucket
                # of the suffix the prefix cache does not already hold.
                hit = (self.kv.peek_hit_len([int(t) for t in nxt.prompt])
                       if self._prefix_cache else 0)
                cost = self._suffix_bucket(max(1, nxt.real_len - hit))
                if admitted_tokens and (
                        admitted_tokens + cost > self.prefill_budget):
                    stopped = "budget"
                    break
                self._waiting.popleft()
                if nxt.cancelled:
                    continue
                nxt.slot = free
                self._slot_req[free] = nxt
                self._slot_len[free] = nxt.real_len
                self._active[free] = True
                self._greedy[free] = nxt.temperature <= 0
                self._temps[free] = nxt.temperature if nxt.temperature > 0 else 0.0
            t_admit = tracing.now_ns()
            if nxt.trace_ctx is not None:
                nxt.prefill_span = tracing.new_span_id()
            try:
                self._dispatch_prefill(nxt, free, st)
            except NoFreeBlocks:
                # Paged pool exhausted even after cache eviction: put the
                # request back at the head and stop admitting — in-flight
                # retires free blocks, and the first admission of a step is
                # exempt from the budget so progress is guaranteed once
                # blocks return.
                with self._state_lock:
                    self._free_slot_locked(free)
                    if not nxt.cancelled:
                        self._waiting.appendleft(nxt)
                stopped = "no_blocks"
                self._blocked_since_ns = st.marks[0][1]
                break
            nxt.prefill_end_ns = tracing.now_ns()
            nxt.queued_s = (t_admit - nxt.submitted_ns) / 1e9
            nxt.prefill_s = (nxt.prefill_end_ns - t_admit) / 1e9
            if nxt.trace_ctx is not None:
                tracing.emit(
                    "llm.admission_wait", nxt.trace_ctx,
                    start=nxt.submitted_ns, end=t_admit,
                    attrs={"slot": free, "engine": self.name})
                tracing.emit(
                    "llm.prefill", nxt.trace_ctx, span_id=nxt.prefill_span,
                    start=t_admit, end=nxt.prefill_end_ns,
                    attrs={"slot": free, "bucket": nxt.bucket,
                           "prompt_len": nxt.real_len,
                           "hit_tokens": nxt.hit_tokens,
                           "state_reset": bool(self._slot_state)})
            admitted_tokens += cost
            admitted += 1
        st.attrs.update(admitted=admitted, admit_stopped=stopped)

        st.enter("operands")
        chunk: Optional[_Chunk] = None
        rows: List[tuple] = []      # (slot, request, tokens it may take)
        with self._state_lock:
            if any(r is not None for r in self._slot_req):
                active = self._active.copy()
                greedy = self._greedy.copy()
                temps = self._temps.copy()
                # Cancel paths mutate the block tables and lengths, so they
                # are captured atomically with the active mask.
                tables = self._slot_table.copy()
                lengths = np.asarray(self._slot_len, np.int32)
                # The counts move to dispatch: the next schedule sees the
                # lengths the device will have. A chunk advances every
                # active slot by ``chunk``.
                for slot in np.flatnonzero(active):
                    req = self._slot_req[slot]
                    upto = min(self.chunk, req.max_new - req.scheduled)
                    req.scheduled += upto
                    self._slot_len[slot] += self.chunk
                    rows.append((int(slot), req, upto))
            elif self._pending is None:
                # Nothing in flight (and so nothing pins a block): the pool
                # cannot be what holds an admission back.
                self._blocked_since_ns = None

        # 3. One batched decode chunk advancing every active slot, enqueued
        #    behind the chunk that runs.
        if rows:
            st.enter("dispatch")
            st.attrs.update(batch=len(rows), ahead=self._pending is not None)
            if self._slot_state:
                st.attrs["state_slots"] = len(rows)
            # No local names the tokens: the record alone holds them, and
            # lets go of them at the fetch.
            chunk = _Chunk(
                (self._run_decode(active, greedy, temps, tables, lengths),
                 self._take_step_aux()),
                rows, st.marks[-1][1])

        # 4. Fetch and deliver the chunk dispatched a step earlier; this
        #    step's waits its turn. ``_pending`` keeps the chunk being
        #    fetched until it is delivered, for _fail_inflight.
        ttfts = (self._deliver(st, self._pending, chunk)
                 if self._pending is not None else [])
        self._pending = chunk
        st.enter("observe")
        self._observe(st.attrs["tokens"], ttfts)

    def _deliver(self, st: _StepTrace, due: _Chunk,
                 queued: Optional[_Chunk]) -> List[tuple]:
        """The step's single device sync (device_wait): ``due``'s tokens,
        which returns when ``due`` ends, while ``queued`` (the chunk this
        step dispatched, if any) and its prefills already sit in the
        device's queue. Then each row's tokens go to its request.
        Returns (total, queued, prefill) seconds per first token."""
        st.enter("device_wait")
        host_toks, host_aux = jax.device_get(due.take())
        st.enter("deliver")
        now_ns = st.marks[-1][1]
        dt = (now_ns - due.start_ns) / 1e9
        now = now_ns / 1e9
        if queued is not None:
            queued.start_ns = now_ns    # the device starts on it about now

        delivered_total = 0
        ttfts: List[tuple] = []
        batch_size = len(due.rows)
        firsts: List[tuple] = []  # sampled requests' (req, slot, ntok)
        with self._state_lock:
            # One reading a chunk, under the lock no consumer can take
            # before: the instant its tokens became their requests'.
            delivered_ns = tracing.now_ns()
            for slot, req, upto in due.rows:
                if req.done:
                    # Cancelled since the dispatch (slot and blocks went
                    # then): its tokens are dropped.
                    continue
                if upto > 0 and req.ttft_s is None:
                    req.ttft_s = now - req.submitted_at
                    ttfts.append((req.ttft_s, req.queued_s, req.prefill_s))
                    if req.trace_ctx is not None:
                        firsts.append((req, slot, upto))
                new_toks = host_toks[slot, :upto].tolist()
                if new_toks and not req.tokens:
                    req.delivered_ns = delivered_ns
                req.tokens.extend(new_toks)
                req.out_ids.extend(new_toks)
                req.emitted += upto
                req.decode_tokens += upto
                req.decode_seconds += dt
                delivered_total += upto
                # A request whose last chunk this is was retired by count
                # in phase 1 of this step (``scheduled`` reached ``max_new``
                # when the chunk was dispatched, a step ago).
                if req.retiring is not None and req.emitted == req.scheduled:
                    self._finish_locked(req)
                else:
                    req.cond.notify_all()
            # What the engine still holds once this step's tokens are out:
            # with nothing in flight, the wait for the next step is the
            # traffic's, not the host's.
            inflight = (sum(r is not None for r in self._slot_req)
                        + len(self._waiting))
        with self._agg_lock:
            self.decode_tokens += delivered_total
            self.decode_seconds += dt
        for req, slot, ntok in firsts:
            # Prefill's end to the first tokens reaching the host: the
            # instant ``ttft_s`` is stamped, so admission_wait + prefill +
            # first_chunk IS the engine's TTFT. What follows (the hand-off
            # to the consumer thread) is the step's deliver phase.
            tracing.emit("llm.first_chunk", req.trace_ctx,
                         start=req.prefill_end_ns, end=now_ns,
                         attrs={"slot": slot, "tokens": ntok,
                                "batch": batch_size})
        st.attrs.update(tokens=delivered_total, inflight_after=inflight)
        self._fold_step_aux(host_aux, st)
        return ttfts

    def _dispatch_prefill(self, req: _Request, slot: int,
                          st: _StepTrace) -> None:
        """Take the prompt's blocks and run its (suffix) prefill into
        ``slot``. May raise :class:`NoFreeBlocks` (pool exhausted) — the
        scheduler requeues the request at the head and stops admitting this
        step. The jitted call's own time goes onto ``st``."""
        bt = self.block_tokens
        t_alloc = tracing.now_ns()
        evicted0 = self.kv.evicted_blocks
        tokens = [int(t) for t in req.prompt]
        if self._prefix_cache:
            full, tail, hit_len = self.kv.lookup(tokens)
        else:
            # Neither looked up nor, below, registered: a hit could not be
            # honoured without the slot state at the hit's position.
            full, tail, hit_len = [], None, 0
        try:
            # The table must cover every position this sequence can ever
            # write: the prompt plus whole decode chunks until max_new is
            # reached (decode always writes full chunks; the finishing
            # chunk's spill past max_new still lands in the pool).
            n_chunks = -(-req.max_new // self.chunk)
            max_written = min(self.max_len,
                              req.real_len + n_chunks * self.chunk)
            need = -(-max_written // bt)
            # Full-block hits are shared in place; a tail hit contributes
            # CONTENT only (its copy-on-write destination is a fresh block),
            # so allocation covers everything beyond the full hits.
            fresh = self.kv.alloc(need - len(full))
        except NoFreeBlocks:
            self.kv.release(full + ([tail] if tail is not None else []))
            raise
        ids = list(full)
        if tail is not None:
            dst = fresh.pop(0)
            cf = self._pg.copy_fn()
            self._pool = cf(self._pool, int(tail), int(dst))
            self.kv.note_cow()
            self.kv.release([tail])  # pin the private copy, not the original
            ids.append(dst)
        ids.extend(fresh)
        if req.trace_ctx is not None:
            # The KV manager's share of the admission: hashing, lookup,
            # allocation (with any eviction) and the copy-on-write dispatch.
            tracing.emit(
                "kv.alloc", req.trace_ctx, parent_span_id=req.prefill_span,
                start=t_alloc, end=tracing.now_ns(),
                attrs={"hit_tokens": hit_len,
                       "miss_tokens": req.real_len - hit_len,
                       "blocks_taken": len(ids) - len(full),
                       "blocks_evicted": self.kv.evicted_blocks - evicted0,
                       "cow": tail is not None})
        row = np.zeros(self.blocks_per_seq, np.int32)
        row[:len(ids)] = ids
        req.hit_tokens = hit_len
        req.bucket = self._suffix_bucket(req.real_len - hit_len)

        suffix_len = req.real_len - hit_len
        padded = np.zeros((1, req.bucket), np.int32)
        padded[0, :suffix_len] = req.prompt[hit_len:]
        pf = self._pg.prefill_fn(req.bucket)
        # The jitted call alone: its operands' transfer and the enqueue. It
        # returns before the device runs it, unless the runtime makes the
        # caller wait; wall less CPU says which.
        t_call, cpu_0 = tracing.now_ns(), time.thread_time_ns()
        self._pool, self._slot_state, self._last, self._keys, aux = pf(
            self._pg.params, self._pool, self._slot_state, self._last,
            self._keys, row, padded, hit_len, suffix_len, slot, req.seed)
        cpu_call = time.thread_time_ns() - cpu_0
        t_called = tracing.now_ns()
        st.prefill_ns += t_called - t_call
        st.prefill_cpu_ns += cpu_call
        if req.trace_ctx is not None:
            tracing.emit("llm.prefill.dispatch", req.trace_ctx,
                         parent_span_id=req.prefill_span, start=t_call,
                         end=t_called, attrs={"cpu_ns": cpu_call})
        if aux is not None:
            self._prefill_aux.append(aux)
        # Counted per admission, together: a stats() from another thread
        # never reads one without the other.
        with self._agg_lock:
            self._counts["prefill_rows_total"] += req.bucket
            self._counts["prefill_pad_rows_total"] += req.bucket - suffix_len
            if self._slot_state:    # the prefill wrote the slot's from zero
                self._state_counts["state_resets_total"] += 1
            if not self._prefix_cache:
                self._state_counts["prefix_lookups_refused_total"] += 1
        # Commit ATOMICALLY with the cancel path: this runs outside
        # _state_lock, so a concurrent _cancel may have freed the slot
        # mid-dispatch. Attaching first and registering later would let
        # _free_slot_locked free blocks the prefix table still points
        # at; attaching after a lost cancel would leak the pins forever.
        # Publishing the prompt's FULL blocks here (their content is final —
        # decode writes only at positions >= real_len) lets a concurrent
        # request with the same prefix hit while this one still decodes.
        n_full_prompt = (req.real_len // bt) * bt
        with self._state_lock:
            if self._slot_req[slot] is not req or req.cancelled:
                self.kv.release(ids)  # slot lost mid-dispatch — drop the pins
                return
            self._slot_table[slot, :] = row
            self._slot_blocks[slot] = ids
            if n_full_prompt and self._prefix_cache:
                self.kv.register_chain(tokens, ids, n_full_prompt)
            self._hit_pending += hit_len

    def _run_decode(self, active, greedy, temps, tables, lengths):
        """Dispatch the step's decode program; its tokens come back still on
        the device."""
        df = self._pg.decode_fn(self.chunk)
        (toks, self._pool, self._slot_state, self._last, self._keys,
         self._decode_aux) = df(
            self._pg.params, self._pool, self._slot_state, self._last,
            self._keys, tables, lengths, active, greedy, temps)
        return toks

    def _take_step_aux(self):
        """Device arrays to fetch WITH the chunk's tokens, in its one
        ``device_get``: the family's per-call counts, the decode call's and
        this step's prefills' (None for GPT-2). They go straight into the
        chunk's record and leave it at the fetch (``_Chunk.take``), so that
        no name keeps them alive past it: held as locals through deliver
        and observe they cost the ``longcat-flash-omni`` cell 12 ms of
        device idle a step, 3.4% of its ``serve_out_tok_s`` (PERF.md §6,
        PR 29)."""
        aux, self._decode_aux = self._decode_aux, None
        pre, self._prefill_aux = self._prefill_aux, []
        return None if aux is None and not pre else (aux, pre)

    def _fold_step_aux(self, host_aux, st: _StepTrace) -> None:
        """Fold the family's counts, now on the host, by the names it gives
        them (``PagedFamily.aux_counts``): the decode chunk's go to its
        ``stats()`` keys and onto the ``llm.step`` span, a prefill's to keys
        of their own."""
        if host_aux is None:
            return
        aux, pre = host_aux
        names = self._pg.family.aux_counts
        with self._agg_lock:
            totals = self._aux_totals
            if aux is not None:
                for n, v in zip(names, aux, strict=True):
                    totals[n.decode] += int(v)
                    if n.step_attr:
                        st.attrs[n.step_attr] = int(v)
            for p in pre:
                for n, v in zip(names, p, strict=True):
                    if n.prefill:
                        totals[n.prefill] += int(v)

    def _observe(self, delivered: int, ttfts: List[tuple]) -> None:
        hits, self._hit_pending = self._hit_pending, 0
        m = _metrics()
        if not m.metrics_enabled():
            return
        tags = {"deployment": self.name}
        if delivered:
            m.serve_tokens_total().inc(delivered, tags)
        hist = m.serve_ttft_hist()
        for total, queued, prefill in ttfts:
            # Phase split: queued (submit→admission), prefill (the prefill
            # dispatch), decode (the remainder — first chunk + distribution).
            hist.observe(total, {**tags, "phase": "total"})
            hist.observe(queued, {**tags, "phase": "queued"})
            hist.observe(prefill, {**tags, "phase": "prefill"})
            hist.observe(max(0.0, total - queued - prefill),
                         {**tags, "phase": "decode"})
        if hits:
            m.serve_kv_hit_tokens_total().inc(hits, tags)
        st = self.kv.stats()
        gauge = m.serve_kv_block_occupancy()
        for state in ("active", "cached", "free"):
            gauge.set(st[f"kv_blocks_{state}"], {**tags, "state": state})

    # -- introspection --------------------------------------------------------
    def stats(self) -> Dict[str, float]:
        """Slot occupancy, admission queue depth and the KV pool's counters —
        exported through ``ReplicaActor.get_metrics`` for KV-occupancy-aware
        routing."""
        with self._state_lock:
            busy = sum(1 for r in self._slot_req if r is not None)
            depth = len(self._waiting)
        # ``queue_limit`` is this engine's own ``max_queue``: the router
        # sheds at it, not at the global knob it defaults to.
        out = {"slots_total": float(self.slots), "slots_busy": float(busy),
               "queue_depth": float(depth),
               "queue_limit": float(self.max_queue)}
        # Cumulative counts at the step's boundaries: they count with
        # tracing off too. ``steps_total`` counts steps that dispatched a
        # decode, ``steps_ahead_total`` those of them whose decode call was
        # enqueued while the chunk before it was unfetched;
        # ``admit_blocked_pool_s`` is the time from the start of a
        # step whose admission stopped on NoFreeBlocks (a slot free, a
        # request waiting) to the start of the next.
        # The engine's time, whole: ``step_host_s`` + ``step_device_wait_s``
        # is all the time spent inside steps, ``step_handoff_s`` the time
        # between two steps while the engine held work (no thread was
        # stepping it), so on an engine that is never empty the three grow
        # by the wall time. ``step_host_cpu_s`` is the CPU time of the
        # driver's thread inside ``step_host_s`` (the rest it waited: the
        # GIL, a lock, a blocking runtime call), ``prefill_dispatch_s`` /
        # ``prefill_dispatch_cpu_s`` the part of both inside the prefills'
        # jitted calls; ``step_driver_switches_total`` counts steps run by
        # another thread than the step before: a few in a hundred while a
        # consumer drives for its request's life, most of them once drivers
        # are held in their own ``yield`` and the next step waits for
        # another consumer's poll (read it beside ``step_handoff_s``).
        # Occupancy: ``slot_steps_total`` over ``slot_steps_offered_total``
        # is active slots x token steps over slots x token steps of the
        # decode dispatches. Every step's admission ends in one of
        # ``admit_stopped_{queue_empty,no_slot,budget,no_blocks}_total``;
        # ``admit_starved_total`` counts the steps that dispatched a decode
        # with a slot empty and the queue empty: the clients were elsewhere.
        # ``prefill_rows_total`` counts the rows the prefill programs were
        # handed (an admission's suffix BUCKET), ``prefill_pad_rows_total``
        # those of them behind the suffix's last token: rows the products
        # still multiply and the attention kernels no longer walk for.
        # The first hand-over of a token's way back to its client:
        # ``stream_pickups_total`` counts the consumers' takes of a
        # non-empty ``req.tokens`` in ``drive``, ``stream_pickup_lag_s`` sums
        # how long after ``_deliver`` made the oldest of them the request's
        # each take came (the replica's ``get_metrics`` continues the
        # account: ``stream_items_total`` and on).
        with self._agg_lock:
            out.update({k: v / 1e9 if k.endswith("_s") else float(v)
                        for k, v in self._counts.items()})
        out.update(self.kv.stats())
        with self._agg_lock:
            out.update({k: float(v) for k, v in self._aux_totals.items()})
            if self._slot_state or not self._prefix_cache:
                # What the slot state weighs (all slots; a live
                # slot's share is slots_busy / slots_total of it), the slot
                # steps the decode programs advanced it by, the admissions
                # that wrote one from zero and those that made no prefix
                # lookup because the family keeps one.
                out["state_bytes"] = float(self._state_bytes)
                out.update({k: float(v)
                            for k, v in self._state_counts.items()})
        return out

    def describe(self) -> Dict:
        """What this engine resolved to at run time, for an operator or a
        smoke test to print: names and placements, not numbers (``stats``
        stays all-float — it feeds the router). ``warmed_buckets`` is empty
        until ``warmup`` has compiled every bucket."""
        return {
            "engine": type(self).__name__,
            "slots": self.slots,
            "chunk": self.chunk,
            "max_len": self.max_len,
            "warmed_buckets": list(self.buckets) if self._steady else [],
            "trace_id": self.trace_id,
            "params_devices": sorted(
                {str(d) for leaf in jax.tree.leaves(self._pg.params)
                 for d in leaf.devices()}),
            "params_working_bytes": self._pg.params_working_bytes,
            "attention_kernel": self._pg.attention_kernel,  # as resolved
            "block_tokens": self.block_tokens,
            "pool_blocks": self.kv.num_blocks,
            "model_family": type(self.config).__name__,
            "kv_pool_shapes": [list(a.shape) for a in self._pool],
            "slot_state_shapes": [list(a.shape) for a in self._slot_state],
            "kv_pool_devices": sorted(
                {str(d) for a in self._pool for d in a.devices()}),
            # What the family says its stack is made of (PagedFamily.describe).
            **(self._pg.family.describe(self.config)
               if self._pg.family.describe is not None else {}),
        }

    def decode_tokens_per_sec(self) -> float:
        with self._agg_lock:
            if self.decode_seconds == 0:
                return 0.0
            return self.decode_tokens / self.decode_seconds


def llm_deployment(
    config,
    params_fn: Callable[[], Dict],
    *,
    name: str = "LLM",
    max_new_tokens_default: int = 32,
    slots: Optional[int] = None,
    chunk: int = 8,
    max_queue: Optional[int] = None,
    **deployment_kwargs,
):
    """Build a Serve deployment class around a continuous-batching
    :class:`LLMEngine`.

    ``config`` is a model family's config object: a
    ``models.transformer.TransformerConfig`` (GPT-2), a
    ``models.longcat.LongCatConfig`` or a
    ``models.olmo_hybrid.OlmoHybridConfig``; the engine finds the family's
    pool, its per-slot state and its forward pass through it
    (``models.generate.PagedFamily``); a family that keeps a state a slot
    is served no prefix hit.

    ``params_fn`` runs inside the replica (checkpoint load / init) so weights
    never ship through the controller. Request payload::

        {"prompt_ids": [...], "max_new_tokens": n, "temperature": t,
         "seed": s}

    Responses stream ``{"token": id, "index": i, "decode_tps": rate}``
    dicts (call the handle with ``stream=True``); the final item adds
    ``finish_reason`` ("stop" | "length_cap"). ``decode_tps`` is THIS
    request's decode rate. Sampled requests without an explicit ``seed``
    draw a fresh one per request.

    The replica runs with ``max_concurrency`` sized to the engine so
    concurrent streams batch INSIDE one engine instead of queueing at the
    actor mailbox; ``get_engine_stats`` feeds slot occupancy and queue depth
    to the controller for KV-occupancy-aware routing.
    """
    import random as _random

    from ray_tpu import serve
    from ray_tpu.core.config import config as _get_config  # `config` is the
    # model's config object here

    knobs = _get_config()
    n_slots = int(slots if slots is not None else knobs.serve_llm_slots)
    q_limit = int(max_queue if max_queue is not None
                  else knobs.serve_admission_queue_limit)
    # Streams park threads in the replica: enough actor threads for a full
    # slot set plus a shed-depth of waiters plus control-plane calls.
    deployment_kwargs.setdefault(
        "max_concurrency", n_slots + max(q_limit, 4) + 4)
    # The handle lets through what the engine can hold: a full slot set plus
    # its admission queue (the engine sheds beyond that). The deployment
    # default of 100 would keep a 128-slot engine a fifth empty with
    # callers blocked at the router.
    deployment_kwargs.setdefault(
        "max_ongoing_requests", max(100, n_slots + q_limit))

    @serve.deployment(name=name, **deployment_kwargs)
    class LLMServer:
        def __init__(self):
            self.engine = LLMEngine(params_fn(), config, slots=n_slots,
                                    chunk=chunk, max_queue=q_limit,
                                    name=name)
            self.engine.warmup()

        def __call__(self, payload):
            if "prompt_ids" in payload:
                prompt = payload["prompt_ids"]  # empty list → engine raises
            else:
                prompt = [1] * int(payload.get("prompt_len", 8))
            n = int(payload.get("max_new_tokens", max_new_tokens_default))
            temp = float(payload.get("temperature", 0.0))
            seed = payload.get("seed")
            if seed is None:
                seed = _random.getrandbits(31)
            outcome: dict = {}  # per-request, not the shared engine attr
            stream = self.engine.stream(
                prompt, max_new_tokens=n, temperature=temp, seed=int(seed),
                result=outcome)
            prev: dict | None = None
            for i, tok in enumerate(stream):
                if prev is not None:
                    yield prev
                prev = {"token": tok, "index": i,
                        "decode_tps": round(outcome.get("decode_tps", 0.0), 1)}
            if prev is not None:
                prev["finish_reason"] = outcome.get("finish_reason", "stop")
                if "ttft_s" in outcome:
                    # Measured submit→first-token latency — lets clients (and
                    # the tracing tests) check the span decomposition against
                    # the engine's own clock.
                    prev["ttft_s"] = outcome["ttft_s"]
                yield prev

        def get_engine_stats(self):
            return self.engine.stats()

        def describe(self):
            return self.engine.describe()

    return LLMServer
