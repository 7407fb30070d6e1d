"""Global, env-overridable configuration table.

Analog of the reference's ``RAY_CONFIG`` flag system
(``src/ray/common/ray_config_def.h`` — 218 entries, each overridable by a
``RAY_<name>`` env var or a ``_system_config`` dict passed at init). We use a
typed dataclass-like registry: every flag is a class attribute; the value is
resolved from (1) a ``system_config`` dict given to ``init()``, (2) the
``RAY_TPU_<NAME>`` env var, (3) the default — in that order.
"""

from __future__ import annotations

import os
import threading
from typing import Any


class _Flag:
    __slots__ = ("name", "default", "type")

    def __init__(self, default):
        self.default = default
        self.type = type(default)
        self.name = None  # filled by registry

    def resolve(self, overrides: dict):
        if self.name in overrides:
            return self._coerce(overrides[self.name])
        env = os.environ.get(f"RAY_TPU_{self.name.upper()}")
        if env is not None:
            return self._coerce(env)
        return self.default

    def _coerce(self, value):
        if self.type is bool:
            if isinstance(value, str):
                return value.lower() in ("1", "true", "yes", "on")
            return bool(value)
        try:
            return self.type(value)
        except (TypeError, ValueError) as e:
            name = self.name or "<unbound>"
            raise ValueError(
                f"invalid value {value!r} for config flag '{name}': expected "
                f"{self.type.__name__} (set via the RAY_TPU_{name.upper()} "
                f"env var or the system_config dict passed to init())"
            ) from e


class Config:
    """Runtime configuration. Access via ``config()`` after init.

    Flags mirror the semantically-important knobs of
    ``src/ray/common/ray_config_def.h`` (inline-object threshold :206, health
    check cadence :841-847, lease timeouts) plus TPU-specific additions.
    """

    # -- object store ---------------------------------------------------------
    # Objects at or below this size are carried inline in RPC replies instead of
    # the shared-memory store (reference: max_direct_call_object_size = 100 KiB,
    # ray_config_def.h:206).
    max_inline_object_size = _Flag(100 * 1024)
    # Per-node shared-memory store capacity in bytes (plasma default sizing).
    object_store_memory = _Flag(2 * 1024 * 1024 * 1024)
    # Spill directory for objects evicted from the shm store.
    object_spilling_dir = _Flag("/tmp/ray_tpu_spill")
    # GCS snapshots are mirrored to this many node daemons per tick, so a
    # fresh head can restore after losing its DISK (the external-Redis
    # role of gcs_server.cc:523-524). 0 disables mirroring.
    gcs_snapshot_mirrors = _Flag(2)
    # Use the native C++ shared-memory arena for large object buffers
    # (the plasma path; falls back to heap bytes when the lib can't build).
    use_native_store = _Flag(True)
    # Buffers at or above this size go to the native shm arena.
    native_store_threshold = _Flag(64 * 1024)
    # Node-to-node transfer: objects above pull_chunk_size move as a
    # pipeline of chunk frames (object_manager.cc:812 chunked transfer)
    # with at most pull_chunk_concurrency chunks in flight, and total
    # in-flight pulled bytes capped by pull_memory_budget
    # (pull_manager.cc:801 memory budgeting).
    pull_chunk_size = _Flag(8 * 1024 * 1024)
    # Remote fetches at or below this ride whole in one reply frame;
    # above it they use the chunked pull that lands DIRECTLY in the local
    # shm arena and registers this node as a new replica — so broadcasts
    # fan out across nodes instead of serializing on the origin daemon.
    whole_frame_fetch_max = _Flag(1 * 1024 * 1024)
    # Chunks of one pull in flight at once (the transfer pipeline depth).
    pull_chunk_concurrency = _Flag(4)
    # Total bytes of in-flight pulled chunks across all concurrent pulls.
    pull_memory_budget = _Flag(512 * 1024 * 1024)
    # Batched get(): max refs fetched concurrently by one get([refs]) call
    # (the bounded fan-out of the parallel read path; total in-flight pull
    # bytes stay capped by pull_memory_budget regardless).
    get_fanout = _Flag(8)
    # Chunked pulls of objects at or above this size stripe their chunk
    # ranges across ALL replica locations concurrently (multi-source pull);
    # smaller objects pull from one replica — the per-source pipeline setup
    # isn't worth it below a couple of chunks per source.
    stripe_min_size = _Flag(16 * 1024 * 1024)
    # Object-location push wakeups: waiters blocked in get() subscribe to
    # the GCS object-location channel and wake on seal instead of sleeping
    # through a poll backoff (the poll remains as a low-frequency fallback
    # for GCS-restart recovery). Disable to restore pure polling.
    location_sub_enabled = _Flag(True)
    # Entries kept in the node store's deserialized-value cache (small
    # values only; eviction is LRU).
    deser_cache_entries = _Flag(256)

    # -- scheduling -----------------------------------------------------------
    # Hybrid policy threshold: below this utilization prefer packing on the
    # first (local) node, above it spread (reference
    # hybrid_scheduling_policy.h:28-48 "scheduler_spread_threshold").
    scheduler_spread_threshold = _Flag(0.5)
    # Top-k fraction of candidate nodes to random-pick among.
    scheduler_top_k_fraction = _Flag(0.2)
    # Seconds a leased worker stays bound to a scheduling key while idle before
    # being returned (reference: worker lease reuse in direct_task_transport).
    idle_lease_ttl_s = _Flag(1.0)
    # Max worker processes per node pool (reference: maximum_startup_concurrency
    # and pool sizing in worker_pool.cc).
    max_workers_per_node = _Flag(8)
    # Workers spawned into the idle pool at daemon start, capped by the
    # node's CPU count (reference: worker_pool.cc prestart).
    prestart_workers_per_node = _Flag(4)

    # -- gang scheduling / topology -------------------------------------------
    # Topology-aware atomic gang placement: multi-bundle PACK/STRICT_PACK
    # placement groups are planned as one all-or-nothing reservation over
    # pinned cap-N capacity blocks, packed into a single ICI slice when one
    # has room (STRICT_PACK refuses to spill; PACK spills onto the fewest
    # slices). 0 reproduces the legacy per-bundle 2PC path exactly.
    gang_scheduling_enabled = _Flag(True)
    # Node topology labeling mode: "auto" honors daemon-supplied topo.pod /
    # topo.slice / topo.tier labels (unlabeled nodes become singleton
    # slices); "off" makes the gang planner topology-blind (atomic
    # reservation kept, ICI-locality scoring skipped).
    topology_labels = _Flag("auto")
    # Preemption classes: serve autoscaling under SLO pressure may revoke
    # gangs whose gang_priority is strictly lower than the requester's,
    # through the capacity-block revocation path. 0 disables preemption;
    # placement and priorities are still recorded.
    gang_preemption_enabled = _Flag(True)
    # Simulated-cluster harness (core/sim_cluster.py): hosts per synthetic
    # ICI slice when fabricating topology labels for stub daemons.
    sim_hosts_per_slice = _Flag(16)
    # Simulated-cluster harness: slices per synthetic pod.
    sim_slices_per_pod = _Flag(4)
    # Simulated-cluster harness: stub-daemon heartbeat period. Keep well
    # under health_check_period_s * health_check_failure_threshold or the
    # watchdog will declare sim nodes dead.
    sim_heartbeat_period_s = _Flag(0.5)

    # -- memory monitor / OOM policy (memory_monitor.h:52 analog) -------------
    # Node memory-usage fraction above which the daemon kills the newest
    # busy TASK worker (retriable-FIFO policy). >=1.0 disables.
    memory_monitor_threshold = _Flag(0.95)
    # Seconds between memory-monitor sweeps.
    memory_monitor_period_s = _Flag(1.0)

    # -- health / fault tolerance --------------------------------------------
    # Health-check period and failure threshold (reference
    # ray_config_def.h:841-847 health_check_{initial_delay,period,timeout}_ms,
    # health_check_failure_threshold).
    health_check_period_s = _Flag(1.0)
    # Missed heartbeats before a node is declared dead.
    health_check_failure_threshold = _Flag(5)
    # Default task retries (reference: task max_retries default 3).
    default_max_retries = _Flag(3)
    # Streaming generators: max items a producer may run ahead of the
    # consumer before blocking (reference:
    # _generator_backpressure_num_objects).
    streaming_backpressure_items = _Flag(64)

    # -- timeouts -------------------------------------------------------------
    # TCP connect timeout for every RpcClient (control-plane dials).
    rpc_connect_timeout_s = _Flag(10.0)
    # An untimed get() logs a warning after waiting this long for a seal.
    get_timeout_warn_s = _Flag(30.0)
    # Wait slice for internal Condition/Event waits that re-check their
    # predicate in a loop (actor mailboxes, generator item waits, batcher
    # flush waits): a lost peer wakes the thread at this cadence instead of
    # parking it forever on a condition nobody will ever signal.
    internal_wait_timeout_s = _Flag(60.0)

    # -- RPC fast path --------------------------------------------------------
    # Adaptive frame-coalescing window in MICROSECONDS: a non-urgent lone
    # frame (reply, one-way note) may wait this long for company before its
    # sendmsg — but only while the connection is "hot" (a recent send
    # actually coalesced). Urgent frames (requests) and explicit flushes
    # never wait. Defaults to 0 (disabled): timer waits oversleep by whole
    # scheduler quanta on busy single-core hosts, while the opportunistic
    # coalescing (frames queued during an in-flight sendmsg, plus the
    # pipelined submitters' handoff drainer) batches without ever delaying
    # a frame. Enable (~50) only on NIC-bound multi-host control planes
    # where per-frame syscall overhead dominates end-to-end latency.
    rpc_coalesce_window_us = _Flag(0.0)
    # Caps on one coalesced sendmsg batch: at most this many frames...
    rpc_max_batch_frames = _Flag(64)
    # ...and at most this many payload bytes (a single larger frame still
    # goes out alone — the cap bounds added latency, not frame size).
    rpc_max_batch_bytes = _Flag(1 * 1024 * 1024)
    # Entries kept in each process's task-spec template caches (client-side
    # encoder and server-side store). Content-addressed; eviction only costs
    # a re-send of the ~300-byte template.
    spec_cache_size = _Flag(4096)

    # -- eager collectives ----------------------------------------------------
    # Two-level topology-aware collectives: ranks sharing a node store reduce
    # intra-node through shm first (leader accumulates in place over peers'
    # zero-copy views), node leaders run the inter-node ring (size/num_nodes
    # bytes per node instead of per rank), results fan back out by shm key.
    # 0 restores the flat topology-blind ring on every group member.
    collective_hierarchy_enabled = _Flag(True)
    # Segment size for the pipelined inter-node ring: each ring chunk moves
    # as segments of this many bytes, double-buffered so segment k's
    # reduction overlaps segment k+1's transfer.
    collective_segment_size = _Flag(1 * 1024 * 1024)
    # Timeout for every blocking collective step (member-mailbox take, ring
    # recv, p2p recv without an explicit timeout). Short-lived jobs and
    # tests lower this to fail fast on a lost rank.
    collective_timeout_s = _Flag(120.0)

    # -- compiled DAGs ---------------------------------------------------------
    # Ring depth of a compiled-DAG shm channel: how many ticks can be in
    # flight on one edge before the writer blocks on the reader's ack.
    # 1 restores the capacity-1 seqlock channel (strict lock-step hand-off);
    # deeper rings let burst submission pipeline through the stages.
    dag_channel_slots = _Flag(8)
    # Busy-spin iterations before a blocked channel endpoint falls back to
    # sleep-polling. 0 measured best on core-constrained hosts: spinning
    # starves the peer process of the CPU it needs to make progress.
    dag_channel_tight_spins = _Flag(0)
    # Sleep-poll granularity (microseconds) for a blocked channel endpoint;
    # backs off exponentially to 40x this while idle. Lower = lower hand-off
    # latency on idle cores, higher = less wasted wakeup churn.
    dag_channel_spin_us = _Flag(50.0)
    # Credit window of a cross-host SocketChannel edge: frames the writer
    # may send ahead of the reader's acks. 1 restores per-frame lock-step
    # (every write stalls on an ack round-trip); wider windows let burst
    # submission pipeline over the network like the shm ring does on-host.
    dag_socket_window = _Flag(8)
    # Bound on CompiledDAG.teardown's drain: how long to wait for the stage
    # loops to observe the close pill and detach their channel endpoints
    # before the driver unlinks the shm files (a stage mid-read must not
    # see its backing file vanish).
    dag_teardown_timeout_s = _Flag(10.0)

    # -- serve / LLM engine ---------------------------------------------------
    # KV-cache slots per continuous-batching LLM engine (serve/llm.py): how
    # many sequences decode together in one batched dispatch. More slots =
    # more MXU-friendly matmul batch and higher aggregate tokens/s, at
    # slots x max_len x layers KV-cache HBM.
    serve_llm_slots = _Flag(4)
    # Prefill token budget per engine iteration: new prompts are admitted
    # into free slots until their padded lengths exceed this, so a burst of
    # long prompts can't starve the in-flight decode (the prefill/decode
    # interleave policy). At least one prompt is always admitted when a
    # slot is free, so the budget bounds batching, never progress.
    serve_llm_prefill_tokens = _Flag(128)
    # Admission-control shed threshold: a request arriving while this many
    # are already waiting for a slot fails FAST with serve.Saturated instead
    # of queueing unboundedly (the router also sheds when every replica
    # reports a queue this deep). 0 disables shedding.
    serve_admission_queue_limit = _Flag(32)
    # Tokens per KV block in the paged cache (serve/llm.py LLMEngine +
    # models/generate.py PagedGenerator): sequences hold block TABLES into a
    # shared pool instead of a private max_len slab, and prefix reuse /
    # copy-on-write forks share at this granularity. Smaller blocks = finer
    # sharing and less tail waste, more gather/scatter indices per dispatch.
    serve_kv_block_tokens = _Flag(16)
    # Total blocks in the shared KV pool (block 0 is a reserved trash block
    # that absorbs pad/inactive writes). 0 = auto: 2x the blocks needed to
    # hold every slot at max_len, so retired prefixes stay hash-cached for
    # reuse instead of being evicted the moment a new request arrives.
    serve_kv_pool_blocks = _Flag(0)
    # Router prefix affinity: 1 makes DeploymentHandle hash the prompt's
    # leading KV blocks and prefer the replica that served that prefix last
    # (its pool likely still caches those blocks), layered on the
    # KV-occupancy pow-2 pick; saturated/dead replicas fall back to pow-2.
    serve_prefix_affinity_enabled = _Flag(True)
    # How many leading serve_kv_block_tokens-sized blocks of the prompt feed
    # the affinity hash. Smaller = coarser grouping (more traffic lands on
    # one replica), larger = only near-identical prompts share a replica.
    serve_prefix_affinity_blocks = _Flag(4)
    # Per-queued-request service-time estimate (seconds) used to turn an
    # observed admission-queue depth into the Saturated.retry_after_s
    # backoff hint (hint = overage x this). Advisory only — it never gates
    # admission, it just shapes client retry jitter.
    serve_retry_after_item_s = _Flag(0.05)
    # Minimum seconds between SLO-autoscaler evaluations per deployment
    # (serve/autoscaling.py): the controller reconcile loop ticks at 50ms
    # but pressure signals (polled replica load, pushed ongoing EWMA) only
    # refresh on coarser cadences — deciding faster than this just reads
    # the same stale inputs. Direction changes are additionally gated by
    # the per-deployment cooldowns in AutoscalingConfig.
    serve_autoscaling_interval_s = _Flag(0.25)
    # Minimum seconds between cluster-metrics-rollup reads for the TTFT
    # p99 override (one merged ray_tpu_serve_ttft_s histogram fetch per
    # deployment): bounds the GCS aggregator query rate from the serve
    # controller regardless of its reconcile cadence.
    serve_slo_rollup_interval_s = _Flag(1.0)
    # Paged-attention implementation for the serve engine's decode/prefill
    # forwards: "auto" picks the fused Pallas kernel on TPU (streams only a
    # slot's live KV blocks through the block table — no [S, max_len, H, D]
    # gather) and the XLA gather path on CPU; "pallas" / "interpret" /
    # "gather" force a mode ("interpret" runs the same Pallas kernel in
    # interpreter mode, the CPU-testable twin of the TPU path).
    serve_paged_attention_kernel = _Flag("auto")

    # -- rllib (Podracer-scale RL) ---------------------------------------------
    # Rollout transport for IMPALA/APPO: 1 parks the env runners in a
    # compiled-DAG rollout lane (rllib/rollout_lanes.py) — fragments fan in
    # to the driver over multi-slot shm channels with deferred acks, so a
    # slow learner backpressures the runners instead of dropping work. 0
    # restores the per-fragment task path (ray_tpu.wait + ObjectRef hop),
    # kept as the A/B baseline for benches/rl_throughput.py.
    rollout_lanes_enabled = _Flag(True)
    # Max observation batches fused into one InferenceActor forward dispatch
    # (Sebulba mode, rllib/inference.py). 0 = auto: one in-flight step per
    # attached runner, capped at a flush quorum of 4 — dispatch
    # amortization saturates there, while waiting on every runner stalls
    # the pool on the slowest one. Same-shaped requests stack into a
    # single vmapped dispatch; odd shapes fall back to per-request calls.
    rl_inference_max_batch = _Flag(0)
    # Batch window (seconds) an InferenceActor waits for further runner
    # requests before flushing a partial batch. Runners desync at fragment
    # boundaries, so a window much larger than one env step leaves the
    # whole pool blocked on the timer; keep it at roughly one env-step
    # time so stragglers cost at most one step of latency.
    rl_inference_window_s = _Flag(0.001)

    # -- control plane (sharded GCS + daemon-local leases) ---------------------
    # Lock domains for the GCS object-location / KV / pubsub tables: state
    # is hash-partitioned across this many independent shards so location
    # storms and KV churn stop contending with the scheduling lock. 1
    # reproduces the single-table behavior byte-for-byte.
    gcs_shards = _Flag(8)
    # Batched daemon-local lease grants: the client asks the GCS for one
    # revocable *capacity block* per (resource-shape, locality) key and the
    # node daemon carves per-task worker leases out of it locally, so a
    # deep queue costs one GCS hop instead of one per task. 0 restores
    # per-task request_lease round trips.
    lease_batch_enabled = _Flag(True)
    # Max leases requested in one capacity block (the batch amortization
    # ceiling; partial grants below this are normal).
    lease_batch_max = _Flag(16)
    # Threads in the per-CoreWorker lease-requester pool. Bounds the old
    # one-thread-per-in-flight-request spawn so a 10k-task burst keeps a
    # small, fixed requester footprint.
    lease_requester_threads = _Flag(16)
    # Non-blocking observability ingest: report_metrics / task-event /
    # trace-span RPCs land in a bounded staging queue drained by a
    # dedicated GCS ingest thread, so a burst of spans or a slow aggregator
    # lags (with a drop counter) instead of holding RPC handler threads
    # against lease grants. 0 applies reports inline as before.
    gcs_ingest_async_enabled = _Flag(True)
    # Staging-queue capacity for the async observability ingest; overflow
    # is dropped (counted in the gcs_ingest_dropped gauge), never blocked on.
    gcs_ingest_queue_max = _Flag(4096)

    # -- metrics / observability ----------------------------------------------
    # Cluster-wide metrics pipeline: every process (gcs_server, node_daemon,
    # worker, driver) runs an exporter thread that snapshots its
    # util.metrics registry and ships it to the GCS, which serves the merged
    # exposition at the dashboard's /metrics. 0 disables both the exporters
    # AND the built-in hot-path instrumentation (task phase histograms,
    # serve latency, object-plane counters).
    metrics_export_enabled = _Flag(True)
    # Seconds between exporter ticks (the reference's metrics agent reports
    # on the same ~10s cadence). Read every tick, so a cluster-adopted
    # config applies without an exporter restart.
    metrics_export_interval_s = _Flag(10.0)
    # Request tracing master gate: spans from the serve data plane, compiled
    # DAG ticks and traced RPCs. Off = every potential span costs one flag
    # check (the metrics_export_enabled pattern); on, head-based sampling
    # below decides per-trace at the ROOT.
    trace_enabled = _Flag(True)
    # Head-based sampling probability in [0, 1]: decided ONCE where a trace
    # root is stamped (serve handle, user span, DAG tick) and carried in the
    # context, so a trace is either fully collected or not at all — never a
    # half-collected tree. 1.0 samples everything (test/dev default).
    trace_sample_rate = _Flag(1.0)
    # Also annotate blocking RpcClient.call()s reachable from a SAMPLED
    # trace context with client-side rpc spans. Off by default — control
    # planes make many calls per request and the span volume is rarely
    # worth it outside latency investigations.
    trace_rpc_enabled = _Flag(False)
    # Bound on the GCS trace_id -> event-index side table (per-trace
    # retrieval without scanning the 100k-event ring). Oldest traces are
    # evicted first; events older than the ring's base are pruned lazily.
    trace_max_traces = _Flag(2048)
    # Per-process black-box flight recorder (util.flightrec): every process
    # mmaps a bounded ring file under the session dir and appends compact
    # binary events at state transitions (task/actor edges, RPC connect/fail,
    # lease carve/revoke, channel stall, serve shed, collective enter/exit).
    # The mmap survives SIGKILL, so `ray-tpu debug` reads it postmortem.
    # Off = every record site costs one None check.
    flightrec_enabled = _Flag(True)
    # Flight-recorder ring size per process, KiB. 128-byte fixed slots:
    # the default 256 KiB keeps the last ~2k events per process.
    flightrec_ring_kb = _Flag(256)
    # Health watchdog (core.health, runs inside the GCS health loop):
    # a node whose heartbeat (or a component whose metrics report) is older
    # than `stall_factor` periods — but younger than the death bound — is
    # classified `stalled` (SIGSTOP/deadlock posture) instead of `healthy`.
    health_stall_factor = _Flag(2.5)

    # -- debugging ------------------------------------------------------------
    # Opt-in runtime lock-order validator (ray_tpu.devtools.lockcheck):
    # threading.Lock/RLock/Condition are replaced with instrumented wrappers
    # that track per-thread held-sets, maintain a global acquisition-order
    # graph, and raise LockOrderError on an inversion. Dev/test only — adds
    # per-acquire bookkeeping to every lock in the process.
    lock_order_check_enabled = _Flag(False)
    # Opt-in runtime leak validator (ray_tpu.devtools.leakcheck): threads,
    # os.open/os.pipe fds and sockets are stamped with their allocation
    # site; the test harness snapshots live threads/fds/shm segments per
    # test and fails on anything that survives teardown. Dev/test only.
    leak_check_enabled = _Flag(False)
    # Opt-in runtime JAX compile-churn guard (ray_tpu.devtools.jitcheck):
    # jax.jit is wrapped to stamp construction sites and count XLA
    # compilations per (site, abstract signature); jitcheck.steady_state()
    # — entered by the serve engine after warmup and by IMPALA after
    # iteration 1 — records any new compile or implicit device->host read
    # as a contract violation. Dev/test only.
    jit_check_enabled = _Flag(False)

    # -- TPU ------------------------------------------------------------------
    # Logical chips per host for resource autodetection when no TPU present
    # (reference python/ray/_private/accelerators/tpu.py:13-46 — 4 chips/host).
    tpu_chips_per_host = _Flag(4)

    def __init__(self, system_config: dict | None = None):
        overrides = dict(system_config or {})
        for name in dir(type(self)):
            flag = getattr(type(self), name)
            if isinstance(flag, _Flag):
                flag.name = name
                object.__setattr__(self, name, flag.resolve(overrides))
        unknown = set(overrides) - {
            n for n in dir(type(self)) if isinstance(getattr(type(self), n), _Flag)
        }
        if unknown:
            raise ValueError(f"Unknown system_config keys: {sorted(unknown)}")

    def to_dict(self) -> dict[str, Any]:
        return {
            n: getattr(self, n)
            for n in dir(type(self))
            if isinstance(getattr(type(self), n), _Flag)
        }


_global: Config | None = None
_lock = threading.Lock()


def config() -> Config:
    global _global
    if _global is None:
        with _lock:
            if _global is None:
                _global = Config()
    return _global


def set_config(cfg: Config) -> None:
    global _global
    with _lock:
        _global = cfg
