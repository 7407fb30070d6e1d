"""Benchmarks. The headline: GPT-2-124M training throughput, tokens/sec/chip.

``python bench.py`` runs the full sharded train step (forward + backward +
adamw, bf16 compute, flash attention, remat) data-parallel over every local
chip, in THIS process — the chip belongs to one process at a time — and
prints one JSON line that names the device it ran on (platform,
``device_kind``, device count). It measures a chip: where JAX finds only the
CPU it exits non-zero and prints no result. Steps are synced with
``jax.block_until_ready``; compilation is reported apart from the timed
window.

Every other mode (``--metrics-overhead``, ``--trace-overhead``,
``--flight-overhead``, ``--control-plane``, ``--sched-sim``, ``--slo``,
``--rl``) measures host code: its children run with ``JAX_PLATFORMS=cpu`` and
never need the chip.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time


def run_bench() -> None:
    from ray_tpu.util.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.models import transformer
    from ray_tpu.models.training import make_train_step
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh
    from ray_tpu.parallel.sharding import ShardingRules

    devices = jax.devices()
    if devices[0].platform == "cpu":
        sys.exit("bench.py: the headline measures a chip and JAX found only "
                 "the CPU; nothing was measured")
    n = len(devices)

    # Data-parallel over every chip; single chip → trivial mesh.
    mesh = make_mesh(MeshSpec(data=-1), devices=devices)
    rules = ShardingRules()

    cfg = transformer.gpt2_small(
        max_seq_len=1024,
        remat=os.environ.get("RT_BENCH_REMAT", "1") == "1",
        remat_policy=os.environ.get("RT_BENCH_REMAT_POLICY", "full"),
        attn_impl=os.environ.get("RT_BENCH_ATTN", "auto"),
    )
    batch_per_chip, seq = int(os.environ.get("RT_BENCH_BATCH", "16")), 1024
    steps, warmup = 20, 3

    bundle = make_train_step(
        loss_fn=lambda p, b: transformer.lm_loss(p, b, cfg, mesh=mesh, rules=rules),
        init_params_fn=lambda k: transformer.init_params(cfg, k),
        logical_params=transformer.logical_axes(cfg),
        mesh=mesh,
        rules=rules,
        optimizer=optax.adamw(3e-4, weight_decay=0.1),
        batch_logical=("batch", None),
    )
    params, opt_state = bundle.init(jax.random.key(0))

    global_batch = batch_per_chip * n
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jax.device_put(
            jnp.asarray(rng.integers(0, cfg.vocab_size, (global_batch, seq)), jnp.int32),
            bundle.batch_sharding,
        )
    }

    t0 = time.perf_counter()
    params, opt_state, metrics = bundle.step(params, opt_state, batch)
    jax.block_until_ready(metrics)
    first_step_s = time.perf_counter() - t0  # compile (or cache load) + 1 step
    for _ in range(warmup - 1):
        params, opt_state, metrics = bundle.step(params, opt_state, batch)
    jax.block_until_ready(metrics)

    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, metrics = bundle.step(params, opt_state, batch)
    jax.block_until_ready((params, metrics))
    dt = time.perf_counter() - t0

    tokens_per_sec = global_batch * seq * steps / dt
    print(
        json.dumps(
            {
                "metric": "gpt2_train_tokens_per_sec_per_chip",
                "value": round(tokens_per_sec / n, 1),
                "unit": "tokens/s/chip",
                "platform": devices[0].platform,
                "device_kind": devices[0].device_kind,
                "devices": n,
                "batch_per_chip": batch_per_chip,
                "seq": seq,
                "steps": steps,
                "first_step_s": round(first_step_s, 2),
                "compile_cache_dir": cache_dir,
                "loss": round(float(metrics["loss"]), 4),
            }
        )
    )


def run_metrics_child(enabled: bool) -> None:
    """A/B child: in-process task hot loop + raw instrumentation cost, with
    the metrics plane on or off (RAY_TPU_METRICS_EXPORT_ENABLED set by the
    parent before this interpreter booted, so config resolves it)."""
    import ray_tpu

    ray_tpu.init(num_cpus=2)

    @ray_tpu.remote
    def nop():
        return None

    for _ in range(50):  # warmup: worker paths + metric lazies
        ray_tpu.get(nop.remote())
    n = 800
    t0 = time.perf_counter()
    for _ in range(n):
        ray_tpu.get(nop.remote())
    tasks_per_s = n / (time.perf_counter() - t0)

    # Raw per-observation cost of the gated hot-path hook (bisect histogram
    # when on, the metrics_enabled() flag check when off).
    from ray_tpu.core.metrics_export import observe_task_phases

    phases = {"queued": 1e-4, "args_fetch": 1e-5, "execute": 1e-3,
              "total": 2e-3}
    m = 50_000
    t0 = time.perf_counter()
    for _ in range(m):
        observe_task_phases(phases)
    hook_ns = (time.perf_counter() - t0) / m * 1e9
    print(json.dumps({"metrics_enabled": enabled,
                      "task_seq_per_s": round(tasks_per_s, 1),
                      "phase_hook_ns": round(hook_ns, 1)}))


def run_metrics_overhead() -> None:
    """Metrics-plane overhead micro: the same in-process task hot loop with
    instrumentation on vs ``metrics_export_enabled=0``, recorded in
    ``BENCH_obs_r01.json`` — the A/B that justifies shipping the built-in
    instrumentation enabled by default."""
    def trial(setting: str) -> dict:
        env = dict(os.environ)
        env.update({"JAX_PLATFORMS": "cpu",
                    "RAY_TPU_METRICS_EXPORT_ENABLED": setting})
        r = subprocess.run(
            [sys.executable, __file__, "--metrics-child", setting],
            capture_output=True, text=True, timeout=600, env=env)
        if r.returncode != 0:
            print(json.dumps({"metric": "metrics_overhead",
                              "error": (r.stderr or "")[-400:]}))
            sys.exit(1)
        return json.loads(r.stdout.strip().splitlines()[-1])

    # Alternating trial order + medians: a 1-core shared box jitters task
    # throughput far more than the instrumentation costs, and a fixed A/B
    # order folds warmup drift into the comparison.
    trials = {"1": [], "0": []}
    for setting in ("1", "0", "0", "1", "1", "0"):
        trials[setting].append(trial(setting))

    def median(vals):
        vals = sorted(vals)
        return vals[len(vals) // 2]

    results = {}
    for setting, key in (("1", "on"), ("0", "off")):
        results[f"task_seq_per_s_metrics_{key}"] = median(
            [t["task_seq_per_s"] for t in trials[setting]])
        results[f"phase_hook_ns_metrics_{key}"] = median(
            [t["phase_hook_ns"] for t in trials[setting]])
    on = results["task_seq_per_s_metrics_on"]
    off = results["task_seq_per_s_metrics_off"]
    results["overhead_pct"] = round((off - on) / off * 100.0, 2)
    results["trials_per_setting"] = 3
    # Single-box noise floor: sequential task latency on a shared host
    # jitters ~±10%; instrumentation stays default-on while inside it.
    results["within_noise"] = abs(results["overhead_pct"]) <= 10.0
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "BENCH_obs_r01.json")
    with open(out, "w") as f:
        json.dump({"results": results}, f, indent=1)
    print(json.dumps({"metric": "metrics_overhead", **results}))


def run_trace_child(enabled: bool) -> None:
    """A/B child: serve request round-trips + raw root-stamp cost, with
    request tracing sampled-on or gated-off (RAY_TPU_TRACE_ENABLED set by
    the parent before this interpreter booted, so config resolves it)."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.util import tracing

    ray_tpu.init(num_cpus=2)

    @serve.deployment
    class Echo:
        def __call__(self, x):
            return x

    handle = serve.run(Echo.bind())

    def req_loop(n=300):
        for _ in range(30):  # warmup: replica + router + span paths
            handle.remote(0).result()
        t0 = time.perf_counter()
        for i in range(n):
            handle.remote(i).result()
        return n / (time.perf_counter() - t0)

    req_per_s = req_loop()
    # With tracing enabled, also measure the head-sampling REJECT path —
    # the per-request posture of a production sample rate, where most
    # requests carry an unsampled context and emit nothing.
    unsampled_per_s = None
    if enabled:
        from ray_tpu.core.config import Config, set_config

        set_config(Config({"trace_sample_rate": 0.0}))
        unsampled_per_s = req_loop()
        set_config(Config())

    # Raw cost of stamping a trace root (the per-request hot hook): the
    # sampling decision + id generation when on, one flag check when off.
    m = 50_000
    t0 = time.perf_counter()
    for _ in range(m):
        tracing.new_root_context()
    root_ns = (time.perf_counter() - t0) / m * 1e9
    serve.shutdown()
    print(json.dumps({"trace_enabled": enabled,
                      "serve_req_per_s": round(req_per_s, 1),
                      "serve_req_per_s_unsampled":
                          round(unsampled_per_s, 1) if unsampled_per_s else None,
                      "root_stamp_ns": round(root_ns, 1)}))


def run_trace_overhead() -> None:
    """Tracing overhead micro: the same serve request loop fully sampled
    (``trace_sample_rate=1``, the default) vs ``trace_enabled=0``, recorded
    in ``BENCH_obs_r02.json`` — the A/B that justifies shipping request
    tracing enabled by default."""
    def trial(setting: str) -> dict:
        env = dict(os.environ)
        env.update({"JAX_PLATFORMS": "cpu",
                    "RAY_TPU_TRACE_ENABLED": setting})
        r = subprocess.run(
            [sys.executable, __file__, "--trace-child", setting],
            capture_output=True, text=True, timeout=600, env=env)
        if r.returncode != 0:
            print(json.dumps({"metric": "trace_overhead",
                              "error": (r.stderr or "")[-400:]}))
            sys.exit(1)
        return json.loads(r.stdout.strip().splitlines()[-1])

    # Alternating trial order + medians, same protocol as the metrics A/B:
    # shared-box jitter dwarfs the per-span cost, and a fixed order folds
    # warmup drift into the comparison.
    trials = {"1": [], "0": []}
    for setting in ("1", "0", "0", "1", "1", "0"):
        trials[setting].append(trial(setting))

    def median(vals):
        vals = sorted(vals)
        return vals[len(vals) // 2]

    results = {}
    for setting, key in (("1", "on"), ("0", "off")):
        results[f"serve_req_per_s_trace_{key}"] = median(
            [t["serve_req_per_s"] for t in trials[setting]])
        results[f"root_stamp_ns_trace_{key}"] = median(
            [t["root_stamp_ns"] for t in trials[setting]])
    results["serve_req_per_s_trace_on_unsampled"] = median(
        [t["serve_req_per_s_unsampled"] for t in trials["1"]])
    on = results["serve_req_per_s_trace_on"]
    off = results["serve_req_per_s_trace_off"]
    unsampled = results["serve_req_per_s_trace_on_unsampled"]
    # A fully-SAMPLED request pays for its spans — report that as an
    # absolute per-request cost (it amortizes into ms-scale LLM requests;
    # this no-op Echo round trip is the worst case). The posture that must
    # sit in the noise is the common one: tracing enabled but the request
    # not picked by head sampling, one root stamp + context carry.
    results["sampled_overhead_pct"] = round((off - on) / off * 100.0, 2)
    results["sampled_overhead_us_per_req"] = round(
        (1.0 / on - 1.0 / off) * 1e6, 1)
    results["unsampled_overhead_pct"] = round(
        (off - unsampled) / off * 100.0, 2)
    results["trials_per_setting"] = 3
    # Same noise floor as the metrics A/B: serve round-trip latency on a
    # shared host jitters ~±10%; tracing stays default-on while inside it.
    results["within_noise"] = abs(results["unsampled_overhead_pct"]) <= 10.0
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "BENCH_obs_r02.json")
    with open(out, "w") as f:
        json.dump({"results": results}, f, indent=1)
    print(json.dumps({"metric": "trace_overhead", **results}))


def run_flight_child(enabled: bool, quick: bool = False) -> None:
    """A/B child: in-process task hot loop + raw ring-record cost, with the
    flight recorder on or off (RAY_TPU_FLIGHTREC_ENABLED set by the parent
    before this interpreter booted, so config resolves it)."""
    import tempfile

    import ray_tpu
    from ray_tpu.util import flightrec

    # Rings land in a scratch session dir, not the shared default.
    os.environ[flightrec.ENV_SESSION_DIR] = tempfile.mkdtemp(
        prefix="rt_bench_flightrec_")
    ray_tpu.init(num_cpus=2)

    @ray_tpu.remote
    def nop():
        return None

    for _ in range(50):  # warmup: worker paths + ring mmap page-in
        ray_tpu.get(nop.remote())
    n = 200 if quick else 800
    t0 = time.perf_counter()
    for _ in range(n):
        ray_tpu.get(nop.remote())
    tasks_per_s = n / (time.perf_counter() - t0)

    # Raw per-event cost of the record hook (lock-free pack_into on a
    # dirty mmap page when on; one global load + None check when off).
    m = 20_000 if quick else 200_000
    t0 = time.perf_counter()
    for i in range(m):
        flightrec.record("task", "bench", "hot-loop event")
    record_ns = (time.perf_counter() - t0) / m * 1e9
    print(json.dumps({"flightrec_enabled": enabled,
                      "task_seq_per_s": round(tasks_per_s, 1),
                      "record_ns": round(record_ns, 1)}))


def run_flight_overhead(quick: bool = False,
                        out: Optional[str] = None) -> None:
    """Flight-recorder overhead micro: the same in-process task hot loop
    with the black box on (default) vs ``flightrec_enabled=0``, recorded in
    ``BENCH_obs_r03.json`` — the A/B that justifies keeping the always-on
    crash ring. The headline numbers: ring record stays ~1 µs/event and the
    disabled path is a single flag check."""
    def trial(setting: str) -> dict:
        env = dict(os.environ)
        env.update({"JAX_PLATFORMS": "cpu",
                    "RAY_TPU_FLIGHTREC_ENABLED": setting})
        cmd = [sys.executable, __file__, "--flight-child", setting]
        if quick:
            cmd.append("--quick")
        r = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                           env=env)
        if r.returncode != 0:
            print(json.dumps({"metric": "flight_overhead",
                              "error": (r.stderr or "")[-400:]}))
            sys.exit(1)
        return json.loads(r.stdout.strip().splitlines()[-1])

    # Alternating trial order + medians, same protocol as the metrics and
    # tracing A/Bs: shared-box jitter dwarfs a µs-scale write, and a fixed
    # order folds warmup drift into the comparison.
    order = ("1", "0") if quick else ("1", "0", "0", "1", "1", "0")
    trials = {"1": [], "0": []}
    for setting in order:
        trials[setting].append(trial(setting))

    def median(vals):
        vals = sorted(vals)
        return vals[len(vals) // 2]

    results = {}
    for setting, key in (("1", "on"), ("0", "off")):
        results[f"task_seq_per_s_flight_{key}"] = median(
            [t["task_seq_per_s"] for t in trials[setting]])
        results[f"record_ns_flight_{key}"] = median(
            [t["record_ns"] for t in trials[setting]])
    on = results["task_seq_per_s_flight_on"]
    off = results["task_seq_per_s_flight_off"]
    results["overhead_pct"] = round((off - on) / off * 100.0, 2)
    results["trials_per_setting"] = len(trials["1"])
    # Same noise floor as the other observability A/Bs: sequential task
    # latency on a shared host jitters ~±10%; the recorder stays
    # default-on while inside it.
    results["within_noise"] = abs(results["overhead_pct"]) <= 10.0
    out = out or os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "BENCH_obs_r03.json")
    with open(out, "w") as f:
        json.dump({"results": results}, f, indent=1)
    print(json.dumps({"metric": "flight_overhead", **results}))


def run_stub_daemon(gcs_address: str, num_cpus: int) -> None:
    """Bench stub node daemon (own process): the daemon's lease surface
    with REAL block accounting (LocalLeaseTable) but fake worker processes
    — control-plane cost without worker execution. Registers itself,
    heartbeats, serves until killed."""
    import threading

    from ray_tpu.core.ids import NodeID
    from ray_tpu.core.lease_table import LocalLeaseTable, is_block_lease
    from ray_tpu.core.rpc import RpcClient, RpcServer

    class StubDaemon:
        def __init__(self):
            self.table = LocalLeaseTable()
            self._lock = threading.Lock()
            self._leases = {}
            self._n = 0

        def ping(self):
            return "pong"

        def adopt_capacity_block(self, block_id, shape, total):
            self.table.adopt(block_id, shape, total)

        def revoke_capacity_block(self, block_id):
            self.table.revoke(block_id)

        def _fake_worker(self, lease_id):
            with self._lock:
                self._n += 1
                wid = b"bench-worker-%016d" % self._n
                self._leases[wid] = lease_id
            return wid

        def lease_worker_block(self, block_id, shape, total):
            lease = self.table.carve(block_id, shape=shape, total=total)
            if lease is None:
                return None
            return lease, self._fake_worker(lease), "127.0.0.1:9"

        def lease_worker_block_n(self, block_id, shape, total, n):
            grants = []
            for _ in range(max(1, int(n))):
                got = self.lease_worker_block(block_id, shape, total)
                if got is None:
                    break
                grants.append(got)
            return grants

        def lease_worker(self, lease_id):
            return self._fake_worker(lease_id), "127.0.0.1:9"

        def return_leased_worker(self, wid):
            with self._lock:
                lease = self._leases.pop(wid, None)
            if lease is not None and is_block_lease(lease):
                self.table.release(lease)

    stub = StubDaemon()
    server = RpcServer(stub, max_workers=64, name="bench-daemon")
    node_id = NodeID.from_random()
    gcs = RpcClient(gcs_address)
    gcs.call("register_node", node_id, server.address,
             {"CPU": float(num_cpus)}, {}, timeout=30.0)
    print(f"STUB_READY={server.address}", flush=True)
    while True:
        time.sleep(1.0)
        try:
            gcs.call("heartbeat", node_id, timeout=5.0)
        except Exception:
            os._exit(0)  # GCS gone: bench over


def run_control_plane_driver(mode: str, tasks: int, threads: int,
                             gcs_address: str) -> None:
    """Bench client process: drive ``tasks`` lease cycles from ``threads``
    threads. mode "baseline" = per-task request_lease + lease_worker +
    return + release (2 synchronous GCS RPCs per task — the pre-round-8
    plane). mode "batched" = request_lease_batch covering up to 16 tasks
    per GCS hop, per-task leases carved at the node daemon."""
    import threading as _threading

    from ray_tpu.core.rpc import RpcClient

    todo = [tasks]
    todo_lock = _threading.Lock()

    def claim(n: int) -> int:
        with todo_lock:
            take = min(n, todo[0])
            todo[0] -= take
            return take

    def unclaim(n: int) -> None:
        with todo_lock:
            todo[0] += n

    shape = {"CPU": 1}

    def client_baseline():
        gcs = RpcClient(gcs_address)
        daemons = {}
        try:
            while claim(1):
                lease_id, _nid, addr = gcs.call(
                    "request_lease", shape, None, 60.0, timeout=None)
                d = daemons.get(addr)
                if d is None:
                    d = daemons[addr] = RpcClient(addr)
                wid, _waddr = d.call("lease_worker", lease_id, timeout=30.0)
                d.notify("return_leased_worker", wid)
                gcs.notify("release_lease", lease_id)
        finally:
            for d in daemons.values():
                d.close()
            gcs.close()

    def client_batched():
        gcs = RpcClient(gcs_address)
        daemons = {}
        try:
            while True:
                take = claim(16)
                if not take:
                    return
                block_id, _nid, addr, granted = gcs.call(
                    "request_lease_batch", shape, None, take, 60.0,
                    timeout=None)
                d = daemons.get(addr)
                if d is None:
                    d = daemons[addr] = RpcClient(addr)
                # One carve hop covers the whole grant (lease_worker_block_n
                # amortizes the daemon RPC like the batch grant amortized
                # the GCS one).
                grants = d.call("lease_worker_block_n", block_id, shape,
                                granted, granted, timeout=30.0)
                for _lease, wid, _waddr in grants:
                    d.notify("return_leased_worker", wid)
                # Zero-TTL sweep stand-in: the real daemon returns idle
                # capacity on its background sweep — off the task critical
                # path — so the return rides a notify, not a sync call.
                gcs.notify("return_block_capacity", block_id, granted)
                done = len(grants)
                if take > done:
                    unclaim(take - done)
        finally:
            for d in daemons.values():
                d.close()
            gcs.close()

    target = client_batched if mode == "batched" else client_baseline
    ts = [_threading.Thread(target=target, daemon=True)
          for _ in range(threads)]
    # GO handshake: the parent times the drive window only, so interpreter
    # boot (seconds, on a small box) never skews the A/B ratio.
    print("DRIVER_READY=1", flush=True)
    sys.stdin.readline()
    t0 = time.perf_counter()
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=600)
    print(json.dumps({"done": tasks - todo[0],
                      "elapsed_s": time.perf_counter() - t0}), flush=True)


def run_control_plane_child(mode: str, tasks: int, clients: int) -> None:
    """One A/B arm, orchestrated across REAL process boundaries: the actual
    GCS server process, 4 stub-daemon processes, and 8 client driver
    processes — so the GCS's capacity (the thing this round shards) is what
    saturates, not a shared GIL. Flag env (shards/batching/ingest) is set
    by the parent and inherited by every child."""
    import threading

    from ray_tpu.core.cluster import _read_tagged_line
    from ray_tpu.core.rpc import RpcClient

    env = dict(os.environ)
    procs = []
    try:
        gcs_proc = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu.core.gcs_server"],
            stdout=subprocess.PIPE, env=env)
        procs.append(gcs_proc)
        gcs_address = _read_tagged_line(gcs_proc, "GCS_ADDRESS")
        for _ in range(4):
            p = subprocess.Popen(
                [sys.executable, __file__, "--stub-daemon", gcs_address,
                 "64"], stdout=subprocess.PIPE, env=env)
            procs.append(p)
            _read_tagged_line(p, "STUB_READY")

        driver_procs = 8
        per = [tasks // driver_procs] * driver_procs
        per[0] += tasks - sum(per)
        threads = max(1, clients // driver_procs)
        drivers = [subprocess.Popen(
            [sys.executable, __file__, "--control-plane-driver", mode,
             str(n), str(threads), gcs_address],
            stdout=subprocess.PIPE, stdin=subprocess.PIPE, text=True,
            env=env) for n in per]
        procs.extend(drivers)
        for p in drivers:
            _read_tagged_line(p, "DRIVER_READY")
        t0 = time.perf_counter()
        for p in drivers:
            p.stdin.write("GO\n")
            p.stdin.flush()
        done = 0
        for p in drivers:
            out, _ = p.communicate(timeout=600)
            done += json.loads(out.strip().splitlines()[-1])["done"]
        dt = time.perf_counter() - t0

        # Scenario 2: lease-grant latency while a slow aggregator chews on
        # a telemetry flood. This needs a monkeypatched store, so it runs
        # against an in-process service (same env-resolved flags); flood
        # and grants share one handler pool, as in production.
        from ray_tpu.core.gcs_server import GcsService
        from ray_tpu.core.ids import NodeID
        from ray_tpu.core.rpc import RpcServer

        svc = GcsService()
        server = RpcServer(svc, max_workers=128, name="bench-gcs-lag")
        orig_report = svc.store.report_metrics
        svc.store.report_metrics = (
            lambda *a, **k: (time.sleep(0.05), orig_report(*a, **k)))
        svc.register_node(NodeID.from_random(), "127.0.0.1:1",
                          {"CPU": 64}, {})
        flood = RpcClient(server.address)
        probe = RpcClient(server.address)
        lat = []
        try:
            for i in range(200):
                flood.notify("report_metrics", "bench-node", "comp", i, [])
            for _ in range(60):
                t1 = time.perf_counter()
                lease_id, _nid, _a = probe.call(
                    "request_lease", {"CPU": 1}, None, 30.0, timeout=60.0)
                lat.append(time.perf_counter() - t1)
                probe.notify("release_lease", lease_id)
            ingest = probe.call("ingest_stats")
        finally:
            svc.store.report_metrics = orig_report
            flood.close()
            probe.close()
            server.stop()
            svc.shutdown()
        lat.sort()
        print(json.dumps({
            "mode": mode,
            "tasks": tasks,
            "tasks_done": done,
            "clients": clients,
            "lease_cycles_per_s": round(done / dt, 1),
            "stalled_ingest_lease_p50_ms": round(
                lat[len(lat) // 2] * 1e3, 2),
            "stalled_ingest_lease_p99_ms": round(
                lat[min(len(lat) - 1, int(len(lat) * 0.99))] * 1e3, 2),
            "ingest_dropped": ingest["dropped"],
            "ingest_submitted": ingest["submitted"],
        }))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()


def run_control_plane(quick: bool = False) -> None:
    """Control-plane scaling A/B: the round-8 sharded plane (capacity-block
    batching + gcs_shards=8 + async ingest) vs the single-lock per-task
    plane it replaces, recorded in ``BENCH_core_r08.json``. Each arm runs in
    a fresh interpreter with its flags resolved from env at boot, exactly as
    a deployed GCS would."""
    tasks = 600 if quick else 10_000
    clients = 16 if quick else 64

    def trial(mode: str) -> dict:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        if mode == "batched":
            env.update({"RAY_TPU_GCS_SHARDS": "8",
                        "RAY_TPU_LEASE_BATCH_ENABLED": "1",
                        "RAY_TPU_GCS_INGEST_ASYNC_ENABLED": "1"})
        else:
            env.update({"RAY_TPU_GCS_SHARDS": "1",
                        "RAY_TPU_LEASE_BATCH_ENABLED": "0",
                        "RAY_TPU_GCS_INGEST_ASYNC_ENABLED": "0"})
        r = subprocess.run(
            [sys.executable, __file__, "--control-plane-child", mode,
             str(tasks), str(clients)],
            capture_output=True, text=True, timeout=900, env=env)
        if r.returncode != 0:
            print(json.dumps({"metric": "control_plane",
                              "error": (r.stderr or "")[-400:]}))
            sys.exit(1)
        return json.loads(r.stdout.strip().splitlines()[-1])

    # Alternating order + medians, the same shared-box protocol as the
    # observability A/Bs.
    order = (("batched", "baseline") if quick
             else ("batched", "baseline", "baseline", "batched",
                   "batched", "baseline"))
    trials = {"batched": [], "baseline": []}
    for mode in order:
        trials[mode].append(trial(mode))

    def median(vals):
        vals = sorted(vals)
        return vals[len(vals) // 2]

    results = {"tasks_in_flight": tasks, "client_threads": clients,
               "trials_per_mode": len(trials["batched"])}
    for mode in ("batched", "baseline"):
        results[f"lease_cycles_per_s_{mode}"] = median(
            [t["lease_cycles_per_s"] for t in trials[mode]])
        results[f"stalled_ingest_lease_p99_ms_{mode}"] = median(
            [t["stalled_ingest_lease_p99_ms"] for t in trials[mode]])
    results["speedup"] = round(
        results["lease_cycles_per_s_batched"]
        / results["lease_cycles_per_s_baseline"], 2)
    results["meets_2x_target"] = results["speedup"] >= 2.0
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "BENCH_core_r08.json")
    with open(out, "w") as f:
        json.dump({"results": results}, f, indent=1)
    print(json.dumps({"metric": "control_plane", **results}))


def run_sched_sim_child(arm: str, nodes: int, quick: bool) -> None:
    """One gang-scheduling arm over the in-process SimCluster (fresh
    interpreter; the parent resolved this arm's flags into env). Three
    measurements per arm: cross-tier edges of a slice-sized gang on the
    empty cluster, then gang create latency p50/p99 + churn throughput at
    ~60% utilization, then raw lease-cycle scheduler throughput."""
    from ray_tpu.core.sim_cluster import SimCluster

    hosts_per_slice = 16
    # Slice-sized gang: one full-host bundle per host in a slice, so the
    # topology-aware planner can land it DCN-free and the blind one can't.
    slice_gang = [{"CPU": 16.0}] * hosts_per_slice
    # Churn gang: 16 x quarter-host bundles (4 nodes' worth).
    churn_gang = [{"CPU": 4.0}] * 16
    churn = 40 if quick else 200
    lease_cycles = 300 if quick else 2000

    cluster = SimCluster(nodes, cpus_per_node=16, tpus_per_node=4, seed=0)
    try:
        pg = cluster.create_gang(slice_gang, strategy="PACK")
        edges = cluster.gang_cross_tier_edges(pg)
        cluster.remove_gang(pg)

        # Fill to ~60% so churn placement works a realistically loaded
        # scheduler, then steady-state: remove the oldest gang, time the
        # create that replaces it.
        fill = max(1, int(nodes * 0.6) // 4)
        live = [cluster.create_gang(churn_gang) for _ in range(fill)]
        lat = []
        t0 = time.perf_counter()
        for _ in range(churn):
            cluster.remove_gang(live.pop(0))
            t1 = time.perf_counter()
            live.append(cluster.create_gang(churn_gang))
            lat.append(time.perf_counter() - t1)
        churn_dt = time.perf_counter() - t0

        t2 = time.perf_counter()
        for _ in range(lease_cycles):
            lease_id, _nid, _addr = cluster.svc.request_lease(
                {"CPU": 1.0}, None, 30.0)
            cluster.svc.release_lease(lease_id)
        lease_dt = time.perf_counter() - t2
    finally:
        cluster.shutdown()

    lat.sort()
    print(json.dumps({
        "arm": arm,
        "nodes": nodes,
        "cross_tier_edges": edges,
        "gang_create_p50_ms": round(lat[len(lat) // 2] * 1e3, 3),
        "gang_create_p99_ms": round(
            lat[min(len(lat) - 1, int(len(lat) * 0.99))] * 1e3, 3),
        "gang_cycles_per_s": round(churn / churn_dt, 1),
        "lease_cycles_per_s": round(lease_cycles / lease_dt, 1),
    }))


def run_sched_sim_watchdog(nodes: int) -> None:
    """Watchdog-detection measurement: a node's heartbeats stop silently
    (SIGKILL posture, nothing declared) and we time how long the GCS
    health loop takes to mark it dead. Short health periods come from the
    parent's env so the number is about the detection path, not the
    default 5s budget."""
    from ray_tpu.core.sim_cluster import SimCluster, wait_for

    cluster = SimCluster(nodes, cpus_per_node=16, tpus_per_node=4, seed=0)
    try:
        victim = cluster.daemons[nodes // 2]
        # Let a couple of heartbeat rounds land so the victim is healthy.
        assert wait_for(lambda: cluster.svc.heartbeat(victim.node_id) == "ok",
                        timeout=10.0)
        cluster.stop_heartbeat(nodes // 2)
        t0 = time.perf_counter()
        detected = wait_for(
            lambda: victim.node_id in cluster.svc._dead_nodes, timeout=30.0)
        dt = time.perf_counter() - t0
    finally:
        cluster.shutdown()
    print(json.dumps({
        "nodes": nodes,
        "watchdog_detected": detected,
        "watchdog_detection_s": round(dt, 3),
    }))


def run_sched_sim(quick: bool = False) -> None:
    """Gang-scheduling-at-scale A/B over the simulated control plane
    (``ray_tpu.core.sim_cluster``): the topology-aware atomic gang path vs
    the per-bundle 2PC baseline it replaces, at 300-1000 stub-daemon nodes
    with real lease tables and live heartbeats. Records gang-placement
    latency p50/p99, gang churn + lease-cycle throughput, cross-tier-edge
    counts vs a topology-blind arm, and watchdog detection time in
    ``BENCH_sched_r01.json``. Each arm runs in a fresh interpreter with its
    flags resolved from env at boot, exactly as a deployed GCS would."""
    nodes = 64 if quick else 1000

    arm_env = {
        # Atomic topology-aware gang placement (the round-18 path).
        "gang": {"RAY_TPU_GANG_SCHEDULING_ENABLED": "1",
                 "RAY_TPU_TOPOLOGY_LABELS": "auto"},
        # Legacy per-bundle 2PC placement (gang scheduling off).
        "baseline": {"RAY_TPU_GANG_SCHEDULING_ENABLED": "0"},
        # Atomic gang reservation but topology-blind packing: isolates the
        # ICI-locality scoring's contribution to cross-tier edges.
        "blind": {"RAY_TPU_GANG_SCHEDULING_ENABLED": "1",
                  "RAY_TPU_TOPOLOGY_LABELS": "off"},
    }

    def trial(arm: str) -> dict:
        env = dict(os.environ)
        env.update({"JAX_PLATFORMS": "cpu",
                    "RAY_TPU_LOG_LEVEL": "WARNING"})
        env.update(arm_env[arm])
        r = subprocess.run(
            [sys.executable, __file__, "--sched-sim-child", arm, str(nodes)]
            + (["--quick"] if quick else []),
            capture_output=True, text=True, timeout=600, env=env)
        if r.returncode != 0:
            print(json.dumps({"metric": "sched_sim",
                              "error": (r.stderr or "")[-400:]}))
            sys.exit(1)
        return json.loads(r.stdout.strip().splitlines()[-1])

    # Alternating order + medians for the two timed arms; the blind arm
    # only contributes its (deterministic) cross-tier edge count.
    order = (("gang", "baseline") if quick
             else ("gang", "baseline", "baseline", "gang",
                   "gang", "baseline"))
    trials = {"gang": [], "baseline": []}
    for arm in order:
        trials[arm].append(trial(arm))
    blind = trial("blind")

    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "RAY_TPU_LOG_LEVEL": "WARNING",
                "RAY_TPU_HEALTH_CHECK_PERIOD_S": "0.2",
                "RAY_TPU_HEALTH_CHECK_FAILURE_THRESHOLD": "3",
                "RAY_TPU_SIM_HEARTBEAT_PERIOD_S": "0.1"})
    wd_nodes = nodes if quick else 300
    r = subprocess.run(
        [sys.executable, __file__, "--sched-sim-watchdog", str(wd_nodes)],
        capture_output=True, text=True, timeout=600, env=env)
    if r.returncode != 0:
        print(json.dumps({"metric": "sched_sim",
                          "error": (r.stderr or "")[-400:]}))
        sys.exit(1)
    watchdog = json.loads(r.stdout.strip().splitlines()[-1])

    def median(vals):
        vals = sorted(vals)
        return vals[len(vals) // 2]

    results = {"nodes": nodes, "hosts_per_slice": 16,
               "trials_per_arm": len(trials["gang"])}
    for arm in ("gang", "baseline"):
        for key in ("gang_create_p50_ms", "gang_create_p99_ms",
                    "gang_cycles_per_s", "lease_cycles_per_s"):
            results[f"{key}_{arm}"] = median(
                [t[key] for t in trials[arm]])
    results["cross_tier_edges_topology_aware"] = median(
        [t["cross_tier_edges"] for t in trials["gang"]])
    results["cross_tier_edges_blind"] = blind["cross_tier_edges"]
    results["watchdog_nodes"] = watchdog["nodes"]
    results["watchdog_detection_s"] = watchdog["watchdog_detection_s"]
    results["speedup"] = round(
        results["gang_cycles_per_s_gang"]
        / results["gang_cycles_per_s_baseline"], 2)
    results["p99_ratio"] = round(
        results["gang_create_p99_ms_baseline"]
        / results["gang_create_p99_ms_gang"], 2)
    results["meets_2x_target"] = (results["speedup"] >= 2.0
                                  or results["p99_ratio"] >= 2.0)
    if not quick:
        # --quick is the CI smoke (64 nodes, 1 trial): schema check only,
        # never overwrite the published at-scale artifact.
        out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_sched_r01.json")
        with open(out, "w") as f:
            json.dump({"results": results}, f, indent=1)
    print(json.dumps({"metric": "sched_sim", **results}))


def run_slo(quick: bool = False) -> None:
    """SLO-driven autoscaling bench: the open-loop load harness
    (``benches/loadgen.py``) sweeps offered load against fixed-1 / fixed-N /
    autoscaled sim-LLM deployments plus a tenant-quota A/B, and records the
    p99-TTFT-vs-offered-load curves in ``BENCH_slo_r01.json``. Runs in a
    fresh interpreter so serve/controller state can't leak into (or out of)
    the bench; ``--quick`` is the CI smoke (few hundred requests, schema +
    zero-unexplained-errors assertions inside the child)."""
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "RAY_TPU_METRICS_EXPORT_INTERVAL_S": "0.5"})
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "benches", "loadgen.py")
    cmd = [sys.executable, script]
    if quick:
        cmd.append("--quick")
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=1800,
                       env=env)
    if r.returncode != 0:
        print(json.dumps({"metric": "slo_loadgen",
                          "error": (r.stderr or "")[-400:]}))
        sys.exit(1)
    print(json.dumps({"metric": "slo_loadgen", **json.loads(
        r.stdout.strip().splitlines()[-1])}))


def run_rl(quick: bool = False) -> None:
    """Podracer RL throughput bench: ``benches/rl_throughput.py`` runs the
    {task path, DAG lane} x {runner-local, inference actor} IMPALA grid
    with alternating-order medians plus the LLM-RL reward-improvement
    smoke, and records ``BENCH_rl_r01.json``. Fresh interpreter so the
    in-process runtime and jit caches can't leak across benches;
    ``--quick`` is the CI smoke (tiny grid, one rep)."""
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu"})
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "benches", "rl_throughput.py")
    cmd = [sys.executable, script]
    if quick:
        cmd.append("--quick")
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=1800,
                       env=env)
    if r.returncode != 0:
        print(json.dumps({"metric": "rl_throughput",
                          "error": (r.stderr or "")[-400:]}))
        sys.exit(1)
    print(json.dumps({"metric": "rl_throughput", **json.loads(
        r.stdout.strip().splitlines()[-1])}))


if __name__ == "__main__":
    if "--metrics-child" in sys.argv:
        run_metrics_child(sys.argv[sys.argv.index("--metrics-child") + 1]
                          == "1")
    elif "--metrics-overhead" in sys.argv:
        run_metrics_overhead()
    elif "--trace-child" in sys.argv:
        run_trace_child(sys.argv[sys.argv.index("--trace-child") + 1]
                        == "1")
    elif "--trace-overhead" in sys.argv:
        run_trace_overhead()
    elif "--flight-child" in sys.argv:
        run_flight_child(sys.argv[sys.argv.index("--flight-child") + 1]
                         == "1", quick="--quick" in sys.argv)
    elif "--flight-overhead" in sys.argv:
        run_flight_overhead(
            quick="--quick" in sys.argv,
            out=(sys.argv[sys.argv.index("--out") + 1]
                 if "--out" in sys.argv else None))
    elif "--stub-daemon" in sys.argv:
        i = sys.argv.index("--stub-daemon")
        run_stub_daemon(sys.argv[i + 1], int(sys.argv[i + 2]))
    elif "--control-plane-driver" in sys.argv:
        i = sys.argv.index("--control-plane-driver")
        run_control_plane_driver(sys.argv[i + 1], int(sys.argv[i + 2]),
                                 int(sys.argv[i + 3]), sys.argv[i + 4])
    elif "--control-plane-child" in sys.argv:
        i = sys.argv.index("--control-plane-child")
        run_control_plane_child(sys.argv[i + 1], int(sys.argv[i + 2]),
                                int(sys.argv[i + 3]))
    elif "--control-plane" in sys.argv:
        run_control_plane(quick="--quick" in sys.argv)
    elif "--sched-sim-child" in sys.argv:
        i = sys.argv.index("--sched-sim-child")
        run_sched_sim_child(sys.argv[i + 1], int(sys.argv[i + 2]),
                            quick="--quick" in sys.argv)
    elif "--sched-sim-watchdog" in sys.argv:
        i = sys.argv.index("--sched-sim-watchdog")
        run_sched_sim_watchdog(int(sys.argv[i + 1]))
    elif "--sched-sim" in sys.argv:
        run_sched_sim(quick="--quick" in sys.argv)
    elif "--slo" in sys.argv:
        run_slo(quick="--quick" in sys.argv)
    elif "--rl" in sys.argv:
        run_rl(quick="--quick" in sys.argv)
    else:
        run_bench()
