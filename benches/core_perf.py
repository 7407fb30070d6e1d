"""Core runtime microbenchmarks — the ray_perf analog.

Mirrors the reference's microbenchmark suite
(``python/ray/_private/ray_perf.py:93``, run by
``release/microbenchmark/run_microbenchmark.py``): trivial-task throughput,
actor-call latency/throughput (sync + pipelined), object put/get bandwidth,
and a multi-node broadcast — run against BOTH runtimes (the in-process
``Runtime`` and the multiprocess cluster) so control-plane cost is visible.

Writes one JSON line per metric and aggregates into
``BENCH_core_r{N}.json`` at the repo root when ``--round N`` is given.

Usage::

    python benches/core_perf.py [--round 3] [--quick]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# Control-plane benchmark: always CPU — it measures host code, and must not
# take the chip from whatever else runs on the machine. Overriding (not
# setdefault) so spawned cluster processes inherit it too.
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import ray_tpu


def timed(fn, *, repeat: int = 1):
    t0 = time.perf_counter()
    for _ in range(repeat):
        fn()
    return (time.perf_counter() - t0) / repeat


def bench_tasks(results: dict, n_seq: int, n_par: int) -> None:
    @ray_tpu.remote
    def nop():
        return None

    # Warmup: force worker spawns + lease acquisition out of the timing.
    ray_tpu.get([nop.remote() for _ in range(32)], timeout=300)

    t = timed(lambda: ray_tpu.get(nop.remote(), timeout=60), repeat=n_seq)
    results["task_seq_latency_us"] = round(t * 1e6, 1)
    results["task_seq_per_s"] = round(1.0 / t, 1)

    def burst():
        ray_tpu.get([nop.remote() for _ in range(n_par)], timeout=600)

    burst()  # warm leases for the burst width
    dt = timed(burst)
    results["task_throughput_per_s"] = round(n_par / dt, 1)


def bench_actors(results: dict, n_seq: int, n_par: int) -> None:
    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.x = 0

        def incr(self):
            self.x += 1
            return self.x

    c = Counter.remote()
    ray_tpu.get(c.incr.remote(), timeout=120)

    t = timed(lambda: ray_tpu.get(c.incr.remote(), timeout=60), repeat=n_seq)
    results["actor_call_latency_us"] = round(t * 1e6, 1)
    results["actor_call_per_s"] = round(1.0 / t, 1)

    def pipelined():
        ray_tpu.get([c.incr.remote() for _ in range(n_par)], timeout=600)

    pipelined()
    dt = timed(pipelined)
    results["actor_pipelined_per_s"] = round(n_par / dt, 1)

    @ray_tpu.remote
    class AsyncActor:
        async def hit(self):
            return 1

    a = AsyncActor.options(max_concurrency=32).remote()
    ray_tpu.get(a.hit.remote(), timeout=120)

    def async_burst():
        ray_tpu.get([a.hit.remote() for _ in range(n_par)], timeout=600)

    async_burst()
    dt = timed(async_burst)
    results["async_actor_per_s"] = round(n_par / dt, 1)


def bench_objects(results: dict, big_mb: int, n_small: int) -> None:
    big = np.random.default_rng(0).random(big_mb * 1024 * 1024 // 8)

    t0 = time.perf_counter()
    ref = ray_tpu.put(big)
    put_s = time.perf_counter() - t0
    results["put_gbps"] = round(big.nbytes / put_s / 1e9, 3)

    @ray_tpu.remote
    def touch(arr):
        return float(arr[0])  # forces a cross-process fetch of the buffer

    t0 = time.perf_counter()
    ray_tpu.get(touch.remote(ref), timeout=600)
    fetch_s = time.perf_counter() - t0
    results["object_fetch_gbps"] = round(big.nbytes / fetch_s / 1e9, 3)
    results["object_size_mb"] = big_mb
    del ref

    payload = b"x" * 1024
    t0 = time.perf_counter()
    refs = [ray_tpu.put(payload) for _ in range(n_small)]
    ray_tpu.get(refs, timeout=600)
    dt = time.perf_counter() - t0
    results["small_put_get_per_s"] = round(2 * n_small / dt, 1)


def bench_object_plane(results: dict, core, cluster, quick: bool) -> None:
    """Parallel object-plane read-path metrics (multiprocess runtime only):

    - ``get_batch_per_s``: one ``get([64 refs])`` where every ref is owned
      by another process (owner-served fetches) — the batched-get fan-out
      vs the serial per-ref loop.
    - ``multi_source_pull_gbps``: a 64 MB chunked pull with TWO replica
      daemons available — the multi-source stripe vs a single source.
    - ``seal_wakeup_latency_us``: time from a remote seal to get() return
      on a waiting consumer — location-push wakeup vs the poll backoff.
    """
    import threading

    from ray_tpu.core.ids import ObjectID
    from ray_tpu.core.object_ref import ObjectRef

    # -- batched multi-ref get -----------------------------------------------
    @ray_tpu.remote
    class Holder:
        def make(self, n, size):
            return [ray_tpu.put(os.urandom(size)) for _ in range(n)]

        def seal_after(self, oid_bytes, delay, size):
            from ray_tpu.core import serialization as _ser
            from ray_tpu.core.ids import ObjectID as _OID
            from ray_tpu.core.runtime import get_runtime

            payload = _ser.serialize(b"x" * size).to_bytes()
            time.sleep(delay)
            # Timestamp BEFORE the seal: the push can wake the waiter
            # before this method even returns from seal_payload (the
            # daemon note is one-way), so an after-seal stamp underflows.
            t_seal = time.monotonic()
            get_runtime().seal_payload(_OID(oid_bytes), payload)
            return t_seal

    holder = Holder.remote()
    n_refs = 64
    refs = ray_tpu.get(holder.make.remote(n_refs, 4096), timeout=120)
    reps = 10 if quick else 30

    def batch_get():
        # Values re-fetch from the owner each pass: drop the local cache.
        with core._cache_lock:
            for r in refs:
                core._cache.pop(r.id, None)
        ray_tpu.get(refs, timeout=120)

    batch_get()  # warm connections
    dt = timed(batch_get, repeat=reps)
    results["get_batch_per_s"] = round(n_refs / dt, 1)
    results["get_batch_latency_us"] = round(dt * 1e6, 1)

    # -- multi-source chunked pull -------------------------------------------
    mb = 64
    blob = np.random.default_rng(1).random(mb * 1024 * 1024 // 8)
    ref = ray_tpu.put(blob)
    origin = core._gcs_rpc.call("locate_object", ref.id.binary())[0][0]
    other = next(h for h in cluster.nodes if h.node_id != origin)

    @ray_tpu.remote(scheduling_strategy=ray_tpu.NodeAffinitySchedulingStrategy(
        node_id=other.node_id, soft=False))
    def replicate(refs):
        # Pull the object AND seal a replica on this node explicitly —
        # heap-fallback pulls don't auto-register new locations (only
        # shm-landing pulls do), and the bench needs a guaranteed second
        # source either way.
        from ray_tpu.core import serialization as _ser
        from ray_tpu.core.runtime import get_runtime

        value = ray_tpu.get(refs[0])
        get_runtime().seal_serialized(refs[0].id, _ser.serialize(value))
        return True

    ray_tpu.get(replicate.remote([ref]), timeout=600)
    deadline = time.time() + 60
    while (len(core._gcs_rpc.call("locate_object", ref.id.binary())) < 2
           and time.time() < deadline):
        time.sleep(0.2)
    n_srcs = len(core._gcs_rpc.call("locate_object", ref.id.binary()))

    def pull():
        with core._cache_lock:
            core._cache.pop(ref.id, None)
        ray_tpu.get(ref, timeout=600)

    pull()
    dt = timed(pull, repeat=2 if quick else 4)
    results["multi_source_pull_gbps"] = round(blob.nbytes / dt / 1e9, 3)
    results["multi_source_pull_sources"] = n_srcs
    del ref, blob

    # -- seal-to-wakeup latency ----------------------------------------------
    stats_fn = getattr(core, "get_stats", None)
    lat = []
    sleeps0 = stats_fn()["backoff_sleeps"] if stats_fn else 0
    for _ in range(5 if quick else 10):
        oid = ObjectID.for_put()
        seal_fut = holder.seal_after.remote(oid.binary(), 0.05, 256 * 1024)
        ray_tpu.get(ObjectRef(oid), timeout=60)
        t_ret = time.monotonic()
        t_seal = ray_tpu.get(seal_fut, timeout=60)
        lat.append(t_ret - t_seal)
    lat.sort()
    results["seal_wakeup_latency_us"] = round(lat[len(lat) // 2] * 1e6, 1)
    if stats_fn:
        s = stats_fn()
        results["get_backoff_sleeps"] = s["backoff_sleeps"] - sleeps0
        results["get_push_wakeups"] = s.get("push_wakeups", 0)


def bench_broadcast(results: dict, mb: int, n_nodes: int) -> None:
    """1-to-N object broadcast across node daemons (the reference's 1 GiB
    broadcast envelope row, release/benchmarks/README.md:17-19)."""
    blob = np.ones(mb * 1024 * 1024 // 8)
    ref = ray_tpu.put(blob)

    @ray_tpu.remote(scheduling_strategy=ray_tpu.SpreadSchedulingStrategy())
    def consume(arr):
        return float(arr.sum())

    # Warm the spread lease on every node first (with a TINY object, so the
    # payload itself is not pre-distributed): the timed pass must measure
    # the transfer plane, not interpreter spawns on nodes that have never
    # run a task (ray_perf warms the same way).
    warm = ray_tpu.put(np.ones(8))
    ray_tpu.get([consume.remote(warm) for _ in range(n_nodes)], timeout=600)
    del warm

    t0 = time.perf_counter()
    out = ray_tpu.get([consume.remote(ref) for _ in range(n_nodes)],
                      timeout=600)
    dt = time.perf_counter() - t0
    assert all(abs(v - blob.sum()) < 1e-6 for v in out)
    results["broadcast_mb"] = mb
    results["broadcast_nodes"] = n_nodes
    results["broadcast_gbps"] = round(n_nodes * blob.nbytes / dt / 1e9, 3)


# Regression floors for the multiprocess runtime on the 1-core CI box —
# the standing perf gate (VERDICT r3 #1). Values are deliberately below
# current measurements (put ~1.8-3.5 GB/s, broadcast ~0.3, actor ~550-850us
# depending on box load) so only real regressions trip them.
FLOORS = {
    "put_gbps": ("min", 1.0),
    # r5 zero-copy transfer lifted 4-node 64MB broadcast to ~1.0-1.4 GB/s;
    # the floor locks in a conservative slice of that (r4's was 0.15).
    "broadcast_gbps": ("min", 0.5),
    "object_fetch_gbps": ("min", 0.3),
    "small_put_get_per_s": ("min", 50_000),
    # Settled-box actor call measures ~280-550µs (PROFILE_NOTES.md); 700
    # trips on structural regressions while riding out 1-core box jitter.
    "actor_call_latency_us": ("max", 700.0),
    "task_seq_latency_us": ("max", 900.0),
}


# Floors that only hold with the native shm arena loaded: on containers
# where the store .so cannot load (glibc mismatch -> heap fallback), the
# zero-copy object plane is off and bandwidth collapses for EVERY build —
# gating on it would fail seed and candidate alike. They are reported as
# skipped (with the reason) instead of violated; the latency/throughput
# floors still gate.
SHM_DEPENDENT_FLOORS = {"put_gbps", "broadcast_gbps", "object_fetch_gbps"}


def check_floors(results: dict, shm_available: bool = True) -> list:
    violations = []
    skipped = []
    for key, (kind, bound) in FLOORS.items():
        if key not in results:
            continue
        if not shm_available and key in SHM_DEPENDENT_FLOORS:
            skipped.append(key)
            continue
        v = results[key]
        if (kind == "min" and v < bound) or (kind == "max" and v > bound):
            violations.append(f"{key}={v} violates {kind} {bound}")
    if skipped:
        results["floors_skipped_no_shm"] = skipped
    return violations


def run_suite(runtime: str, quick: bool) -> dict:
    results: dict = {"runtime": runtime}
    n_seq = 100 if quick else 300
    n_par = 500 if quick else 2000
    big_mb = 64 if quick else 256

    bench_tasks(results, n_seq, n_par)
    bench_actors(results, n_seq, n_par)
    bench_objects(results, big_mb, 200 if quick else 1000)
    if runtime == "multiprocess":
        bench_broadcast(results, 16 if quick else 64, 4)
    return results


def _settle(core, cluster, timeout: float = 120.0) -> None:
    """Wait for every daemon's prestarted workers to finish booting —
    interpreter spawns (~2s of imports each) otherwise steal the box's CPU
    mid-measurement and the bench reads as contention, not transport."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        stats = [core._daemons.get(h.address).call("stats", timeout=10)
                 for h in cluster.nodes]
        if all(s["idle"] >= 2 for s in stats):
            break
        time.sleep(1.0)
    time.sleep(2.0)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--round", type=int, default=0)
    parser.add_argument("--quick", action="store_true")
    parser.add_argument("--runtime", choices=["local", "multiprocess", "both"],
                        default="both")
    args = parser.parse_args()

    all_results = []

    if args.runtime in ("local", "both"):
        ray_tpu.init(num_nodes=1)
        r = run_suite("local", args.quick)
        ray_tpu.shutdown()
        print(json.dumps(r), flush=True)
        all_results.append(r)

    if args.runtime in ("multiprocess", "both"):
        from ray_tpu.core import rpc as rpc_mod
        from ray_tpu.core.cluster import Cluster, connect

        cluster = Cluster(num_nodes=4, resources_per_node={"CPU": 2})
        core = connect(cluster.gcs_address)
        try:
            _settle(core, cluster)
            rpc_mod.reset_send_stats()  # measure the suite, not the boot
            r = run_suite("multiprocess", args.quick)
            bench_object_plane(r, core, cluster, args.quick)
            # Control-plane fast-path health: how many frames each sendmsg
            # carried (driver-side) and how often steady-state calls skipped
            # the task-spec template (see README "Control-plane performance").
            send = rpc_mod.send_stats()
            r["frames_per_syscall"] = round(send["frames_per_syscall"], 3)
            spec = core.spec_cache_stats()
            r["spec_cache_hit_rate"] = round(spec["hit_rate"], 4)
            stats = [core._daemons.get(h.address).call("node_stats",
                                                       timeout=10)
                     for h in cluster.nodes]
            shm_ok = any(s.get("store_capacity", 0) > 0 for s in stats)
            r["native_store"] = shm_ok
            violations = check_floors(r, shm_available=shm_ok)
            r["floors"] = {k: v[1] for k, v in FLOORS.items()}
            r["floor_violations"] = violations
            print(json.dumps(r), flush=True)
            all_results.append(r)
        finally:
            core.shutdown()
            cluster.shutdown()

    if args.round:
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), f"BENCH_core_r{args.round:02d}.json")
        with open(path, "w") as f:
            json.dump({"results": all_results}, f, indent=1)
        print(f"wrote {path}")
    # The floor gate is only meaningful if it can FAIL the run.
    for r in all_results:
        if r.get("floor_violations"):
            print(f"FLOOR VIOLATIONS: {r['floor_violations']}",
                  file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
