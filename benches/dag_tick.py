"""Compiled-DAG tick microbench: the µs-scale execution path, A/B'd
against per-call actor task submission on the same box.

Three modes per chain length, all over REAL worker processes (multiprocess
cluster, same host):

- **task_path** — the per-call baseline: each tick submits one actor task
  per stage (spec encode → push → execute → result seal), chained by
  ObjectRef. What PRs 1–2 made fast; still a full control-plane round
  trip per stage per tick.
- **compiled_serial** — one resident compiled DAG, one tick in flight:
  ``execute(x).get()`` per tick. Measures the pure channel hand-off
  latency (no pipelining).
- **compiled_pipelined** — the steady-state shape: a sliding window of
  in-flight ticks keeps every stage busy, so per-tick wall time collapses
  to the bottleneck stage + channel cost. Run at the configured
  ``dag_channel_slots`` ring depth AND at ``slots=1`` (the old capacity-1
  seqlock channel) — the multi-slot ring is what lets >1 tick ride each
  edge, which is the whole burst-throughput win.

Usage:: python benches/dag_tick.py [--ticks 300] [--quick] [--round 1]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

# Control-plane benchmark: always CPU (it measures host code and must not
# take the chip — see core_perf.py).
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import ray_tpu  # noqa: E402
from ray_tpu.core import runtime as runtime_mod  # noqa: E402
from ray_tpu.core.cluster import Cluster, connect  # noqa: E402
from ray_tpu.dag import InputNode  # noqa: E402


def _percentiles(samples_s):
    samples_us = sorted(s * 1e6 for s in samples_s)
    n = len(samples_us)
    return (statistics.median(samples_us),
            samples_us[min(n - 1, int(n * 0.9))])


def _row(stages, mode, slots, samples_s, window=1):
    p50, p90 = _percentiles(samples_s)
    total = sum(samples_s)
    return {
        "metric": "dag_tick",
        "stages": stages,
        "mode": mode,
        "slots": slots,
        "window": window,
        "ticks": len(samples_s),
        "tick_us_p50": round(p50, 1),
        "tick_us_p90": round(p90, 1),
        "ticks_per_s": round(len(samples_s) / total, 1),
    }


def bench_chain(stages: int, ticks: int, slots_list) -> list:
    """All modes for one chain length inside one cluster (same workers)."""
    cluster = Cluster(num_nodes=1,
                      resources_per_node={"CPU": stages + 2})
    rows = []
    try:
        core = connect(cluster.gcs_address)
        try:
            @ray_tpu.remote
            class Echo:
                def apply(self, x):
                    return x

            # -- task path: per-call actor submission, chained refs ------
            actors = [Echo.remote() for _ in range(stages)]
            ray_tpu.get([a.apply.remote(0) for a in actors], timeout=120)
            samples = []
            for i in range(max(20, ticks // 4)):
                t0 = time.perf_counter()
                ref = i
                for a in actors:
                    ref = a.apply.remote(ref)
                ray_tpu.get(ref, timeout=60)
                samples.append(time.perf_counter() - t0)
            rows.append(_row(stages, "task_path", 0, samples))

            for slots in slots_list:
                dag_actors = [Echo.remote() for _ in range(stages)]
                ray_tpu.get([a.apply.remote(0) for a in dag_actors],
                            timeout=120)
                node = InputNode()
                for a in dag_actors:
                    node = a.apply.bind(node)
                compiled = node.experimental_compile(channel_slots=slots)
                try:
                    assert compiled.execute(-1).get(timeout=60) == -1  # warm
                    # -- serial: one tick in flight ----------------------
                    samples = []
                    for i in range(ticks):
                        t0 = time.perf_counter()
                        assert compiled.execute(i).get(timeout=60) == i
                        samples.append(time.perf_counter() - t0)
                    rows.append(_row(stages, "compiled_serial", slots,
                                     samples))
                    # -- pipelined: sliding window of in-flight ticks ----
                    # Window sized to the ring so submission never parks
                    # on a full pipeline (capacity-1 gets the widest
                    # window IT can sustain: one tick per edge).
                    window = max(2, min(16, slots * 2))
                    refs = [compiled.execute(i) for i in range(window)]
                    samples = []
                    for i in range(ticks):
                        t0 = time.perf_counter()
                        assert refs[0].get(timeout=60) == i
                        refs.pop(0)
                        refs.append(compiled.execute(window + i))
                        samples.append(time.perf_counter() - t0)
                    for r in refs:
                        r.get(timeout=60)
                    rows.append(_row(stages, "compiled_pipelined", slots,
                                     samples, window=window))
                finally:
                    compiled.teardown()
        finally:
            core.shutdown()
            runtime_mod._global_runtime = None
    finally:
        cluster.shutdown()
    return rows


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--ticks", type=int, default=300)
    parser.add_argument("--stages", default="2,4",
                        help="comma list of chain lengths")
    parser.add_argument("--quick", action="store_true",
                        help="CI smoke: one short 2-stage sweep")
    parser.add_argument("--round", type=int, default=0,
                        help="write BENCH_dag_rNN.json at repo root")
    args = parser.parse_args()
    from ray_tpu.core.config import config

    default_slots = int(config().dag_channel_slots)
    if args.quick:
        stage_list, ticks = [2], 40
        slots_per_chain = {2: [default_slots]}
    else:
        stage_list = [int(s) for s in args.stages.split(",")]
        ticks = args.ticks
        # The multi-slot-vs-capacity-1 burst A/B rides the LONGEST chain
        # (where pipelining matters most).
        slots_per_chain = {s: [default_slots] for s in stage_list}
        slots_per_chain[max(stage_list)] = [1, default_slots]
    results = []
    for stages in stage_list:
        for r in bench_chain(stages, ticks, slots_per_chain[stages]):
            r["cpus"] = os.cpu_count()
            print(json.dumps(r), flush=True)
            results.append(r)
    if args.round:
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            f"BENCH_dag_r{args.round:02d}.json")
        existing = []
        if os.path.exists(path):
            with open(path) as f:
                existing = json.load(f).get("results", [])
        with open(path, "w") as f:
            json.dump({"results": existing + results}, f, indent=1)
        print(f"wrote {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
