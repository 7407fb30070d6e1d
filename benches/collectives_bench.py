"""Eager-collective microbench: allreduce across actor processes, per
topology and schedule.

Prints one JSON line per (world, nodes, hierarchy, MB) cell. Two schedules
are compared on the same box:

- **flat** (``collective_hierarchy_enabled=0``): the topology-blind ring —
  per-rank traffic is 2*(N-1)/N * size, constant in world size.
- **hier**: the two-level schedule — ranks sharing a node store reduce
  intra-node through shm at a leader, node leaders run the segmented
  pipelined ring (size/num_nodes bytes per node across the DCN analog),
  results fan back out by shm key.

``per_rank_gbps`` keeps the r05-comparable ring-algorithm definition
(2*(N-1)/N * size / wall) so rounds are comparable across rounds;
``cross_store_mb`` is the instrumented DCN-analog byte counter summed over
ranks — the quantity the hierarchy minimizes.

Usage:: python benches/collectives_bench.py [--mb 64] [--worlds 4]
            [--topos 1,2] [--quick] [--round 6]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# Host-collective benchmark: always CPU (see core_perf.py — it measures
# host code and must not take the chip).
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import ray_tpu  # noqa: E402
from ray_tpu.core.cluster import Cluster, connect  # noqa: E402
from ray_tpu.core import runtime as runtime_mod  # noqa: E402


def bench_world(world: int, mb: int, nodes: int = 1, hierarchy: bool = True,
                repeat: int = 3) -> dict:
    assert world % nodes == 0, (world, nodes)
    per_node = world // nodes
    cluster = Cluster(
        num_nodes=nodes, resources_per_node={"CPU": per_node},
        system_config={"collective_hierarchy_enabled": hierarchy})
    try:
        core = connect(cluster.gcs_address)
        try:
            @ray_tpu.remote
            class Member:
                def __init__(self, rank, world):
                    from ray_tpu.parallel import collectives as c

                    c.init_collective_group(world, rank, backend="gloo",
                                            group_name="bench")
                    self.rank = rank

                def allreduce(self, mb, repeat):
                    from ray_tpu.parallel import collectives as c

                    arr = np.ones(mb * 1024 * 1024 // 8)
                    c.allreduce(arr, group_name="bench")  # warm
                    stats0 = c.get_group_stats("bench")
                    t0 = time.perf_counter()
                    for _ in range(repeat):
                        c.allreduce(arr, group_name="bench")
                    dt = (time.perf_counter() - t0) / repeat
                    stats1 = c.get_group_stats("bench")
                    delta = {k: (stats1[k] - stats0[k]) / repeat
                             for k in stats1}
                    return dt, delta

            # Pin ranks CONTIGUOUSLY across nodes (rank r on node
            # r*nodes/world) so the store grouping is deterministic.
            members = []
            for r in range(world):
                node = cluster.nodes[r * nodes // world]
                members.append(Member.options(
                    num_cpus=1,
                    scheduling_strategy=ray_tpu.NodeAffinitySchedulingStrategy(
                        node_id=node.node_id)).remote(r, world))
            results = ray_tpu.get(
                [m.allreduce.remote(mb, repeat) for m in members],
                timeout=600)
            dt = max(t for t, _ in results)
            cross = sum(d.get("bytes_cross_store", 0) for _, d in results)
            hier_rounds = sum(d.get("hier_rounds", 0) for _, d in results)
            size = mb * 1024 * 1024
            return {
                "metric": "ring_allreduce",
                "world": world,
                "nodes": nodes,
                "topology": f"{nodes}x{per_node}",
                "hierarchy": bool(hierarchy and hier_rounds),
                "mb": mb,
                "wall_s": round(dt, 4),
                "per_rank_mb_moved": round(2 * (world - 1) / world * mb, 2),
                "per_rank_gbps": round(2 * (world - 1) / world * size
                                       / dt / 1e9, 3),
                "cross_store_mb": round(cross / 1e6, 2),
            }
        finally:
            core.shutdown()
            runtime_mod._global_runtime = None
    finally:
        cluster.shutdown()


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--mb", type=int, default=64)
    parser.add_argument("--worlds", default="4")
    parser.add_argument("--topos", default="1,2",
                        help="comma list of node counts per cell")
    parser.add_argument("--quick", action="store_true",
                        help="one small-size smoke per topology (CI: no "
                             "multi-hundred-MB sweeps)")
    parser.add_argument("--round", type=int, default=0,
                        help="write BENCH_collectives_rNN.json at repo root")
    args = parser.parse_args()
    oob = os.environ.get("RAY_TPU_RPC_OOB", "1") != "0"
    shm = os.environ.get("RAY_TPU_COLLECTIVE_SHM", "1") != "0"
    transport = (("oob" if oob else "pickled") + "-socket"
                 + ("+shm" if shm else ""))
    worlds = [int(w) for w in args.worlds.split(",")]
    topos = [int(t) for t in args.topos.split(",")]
    cells = []
    for world in worlds:
        for nodes in topos:
            if world % nodes:
                continue
            for hierarchy in (False, True):
                if args.quick and not hierarchy:
                    continue  # quick mode: one smoke per topology
                mb = 4 if args.quick else args.mb
                repeat = 1 if args.quick else 3
                cells.append((world, mb, nodes, hierarchy, repeat))
    results = []
    for world, mb, nodes, hierarchy, repeat in cells:
        r = bench_world(world, mb, nodes=nodes, hierarchy=hierarchy,
                        repeat=repeat)
        r["transport"] = transport
        print(json.dumps(r), flush=True)
        results.append(r)
    if args.round:
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            f"BENCH_collectives_r{args.round:02d}.json")
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
