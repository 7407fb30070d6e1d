#!/usr/bin/env python3
"""The benchmark's command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

runs one cell of BENCHMARK.json on the machine it is started on and prints,
as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced, ``breakdown``.
With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics. Without an accelerator it exits non-zero
and prints no result. ``--rehearse`` (tiny sizes, CPU, interpreted kernels,
virtual devices) is for the builder and the tests and is never a result.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()      # set-up is counted from here

import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--sweep", default="", help="builder's tool: session "
                    "rates to try in one process, comma-separated")
    ap.add_argument("--keep", default="", help="directory for the reduced "
                    "trace and its summary (a debugging aid)")
    args = ap.parse_args(argv)

    from benchmark.manifest import Manifest

    man = Manifest(ROOT)
    cell = man.cell(args.workload)
    config = man.load_config(cell["config"])
    traffic = man.load_traffic(cell["traffic"])
    if args.rehearse:
        traffic = _merge(traffic, traffic.get("rehearse", {}))
        config = _merge(config, config.get("rehearse", {}))
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_"
            f"device_count={cell['chips']}").strip()
    seconds = float(args.seconds if args.seconds is not None
                    else man.doc["run_seconds"])

    # The persistent compile cache: where the environment says, else a fixed
    # directory of the checkout (the path is part of the cache's key). The
    # program's own `enable_compile_cache` reads the same variable. Every
    # program is kept, however short its compilation.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # What the program would write under a fixed /tmp path goes inside the
    # checkout instead, so that two checkouts share nothing.
    scratch = os.path.join(ROOT, ".bench_run")
    os.makedirs(scratch, exist_ok=True)
    os.environ.setdefault("RAY_TPU_SESSION_DIR", os.path.join(scratch, "flightrec"))

    from benchmark.drivers import common
    from benchmark.reduce import shapes, trace as tr

    device = common.device_report(int(cell["chips"]), args.rehearse)
    peaks = None if args.rehearse else shapes.peaks(device["kind"])
    watch = common.CompileWatch()
    trace_dir = os.path.join(ROOT, ".bench_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    if args.keep:
        os.makedirs(args.keep, exist_ok=True)

    ctx = {"config": config, "traffic": traffic,
           "seed": args.seed, "seconds": seconds, "trace": bool(args.trace),
           "rehearse": args.rehearse, "chips": int(cell["chips"]),
           "trace_dir": trace_dir, "scratch_dir": scratch,
           "sweep": [float(x) for x in args.sweep.split(",") if x]}
    driver = __import__(f"benchmark.drivers.{traffic['driver']}",
                        fromlist=["run"])
    run = driver.run(ctx)
    if "sweep" in run:
        print(json.dumps(run))
        return 0

    compiles = watch.between(run["t_open"], run["t_close"])
    run["correct_parts"]["no_compile_in_window"] = compiles == 0
    run.update(config=config, traffic=traffic, peaks=peaks or {
        "bf16_flops_per_s": float("nan"), "hbm_bytes_per_s": float("nan"),
        "hbm_bytes": float("nan")}, trace=None)
    memory = common.memory_stats()
    run["memory_peak_bytes"] = int(memory.get("peak_bytes_in_use", 0))
    device["memory_peak_bytes"] = run["memory_peak_bytes"]

    breakdown = None
    tracer = run.get("tracer")
    if tracer is not None and tracer.xplane():
        with open(os.path.join(man.home, "reduce", "host_spans.json")) as f:
            labels = {k: v for k, v in json.load(f).items()
                      if not k.startswith("_")}
        keep = [n for needles in labels.values() for n in needles]
        run["trace"] = tr.load_xplane(tracer.xplane(), keep)
        run["trace_host_t0"] = tracer.t_start
        if tr.device_lines(run["trace"]):
            b = tr.busy(run["trace"])
            device["busy_s"], device["window_s"] = b["busy_s"], b["window_s"]
            breakdown = {"device_ops": tr.top_ops(run["trace"], 10),
                         "idle_gaps": tr.idle_gaps(run["trace"], labels, 10)}
        else:
            run["trace"] = None
        if args.keep:
            if run["trace"] is not None:
                with open(os.path.join(args.keep, "trace_summary.json"), "w") as f:
                    json.dump(tr.summarise(run["trace"]), f, indent=1)
            shutil.copy(tracer.xplane(), os.path.join(args.keep, "trace.xplane.pb"))
    shutil.rmtree(trace_dir, ignore_errors=True)

    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in man.metrics_of(args.workload, kind):
        if m["name"] == "setup_s":
            value = run["t_open"] - _T_START
        else:
            value = man.reader(m["name"])(run)
        if value is not None and value == value:      # a reader that found
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}  # nothing is left out
    if args.trace and "busy_s" not in device and not args.rehearse:
        print("benchmark: the traced run holds no device operation",
              file=sys.stderr)
        return 1

    out = {"correct": all(run["correct_parts"].values()),
           "attempted": run["attempted"], "failed": run["failed"],
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    # What the verdict rests on, on a line of its own before the result.
    print(json.dumps({"detail": {
        "correct_parts": run["correct_parts"], "check": run["check"],
        "window_s": run["window_s"], "compiles_in_window": compiles,
        "compile_cache": {**watch.cache, "backend_compile_s": watch.compile_s,
                          "dir": os.environ["JAX_COMPILATION_CACHE_DIR"]},
        "phases_s": {k: v - _T_START for k, v in run.get("phases", {}).items()},
        "memory_stats": memory,
        "realised": run.get("realised"), "seed": args.seed,
        "rehearsal": args.rehearse}}), flush=True)
    if args.keep:
        with open(os.path.join(args.keep, "records.json"), "w") as f:
            json.dump({k: run.get(k) for k in
                       ("records", "counters", "step_s", "t_open", "t_close",
                        "trace_host_t0")},
                      f, default=str)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
