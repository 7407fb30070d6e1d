"""What the serve engine's span ring says about a run, beyond its counters.

    python -m ray_tpu.devtools.stepspans OUT.json SCRIPT [ARGS...]

runs SCRIPT in this process (as ``python SCRIPT ARGS...`` would: it has to
hold the engine in-process, as ``benchmark/run.py`` does), then reads
``tracing.recorded()`` and writes :func:`summarise`'s dict to OUT.json. It is
an operator's tool for a run the profiler did not trace: ``stats()`` says how
much of the window was host CPU, host waiting, ``device_get`` and hand-off;
this says WHERE in the step (wall and ``cpu_ns`` of each ``llm.step.<phase>``,
the prefill's jitted call by quantile) and WHEN in the run (by 5 s bucket
before the last span: the hand-off's ``handoff_ns`` and how often the driver
changed, the rows a decode carried, how late a request's last item reached its
client and, beside it, the four hand-overs of an item's way back: the pick-up
in ``drive``, the replica's publish, the handle's take, the caller's hold,
each as mean and max, and the CPU the streams' two sides took).
"""
from __future__ import annotations

import json
import os
import runpy
import sys
from typing import Dict, Iterable, List, Optional

BUCKET_S = 5


def _quantile(values: List[float], q: float) -> Optional[float]:
    values = sorted(values)
    return values[min(len(values) - 1, int(q * len(values)))] if values else None


def _ms(spans: list) -> List[float]:
    return [(s.end_ns - s.start_ns) / 1e6 for s in spans]


def _attr(span, key: str):
    return (span.attrs or {}).get(key, 0)


def _mean(values: List[float]) -> Optional[float]:
    return sum(values) / len(values) if values else None


def summarise(spans: Iterable) -> Dict:
    """``spans``: ``tracing.recorded()``'s list. Times in ms unless named."""
    by: Dict[str, list] = {}
    for s in spans:
        by.setdefault(s.name, []).append(s)
    t_end = max((s.end_ns for ss in by.values() for s in ss), default=0)

    def bucket(ns: int) -> int:     # seconds before the last span's end
        return int((ns - t_end) / 1e9 // BUCKET_S) * BUCKET_S

    # The steps that dispatched a decode, a phase's mean wall and CPU a step.
    steps = by.get("llm.step", [])
    decode = {s.span_id for s in steps if _attr(s, "batch")}
    phases: Dict[str, list] = {}
    for name, ss in by.items():
        if name.startswith("llm.step."):
            for s in ss:
                if s.parent_id in decode:
                    p = phases.setdefault(name[len("llm.step."):], [0.0, 0.0])
                    p[0] += (s.end_ns - s.start_ns) / 1e6
                    p[1] += _attr(s, "cpu_ns") / 1e6
    n = max(1, len(decode))
    calls = by.get("llm.prefill.dispatch", [])
    steps_by: Dict[int, list] = {}
    driver = None
    for s in sorted(steps, key=lambda s: s.start_ns):
        b = steps_by.setdefault(bucket(s.start_ns), [0, 0.0, 0, 0, 0])
        b[0] += 1
        b[1] += _attr(s, "handoff_ns") / 1e9
        b[2] += _attr(s, "batch")
        b[3] += _attr(s, "admit_stopped") == "queue_empty"
        # A step run by another thread than the step before: the driver was
        # held in its own yield and another consumer elected itself.
        b[4] += driver not in (None, _attr(s, "driver"))
        driver = _attr(s, "driver")

    # A request's way around the engine, by the first span of each name in
    # its trace: client -> engine's queue, engine's finish -> client's end.
    def first(name: str) -> Dict[str, object]:
        out: Dict[str, object] = {}
        for s in by.get(name, []):
            out.setdefault(s.trace_id, s)
        return out

    outer, waits = first("serve.request"), first("llm.admission_wait")
    streams = first("serve.replica_stream")
    requests_by: Dict[int, list] = {}
    hops_by: Dict[int, list] = {}
    submit, tail = [], []
    for tid, inner in first("llm.request").items():
        if tid in outer and tid in waits:
            submit.append((waits[tid].start_ns - outer[tid].start_ns) / 1e6)
            tail.append((outer[tid].end_ns - inner.end_ns) / 1e6)
            requests_by.setdefault(bucket(inner.end_ns), []).append(tail[-1])
            if tid in streams:
                hops_by.setdefault(bucket(inner.end_ns), []).append(
                    (inner, streams[tid], outer[tid]))
    return {
        "spans": sum(len(ss) for ss in by.values()),
        "decode_steps": len(decode),
        "phases": {k: {"wall_ms_a_step": w / n, "cpu_ms_a_step": c / n}
                   for k, (w, c) in sorted(phases.items())},
        "prefill": {
            "calls": len(calls),
            "prefill_ms": _mean(_ms(by.get("llm.prefill", []))),
            "kv_alloc_ms": _mean(_ms(by.get("kv.alloc", []))),
            "dispatch_ms": _mean(_ms(calls)),
            "dispatch_cpu_ms": _mean(
                [_attr(s, "cpu_ns") / 1e6 for s in calls]),
            "dispatch_ms_p10_p50_p90": [
                _quantile(_ms(calls), q) for q in (0.1, 0.5, 0.9)]},
        "requests": len(tail),
        "submit_ms": {"p50": _quantile(submit, 0.5),
                      "p90": _quantile(submit, 0.9)},
        "tail_ms": {"p50": _quantile(tail, 0.5), "p90": _quantile(tail, 0.9),
                    "max": _quantile(tail, 1.0)},
        "steps_by_bucket_s": {
            str(b): {"steps": v[0], "handoff_s": v[1],
                     "mean_batch": v[2] / v[0], "queue_empty": v[3],
                     "driver_switches": v[4]}
            for b, v in sorted(steps_by.items())},
        "requests_by_bucket_s_of_engine_finish": {
            str(b): {"n": len(v), "tail_p50_ms": _quantile(v, 0.5),
                     "tail_p90_ms": _quantile(v, 0.9),
                     **_hops(hops_by.get(b, []))}
            for b, v in sorted(requests_by.items())},
    }


def _hops(requests: List[tuple]) -> Dict:
    """The four hand-overs of an item's way back over the requests of one
    bucket, ``(llm.request, serve.replica_stream, serve.request)`` each:
    a hop's mean is its summed attr over the takes or items it was summed
    over, its max the largest single one; the CPU shares are the streams'
    two sides' ``cpu_ns`` over the bucket's length (a request's CPU is
    counted where it ENDED)."""
    if not requests:
        return {}

    def hop(i: int, total: str, per: str, largest: str, unit: float) -> Dict:
        n = sum(_attr(r[i], per) for r in requests)
        return {"mean": sum(_attr(r[i], total) for r in requests) / unit / n
                if n else None,
                "max": max(_attr(r[i], largest) for r in requests) / unit}

    def cpu_share(i: int) -> float:
        return 100.0 * sum(_attr(r[i], "cpu_ns") for r in requests) / (
            BUCKET_S * 1e9)

    return {
        "pickup_lag_ms": hop(0, "pickup_lag_ns", "pickups",
                             "pickup_lag_max_ns", 1e6),
        "publish_us_per_item": hop(1, "publish_ns", "items",
                                   "publish_max_ns", 1e3),
        "take_lag_ms": hop(2, "take_lag_ns", "items", "take_lag_max_ns", 1e6),
        "client_hold_ms": hop(2, "client_hold_ns", "items",
                              "client_hold_max_ns", 1e6),
        # Of the take, the part inside ray_tpu.get once the ref was in
        # hand; the rest is the iterator's wake-up.
        "get_ms": sum(_attr(r[2], "get_ns") for r in requests) / 1e6
        / max(1, sum(_attr(r[2], "items") for r in requests)),
        "end_wait_ms": {
            "mean": _mean([_attr(r[2], "end_wait_ns") / 1e6
                           for r in requests]),
            "max": max(_attr(r[2], "end_wait_ns") for r in requests) / 1e6},
        # Of the stream's span, the part its thread spent driving steps for
        # every slot: a consumer that is late because it was the driver.
        "drove_share": 100.0 * sum(_attr(r[1], "drove_ns") for r in requests)
        / max(1, sum(r[1].end_ns - r[1].start_ns for r in requests)),
        "producer_cpu_share": cpu_share(1),
        "consumer_cpu_share": cpu_share(2),
    }


def main(argv: List[str]) -> int:
    if len(argv) < 2:
        print(__doc__, file=sys.stderr)
        return 2
    out_path, script = argv[0], argv[1]
    sys.argv = argv[1:]
    sys.path.insert(0, os.getcwd())
    try:
        runpy.run_path(script, run_name="__main__")
    except SystemExit as e:
        if e.code not in (None, 0):
            return e.code if isinstance(e.code, int) else 1
    from ray_tpu.util import tracing
    summary = summarise(tracing.recorded())
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(summary, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
