"""Readers of the program's own spans: ``ray_tpu.util.tracing.recorded()``,
the in-memory ring of the process that holds the chip. Every span is an
interval on ``time.perf_counter_ns``, the clock of the driver's ``t_open`` /
``t_close``, so spans are windowed by the driver's own times.

Each function is generic and driven by the metric's file: which span, which
statistic. A program that records no such span (a parent commit without the
ring, a cell that serves nothing) gives every reader nothing to read, and the
metric is left out of the line.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

from benchmark.reduce.stats import median, percentile

_SCALE = {"s": 1.0, "ms": 1e3, "us": 1e6}      # from seconds to the unit


def _spans(run: Dict) -> List:
    """The ring's spans, oldest first; none where the program has no ring.
    A test hands its own list in as ``run["spans"]``."""
    if "spans" in run:
        return list(run["spans"])
    try:
        from ray_tpu.util.tracing import recorded
    except ImportError:
        return []
    return recorded()


def _named(run: Dict, name: str, windowed: bool = True) -> List:
    """Spans called ``name``; ``windowed``: whose start lies in the window."""
    t0, t1 = run["t_open"] * 1e9, run["t_close"] * 1e9
    return [s for s in _spans(run) if s.name == name
            and (not windowed or t0 <= s.start_ns < t1)]


def _stat(values: Sequence[float], stat: str) -> Optional[float]:
    if not values:
        return None
    if stat == "mean":
        return sum(values) / len(values)
    if stat == "median":
        return median(values)
    if stat == "sum":
        return float(sum(values))
    return percentile(values, float(stat.lstrip("p")))     # "p90"


def duration(run: Dict, spec: Dict) -> Optional[float]:
    """``stat`` of the durations of the spans called ``span``, in the
    metric's unit. ``"window": false`` reads the whole run (warm-up spans
    end before the window opens)."""
    spans = _named(run, spec["span"], spec.get("window", True))
    return _stat([(s.end_ns - s.start_ns) / 1e9 * _SCALE[spec["unit"]]
                  for s in spans], spec["stat"])


def between(run: Dict, spec: Dict) -> Optional[float]:
    """Per trace id, from one span's edge to another's: ``from`` and ``to``
    are ``[span name, "start" | "end"]``. The first span of each name in a
    trace counts (a request has one of each); traces are windowed by the
    ``from`` span's start."""
    (f_name, f_edge), (t_name, t_edge) = spec["from"], spec["to"]
    edge = lambda s, e: s.start_ns if e == "start" else s.end_ns  # noqa: E731
    ends: Dict[str, int] = {}
    for s in _named(run, t_name, windowed=False):
        ends.setdefault(s.trace_id, edge(s, t_edge))
    vals, seen = [], set()
    for s in _named(run, f_name):
        if s.trace_id in ends and s.trace_id not in seen:
            seen.add(s.trace_id)
            vals.append((ends[s.trace_id] - edge(s, f_edge)) / 1e9
                        * _SCALE[spec["unit"]])
    return _stat(vals, spec["stat"])


def attr_sum(run: Dict, spec: Dict) -> Optional[float]:
    """The sum over the spans called ``span`` of the attrs ``attrs``."""
    spans = _named(run, spec["span"], spec.get("window", True))
    vals = [float(s.attrs.get(k, 0.0)) for s in spans if s.attrs
            for k in spec["attrs"]]
    return _stat(vals, "sum") if spans else None


# -- the engine step ----------------------------------------------------------

def _steps(run: Dict) -> List[Dict]:
    """Every ``llm.step`` that dispatched a decode, in order, as
    ``{"span": the step, <phase>: (start_ns, end_ns), ...}``."""
    spans = _spans(run)
    steps = {s.span_id: {"span": s} for s in spans if s.name == "llm.step"}
    for s in spans:
        if s.name.startswith("llm.step.") and s.parent_id in steps:
            steps[s.parent_id][s.name[len("llm.step."):]] = (s.start_ns, s.end_ns)
    out = [st for st in steps.values() if "dispatch" in st and "device_wait" in st]
    return sorted(out, key=lambda st: st["span"].start_ns)


def _gaps(run: Dict) -> List[Dict[str, float]]:
    """For each step of the window that left the engine with work in flight:
    the stretch from its ``device_wait``'s end to the end of the next step's
    ``dispatch`` call, in seconds, whole and its first two parts (the third,
    operands + the dispatch call, is the rest). The loop is synchronous: the
    decode has nothing queued from the moment the host has the step's
    tokens until the next program is enqueued, which happens inside the
    jitted call (after the transfer of its operands). An UPPER BOUND on the
    idle the host imposes: a prefill dispatched during ``admit`` keeps the
    device busy for part of it."""
    t0, t1 = run["t_open"] * 1e9, run["t_close"] * 1e9
    steps = _steps(run)
    out = []
    for a, b in zip(steps, steps[1:]):
        got = a["device_wait"][1]
        if not t0 <= got < t1:
            continue
        if not (a["span"].attrs or {}).get("inflight_after", 1):
            continue        # the engine ran dry: the wait is the traffic's
        out.append({
            "all": (b["dispatch"][1] - got) / 1e9,
            # deliver + observe + what lies between the two steps
            "deliver": (b["span"].start_ns - got) / 1e9,
            # retire + admit (with any prefill it dispatches)
            "admit": (b["operands"][0] - b["span"].start_ns) / 1e9,
        })
    return out


def step_gap(run: Dict, spec: Dict) -> Optional[float]:
    """``stat`` over the steps of the gap to the next dispatch, or of one
    ``part`` of it."""
    vals = [g[spec["part"]] * _SCALE[spec["unit"]] for g in _gaps(run)]
    return _stat(vals, spec["stat"])


def counter_share(run: Dict, spec: Dict) -> Optional[float]:
    """Growth of a cumulative seconds counter of ``stats()`` over the
    window, as a share of the window (%)."""
    b, a = run["counters"]["before"], run["counters"]["after"]
    key = spec["counter"]
    if key not in a or key not in b:
        return None
    return 100.0 * (a[key] - b[key]) / (run["t_close"] - run["t_open"])


def counter_ratio(run: Dict, spec: Dict) -> Optional[float]:
    """Growth of one cumulative counter of ``stats()`` over the window, as
    a share (%) of the summed growth of the counters ``over``."""
    b, a = run["counters"]["before"], run["counters"]["after"]
    keys = [spec["counter"]] + list(spec["over"])
    if any(k not in a or k not in b for k in keys):
        return None
    den = sum(a[k] - b[k] for k in spec["over"])
    return 100.0 * (a[keys[0]] - b[keys[0]]) / den if den > 0 else None
