"""From a profiler trace to numbers: device busy and idle time, a kernel's
or a program's device time, the top device operations, and idle gaps
attributed to what the host was doing.

``load_xplane`` reads JAX's ``.xplane.pb`` with nothing but JAX and keeps
what the reduction needs in a plain structure; everything else works on that
structure, which is also what ``tests/data/small_trace.json`` holds, so the
reduction is checked on a recorded trace without a chip.

Structure: ``{"devices": {plane: {line: [[name, start_ns, dur_ns], ...]}},
"host": [[thread, name, start_ns, dur_ns], ...]}``. Times are the
profiler's own, nanoseconds from the start of the session.
"""

from __future__ import annotations

import bisect
import json
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
CONTAINERS = ("while", "conditional", "call")   # they hold their children's time
MODULES_LINE = "XLA Modules"
ASYNC_LINE = "Async XLA Ops"
SHORT_GAP_NS = 50_000
_COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all",
    re.I)


_LHS_TAIL = re.compile(r"((\.clone)|(\.\d+))+$")
_OPKIND = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"[a-z]+\d*\[[\d,]*\]")


def short_name(text: str) -> str:
    """``<base>:<op kind>:<first output shape>`` of an HLO instruction's text.

    The profiler names a device operation by its whole HLO text, e.g.
    ``%closed_call.269 = bf16[36,16,1,64]{3,2,1,0:T(2,128)} custom-call(...)``
    -> ``closed_call:custom-call:bf16[36,16,1,64]``. The numbering that tells
    one layer's instance from another's is dropped, so instances add up.
    Names that are no HLO text (module names) pass unchanged.
    """
    if " = " not in text:
        return text
    lhs, rhs = text.split(" = ", 1)
    base = _LHS_TAIL.sub("", lhs.lstrip("%"))
    kind = _OPKIND.search(" " + rhs)
    shape = _SHAPE.search(rhs)
    return (f"{base}:{kind.group(1) if kind else '?'}:"
            f"{shape.group(0) if shape else '?'}")


def _is_container(name: str) -> bool:
    parts = name.split(":")
    return len(parts) >= 2 and parts[1] in CONTAINERS


def load_xplane(path: str, host_keep: Sequence[str]) -> Dict:
    """Device lines with short names; of the host's events only those whose
    name holds one of ``host_keep``."""
    import jax

    keep = re.compile("|".join(re.escape(k) for k in host_keep)) if host_keep else None
    out: Dict = {"devices": {}, "host": []}
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") or plane.name.startswith("/device:GPU:"):
            lines = {}
            for line in plane.lines:
                lines[line.name] = [[short_name(e.name), int(e.start_ns),
                                     int(e.duration_ns)] for e in line.events]
            out["devices"][plane.name] = lines
        elif plane.name.startswith("/host:CPU") and keep is not None:
            for line in plane.lines:
                for e in line.events:
                    if keep.search(e.name):
                        out["host"].append([line.name, e.name, int(e.start_ns),
                                            int(e.duration_ns)])
    return out


def load(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def summarise(trace: Dict, top: int = 40) -> Dict:
    """What planes, lines and names a trace holds: for looking at one by hand."""
    out: Dict = {"devices": {}, "host_names": {}}
    for plane, lines in trace["devices"].items():
        out["devices"][plane] = {}
        for line, evs in lines.items():
            tot: Dict[str, List[float]] = {}
            for name, _s, d in evs:
                t = tot.setdefault(name, [0, 0.0])
                t[0] += 1
                t[1] += d / 1e9
            ranked = sorted(tot.items(), key=lambda kv: -kv[1][1])[:top]
            out["devices"][plane][line] = {
                "events": len(evs),
                "top": [[n, c, s] for n, (c, s) in ranked]}
    for _th, name, _s, d in trace["host"]:
        t = out["host_names"].setdefault(name, [0, 0.0])
        t[0] += 1
        t[1] += d / 1e9
    return out


# -- intervals ----------------------------------------------------------------

def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Sorted, merged [start, end) intervals."""
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _length(intervals: Sequence[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in intervals)


def _clip(intervals, t0: int, t1: int):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if min(e, t1) > max(s, t0)]


def device_lines(trace: Dict, line: str = OPS_LINE) -> Dict[str, List]:
    return {p: lines[line] for p, lines in sorted(trace["devices"].items())
            if lines.get(line)}


def window(trace: Dict) -> Tuple[int, int]:
    """From the first to the last device operation of any device."""
    starts, ends = [], []
    for evs in device_lines(trace).values():
        starts.append(min(s for _n, s, _d in evs))
        ends.append(max(s + d for _n, s, d in evs))
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts), max(ends)


def busy(trace: Dict) -> Dict:
    """Seconds in which an operation ran, averaged over the devices, and the
    window's length. Idle share = 1 - busy_s / window_s."""
    t0, t1 = window(trace)
    per_dev = [_length(_clip(union((s, s + d) for _n, s, d in evs), t0, t1))
               for evs in device_lines(trace).values()]
    return {"busy_s": sum(per_dev) / len(per_dev) / 1e9,
            "window_s": (t1 - t0) / 1e9, "devices": len(per_dev)}


def whole_events(trace: Dict, pattern: str,
                 line: str = MODULES_LINE) -> Dict[str, List[Tuple[int, int]]]:
    """Per device, [start, end) of the events of ``line`` whose name matches
    and that the trace holds whole.

    The profiler clips the event that is running when the session starts, and
    the one running when it stops, to the session's edges: nine ``jit_step``
    events that fill a 4 s trace are seven steps and two parts of steps. So
    the first and the last event of each device's line are left out, whatever
    their names; what is counted per event (steps, operations, bytes) is
    counted over the events that remain."""
    rx = re.compile(pattern)
    out = {}
    for plane, evs in device_lines(trace, line).items():
        inner = sorted(evs, key=lambda e: e[1])[1:-1]
        out[plane] = [(s, s + d) for name, s, d in inner if rx.search(name)]
    return out


def whole_seconds(trace: Dict, pattern: str, line: str = MODULES_LINE) -> Dict:
    """Device time and count of the whole matching events, per device mean."""
    per_dev = whole_events(trace, pattern, line)
    n = max(len(per_dev), 1)
    return {"seconds": sum(_length(iv) for iv in per_dev.values()) / n / 1e9,
            "count": sum(len(iv) for iv in per_dev.values()) / n}


def op_seconds(trace: Dict, pattern: str, line: str = OPS_LINE,
               inside: Optional[Dict[str, List[Tuple[int, int]]]] = None) -> Dict:
    """Device time and count of events whose name matches, per device mean.
    With ``inside`` (per device, sorted disjoint intervals, as
    ``whole_events`` gives them) only events that start in one of them."""
    rx = re.compile(pattern)
    tot, cnt, n = 0, 0, 0
    for plane, evs in device_lines(trace, line).items():
        n += 1
        ivs = None if inside is None else inside.get(plane, [])
        starts = None if ivs is None else [a for a, _b in ivs]
        for name, s, d in evs:
            if not rx.search(name) or _is_container(name):
                continue
            if ivs is not None:
                i = bisect.bisect_right(starts, s) - 1
                if i < 0 or s >= ivs[i][1]:
                    continue
            tot += d
            cnt += 1
    n = max(n, 1)
    return {"seconds": tot / n / 1e9, "count": cnt / n}


def top_ops(trace: Dict, n: int = 10) -> List[List]:
    tot: Dict[str, float] = {}
    devs = device_lines(trace)
    for evs in devs.values():
        for name, _s, d in evs:
            if not _is_container(name):
                tot[name] = tot.get(name, 0.0) + d / 1e9 / len(devs)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Dict, labels: Dict[str, List[str]], n: int = 10) -> List[List]:
    """The first device's idle gaps, each given to the host label that covers
    most of it; what no labelled host span covers is ``host-unattributed``,
    and gaps under 50 us are lumped together."""
    devs = device_lines(trace)
    if not devs:
        return []
    evs = next(iter(devs.values()))
    t0, t1 = window(trace)
    merged = _clip(union((s, s + d) for _n, s, d in evs), t0, t1)
    gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:]) if b[0] > a[1]]
    spans: Dict[str, List[Tuple[int, int]]] = {}
    for label, needles in labels.items():
        spans[label] = union(
            (s, s + d) for _th, name, s, d in trace["host"]
            if any(k in name for k in needles))
    tot: Dict[str, float] = {}
    for g0, g1 in gaps:
        if g1 - g0 < SHORT_GAP_NS:
            tot["gaps-under-50us"] = tot.get("gaps-under-50us", 0.0) + (g1 - g0) / 1e9
            continue
        best, cover = "host-unattributed", 0
        for label, ivs in spans.items():
            c = _length(_clip(ivs, g0, g1))
            if c > cover:
                best, cover = label, c
        tot[best] = tot.get(best, 0.0) + (g1 - g0) / 1e9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def collective_exposed_seconds(trace: Dict) -> Dict:
    """Per device mean: time inside collective operations during which no
    other operation runs on that device, and the collectives' whole time.

    A collective is an event of the operations line or of the asynchronous
    operations line (where ``all-gather-start`` .. ``-done`` spans lie) whose
    name says so; compute is every other operation, containers left out."""
    exposed, total, n = 0, 0, 0
    for plane, lines in sorted(trace["devices"].items()):
        evs = lines.get(OPS_LINE) or []
        if not evs:
            continue
        n += 1
        both = evs + (lines.get(ASYNC_LINE) or [])
        coll = union((s, s + d) for name, s, d in both
                     if _COLLECTIVE.search(name))
        comp = union((s, s + d) for name, s, d in evs
                     if not _COLLECTIVE.search(name) and not _is_container(name))
        total += _length(coll)
        exposed += _length(coll) - sum(_length(_clip(comp, c0, c1))
                                       for c0, c1 in coll)
    n = max(n, 1)
    return {"exposed_s": exposed / n / 1e9, "total_s": total / n / 1e9}
