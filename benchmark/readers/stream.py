"""Readers of a token's way back to its client: the stream account the
program keeps at the four hand-overs between the engine's ``_deliver`` and
the handle's iterator (``PERF.md`` §3), as cumulative counters beside the
engine's (``run["counters"]``) and as attrs on three per-request spans.

Each function is generic and driven by the metric's file. A program that
keeps no such counter or attr (a parent commit) gives every reader nothing,
and the metric is left out of the line.
"""

from __future__ import annotations

from typing import Dict, Optional

from benchmark.readers.spans import _SCALE, _named


def counter_mean(run: Dict, spec: Dict) -> Optional[float]:
    """Growth of the seconds counter ``counter`` over the window, per unit
    of growth of the counter ``per``, in the metric's unit."""
    b, a = run["counters"]["before"], run["counters"]["after"]
    num, per = spec["counter"], spec["per"]
    if any(k not in a or k not in b for k in (num, per)):
        return None
    n = a[per] - b[per]
    return (a[num] - b[num]) / n * _SCALE[spec["unit"]] if n > 0 else None


def _attr_sum(run: Dict, span: str, attr: str) -> Optional[float]:
    """The nanoseconds (or the count) attr ``attr`` summed over the spans
    called ``span`` whose start lies in the window; None where none holds
    it."""
    vals = [s.attrs[attr] for s in _named(run, span)
            if s.attrs and attr in s.attrs]
    return float(sum(vals)) if vals else None


def attr_ratio(run: Dict, spec: Dict) -> Optional[float]:
    """The summed nanosecond attrs ``sum`` (``[span, attr]`` pairs) over the
    summed count attr ``per`` (one ``[span, attr]``), in the metric's unit:
    a mean per item across the window's requests."""
    parts = [_attr_sum(run, span, attr) for span, attr in spec["sum"]]
    per = _attr_sum(run, *spec["per"])
    if any(p is None for p in parts) or not per:
        return None
    return sum(parts) / 1e9 / per * _SCALE[spec["unit"]]


def attr_share(run: Dict, spec: Dict) -> Optional[float]:
    """The nanosecond attr ``attr`` summed over the spans called ``span``
    that start in the window, as a share of the window (%)."""
    total = _attr_sum(run, spec["span"], spec["attr"])
    if total is None:
        return None
    return 100.0 * total / 1e9 / (run["t_close"] - run["t_open"])
