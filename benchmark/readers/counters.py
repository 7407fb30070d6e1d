"""Readers of the program's own counters, taken at the window's edges."""

from __future__ import annotations


def prefix_hit_share(run, spec):
    b, a = run["counters"]["before"], run["counters"]["after"]
    hit = a["kv_hit_tokens"] - b["kv_hit_tokens"]
    miss = a["kv_miss_tokens"] - b["kv_miss_tokens"]
    return 100.0 * hit / (hit + miss) if hit + miss > 0 else None


def kv_blocks_peak_share(run, spec):
    t0, t1 = run["t_open"], run["t_close"]
    polled = [s for s in run["counters"]["polled"] if t0 <= s["t"] < t1]
    if not polled:
        return None
    return 100.0 * max(s["kv_blocks_active"] / s["kv_blocks_total"]
                       for s in polled)


def queue_wait_ms(run, spec):
    """The engine's queued phase (submit to admission): the mean over the
    window's first tokens, from the sum and count of the program's TTFT
    histogram (its buckets are too wide for a median)."""
    b, a = run["counters"]["queued_before"], run["counters"]["queued_after"]
    n = a["count"] - b["count"]
    return (a["sum_s"] - b["sum_s"]) / n * 1e3 if n > 0 else None
