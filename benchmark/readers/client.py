"""Readers of the client's own records: host clocks at the handle."""

from __future__ import annotations

from typing import Dict, List, Optional

from benchmark.reduce.stats import median, percentile


def ok_request(r: Dict) -> bool:
    """The stream returned the tokens asked for and a finish reason."""
    return ("error" not in r and r.get("finish_reason") is not None
            and len(r.get("tokens", ())) == r.get("asked"))


def measured(run: Dict) -> List[Dict]:
    """Requests whose latencies count: in a session run those that became due
    inside the window; in a closed loop those that finished inside it."""
    t0, t1 = run["t_open"], run["t_close"]
    out = []
    for r in run["records"]:
        if not ok_request(r):
            continue
        if "in_window" in r:
            if r["in_window"]:
                out.append(r)
        elif t0 <= r["done_t"] < t1:
            out.append(r)
    return out


def ttfts_ms(run: Dict) -> List[float]:
    """First token at the client, from when the request was due."""
    return [(r["times"][0] - r.get("due_t", r["send_t"])) * 1e3
            for r in measured(run) if r["times"]]


def tpots_ms(run: Dict) -> List[float]:
    """Per request: (t_last - t_first) / (n - 1)."""
    return [(r["times"][-1] - r["times"][0]) / (len(r["times"]) - 1) * 1e3
            for r in measured(run) if len(r["times"]) >= 2]


def _arrivals(run: Dict, t0: float, t1: float) -> List[float]:
    return sorted(t for r in run["records"] for t in r.get("times", ())
                  if t0 <= t < t1)


def serve_out_tok_s(run: Dict, spec: Dict) -> Optional[float]:
    """Tokens whose arrival at the client falls inside the window, over the
    window's length; a request that straddles an edge counts for its part."""
    n = len(_arrivals(run, run["t_open"], run["t_close"]))
    return n / (run["t_close"] - run["t_open"])


def latency_per_token_ms(run, spec):
    """Per request: from when it was due to its last token, over the tokens
    it returned (the wait for the first token spread over the answer, plus
    the gaps); the mean over all the window's requests."""
    vals = [(r["times"][-1] - r["due_t"]) / len(r["times"]) * 1e3
            for r in measured(run) if r["times"]]
    return sum(vals) / len(vals) if vals else None


def ttft_p50_ms(run, spec):
    return median(ttfts_ms(run))


def ttft_p90_ms(run, spec):
    return percentile(ttfts_ms(run), 90.0)


def tpot_p50_ms(run, spec):
    return median(tpots_ms(run))


def tpot_p90_ms(run, spec):
    return percentile(tpots_ms(run), 90.0)


def decode_batch_mean(run, spec):
    """Streams decoding at once: the time-average over the window of the
    number of streams between their first and their last token, which is the
    tokens a decode step delivers over the chunk's length. Taken from the
    client's arrival times, so it needs no step boundaries."""
    t0, t1 = run["t_open"], run["t_close"]
    busy = 0.0
    for r in run["records"]:
        ts = r.get("times", ())
        if len(ts) >= 2:
            busy += max(0.0, min(ts[-1], t1) - max(ts[0], t0))
    return busy / (t1 - t0)


def route_overhead_ms(run, spec):
    """The client's first-token time from the due time, less the engine's own
    submit-to-first-token time on the stream's last item: what the handle,
    the router, the replica's mailbox and a late generator add. Median."""
    vals = [(r["times"][0] - r["due_t"] - r["engine_ttft_s"]) * 1e3
            for r in measured(run)
            if r["times"] and r.get("engine_ttft_s") is not None]
    return median(vals)


def gen_late_p95_ms(run, spec):
    """Actual send time less due time: how late the generator ran."""
    return percentile([(r["send_t"] - r["due_t"]) * 1e3
                       for r in measured(run)], 95.0)


def kv_read_bytes(run: Dict, heads: int, head_dim: int, layers: int,
                  t0: float, t1: float) -> float:
    """K and V bytes that the decode steps behind the tokens delivered in
    [t0, t1) had to read: for each such token, the context it attended to
    (its prompt plus the tokens before it)."""
    from benchmark.reduce.shapes import paged_decode_kv_bytes

    ctx_tokens = 0
    for r in run["records"]:
        p = r.get("prompt_tokens", 0)
        for j, t in enumerate(r.get("times", ())):
            if t0 <= t < t1:
                ctx_tokens += p + j
    return paged_decode_kv_bytes(ctx_tokens, heads, head_dim, layers)
