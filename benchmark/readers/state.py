"""Readers of a per-slot state's counters (``stats()``: ``state_bytes``,
``state_slot_steps_total``) and of the kernel that moves it in the device
trace. Each returns nothing where the program has no such counter, as a
program whose sequences keep rows in a pool alone has not."""

from __future__ import annotations

from typing import Dict, Optional

from benchmark.manifest import config_count
from benchmark.reduce import trace as tr


def active_slots_per_step(run: Dict) -> Optional[float]:
    """The window's mean of slots a decode token step advanced."""
    b, a = run["counters"]["before"], run["counters"]["after"]
    keys = ("state_slot_steps_total", "steps_total")
    if any(k not in a or k not in b for k in keys):
        return None
    steps = (a["steps_total"] - b["steps_total"]) * run["chunk"]
    if steps <= 0:
        return None
    return (a["state_slot_steps_total"] - b["state_slot_steps_total"]) / steps


def state_update_roofline(run, spec):
    """See the metric's file. Counted over the decode calls the trace holds
    whole (``trace.whole_events``), bytes and time alike."""
    if run.get("trace") is None:
        return None
    active = active_slots_per_step(run)
    if active is None or "recurrent_bytes_per_slot" not in run["config"]["counts"]:
        return None
    calls = tr.whole_events(run["trace"], spec["step_pattern"])
    n_calls = sum(len(v) for v in calls.values()) / max(len(calls), 1)
    k = tr.op_seconds(run["trace"], spec["pattern"], inside=calls)
    if not n_calls or not k["seconds"]:
        return None
    need = (active * n_calls * run["chunk"] * 2 * config_count(
        run["root"], run["config"], "recurrent_bytes_per_slot"))
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / k["seconds"]


def state_cache_share(run, spec):
    """See the metric's file."""
    if "state_bytes_per_slot" not in run["config"]["counts"]:
        return None
    t0, t1 = run["t_open"], run["t_close"]
    polled = [s for s in run["counters"]["polled"]
              if t0 <= s["t"] < t1 and "state_bytes" in s]
    if not polled:
        return None
    block = (int(run["traffic"]["engine"]["system_config"]["serve_kv_block_tokens"])
             * config_count(run["root"], run["config"],
                            "kv_bytes_per_context_token"))
    shares = []
    for s in polled:
        state = s["state_bytes"] * s["slots_busy"] / s["slots_total"]
        held = state + s["kv_blocks_active"] * block
        if held > 0:
            shares.append(100.0 * state / held)
    return sum(shares) / len(shares) if shares else None
