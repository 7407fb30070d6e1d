"""Readers of the expert layer's pick counters (``stats()``: ``moe_*_total``,
which count the DECODE programs' picks) and of its grouped products in the
device trace. Each returns nothing where the program has no such counter, as
a program without an expert layer has not."""

from __future__ import annotations

from typing import Dict, Optional

from benchmark.manifest import config_count
from benchmark.reduce import trace as tr


def _delta(run: Dict, *keys: str) -> Optional[Dict[str, float]]:
    b, a = run["counters"]["before"], run["counters"]["after"]
    if any(k not in a or k not in b for k in keys):
        return None
    return {k: a[k] - b[k] for k in keys}


def held_tokens_per_expert(run, spec):
    d = _delta(run, "moe_picks_held_total", "moe_steps_total")
    c = run["config"]
    if d is None or d["moe_steps_total"] <= 0:
        return None
    return d["moe_picks_held_total"] / (
        d["moe_steps_total"] * c["num_layers"] * c["n_routed_experts"])


def load_imbalance(run, spec):
    d = _delta(run, "moe_held_pairs_max_total", "moe_picks_held_total")
    if d is None or d["moe_picks_held_total"] <= 0:
        return None
    return (d["moe_held_pairs_max_total"] * run["config"]["n_routed_experts"]
            / d["moe_picks_held_total"])


def experts_hit_per_layer_step(run) -> Optional[float]:
    """The window's mean of held experts with at least one pair, a decode
    token step and layer."""
    d = _delta(run, "moe_experts_hit_total", "moe_steps_total")
    if d is None or d["moe_steps_total"] <= 0:
        return None
    return d["moe_experts_hit_total"] / (
        d["moe_steps_total"] * run["config"]["num_layers"])


def moe_ffn_roofline(run, spec):
    """See the metric's file. Counted over the decode calls the trace holds
    whole (``trace.whole_events``), operations and bytes alike."""
    if run.get("trace") is None:
        return None
    hit = experts_hit_per_layer_step(run)
    if hit is None:
        return None
    calls = tr.whole_events(run["trace"], spec["step_pattern"])
    n_calls = sum(len(v) for v in calls.values()) / max(len(calls), 1)
    k = tr.op_seconds(run["trace"], spec["pattern"], inside=calls)
    if not n_calls or not k["seconds"]:
        return None
    need = (hit * n_calls * run["chunk"] * run["config"]["num_layers"]
            * config_count(run["root"], run["config"], "expert_weight_bytes"))
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / k["seconds"]
