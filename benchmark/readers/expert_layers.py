"""Readers of the expert layer's pick counters and grouped products for a
stack whose layers do not all have experts: what is counted a layer is
counted over ``counts.expert_layers`` of the configuration (the layers with
a router), where ``readers/moe.py`` multiplies by the configuration's depth.
Each returns nothing where the program has no such counter or the
configuration no such count."""

from __future__ import annotations

from typing import Dict, Optional

from benchmark.manifest import config_count
from benchmark.readers.moe import _delta
from benchmark.reduce import trace as tr


def _expert_layers(run: Dict) -> Optional[float]:
    if "expert_layers" not in run["config"].get("counts", {}):
        return None
    return config_count(run["root"], run["config"], "expert_layers")


def tokens_per_expert(run, spec):
    """Held picks a decode token step, expert layer and held expert."""
    layers = _expert_layers(run)
    d = _delta(run, "moe_picks_held_total", "moe_steps_total")
    if not layers or d is None or d["moe_steps_total"] <= 0:
        return None
    return d["moe_picks_held_total"] / (
        d["moe_steps_total"] * layers * run["config"]["n_routed_experts"])


def experts_hit_per_layer_step(run) -> Optional[float]:
    """The window's mean of held experts with at least one pair, a decode
    token step and expert layer."""
    layers = _expert_layers(run)
    d = _delta(run, "moe_experts_hit_total", "moe_steps_total")
    if not layers or d is None or d["moe_steps_total"] <= 0:
        return None
    return d["moe_experts_hit_total"] / (d["moe_steps_total"] * layers)


def ffn_roofline(run, spec):
    """See the metric's file. Counted over the decode calls the trace holds
    whole (``trace.whole_events``), bytes and time alike."""
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
    need = (hit * n_calls * run["chunk"] * _expert_layers(run)
            * config_count(run["root"], run["config"], "expert_weight_bytes"))
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / k["seconds"]
