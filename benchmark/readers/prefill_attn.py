"""Reader of the prefill programs' attention kernels. A prefill's attention
is compute-bound (every query of a prompt meets its keys in one call), and
what it HAS to compute depends on the stack: a full layer's causal triangle,
a window layer's band. So the configuration counts it itself, prompt by
prompt (``counts.attention_prefill_flops``). Returns nothing where the run
was not traced, the configuration has no such count, or the trace holds no
such kernel inside a whole prefill call."""

from __future__ import annotations

from benchmark.manifest import load_function
from benchmark.reduce import trace as tr


def _prompts_of(calls, firsts):
    """The prompt behind each whole prefill call, by time: calls ``[(start,
    end)]`` and requests ``[(first token's host time, prompt tokens)]`` on
    one clock, both sorted. The device runs the calls in the order the engine
    admitted their requests, and a request's first token reaches the client
    after its prefill call ended and (with the decode chunk behind it) about
    when the next one starts: call j is the earliest request not yet taken
    whose first token came after the call's end."""
    out, k = [], 0
    for _start, end in calls:
        while k < len(firsts) and firsts[k][0] <= end:
            k += 1
        if k == len(firsts):
            break
        out.append(firsts[k][1])
        k += 1
    return out


def prefill_attn_roofline(run, spec):
    """Compute-bound: the attention FLOPs that the prompts behind the traced
    whole ``step_pattern`` calls HAD to make (the configuration's
    ``counts.attention_prefill_flops`` of each prompt's real length: causal
    in a full layer, banded in a window layer, no pad position), over the
    bf16 peak, over the device time of the kernels matching ``pattern``
    inside those calls. A walk that multiplies masked or padded positions
    does more than this and reads low."""
    if run.get("trace") is None:
        return None
    where = run["config"].get("counts", {}).get("attention_prefill_flops")
    if where is None:
        return None
    calls = tr.whole_events(run["trace"], spec["step_pattern"])
    k = tr.op_seconds(run["trace"], spec["pattern"], inside=calls)
    if not k["seconds"]:
        return None
    count = load_function(run["root"], where)
    t0 = run["trace_host_t0"]
    firsts = sorted((r["times"][0], r["prompt_tokens"])
                    for r in run["records"] if r.get("times"))
    need = 0
    for evs in calls.values():
        host = sorted((t0 + a / 1e9, t0 + b / 1e9) for a, b in evs)
        need += sum(count(run["config"], n) for n in _prompts_of(host, firsts))
    need /= max(len(calls), 1)
    if not need:
        return None
    return 100.0 * need / run["peaks"]["bf16_flops_per_s"] / k["seconds"]
