"""Readers of a stack whose attention layers do not all read the whole
context: what the decode steps HAD to read is not a number of bytes a context
token, so the configuration counts it itself, context by context
(``counts.attention_bytes_read``). Each returns nothing where the run was not
traced, the configuration has no such count, or the trace no such kernel."""

from __future__ import annotations

from benchmark.manifest import load_function
from benchmark.reduce import trace as tr


def windowed_attn_roofline(run, spec):
    """Bandwidth-bound, as ``readers/device.py:paged_attn_roofline``: the K
    and V bytes that the decode steps behind the tokens delivered in the
    traced interval had to read (the configuration's
    ``counts.attention_bytes_read`` of each token's context: its prompt, the
    tokens before it and itself), over the HBM peak, over the device time of
    the kernels matching ``pattern`` in that interval. Tokens arrive a chunk
    at a time, so some twenty chunks to a 4 s trace put the count off by a
    chunk's share at most."""
    if run.get("trace") is None:
        return None
    where = run["config"].get("counts", {}).get("attention_bytes_read")
    if where is None:
        return None
    w0, w1 = tr.window(run["trace"])
    t0 = run["trace_host_t0"] + w0 / 1e9
    t1 = run["trace_host_t0"] + w1 / 1e9
    contexts = [r.get("prompt_tokens", 0) + j + 1
                for r in run["records"]
                for j, t in enumerate(r.get("times", ())) if t0 <= t < t1]
    need = load_function(run["root"], where)(run["config"], contexts)
    k = tr.op_seconds(run["trace"], spec["pattern"])
    if not need or not k["seconds"]:
        return None
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / k["seconds"]
