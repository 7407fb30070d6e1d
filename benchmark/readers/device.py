"""Readers of the profiler's trace and of the device's memory counter.
Each returns nothing where the run was not traced."""

from __future__ import annotations

from benchmark.reduce import shapes, trace as tr


def device_idle_share(run, spec):
    if run.get("trace") is None:
        return None
    b = tr.busy(run["trace"])
    return 100.0 * (1.0 - b["busy_s"] / b["window_s"])


def hbm_peak_share(run, spec):
    return 100.0 * run["memory_peak_bytes"] / run["peaks"]["hbm_bytes"]


def program_step_ms(run, spec):
    """Device time of the programs whose name matches, per decode step: the
    decode program runs ``chunk`` steps to a call. Only calls the trace holds
    whole are counted, not the two its edges cut."""
    if run.get("trace") is None:
        return None
    m = tr.whole_seconds(run["trace"], spec["pattern"])
    if not m["count"]:
        return None
    return m["seconds"] / (m["count"] * run["chunk"]) * 1e3


def program_dev_share(run, spec):
    """Device time inside the matching programs over the device's busy time."""
    if run.get("trace") is None:
        return None
    m = tr.op_seconds(run["trace"], spec["pattern"], tr.MODULES_LINE)
    return 100.0 * m["seconds"] / tr.busy(run["trace"])["busy_s"]


def op_dev_share(run, spec):
    """Device time of the matching operations over the device's busy time."""
    if run.get("trace") is None:
        return None
    m = tr.op_seconds(run["trace"], spec["pattern"])
    return 100.0 * m["seconds"] / tr.busy(run["trace"])["busy_s"]


def paged_attn_roofline(run, spec):
    """Bandwidth-bound: the K and V bytes that the decode steps of the traced
    interval had to read, over the HBM peak, divided by the kernel's device
    time in the same interval. 100% = the kernel moves only what it must, at
    the peak rate. The bytes are those of the tokens that reached the client
    inside the interval (the trace's clock starts with the session, at host
    time ``trace_host_t0``); tokens arrive a chunk at a time, so some fifteen
    chunks to a 4 s trace put the count off by a chunk's share at most."""
    if run.get("trace") is None:
        return None
    from benchmark.readers.client import kv_read_bytes

    c = run["config"]
    w0, w1 = tr.window(run["trace"])
    t0 = run["trace_host_t0"]
    need = kv_read_bytes(run, c["n_head"], c["head_dim"], c["n_layer"],
                         t0 + w0 / 1e9, t0 + w1 / 1e9)
    k = tr.op_seconds(run["trace"], spec["pattern"])
    if not need or not k["seconds"]:
        return None
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / k["seconds"]


def flash_attn_roofline(run, spec):
    """Compute-bound: causal forward and backward FLOPs of every layer for
    the steps the trace holds whole, over the bf16 peak, divided by the flash
    kernels' device time inside those steps (per device)."""
    if run.get("trace") is None:
        return None
    c = run["config"]
    steps = tr.whole_events(run["trace"], spec["step_pattern"])
    n_steps = sum(len(v) for v in steps.values()) / max(len(steps), 1)
    k = tr.op_seconds(run["trace"], spec["pattern"], inside=steps)
    if not n_steps or not k["seconds"]:
        return None
    flops = (shapes.flash_causal_flops(run["global_batch"] / run["chips"],
                                       c["n_head"], run["sequence_tokens"],
                                       c["head_dim"]) * c["n_layer"] * n_steps)
    return 100.0 * flops / run["peaks"]["bf16_flops_per_s"] / k["seconds"]


def collective_exposed_share(run, spec):
    if run.get("trace") is None:
        return None
    e = tr.collective_exposed_seconds(run["trace"])
    return 100.0 * e["exposed_s"] / tr.busy(run["trace"])["window_s"]
