"""A reader for the prefill side of a learned sparse selection: device time
of an operation a prefill CALL (``device.py:op_ms_per_step`` divides by the
decode program's steps a call, which a prefill has none of)."""

from __future__ import annotations

from benchmark.reduce import trace as tr


def op_ms_per_call(run, spec):
    """Device time of the operations matching ``pattern`` inside the whole
    calls of the program matching ``step_pattern``, per call. Nothing where
    the run was not traced, or the trace holds no such call or operation."""
    if run.get("trace") is None:
        return None
    calls = tr.whole_events(run["trace"], spec["step_pattern"])
    n_calls = sum(len(v) for v in calls.values()) / max(len(calls), 1)
    k = tr.op_seconds(run["trace"], spec["pattern"], inside=calls)
    if not n_calls or not k["seconds"]:
        return None
    return k["seconds"] / n_calls * 1e3
