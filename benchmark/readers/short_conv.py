"""A reader for an operation that reads the same WEIGHTS every token step
whatever the batch (a convolution mixer's projections: no cache row, no
state, no routing decides what it reads): the bytes a count of the
configuration gives a token step, over the HBM peak, over the matching
operations' device time. Returns nothing where the run was not traced, the
configuration has no such count or the trace no such operation."""

from __future__ import annotations

from benchmark.manifest import config_count
from benchmark.readers.device import op_ms_per_step


def weights_roofline(run, spec):
    """See the metric's file. Time and token steps are ``op_ms_per_step``'s:
    the matching operations inside the decode calls the trace holds whole."""
    if spec["count"] not in run["config"].get("counts", {}):
        return None
    ms = op_ms_per_step(run, spec)
    if ms is None:
        return None
    need = config_count(run["root"], run["config"], spec["count"])
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / (ms * 1e-3)
