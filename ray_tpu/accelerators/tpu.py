"""TPU accelerator manager — chip discovery, topology, slice resources.

Analog of the reference's ``python/ray/_private/accelerators/tpu.py`` (the
key extension point SURVEY §2.2 calls out): detect chips on this host, derive
the pod/slice topology, and emit the resource markers the scheduler places
against —

- ``TPU`` chip-count resource (``tpu.py:13-46`` — 4 chips/host default),
- a version marker resource like ``TPU-V4`` / ``TPU-V5E`` (``:294-315``),
- a per-slice head resource ``TPU-{pod_type}-head`` (``:363-382``) so exactly
  one actor can claim a whole slice and fan out jax.distributed workers.

Detection prefers a live JAX client, then GCE metadata env vars
(``TPU_ACCELERATOR_TYPE``, ``TPU_WORKER_ID`` — what real TPU VMs expose), then
nothing. Asking JAX for its devices takes the chips for this process (a chip
belongs to one process at a time), and a JAX that cannot bring up the
platform it was configured for raises here rather than leaving the node
without a ``TPU`` resource.
"""

from __future__ import annotations

import os
import re
from dataclasses import dataclass
from typing import Dict, Optional

_GKE_TPU_ACCELERATOR_ENV = "TPU_ACCELERATOR_TYPE"   # e.g. "v5litepod-16"
_TPU_VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
_TPU_WORKER_ID_ENV = "TPU_WORKER_ID"
_TPU_NAME_ENV = "TPU_NAME"
_DEFAULT_CHIPS_PER_HOST = 4

# ``jax.Device.device_kind`` -> generation marker, the same names
# ``_generation_from_type`` derives from an accelerator type, so the
# ``TPU-<gen>`` resource does not depend on which detection branch ran
# (a v5e reports "TPU v5 lite"; "TPU v5" is a v5p).
_GENERATION_BY_DEVICE_KIND = {
    "TPU v2": "V2",
    "TPU v3": "V3",
    "TPU v4": "V4",
    "TPU v5 lite": "V5E",
    "TPU v5e": "V5E",
    "TPU v5": "V5P",
    "TPU v5p": "V5P",
    "TPU v6 lite": "V6E",
    "TPU v6e": "V6E",
}


def _chips_per_host_default() -> int:
    """The tpu_chips_per_host knob, falling back to the classic 4/host
    when the config table isn't importable yet (early startup)."""
    try:
        from ray_tpu.core.config import config

        return config().tpu_chips_per_host
    except Exception:  # noqa: BLE001 — mirror the flag's default
        return _DEFAULT_CHIPS_PER_HOST


@dataclass(frozen=True)
class TpuInfo:
    chips_on_host: int
    accelerator_type: Optional[str]   # "v5litepod-16", "v4-8", ...
    generation: Optional[str]         # "V5E", "V4", ...
    pod_name: Optional[str]
    worker_id: Optional[int]
    hosts_in_slice: int


def _generation_from_type(acc_type: Optional[str]) -> Optional[str]:
    if not acc_type:
        return None
    m = re.match(r"v(\d+)(litepod|[ep])?", acc_type.lower())
    if not m:
        return None
    version, suffix = m.group(1), m.group(2) or ""
    if suffix == "litepod":
        return f"V{version}E"
    return f"V{version}{suffix.upper()}"


def _chips_in_slice(acc_type: Optional[str]) -> Optional[int]:
    if not acc_type or "-" not in acc_type:
        return None
    try:
        return int(acc_type.rsplit("-", 1)[1])
    except ValueError:
        return None


def detect_tpu() -> Optional[TpuInfo]:
    """Detect TPU chips visible to this host."""
    import jax

    generation = None
    tpus = [d for d in jax.devices() if d.platform == "tpu"]
    chips = len(tpus)
    if chips:
        kind = str(tpus[0].device_kind)
        generation = _GENERATION_BY_DEVICE_KIND.get(kind)
        if generation is None:
            from ray_tpu.utils.logging import get_logger

            get_logger("accelerators").warning(
                "unknown TPU device_kind %r: no TPU-<generation> marker "
                "resource for this node", kind)

    acc_type = os.environ.get(_GKE_TPU_ACCELERATOR_ENV)
    if chips == 0:
        visible = os.environ.get(_TPU_VISIBLE_CHIPS_ENV)
        if visible:
            chips = len([c for c in visible.split(",") if c.strip()])
        elif acc_type:
            chips = _chips_per_host_default()
    if chips == 0:
        return None

    generation = generation or _generation_from_type(acc_type)
    total = _chips_in_slice(acc_type)
    hosts = max(1, (total or chips) // max(chips, 1))
    worker_id = os.environ.get(_TPU_WORKER_ID_ENV)
    return TpuInfo(
        chips_on_host=chips,
        accelerator_type=acc_type,
        generation=generation,
        pod_name=os.environ.get(_TPU_NAME_ENV),
        worker_id=int(worker_id) if worker_id is not None else None,
        hosts_in_slice=hosts,
    )


def tpu_resources(info: Optional[TpuInfo] = None) -> Dict[str, float]:
    """Scheduler resources for this host (reference resource markers)."""
    info = info or detect_tpu()
    if info is None:
        return {}
    res: Dict[str, float] = {"TPU": float(info.chips_on_host)}
    if info.generation:
        res[f"TPU-{info.generation}"] = float(info.chips_on_host)
    # worker 0 of a slice carries the slice-head resource (reference
    # tpu.py:363-382) so whole-slice actors schedule exactly once per slice
    if info.accelerator_type and (info.worker_id in (0, None)):
        res[f"TPU-{info.accelerator_type}-head"] = 1.0
    return res


def num_tpu_chips() -> int:
    info = detect_tpu()
    return info.chips_on_host if info else 0


def get_current_pod_name() -> Optional[str]:
    info = detect_tpu()
    return info.pod_name if info else None


def get_current_pod_worker_count() -> int:
    info = detect_tpu()
    return info.hosts_in_slice if info else 0


class TPUAcceleratorManager:
    """Reference-shaped manager interface
    (``_private/accelerators/accelerator.py``)."""

    @staticmethod
    def get_resource_name() -> str:
        return "TPU"

    @staticmethod
    def get_current_node_num_accelerators() -> int:
        return num_tpu_chips()

    @staticmethod
    def get_current_node_additional_resources() -> Dict[str, float]:
        res = tpu_resources()
        res.pop("TPU", None)
        return res

    @staticmethod
    def set_current_process_visible_accelerators(ids) -> None:
        os.environ[_TPU_VISIBLE_CHIPS_ENV] = ",".join(str(i) for i in ids)

    @staticmethod
    def get_current_process_visible_accelerator_ids():
        visible = os.environ.get(_TPU_VISIBLE_CHIPS_ENV)
        if visible is None:
            return None
        return [v for v in visible.split(",") if v]
