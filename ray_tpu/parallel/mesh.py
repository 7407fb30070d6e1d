"""Device meshes — the substrate for every parallelism axis.

This replaces the reference's process-group plumbing (torch
``init_process_group`` in ``python/ray/train/torch/config.py:64-100``, NCCL
groups in ``python/ray/util/collective/``) with the TPU-native model: a single
`jax.sharding.Mesh` whose named axes carry all parallelism dimensions —

- ``data``    data parallelism (gradient psum)
- ``fsdp``    parameter-sharded data parallelism (reduce_scatter/all_gather)
- ``tensor``  tensor/model parallelism (megatron-style row/col sharding)
- ``seq``     sequence/context parallelism (ring attention over ICI neighbors)
- ``pipe``    pipeline parallelism (ppermute stage handoff)
- ``expert``  expert parallelism (all_to_all token routing)

Axis ORDER matters on hardware: the innermost axes map to the
torus-contiguous ICI dimensions, so ``tensor``/``seq`` (latency-sensitive
collectives) sit innermost and ``data`` (bandwidth-tolerant psum) outermost,
possibly spanning DCN between slices — the scaling-book recipe.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# Shared tier vocabulary with the control plane: the gang scheduler scores
# placements by how many bundle pairs are forced onto TIER_DCN, using the
# same two names these axis assignments use. Defined in core (jax-free) so
# the GCS process can import it; re-exported here for mesh-side callers.
from ray_tpu.core.resources import TIER_DCN, TIER_ICI

# Canonical axis order, outermost → innermost (DCN-tolerant → ICI-hungry).
AXIS_ORDER = ("data", "fsdp", "expert", "pipe", "seq", "tensor")

# Fabric tier of each canonical axis: ``data``/``fsdp`` collectives are
# bandwidth-bound and overlappable, so those axes may span the slow
# inter-slice DCN; every inner axis demands single-slice ICI latency. The
# eager host collectives mirror this two-level split at the process level
# (``ray_tpu.parallel.collectives``: intra-node shm tier + inter-node ring).
AXIS_TIER = {"data": TIER_DCN, "fsdp": TIER_DCN, "expert": TIER_ICI,
             "pipe": TIER_ICI, "seq": TIER_ICI, "tensor": TIER_ICI}


@dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh shape: axis name → size (1 = absent).

    ``MeshSpec(data=2, tensor=4)`` on 8 chips ≡ a (2, 4) mesh. Size ``-1``
    on at most one axis means "fill with remaining devices".
    """

    data: int = 1
    fsdp: int = 1
    expert: int = 1
    pipe: int = 1
    seq: int = 1
    tensor: int = 1

    def sizes(self) -> Dict[str, int]:
        return {a: getattr(self, a) for a in AXIS_ORDER}

    def resolve(self, n_devices: int) -> "MeshSpec":
        sizes = self.sizes()
        wild = [a for a, s in sizes.items() if s == -1]
        if len(wild) > 1:
            raise ValueError("at most one axis may be -1")
        known = int(np.prod([s for s in sizes.values() if s != -1]))
        if wild:
            if n_devices % known:
                raise ValueError(
                    f"cannot fill axis {wild[0]}: {n_devices} devices not divisible by {known}"
                )
            sizes[wild[0]] = n_devices // known
            known = n_devices
        if known != n_devices:
            raise ValueError(
                f"mesh {sizes} needs {known} devices but {n_devices} provided"
            )
        return MeshSpec(**sizes)

    def axis_names(self) -> List[str]:
        return [a for a in AXIS_ORDER if self.sizes()[a] > 1]


def best_devices(n: Optional[int] = None) -> List[jax.Device]:
    """The accelerator's devices, or the CPU's where JAX found no
    accelerator; with ``n``, the first ``n`` of them.

    Asking for more than there are is an error: a program written for four
    chips must not come up on virtual CPU devices because the host has one.
    CPU meshes are asked for by name (``cpu_mesh``, ``JAX_PLATFORMS=cpu``)."""
    devs = ([d for d in jax.devices() if d.platform != "cpu"]
            or jax.devices("cpu"))
    if n is not None:
        if len(devs) < n:
            raise ValueError(
                f"need {n} {devs[0].platform} devices, have {len(devs)}")
        devs = devs[:n]
    return devs


def make_mesh(
    spec: MeshSpec | Dict[str, int] | None = None,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build a Mesh with canonical axis ordering.

    All six canonical axes are always present (size-1 axes included), so
    sharding rules can name any axis regardless of the active topology —
    size-1 axes cost nothing under XLA.
    """
    if isinstance(spec, dict):
        spec = MeshSpec(**spec)
    devices = list(devices) if devices is not None else best_devices()
    spec = (spec or MeshSpec(data=-1)).resolve(len(devices))
    sizes = spec.sizes()
    shape = tuple(sizes[a] for a in AXIS_ORDER)
    arr = np.asarray(devices).reshape(shape)
    return Mesh(arr, AXIS_ORDER)


def cpu_mesh(spec: MeshSpec | Dict[str, int] | None = None, n: Optional[int] = None) -> Mesh:
    """A virtual CPU mesh for tests and multi-chip dry runs.

    With ``n=None`` the device count is inferred from the spec (fully
    specified spec → its product; wildcard spec → all CPU devices).
    """
    if isinstance(spec, dict):
        spec = MeshSpec(**spec)
    devices = jax.devices("cpu")
    if n is None and spec is not None:
        sizes = spec.sizes().values()
        if -1 not in sizes:
            n = int(np.prod(list(sizes)))
    return make_mesh(spec, devices[:n] if n else devices)


def mesh_shape(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def dp_axes(mesh: Mesh) -> tuple:
    """Axes over which gradients are reduced (data + fsdp)."""
    return tuple(a for a in ("data", "fsdp") if a in mesh.axis_names)


def hierarchy_split(mesh: Mesh) -> Tuple[tuple, tuple]:
    """(dcn_axes, ici_axes) among the mesh's ACTIVE (size>1) axes.

    The compiled-path statement of the same two-level schedule the eager
    collectives run on hosts: reduce over the ICI axes first (fast, inside
    a slice), cross the DCN tier once with the already-reduced partials.
    """
    active = [a for a, s in mesh_shape(mesh).items() if s > 1]
    return (tuple(a for a in active if AXIS_TIER.get(a) == "dcn"),
            tuple(a for a in active if AXIS_TIER.get(a) != "dcn"))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())
