"""Precompiled serve pipelines — resident compiled DAGs over replicas.

The µs-scale serving path: for a LINEAR chain of deployments
(preprocess → model → postprocess), ``serve.run_pipeline(..., compiled=True)``
precompiles the call chain into resident compiled-DAG lanes. Each lane
parks one replica of every stage in a ``dag_call`` loop over mutable
channels (``ray_tpu.dag``), so a steady-state request costs one channel
write + one read per edge instead of a full per-stage actor RPC
(spec encode → lease → push → seal). The ROADMAP's "compiled DAGs as the
execution substrate for serve replicas", and the host-side analog of the
throughput-per-chip framing in the Gemma-on-TPU serving comparison
(PAPERS.md) — control-plane overhead off the per-token path.

Trade-off (documented in README "Compiled DAG performance"): a replica
parked in a pipeline lane is DEDICATED — the resident loop occupies its
execution thread, so it no longer serves routed ``handle_request`` traffic,
and autoscaling/redeploys must not touch lane members mid-flight. Lanes are
therefore built from a fixed replica snapshot at build time; tear the
pipeline down (``PipelineHandle.shutdown``) before redeploying its stages.

``compiled=False`` builds the same chain over per-call DeploymentHandles —
the A/B baseline ``benches/dag_tick.py`` measures against.
"""

from __future__ import annotations

import itertools
import struct
import threading
import time
from typing import Any, List, Optional, Tuple

import numpy as np

import ray_tpu
from ray_tpu.dag.dag_node import InputNode
from ray_tpu.utils.logging import get_logger, log_swallowed

logger = get_logger("serve_pipeline")


class PipelineResponse:
    """Future-like response (same surface as DeploymentResponse.result)."""

    def __init__(self, ref):
        self._ref = ref

    def result(self, timeout_s: Optional[float] = 30.0):
        return self._ref.get(timeout=timeout_s)


class PipelineHandle:
    """Ingress handle of a COMPILED pipeline: requests round-robin over the
    precompiled lanes; each lane pipelines several in-flight requests
    through its multi-slot ring edges."""

    def __init__(self, stage_names: List[str], lanes: List[Any]):
        self.stage_names = list(stage_names)
        self._lanes = list(lanes)
        self._rr = itertools.count()
        self._lock = threading.Lock()
        self._shut = False
        # serve.run_pipeline registers the handle here so serve.shutdown()
        # can tear down forgotten pipelines; a direct shutdown() call
        # deregisters so repeatedly-rebuilt pipelines don't accrete.
        self._registry: Optional[list] = None

    @property
    def num_lanes(self) -> int:
        return len(self._lanes)

    def remote(self, value: Any) -> PipelineResponse:
        if self._shut:
            raise RuntimeError("pipeline was shut down")
        lane = self._lanes[next(self._rr) % len(self._lanes)]
        return PipelineResponse(lane.execute(value))

    def shutdown(self) -> None:
        """Tear down every lane (close pills propagate, loops exit, the
        driver unlinks the channels). The stage replicas come back to life
        as ordinary routed replicas afterwards. Idempotent."""
        with self._lock:
            if self._shut:
                return
            self._shut = True
            for lane in self._lanes:
                try:
                    lane.teardown()
                except Exception:  # noqa: BLE001 — teardown is best-effort
                    log_swallowed(logger, "pipeline lane teardown")
        if self._registry is not None:
            try:
                self._registry.remove(self)
            except ValueError:
                pass  # serve.shutdown already popped us


class SequentialPipelineHandle:
    """Per-call baseline: the same chain walked with one routed actor RPC
    per stage per request (what ``compiled=True`` collapses)."""

    def __init__(self, stage_names: List[str], handles: List[Any]):
        self.stage_names = list(stage_names)
        self._handles = list(handles)

    def remote(self, value: Any) -> "_SequentialResponse":
        return _SequentialResponse(self._handles, value)

    def shutdown(self) -> None:
        pass  # nothing resident to tear down


class _SequentialResponse:
    def __init__(self, handles, value):
        self._handles = handles
        self._value = value
        self._done = False

    def result(self, timeout_s: Optional[float] = 30.0):
        if not self._done:
            v = self._value
            for h in self._handles:
                v = h.remote(v).result(timeout_s=timeout_s)
            self._value = v
            self._done = True
        return self._value


def build_compiled_pipeline(controller, stage_names: List[str], *,
                            channel_type: str = "auto",
                            channel_capacity: int = 4 * 1024 * 1024,
                            channel_slots: Optional[int] = None,
                            lanes: Optional[int] = None) -> PipelineHandle:
    """Compile ``lanes`` parallel resident DAG lanes over the current
    replica fleet of ``stage_names`` (in chain order). Each lane uses a
    DISTINCT replica per stage (a resident loop occupies the replica), so
    the lane count is capped by the smallest stage's replica count."""
    _version, table = ray_tpu.get(
        controller.get_snapshot.remote(-1, 0.0))
    replica_sets = []
    for name in stage_names:
        entry = table.get(name)
        if not entry or not entry["replicas"]:
            raise RuntimeError(
                f"deployment {name!r} has no live replicas to compile")
        replica_sets.append(list(entry["replicas"]))
    max_lanes = min(len(rs) for rs in replica_sets)
    n_lanes = min(lanes, max_lanes) if lanes else max_lanes
    compiled_lanes = []
    try:
        for lane in range(n_lanes):
            node = InputNode()
            for rs in replica_sets:
                node = rs[lane].dag_call.bind(node)
            compiled_lanes.append(node.experimental_compile(
                channel_type=channel_type,
                channel_capacity=channel_capacity,
                channel_slots=channel_slots))
    except BaseException:
        for built in compiled_lanes:
            try:
                built.teardown()
            except Exception:  # noqa: BLE001 — unwind is best-effort
                log_swallowed(logger, "pipeline build unwind")
        raise
    return PipelineHandle(stage_names, compiled_lanes)


class KVHandoffLane:
    """Engine→engine KV-block transport over one multi-slot shm
    :class:`~ray_tpu.dag.channel.Channel` — the lane the cluster KV tier's
    drain-by-migration ships a retiring replica's warm chains over
    (``serve/llm.py LLMEngine.kv_migrate_out`` / ``kv_migrate_in``).

    A chain's pool blocks travel as one framed payload::

        [meta_len, k_len, v_len : <QQQ>] [pickled meta] [raw K] [raw V]

    where meta carries the chain (its tokens and how many are real) and the
    K/V dtype+shape needed to reinterpret the raw bytes.
    ``send`` lands the arrays DIRECTLY in the ring slot via the channel's
    ``_wait_writable``/``_publish`` split (no intermediate buffer), and
    ``recv`` returns zero-copy ``np.frombuffer`` views into the slot plus an
    ack token: the DEFERRED-ACK protocol (``_consume_view``/``_ack``) built
    for DMA in PR 7 — the receiving engine uploads the views into its own
    pool (a donated ``insert_fn`` dispatch), blocks until the transfer
    lands, and only then releases the slot back to the writer. Up to
    ``slots`` handoffs ride in flight, so the sender keeps extracting while
    the receiver drains.

    Single-writer (the victim) / single-reader (the survivor), in- or
    cross-process: the other endpoint attaches by ``name`` with
    ``create=False``, same as every other channel endpoint.
    """

    _HDR = struct.Struct("<QQQ")

    def __init__(self, name: Optional[str] = None,
                 capacity: int = 8 * 1024 * 1024,
                 slots: Optional[int] = None, create: bool = True):
        from ray_tpu.dag.channel import Channel

        self.chan = Channel(name=name, capacity=capacity, create=create,
                            slots=slots)
        self.name = self.chan.name

    @classmethod
    def attach(cls, name: str, timeout: float = 10.0,
               capacity: int = 8 * 1024 * 1024,
               slots: Optional[int] = None) -> Optional["KVHandoffLane"]:
        """Attach to a lane some OTHER endpoint creates, retrying until it
        appears or ``timeout`` lapses (None on timeout). The KV-tier drain
        path races lane creation against attachment — the survivor creates,
        the retiring victim attaches — so the attach side polls instead of
        requiring create-before-attach ordering. ``capacity``/``slots``
        must MATCH the creator's (the shm mapping is sized from them; both
        drain endpoints derive them from the same model config)."""
        deadline = time.monotonic() + timeout
        while True:
            try:
                return cls(name=name, capacity=capacity, slots=slots,
                           create=False)
            except Exception:  # noqa: BLE001 — shm segment not there yet
                if time.monotonic() > deadline:
                    return None
                time.sleep(0.01)

    # -- writer half (the sending engine) -------------------------------------
    def send(self, meta: dict, k: np.ndarray, v: np.ndarray,
             timeout: Optional[float] = 30.0) -> None:
        from ray_tpu.core import serialization

        k = np.ascontiguousarray(k)
        v = np.ascontiguousarray(v)
        meta = dict(meta)
        meta["dtype"] = str(k.dtype)
        meta["shape"] = tuple(int(d) for d in k.shape)
        blob = serialization.dumps(meta)
        total = self._HDR.size + len(blob) + k.nbytes + v.nbytes
        if total > self.chan.capacity:
            raise ValueError(
                f"KV handoff of {total} bytes exceeds lane capacity "
                f"{self.chan.capacity}")
        self.chan._wait_writable(timeout)
        mm = self.chan._mm
        off = self.chan._wpayload_off
        self._HDR.pack_into(mm, off, len(blob), k.nbytes, v.nbytes)
        off += self._HDR.size
        mm[off:off + len(blob)] = blob
        off += len(blob)
        np.frombuffer(mm, np.uint8, k.nbytes, off)[:] = \
            k.reshape(-1).view(np.uint8)
        off += k.nbytes
        np.frombuffer(mm, np.uint8, v.nbytes, off)[:] = \
            v.reshape(-1).view(np.uint8)
        self.chan._publish(total)

    # -- reader half (the receiving engine) -----------------------------------
    def recv(self, timeout: Optional[float] = 30.0
             ) -> Tuple[dict, np.ndarray, np.ndarray, Tuple[int, int]]:
        """Return ``(meta, k, v, ack_token)``. ``k``/``v`` are views into
        the ring slot — they stay valid (the writer cannot reuse the slot)
        until ``ack(ack_token)``; copy or upload them first."""
        from ray_tpu.core import serialization
        from ray_tpu.dag.channel import _CLOSE, ChannelClosed

        view, length, slot, seq = self.chan._consume_view(timeout)
        if length == len(_CLOSE) and bytes(view[:length]) == _CLOSE:
            self.chan._ack(slot, seq)
            raise ChannelClosed(self.name)
        meta_len, k_len, v_len = self._HDR.unpack_from(view, 0)
        off = self._HDR.size
        meta = serialization.loads(bytes(view[off:off + meta_len]))
        off += meta_len
        dt = np.dtype(meta["dtype"])
        shape = tuple(meta["shape"])
        k = np.frombuffer(view, dt, k_len // dt.itemsize, off).reshape(shape)
        off += k_len
        v = np.frombuffer(view, dt, v_len // dt.itemsize, off).reshape(shape)
        return meta, k, v, (slot, seq)

    def ack(self, token: Tuple[int, int]) -> None:
        self.chan._ack(*token)

    # -- lifecycle ------------------------------------------------------------
    def close(self) -> None:
        self.chan.close()

    def detach(self) -> None:
        self.chan.detach()

    def destroy(self) -> None:
        self.chan.destroy()
