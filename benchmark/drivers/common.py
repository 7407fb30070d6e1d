"""What every driver shares: the device check, the seeded weights, the
compile watch and the profiler session. The only file besides the drivers
that imports the program."""

from __future__ import annotations

import importlib
import os
import threading
import time
from typing import Any, Dict, List, Optional


def resolve(dotted: str) -> Any:
    mod, _, attr = dotted.rpartition(".")
    return getattr(importlib.import_module(mod), attr)


def seed32(seed: int) -> int:
    """Any whole seed folded into what a 32-bit PRNG key takes."""
    return int(seed) % (2 ** 31 - 1)


def model_config(config: Dict, rehearse: bool):
    f = config["rehearse_factory"] if rehearse else config["factory"]
    return resolve(f["path"])(**f.get("kwargs", {}))


def device_report(chips: int, rehearse: bool) -> Dict:
    """The devices as JAX reports them; no chip, or too few, is an error."""
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu" and not rehearse:
        raise SystemExit("benchmark: JAX found no accelerator (platform cpu); "
                         "nothing ran. --rehearse is the CPU rehearsal.")
    if len(devs) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chips, JAX "
                         f"found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_stats() -> Dict:
    """The allocator's counters on the fullest chip, as JAX reports them.
    ``peak_bytes_in_use`` counts arrays that were live, not the temporaries a
    program allocates while it runs (PERF.md, Cells, sets both side by side)."""
    import jax

    all_stats = [d.memory_stats() or {} for d in jax.devices()]
    return max(all_stats, key=lambda s: int(s.get("peak_bytes_in_use", 0)))


class CompileWatch:
    """Counts what JAX lowers or compiles, with the host time of each.

    JAX reports a duration event when it turns a jaxpr into a module and when
    the backend compiles (or the persistent cache is asked). Any of them
    inside the measured window makes the run incorrect."""

    _EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring as mon

        self.times: List[float] = []
        self.compile_s = 0.0
        self.cache = {"hits": 0, "misses": 0}
        self._lock = threading.Lock()
        mon.register_event_duration_secs_listener(self._on)
        mon.register_event_listener(self._on_event)

    def _on_event(self, name: str, **kw) -> None:
        if name.endswith("/cache_hits"):
            self.cache["hits"] += 1
        elif name.endswith("/cache_misses"):
            self.cache["misses"] += 1

    def _on(self, name: str, duration: float, **kw) -> None:
        if name in self._EVENTS:
            with self._lock:
                self.times.append(time.perf_counter())
                if name.endswith("backend_compile_duration"):
                    self.compile_s += duration

    def between(self, t0: float, t1: float) -> int:
        with self._lock:
            return sum(1 for t in self.times if t0 <= t < t1)


class TraceSession:
    """The benchmark's own profiler session over part of the window.

    Only the process that holds the chip can trace it, so this runs here, in
    the process that drives the program. ``start``/``stop`` are host times on
    ``time.perf_counter``; the trace's own clock starts at 0 when the session
    starts, which is how host records are put on it."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.t_start: Optional[float] = None
        self.t_stop: Optional[float] = None

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        # The python tracer would record every call of every thread and slow
        # the host it is meant to watch; the host's TraceMe events (jit
        # dispatches by name) are enough to attribute a gap.
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        self.t_start = time.perf_counter()
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()
        self.t_stop = time.perf_counter()

    def run_later(self, t_begin: float, seconds: float) -> None:
        """Trace from host time ``t_begin`` for ``seconds``, from a thread."""
        def body():
            time.sleep(max(0.0, t_begin - time.perf_counter()))
            self.start()
            time.sleep(seconds)
            self.stop()

        self._thread = threading.Thread(target=body, name="bench-trace",
                                        daemon=True)
        self._thread.start()

    def join(self) -> None:
        self._thread.join()

    def xplane(self) -> Optional[str]:
        import glob

        found = sorted(glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None


TRACE_OFFSET_S = 2.0    # into the window, so that its opening is not traced
TRACE_SECONDS = 4.0     # some 8 train steps or 15 decode calls; 40 MB of trace


def trace_window(ctx: Dict, t_open: float, seconds: float) -> Optional[TraceSession]:
    """With ``--trace 1``: a session over ``TRACE_SECONDS`` of the window
    (at most half of it), ``TRACE_OFFSET_S`` after it opens; else None."""
    if not ctx["trace"]:
        return None
    tracer = TraceSession(ctx["trace_dir"])
    tracer.run_later(t_open + TRACE_OFFSET_S, min(TRACE_SECONDS, seconds / 2))
    return tracer
