"""Test fixtures.

Mirrors the reference's conftest strategy
(``python/ray/tests/conftest.py:419 ray_start_regular``, ``:500
ray_start_cluster``): a fresh runtime per test, plus a multi-virtual-node
cluster fixture with fake resources — the single-host trick that makes all
scheduler/fault-tolerance logic testable without real machines
(``python/ray/cluster_utils.py:135``).

JAX runs on a virtual 8-device CPU mesh so every sharding/collective test
exercises real multi-device SPMD without a TPU pod.
"""

import os

# Must be set before jax import anywhere in the test process. Tests always run
# on the virtual 8-device CPU mesh, even when a real TPU is attached.
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
# The suite compiles thousands of CPU programs and runs each for milliseconds:
# LLVM's optimisation passes were a third of a family file's time (PR 50) and
# buy nothing a case reads. What a case compares is XLA's program against a
# reference, at the same tolerances; the HLO passes run as ever.
if "--xla_backend_optimization_level" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_backend_optimization_level=0"
os.environ["JAX_PLATFORMS"] = "cpu"

# One compile cache a RUN: a program is compiled once, not once a case. Most
# cases build a fresh ``PagedGenerator`` or a fresh ``jax.jit(lambda ...)``,
# which misses JAX's in-memory cache however equal its program is; JAX's own
# persistent cache (it holds CPU executables too) finds it again. The process
# that starts the run makes an EMPTY directory and exports it before xdist
# starts its workers, which inherit it, as do the cluster processes a test
# forks; ``pytest_sessionfinish`` removes it. Nothing outlives a run and
# nothing is read from an earlier one, so a run is as cold and as repeatable
# as without it. A module that measures compiling turns the cache off for
# itself (the ``no_compile_cache`` fixture below); the benchmark's rehearsal
# children set JAX_ENABLE_COMPILATION_CACHE=0 in their own environment and
# stay the cold program a chip would see.
import shutil  # noqa: E402
import tempfile  # noqa: E402

_RUN_CACHE = None
if "PYTEST_XDIST_WORKER" not in os.environ:
    _RUN_CACHE = tempfile.mkdtemp(prefix="ray_tpu_tests_jax_cache_")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _RUN_CACHE
os.environ.pop("JAX_ENABLE_COMPILATION_CACHE", None)
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"

import pytest  # noqa: E402

import jax  # noqa: E402

# Tests run on the CPU: pin the config as well as the environment, so a
# jax imported before this file (a plugin, an embedding runner) is held
# to the CPU too.
jax.config.update("jax_platforms", "cpu")

# Pin all test computation to the virtual CPU devices and full matmul
# precision so numerical oracles are exact.
jax.config.update("jax_default_device", jax.devices("cpu")[0])
jax.config.update("jax_default_matmul_precision", "highest")

import faulthandler  # noqa: E402
import signal  # noqa: E402
import threading  # noqa: E402

# Opt-in runtime lock-order validation (ray_tpu.devtools.lockcheck): with
# RAY_TPU_LOCK_ORDER_CHECK_ENABLED=1 every threading.Lock/RLock/Condition
# is instrumented — per-thread held-sets, a global acquisition-order graph,
# LockOrderError on inversion. ray_tpu/__init__ installs the wrappers at
# the TOP of the package import (so module-level locks like
# runtime._init_lock and collectives._groups_lock are covered too); this
# import triggers that, and the env var propagates to spawned cluster
# processes, which instrument the same way when they import ray_tpu.
from ray_tpu.devtools import lockcheck as _lockcheck  # noqa: E402

_LOCKCHECK_ON = _lockcheck.maybe_install()

# Opt-in runtime leak validation (ray_tpu.devtools.leakcheck): with
# RAY_TPU_LEAK_CHECK_ENABLED=1 threads/fds/sockets are stamped with their
# allocation site, and the autouse fixture below snapshots live
# threads/open fds/own shm segments per test and FAILS any test whose
# teardown leaves new ones behind, naming each survivor.
from ray_tpu.devtools import leakcheck as _leakcheck  # noqa: E402

_LEAKCHECK_ON = _leakcheck.maybe_install()

# Opt-in runtime JAX compile-churn validation (ray_tpu.devtools.jitcheck):
# with RAY_TPU_JIT_CHECK_ENABLED=1, jax.jit is wrapped to stamp and count
# compilations, and the autouse fixture below FAILS any test during which
# a steady-state contract violation (new XLA compile or implicit
# device->host read inside jitcheck.steady_state()) was recorded.
from ray_tpu.devtools import jitcheck as _jitcheck  # noqa: E402

_JITCHECK_ON = _jitcheck.maybe_install()

TEST_TIMEOUT_S = 180  # matches the reference's pytest.ini per-test timeout
WATCHDOG_S = TEST_TIMEOUT_S + 30  # then the watchdog that needs no interpreter


_REAL_STDERR = pytest.StashKey[int]()


def pytest_configure(config):
    """While pytest configures, its output capture is suspended and fd 2 is
    the run's real stderr: a copy is kept for the watchdog below, whose dump
    would else land in the capture's file and go with the process."""
    config.stash[_REAL_STDERR] = os.dup(2)


def pytest_unconfigure(config):
    os.close(config.stash[_REAL_STDERR])


def pytest_sessionstart(session):
    """With RAY_TPU_LINT_IN_CI=1, run raylint against its baseline before
    the suite: tier-1 fails on NEW static findings without a separate CI
    job (`python -m ray_tpu.devtools.lint --check-baseline`)."""
    if os.environ.get("RAY_TPU_LINT_IN_CI", "").lower() not in (
            "1", "true", "yes", "on"):
        return
    from ray_tpu.devtools import lint

    if lint.main(["--check-baseline"]) != 0:
        raise pytest.UsageError(
            "raylint found NEW findings (RAY_TPU_LINT_IN_CI=1) — fix them "
            "or accept deliberately with "
            "`python -m ray_tpu.devtools.lint --update-baseline`")


# xdist hands out FILES (``--dist loadfile``) in collection order, so a long
# file late in the alphabet is the run's tail, with five workers idle. These
# go first: the compile-only children hold their worker for minutes, and the
# kernel's main file is the longest of all. A file joins the list when the
# per-file junit table (``.claude/skills/verify/SKILL.md``) shows it ending
# the run.
_LONG_FILES_FIRST = ("test_v5e_compile.py", "test_paged_attention_kernel.py")


def pytest_collection_modifyitems(items):
    items.sort(key=lambda item: item.path.name not in _LONG_FILES_FIRST)


def pytest_sessionfinish(session):
    """The run's compile cache goes with the run (the process that made it
    removes it; an xdist worker made none)."""
    if _RUN_CACHE is not None:
        shutil.rmtree(_RUN_CACHE, ignore_errors=True)


@pytest.fixture(scope="module")
def no_compile_cache():
    """For a module that MEASURES compiling (counts backend compiles, times
    a warm-up against a second call): the run's compile cache is off while
    its cases run, so a program another file compiled first is compiled
    here again. JAX decides once whether the cache is in use; the reset
    makes it decide again."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(autouse=True)
def _leak_guard(request):
    """With leakcheck installed, fail any test that leaks a thread, fd, or
    shm segment past teardown. Defined FIRST among the autouse fixtures so
    it wraps them all: the snapshot runs before ray_start_* setup and the
    diff after their teardown. `@pytest.mark.leaks("reason")` opts a test
    out (e.g. intentional-crash tests that orphan resources by design)."""
    if not _LEAKCHECK_ON:
        yield
        return
    before = _leakcheck.snapshot()
    yield
    if request.node.get_closest_marker("leaks") is not None:
        return
    leaked = _leakcheck.check(before)
    assert not leaked, (
        "resources leaked past test teardown:\n  " + "\n  ".join(leaked))


@pytest.fixture(autouse=True)
def _lock_order_guard():
    """With lockcheck installed, fail any test during which an inversion was
    recorded — even one raised (and swallowed) on a daemon thread."""
    if not _LOCKCHECK_ON:
        yield
        return
    before = len(_lockcheck.violations())
    yield
    new = _lockcheck.violations()[before:]
    assert not new, "lock-order violations during test:\n" + "\n".join(new)


@pytest.fixture(autouse=True)
def _steady_state_guard(request):
    """With jitcheck installed, fail any test during which a steady-state
    violation was recorded — a new XLA compile or an implicit device->host
    read inside jitcheck.steady_state(). `@pytest.mark.jit_violations`
    opts a test out (tests that provoke violations on purpose)."""
    if not _JITCHECK_ON:
        yield
        return
    before = len(_jitcheck.violations())
    yield
    if request.node.get_closest_marker("jit_violations") is not None:
        return
    new = _jitcheck.violations()[before:]
    assert not new, (
        "steady-state jit violations during test:\n  " + "\n  ".join(new))


@pytest.fixture(autouse=True)
def _per_test_timeout(pytestconfig):
    """Hang protection for a condition-variable-heavy runtime: SIGALRM raises
    in the main thread if a test exceeds the budget (pytest-timeout is not in
    the image). A Python handler runs only when the main thread is back in
    the interpreter, so a wait in native code (PR 45: every worker in a
    futex wait until the run's own clock) outlasts it: at ``WATCHDOG_S``
    faulthandler's C thread prints every thread's stack and ends the
    process. Under xdist that costs the one running case: the worker is
    reported down, its case failed, another worker started."""
    if threading.current_thread() is not threading.main_thread():
        yield
        return

    def _on_alarm(signum, frame):
        raise TimeoutError(f"test exceeded {TEST_TIMEOUT_S}s (possible deadlock)")

    old = signal.signal(signal.SIGALRM, _on_alarm)
    signal.alarm(TEST_TIMEOUT_S)
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True,
                                      file=pytestconfig.stash[_REAL_STDERR])
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture
def ray_start_regular():
    import ray_tpu

    ray_tpu.init(resources={"CPU": 4, "TPU": 8})
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    """4 virtual nodes, 2 CPU + 4 TPU each."""
    import ray_tpu

    ray_tpu.init(resources={"CPU": 2, "TPU": 4}, num_nodes=4)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture
def cpu_mesh_devices():
    import jax

    devices = jax.devices("cpu")
    assert len(devices) >= 8, "conftest must force 8 host-platform devices"
    return devices


def _sweep_stale_shm() -> None:
    """Remove shm arenas left by SIGKILLed test processes (crash tests kill
    whole interpreters, skipping store destructors). Names embed the owning
    pid — only arenas of DEAD processes are removed."""
    import re

    if not os.path.isdir("/dev/shm"):
        return
    for name in os.listdir("/dev/shm"):
        pid_m = re.match(r"rtpu_store_(\d+)_", name)
        if not pid_m:
            continue
        pid = int(pid_m.group(1))
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            try:
                os.unlink(os.path.join("/dev/shm", name))
            except OSError:
                pass
        except PermissionError:
            pass


_sweep_stale_shm()
