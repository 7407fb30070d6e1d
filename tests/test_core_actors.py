"""Actor tests: lifecycle, ordering, named actors, async actors, kill/restart —
the reference's ``python/ray/tests/test_actor.py`` surface."""

import asyncio
import threading
import time

import pytest


def test_actor_basic(ray_start_regular):
    rt = ray_start_regular

    @rt.remote
    class Counter:
        def __init__(self, start=0):
            self.n = start

        def incr(self, by=1):
            self.n += by
            return self.n

        def value(self):
            return self.n

    c = Counter.remote(10)
    assert rt.get(c.incr.remote()) == 11
    assert rt.get(c.incr.remote(5)) == 16
    assert rt.get(c.value.remote()) == 16


def test_actor_method_ordering(ray_start_regular):
    rt = ray_start_regular

    @rt.remote
    class Log:
        def __init__(self):
            self.items = []

        def append(self, x):
            self.items.append(x)

        def get_items(self):
            return self.items

    log = Log.remote()
    for i in range(50):
        log.append.remote(i)
    assert rt.get(log.get_items.remote()) == list(range(50))


def test_actor_method_error_does_not_kill(ray_start_regular):
    rt = ray_start_regular

    @rt.remote
    class A:
        def bad(self):
            raise ValueError("nope")

        def good(self):
            return "ok"

    a = A.remote()
    with pytest.raises(ValueError):
        rt.get(a.bad.remote())
    assert rt.get(a.good.remote()) == "ok"


def test_actor_creation_failure(ray_start_regular):
    rt = ray_start_regular

    @rt.remote
    class Broken:
        def __init__(self):
            raise RuntimeError("ctor boom")

        def m(self):
            return 1

    b = Broken.remote()
    with pytest.raises(rt.ActorError):
        rt.get(b.m.remote(), timeout=10)


def test_named_actor(ray_start_regular):
    rt = ray_start_regular

    @rt.remote
    class Svc:
        def ping(self):
            return "pong"

    Svc.options(name="svc1").remote()
    h = rt.get_actor("svc1")
    assert rt.get(h.ping.remote()) == "pong"


def test_named_actor_duplicate_rejected(ray_start_regular):
    rt = ray_start_regular

    @rt.remote
    class Svc:
        def ping(self):
            return 1

    Svc.options(name="dup").remote()
    time.sleep(0.2)
    with pytest.raises(ValueError, match="already taken"):
        Svc.options(name="dup").remote()


def test_get_if_exists(ray_start_regular):
    rt = ray_start_regular

    @rt.remote
    class Singleton:
        def __init__(self):
            self.token = time.time()

        def get_token(self):
            return self.token

    a = Singleton.options(name="s", get_if_exists=True).remote()
    t1 = rt.get(a.get_token.remote())
    b = Singleton.options(name="s", get_if_exists=True).remote()
    t2 = rt.get(b.get_token.remote())
    assert t1 == t2


def test_kill_actor(ray_start_regular):
    rt = ray_start_regular

    @rt.remote
    class A:
        def m(self):
            return 1

    a = A.remote()
    assert rt.get(a.m.remote()) == 1
    rt.kill(a)
    with pytest.raises(rt.ActorError):
        rt.get(a.m.remote(), timeout=10)


def test_actor_restart(ray_start_regular):
    rt = ray_start_regular

    @rt.remote(max_restarts=1)
    class Phoenix:
        def __init__(self):
            self.state = "alive"

        def get_state(self):
            return self.state

    p = Phoenix.remote()
    assert rt.get(p.get_state.remote()) == "alive"
    rt.kill(p, no_restart=False)
    time.sleep(0.5)
    # After restart the actor serves calls again (state reset).
    assert rt.get(p.get_state.remote(), timeout=10) == "alive"


def test_async_actor(ray_start_regular):
    rt = ray_start_regular

    @rt.remote(max_concurrency=4)
    class AsyncWorker:
        async def work(self, i):
            await asyncio.sleep(0.1)
            return i * 2

    w = AsyncWorker.remote()
    start = time.time()
    refs = [w.work.remote(i) for i in range(4)]
    assert rt.get(refs) == [0, 2, 4, 6]
    # 4 concurrent 0.1s sleeps should take well under 0.4s total.
    assert time.time() - start < 2.0


def test_threaded_actor_concurrency(ray_start_regular):
    rt = ray_start_regular

    @rt.remote(max_concurrency=4)
    class Sleeper:
        def nap(self):
            time.sleep(0.2)
            return 1

    s = Sleeper.remote()
    start = time.time()
    assert sum(rt.get([s.nap.remote() for _ in range(4)])) == 4
    assert time.time() - start < 0.79  # serial would be 0.8s


def test_actor_handle_in_task(ray_start_regular):
    rt = ray_start_regular

    @rt.remote
    class Store:
        def __init__(self):
            self.v = 0

        def set(self, v):
            self.v = v

        def get_v(self):
            return self.v

    @rt.remote
    def writer(store, v):
        rt.get(store.set.remote(v))
        return True

    s = Store.remote()
    rt.get(writer.remote(s, 42))
    assert rt.get(s.get_v.remote()) == 42


def test_actor_resources_held(ray_start_regular):
    rt = ray_start_regular

    @rt.remote(num_tpus=4)
    class MeshHolder:
        def ping(self):
            return 1

    m = MeshHolder.remote()
    assert rt.get(m.ping.remote()) == 1
    assert rt.available_resources().get("TPU", 0) == 4
    rt.kill(m)
    time.sleep(0.3)
    assert rt.available_resources().get("TPU", 0) == 8


# -- how many parked runners a call wakes (``ActorRunner.submit``) -------------


class _CountingCondition(threading.Condition):
    """The runner's condition with its traffic counted; ``wait`` and
    ``notify`` are entered with the runner's lock held, so the counts need
    no lock of their own. ``notify_all`` is ``notify(len(waiters))``."""

    def __init__(self, lock):
        super().__init__(lock)
        self.parked = 0  # runners inside ``wait`` right now
        self.left_wait = 0  # returns from ``wait``, whatever ended it
        self.handed_out = 0  # notifications asked for, over all calls

    def wait(self, timeout=None):
        self.parked += 1
        try:
            return super().wait(timeout)
        finally:
            self.parked -= 1
            self.left_wait += 1

    def notify(self, n=1):
        self.handed_out += n
        super().notify(n)


def _runner_of(handle):
    from ray_tpu.core.runtime import get_runtime

    return get_runtime().actors[handle._actor_id]


def _until(predicate, what, timeout=5.0):
    deadline = time.monotonic() + timeout
    while not predicate():
        assert time.monotonic() < deadline, what
        time.sleep(0.002)


def _count_the_runners(rt, handle):
    """Swap a started actor's condition for a counting one on the same lock
    and send its parked runners over (each re-reads ``self.cv`` a loop)."""
    rt.get(handle.ping.remote(), timeout=10)  # created, every runner started
    runner = _runner_of(handle)
    with runner.lock:
        parked_on, runner.cv = runner.cv, _CountingCondition(runner.lock)
        parked_on.notify_all()
    cv = runner.cv
    _until(lambda: cv.parked == runner.max_concurrency, "the runners did not park")
    cv.left_wait = cv.handed_out = 0
    return runner, cv


def _gate_actor(rt, runners, parties):
    @rt.remote(max_concurrency=runners)
    class Gate:
        def __init__(self):
            self.barrier = threading.Barrier(parties)
            self.release = threading.Event()
            self.order = []

        def ping(self):
            return True

        def enter(self, i):
            # Only ``parties`` calls that are inside at once get past here.
            self.barrier.wait(timeout=5)
            self.release.wait(timeout=5)
            return i

        def note(self, i):
            self.order.append(i)

        def noted(self):
            return self.order

    return Gate.remote()


def _one_call_wakes_one_of_32_parked_runners(rt):
    gate = _gate_actor(rt, runners=32, parties=1)
    _, cv = _count_the_runners(rt, gate)
    assert rt.get(gate.ping.remote(), timeout=5) is True
    _until(lambda: cv.parked == 32, "the runner that took the call did not park again")
    assert cv.handed_out == 1  # ``notify_all`` would hand out 32
    assert cv.left_wait == 1


def _k_calls_wake_k_runners_no_wakeup_lost(rt):
    gate = _gate_actor(rt, runners=8, parties=8)
    runner, cv = _count_the_runners(rt, gate)
    runner.instance.release.set()
    # The barrier lets nobody through unless all eight are inside together.
    assert rt.get([gate.enter.remote(i) for i in range(8)], timeout=10) == list(range(8))
    _until(lambda: cv.parked == 8, "the runners did not park again")
    assert cv.left_wait == 8


def _queued_calls_are_taken_by_runners_that_come_free(rt):
    gate = _gate_actor(rt, runners=8, parties=8)
    runner, cv = _count_the_runners(rt, gate)
    refs = [gate.enter.remote(i) for i in range(16)]
    # Eight are inside (held at ``release``), eight wait in the mailbox with
    # every runner busy: their notifications found nobody to wake.
    _until(lambda: cv.parked == 0 and len(runner.mailbox) == 8, "eight calls did not start")
    assert cv.left_wait == 8
    runner.instance.release.set()
    assert rt.get(refs, timeout=10) == list(range(16))
    _until(lambda: cv.parked == 8, "the runners did not park again")
    assert cv.left_wait == 8  # the second eight were found, not woken for


def _every_runner_thread_ended(runner):
    for t in runner._threads:
        t.join(timeout=5)
    return not [t.name for t in runner._threads if t.is_alive()]


def _kill_wakes_every_parked_runner_and_fails_queued_calls(rt):
    idle = _gate_actor(rt, runners=32, parties=1)
    runner, cv = _count_the_runners(rt, idle)
    rt.kill(idle)
    assert _every_runner_thread_ended(runner)
    assert cv.handed_out == 32 and cv.left_wait == 32
    with pytest.raises(rt.ActorError):
        rt.get(idle.ping.remote(), timeout=5)

    busy = _gate_actor(rt, runners=2, parties=2)
    rt.get(busy.ping.remote(), timeout=10)
    busy_runner = _runner_of(busy)
    calls = [busy.enter.remote(i) for i in range(5)]  # two inside, three queued
    _until(lambda: len(busy_runner.mailbox) == 3, "three calls did not queue")
    rt.kill(busy)
    for ref in calls[2:]:
        with pytest.raises(rt.ActorError):
            rt.get(ref, timeout=5)
    busy_runner.instance.release.set()
    assert _every_runner_thread_ended(busy_runner)


def _one_runner_keeps_submission_order(rt):
    gate = _gate_actor(rt, runners=1, parties=1)
    for i in range(200):
        gate.note.remote(i)
    assert rt.get(gate.noted.remote(), timeout=10) == list(range(200))


@pytest.mark.parametrize(
    "case",
    [
        _one_call_wakes_one_of_32_parked_runners,
        _k_calls_wake_k_runners_no_wakeup_lost,
        _queued_calls_are_taken_by_runners_that_come_free,
        _kill_wakes_every_parked_runner_and_fails_queued_calls,
        _one_runner_keeps_submission_order,
    ],
    ids=lambda case: case.__name__.lstrip("_"),
)
def test_a_call_wakes_as_many_runners_as_it_brings_tasks(ray_start_regular, case):
    case(ray_start_regular)
