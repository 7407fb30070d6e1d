"""The suite's own hang protection (``conftest._per_test_timeout``), driven
from outside: a child ``pytest`` run of two planted cases, the first of which
waits, with the limit patched to a second.

A wait the interpreter can interrupt fails by the SIGALRM handler's message.
A wait in native code, where no Python handler runs (the alarm is blocked
around the sleep), is ended by faulthandler's watchdog thread: every thread's
stack is printed, the process exits, and under xdist that costs the one case,
since the runner replaces the worker and goes on to the next.
"""

import os
import subprocess
import sys
import textwrap
import time

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TESTS)

PLANTED = {
    "native": ("WATCHDOG_S", """
        signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGALRM})
        """, ["-p", "xdist", "-n", "1"]),
    "interruptible": ("TEST_TIMEOUT_S", "", ["-p", "no:xdist"]),
}


@pytest.mark.parametrize("wait", sorted(PLANTED))
def test_a_wait_costs_its_own_case_and_the_run_goes_on(wait, tmp_path):
    constant, before_the_wait, runner = PLANTED[wait]
    (tmp_path / "test_planted.py").write_text(textwrap.dedent(f"""
        import signal, time

        import conftest

        conftest.{constant} = 1


        def test_waits():
            {before_the_wait.strip() or "pass"}
            open("began", "w").write(str(time.time()))
            time.sleep(120)


        def test_after_the_wait():
            pass
        """))
    # ``-p conftest``: the planted file lies outside tests/, so the suite's
    # conftest is named as a plugin. The child's own temporary files (its
    # compile cache, which a process ended by the watchdog leaves behind)
    # go under this case's directory.
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST_")}
    env.update(PYTHONPATH=os.pathsep.join([TESTS, REPO]), TMPDIR=str(tmp_path))
    child = subprocess.run(
        [sys.executable, "-m", "pytest", "test_planted.py", "-q",
         "-p", "no:cacheprovider", "-p", "no:randomly", "-p", "conftest",
         *runner], cwd=tmp_path, env=env, capture_output=True, text=True,
        timeout=100)
    ended = time.time()
    said = child.stdout + child.stderr
    assert child.returncode != 0, said[-3000:]
    assert "1 failed, 1 passed" in child.stdout, said[-3000:]
    # The wait was cut at a second, not slept out (120 s); what follows it is
    # a worker's start-up and the second case, seconds on a loaded machine.
    assert ended - float((tmp_path / "began").read_text()) < 30, said[-3000:]
    if wait == "native":
        # The watchdog's dump: the waiting thread, with the planted frame.
        assert "Timeout (0:00:01)!" in child.stderr, said[-3000:]
        assert "most recent call first" in child.stderr
        assert "in test_waits" in child.stderr
        assert "test_planted.py::test_waits" in child.stdout  # the crashed case
    else:
        assert "test exceeded 1s (possible deadlock)" in child.stdout
