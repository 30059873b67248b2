"""The time limit every test runs under (``tests/time_limit.py``).

Both checks run in a process of their own: a process has one
``ITIMER_REAL`` and one faulthandler watchdog, and the test that runs
them is already under its own limit."""

import json
import os
import subprocess
import sys
import textwrap

TESTS = os.path.dirname(os.path.abspath(__file__))


def _run(args, cwd, timeout):
    env = dict(os.environ, PYTHONPATH=TESTS)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_a_wait_past_its_limit_fails_and_names_the_waiting_frame(tmp_path):
    """The helper itself, at 1 s around a wait nothing will end: the
    wait fails within about 1.5 s, every thread's stack names the
    waiting frame, and what comes next runs with nothing left armed."""
    probe = textwrap.dedent("""
        import json, signal, sys, threading, time
        import pytest
        from time_limit import time_limit

        def blocked_here():
            threading.Event().wait()

        t0 = time.monotonic()
        with open(sys.argv[1], "w") as stacks:
            try:
                with time_limit(1, "probe", file=stacks):
                    blocked_here()
            except pytest.fail.Exception as e:
                message = str(e)
        print(json.dumps({
            "elapsed_s": time.monotonic() - t0,
            "message": message,
            "timer": signal.getitimer(signal.ITIMER_REAL),
            "handler_restored":
                signal.getsignal(signal.SIGALRM) is signal.SIG_DFL,
        }))
    """)
    stacks = tmp_path / "stacks.txt"
    res = _run(["-c", probe, str(stacks)], tmp_path, timeout=60)
    assert res.returncode == 0, res.stderr
    out = json.loads(res.stdout)
    assert 1.0 <= out["elapsed_s"] < 2.0, out
    assert out["message"] == "probe ran over its 1 s time limit"
    assert out["timer"] == [0.0, 0.0] and out["handler_restored"]
    dump = stacks.read_text()
    assert "most recent call first" in dump
    assert "in blocked_here" in dump and "in wait" in dump


def test_a_blocked_test_fails_alone_and_the_run_goes_on(tmp_path):
    """Under xdist, as tier-1 runs: a test blocked in Python fails at its
    limit; one whose main thread never takes the signal is ended at twice
    the limit and its worker replaced; the test after both passes, so the
    run reaches its end.  Tests run on each worker's main thread, where
    the signal is delivered."""
    (tmp_path / "test_inner.py").write_text(textwrap.dedent("""
        import signal, threading, time
        import pytest

        def test_on_the_main_thread():
            assert threading.current_thread() is threading.main_thread()

        @pytest.mark.time_limit(1)
        def test_blocked_in_python():
            threading.Event().wait()

        @pytest.mark.time_limit(1)
        def test_deaf_to_the_signal():
            signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGALRM})
            time.sleep(60)

        def test_after_both():
            pass
    """))
    res = _run(["-m", "pytest", "-p", "time_limit", "-p", "xdist", "-n", "1",
                "-p", "no:cacheprovider", "-p", "no:randomly", "-rA",
                "test_inner.py"], tmp_path, timeout=120)
    out = res.stdout + res.stderr
    assert res.returncode == 1, out
    assert "2 failed, 2 passed" in out, out
    assert ("test_inner.py::test_blocked_in_python (call) ran over its "
            "1 s time limit") in out
    assert "crashed while running 'test_inner.py::test_deaf_to_the_signal'" \
        in out
    assert "PASSED test_inner.py::test_after_both" in out
    # the stacks of both, from the signal and from the backstop
    assert "in test_blocked_in_python" in out
    assert "in test_deaf_to_the_signal" in out
