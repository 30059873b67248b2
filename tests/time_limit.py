"""A time limit on each phase of every test: setup, call and teardown.

``tests/conftest.py`` registers this module as a plugin.  A phase that
outlives its limit fails alone, and the run goes on:

- at the limit, SIGALRM writes every thread's stack to stderr (faulthandler's
  handler, which runs even while the main thread is blocked in C) and then
  fails the phase in the main thread, naming the test and the phase;
- at twice the limit, faulthandler's watchdog writes the stacks again and
  ends the process: the backstop for a main thread that never takes the
  signal.  Under xdist the controller reports the worker down, starts
  another and goes on with the tests left.

A test that needs longer takes ``@pytest.mark.time_limit(seconds)``.  The
limit holds faulthandler's one watchdog, so pytest's ``faulthandler_timeout``
option has no effect under it.

A test waits for a condition with :func:`wait_until`, whose bound is a
share of the limit, not a guess at how fast a loaded machine is.
"""

import contextlib
import faulthandler
import os
import signal
import time

import pytest

#: Seconds one phase of one test may take: over 4x the slowest phase (29.1 s,
#: on 8 Xeon cores) of a whole tier-1 run on six xdist workers while a
#: second such run shares the cores.
LIMIT_S = 120.0

#: Seconds :func:`wait_until` waits by default: a third of the limit, so a
#: condition that never comes fails at the test's own assertion, with time
#: left for its teardown.
WAIT_S = LIMIT_S / 3


def wait_until(cond, timeout=WAIT_S):
    """Poll ``cond`` until it holds; its last value once ``timeout``
    seconds have passed."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return cond()


# the session's stderr, duplicated before any test captures fd 2, so the
# stacks reach the log even when the backstop ends the process
_stderr = pytest.StashKey[int]()


@contextlib.contextmanager
def time_limit(seconds, what, file):
    """Run the body under a limit of ``seconds``; ``what`` names it in the
    failure, ``file`` (a file object or descriptor) takes the stacks.  Main
    thread only, and not nested: a process has one ``ITIMER_REAL`` and one
    faulthandler watchdog."""

    def expire(signum, frame):
        pytest.fail(f"{what} ran over its {seconds:g} s time limit")

    previous = signal.signal(signal.SIGALRM, expire)
    faulthandler.register(signal.SIGALRM, file=file, all_threads=True,
                          chain=True)
    faulthandler.dump_traceback_later(2 * seconds, exit=True, file=file)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()
        faulthandler.unregister(signal.SIGALRM)
        signal.signal(signal.SIGALRM, previous)


def _limited(item, phase):
    marker = item.get_closest_marker("time_limit")
    seconds = float(marker.args[0]) if marker else LIMIT_S
    return time_limit(seconds, f"{item.nodeid} ({phase})",
                      item.config.stash[_stderr])


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "time_limit(seconds): this test's phases each get "
        "`seconds` instead of time_limit.LIMIT_S")
    config.stash[_stderr] = os.dup(2)


def pytest_unconfigure(config):
    os.close(config.stash[_stderr])


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    with _limited(item, "setup"):
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    with _limited(item, "call"):
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_teardown(item, nextitem):
    with _limited(item, "teardown"):
        return (yield)
