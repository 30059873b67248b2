"""The process layer: what the whole process did, seen from inside it.

- :func:`name_os_thread` gives a long-lived thread a kernel name, so a
  profiler capture's host lines (and ``top -H``) name the thread where
  they would read the interpreter's name.
- :class:`StallWitness` times the dispatcher loop's own timed waits.
  The loop wakes every few milliseconds whatever the traffic, so a wake
  that comes :data:`STALL_S` or more late says the process stood still,
  and the CPU time, the kernel's counters and the full collections
  across that wait say how.
- :class:`FullCollections` makes each full (generation 2) garbage
  collection a profiler span of its own, on the thread that triggered
  it, and hands its duration to ``runtime.gc_full_s`` through the
  witness's next wake: a collection runs wherever the interpreter
  allocates, also under a timer's lock, so the callback takes none.

Every span here opens through :meth:`Timer.time` or :meth:`Timer.mark`,
so it lands in the registry and, under a profiler session, on the
profiler's clock.
"""

from __future__ import annotations

import collections
import ctypes
import functools
import gc
import sys
import time
import types
from typing import Callable, Optional

try:
    import resource
except ImportError:   # not a Unix: no kernel counters to read
    resource = None

#: A loop wake this many seconds or more past its timeout is a stall.
#: The loop's own timeout is at most a few milliseconds, so 0.1 s is
#: far outside scheduling noise and well inside the 1–4 s standstills a
#: window can lose sends to.  On a TPU v5e host a wake 100–115 ms late
#: recurs a few times a minute with a third of a core busy, so a healthy
#: run holds stalls of this size: they are timed and kept, not dumped.
STALL_S = 0.1

#: A stall this many seconds or more long is one that can cost sends:
#: the dispatcher also dumps the flight recorder for it (``stall``).
DUMP_STALL_S = 1.0

# the kernel counters a stall record carries, as getrusage names them
RUSAGE_FIELDS = (
    ("major_faults", "ru_majflt"),
    ("minor_faults", "ru_minflt"),
    ("voluntary_switches", "ru_nvcsw"),
    ("involuntary_switches", "ru_nivcsw"),
    ("block_in", "ru_inblock"),
    ("block_out", "ru_oublock"),
)


_NO_RUSAGE = types.SimpleNamespace(**{attr: 0 for _, attr in RUSAGE_FIELDS})


def read_rusage():
    """This process's kernel counters (all threads) as ``getrusage``
    gives them (``ru_*`` attributes); zeros where there is none.  Read
    raw: the deltas are taken only after a stall."""
    if resource is None:
        return _NO_RUSAGE
    return resource.getrusage(resource.RUSAGE_SELF)


class FullCollections:
    """A ``gc.callbacks`` entry that times every full collection: a
    ``runtime.gc_full_s`` profiler span entered at generation 2's
    ``start`` and exited at its ``stop``, on the thread that triggered
    it.  The younger generations return at once.

    The callback observes nothing into the timer: a collection can run
    on a thread that holds the timer's lock (any allocation under it),
    so the duration goes to :attr:`done` (a deque append, atomic) and
    :meth:`drain` observes it later, from a thread at a point of its
    own.  :attr:`total` sums every collection's seconds as it ends (the
    collections run one at a time).  :meth:`install` and :meth:`remove`
    add and take away the one entry."""

    def __init__(self, metrics):
        self._timer = metrics.timer("runtime.gc_full_s")
        self._span = None
        self.done: collections.deque = collections.deque()
        self.total = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            span = self._timer.time()
            span.discard()   # observed by drain(), outside the collection
            self._span = span.__enter__()
        elif self._span is not None:
            span, self._span = self._span, None
            span.__exit__(None, None, None)
            self.total += span.elapsed
            self.done.append(span.elapsed)

    def drain(self) -> None:
        """Observe the collections that ended since the last drain."""
        while True:
            try:
                seconds = self.done.popleft()
            except IndexError:
                return
            self._timer.observe(seconds)

    def install(self) -> None:
        if self not in gc.callbacks:
            # the span's first construction imports the profiler's
            # annotation: do it here, not inside a collection
            self._timer.time().discard()
            gc.callbacks.append(self)

    def remove(self) -> None:
        try:
            gc.callbacks.remove(self)
        except ValueError:
            pass
        self.drain()


class StallWitness:
    """Times one thread's timed waits and records the late ones.

    :meth:`wait` is ``event.wait(timeout)`` between two reads of the
    wall clock.  The baseline of the costlier counters (the process's
    CPU clock, all threads, :func:`read_rusage` and the full
    collections' seconds) is taken before a wait only where the last
    one is :data:`STALL_S` / 2 or more old, so a stall's deltas cover
    the wait and at most that much before it.  A wake :data:`STALL_S`
    or more past its timeout

    - marks ``runtime.stall_s`` with the lateness (a profiler event
      closed at the wake, tagged ``late_ms``);
    - observes the process's CPU across the stall into
      ``runtime.stall_cpu_s``: the lateness times the cores busy over
      the deltas' span (near 0: every thread was off the CPU; about 1:
      one thread computed, holding the interpreter);
    - keeps a record in :attr:`recent` and hands it to ``report`` (the
      dispatcher's flight recorder): the wake's wall time, the lateness,
      the wait, the deltas' span and the cores busy over it, the kernel
      counters' deltas, the full-collection seconds inside the span
      (:attr:`full_collections`), and ``save_running`` where
      ``save_probe`` is given (called with the span's length).

    Every wake also drains :attr:`full_collections` into
    ``runtime.gc_full_s``, so its callback never takes the timer's lock.
    The clocks are injectable so a test can drive a stall without one.
    """

    def __init__(self, metrics, report: Optional[Callable] = None,
                 save_probe: Optional[Callable[[float], bool]] = None,
                 clock: Callable[[], float] = time.perf_counter,
                 cpu_clock: Callable[[], float] = time.process_time,
                 rusage: Callable[[], object] = read_rusage):
        self._m_stall = metrics.timer("runtime.stall_s")
        self._m_cpu = metrics.timer("runtime.stall_cpu_s")
        self.full_collections = FullCollections(metrics)
        self.report = report
        self.save_probe = save_probe
        self._clock = clock
        self._cpu_clock = cpu_clock
        self._rusage = rusage
        # the baseline: when it was taken, and the CPU clock, the kernel
        # counters and the full collections' seconds then
        self._base = (float("-inf"), 0.0, None, 0.0)
        #: the newest stall records, oldest first
        self.recent: collections.deque = collections.deque(maxlen=32)

    def wait(self, event, timeout: float) -> bool:
        """``event.wait(timeout)``, witnessed; returns what it returns."""
        t0 = self._clock()
        if t0 - self._base[0] >= STALL_S / 2:
            self._base = (t0, self._cpu_clock(), self._rusage(),
                          self.full_collections.total)
        stopped = event.wait(timeout)
        t1 = self._clock()
        if self.full_collections.done:
            self.full_collections.drain()
        if t1 - t0 - timeout >= STALL_S:
            self._stalled(t1 - t0, t1 - t0 - timeout, t1)
        return stopped

    def _stalled(self, elapsed: float, late: float, t1: float) -> None:
        b_t, b_cpu, b_r, b_gc = self._base
        span = t1 - b_t
        cores = (self._cpu_clock() - b_cpu) / span
        self._m_stall.mark(late, late_ms=round(late * 1e3, 3))
        self._m_cpu.observe(cores * late)
        record = {"at": round(time.time(), 3),
                  "late_ms": round(late * 1e3, 3),
                  "wait_ms": round(elapsed * 1e3, 3),
                  "span_ms": round(span * 1e3, 3),
                  "cpu_cores": round(cores, 4),
                  "gc_full_ms": round(
                      (self.full_collections.total - b_gc) * 1e3, 3)}
        r1 = self._rusage()
        record.update((name, getattr(r1, attr) - getattr(b_r, attr))
                      for name, attr in RUSAGE_FIELDS)
        if self.save_probe is not None:
            record["save_running"] = bool(self.save_probe(span))
        self.recent.append(record)
        if self.report is not None:
            self.report(record)


_PR_SET_NAME = 15
_NAME_MAX = 15   # the kernel's TASK_COMM_LEN less its NUL


@functools.cache
def _prctl():
    """libc's ``prctl`` with its argument types declared, or None."""
    try:
        fn = ctypes.CDLL(None).prctl
    except (OSError, AttributeError):
        return None
    fn.argtypes = (ctypes.c_int, ctypes.c_char_p, ctypes.c_ulong,
                   ctypes.c_ulong, ctypes.c_ulong)
    fn.restype = ctypes.c_int
    return fn


def name_os_thread(name: str) -> None:
    """Name the calling thread ``name`` (cut to 15 bytes) in the kernel
    (``prctl(PR_SET_NAME)``).  CPython does not pass a
    ``threading.Thread`` name on; Linux only, a no-op elsewhere or where
    libc's ``prctl`` cannot be found."""
    if not sys.platform.startswith("linux"):
        return
    prctl = _prctl()
    if prctl is not None:
        prctl(_PR_SET_NAME, name.encode()[:_NAME_MAX], 0, 0, 0)
