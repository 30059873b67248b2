"""Dispatcher intake: mean milliseconds a live wire payload waited for
the intake lock (``_lock`` at ``_take``, the acquire alone) - window
delta of ``pipeline.lock_wait_wire_s`` over its observations, one a
payload.  A mean: about one payload in ten waits tens of ms while the
others wait none.  None where the program keeps no such timer, or no
payload came in."""


def read(run):
    seconds, count = run.timer("pipeline.lock_wait_wire_s")
    return seconds / count * 1e3 if count else None
