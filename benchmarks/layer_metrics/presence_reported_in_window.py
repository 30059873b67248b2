"""Presence sweep: rows the window's sweeps handed on for re-injection,
one STATE_CHANGE a device gone silent (growth of the counter
``presence.reported``): the cohorts that crossed ``missing_after_s``
inside the window.  None where the program keeps no such counter."""

REPORTED = "presence.reported"


def read(run):
    if REPORTED not in run.marks1:
        return None
    return run.counter(REPORTED)
