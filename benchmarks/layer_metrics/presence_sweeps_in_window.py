"""Presence sweep: sweeps that ended inside the window (growth of the
counter ``presence.sweeps``).  The cell's scan interval puts seven or
eight in a 40 s window; another count and the cell is not what its
``why`` says.  None where the program keeps no such counter."""

SWEEPS = "presence.sweeps"


def read(run):
    if SWEEPS not in run.marks1:
        return None
    return run.counter(SWEEPS)
