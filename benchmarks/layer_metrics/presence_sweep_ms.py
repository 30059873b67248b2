"""Presence sweep: mean host milliseconds of one served sweep, whole
(``DeviceStateManager.apply_presence_sweep``: lock wait, the sweep
program, the mask's fetch, building the report) - window delta of the
timer ``presence.sweep_s`` over its observations.  None where the
program keeps no such timer, or no sweep ended in the window."""


def read(run):
    seconds, count = run.timer("presence.sweep_s")
    return seconds / count * 1e3 if count else None
