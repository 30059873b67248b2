"""Protection: share of the 100 ms samples of the overload controller's
state inside the window that were not NORMAL, in percent."""


def read(run):
    states = run.overload_states
    return 100.0 * float((states > 0).mean()) if len(states) else None
