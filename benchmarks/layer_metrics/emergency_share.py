"""Protection: share of the 100 ms samples of the overload controller's
state inside the window that read EMERGENCY, in percent: which overload
regime the run was in, where ``not_normal_share`` reads 100 either way."""

EMERGENCY = 3     # runtime/overload.py OverloadState.EMERGENCY


def read(run):
    states = run.overload_states
    return 100.0 * float((states >= EMERGENCY).mean()) if len(states) else None
