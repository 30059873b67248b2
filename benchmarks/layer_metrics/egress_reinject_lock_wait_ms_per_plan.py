"""D2H and egress: mean milliseconds a plan's egress waited for the
intake lock to re-inject its derived alerts - window total of
``pipeline.lock_wait_reinject_s`` (``_take``'s acquire alone, on the
egress worker) over the plans egressed.  A child of
``egress_reinject_ms_per_plan``.  None where the program keeps no such
timer, or no plan egressed."""


def read(run):
    if "pipeline.lock_wait_reinject_s" not in run.marks1:
        return None
    seconds, _ = run.timer("pipeline.lock_wait_reinject_s")
    _, plans = run.timer("pipeline.stage_egress_s")
    return seconds / plans * 1e3 if plans else None
