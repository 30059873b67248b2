"""D2H and egress: mean milliseconds a plan's store append spent
sealing a segment on the egress worker's own thread, the store's
backpressure valve (``SegmentStore.append_columns`` → ``pump_one``
when the seal queue runs more than ``4 + workers`` jobs behind) -
window total of ``store.inline_seal_s`` over the plans egressed; 0
where the valve never opened.  A child of
``egress_persist_ms_per_plan``.  None where the program keeps no such
timer, or no plan egressed in the window."""


def read(run):
    if "store.inline_seal_s" not in run.marks1:
        return None
    seconds, _ = run.timer("store.inline_seal_s")
    _, plans = run.timer("pipeline.stage_egress_s")
    return seconds / plans * 1e3 if plans else None
