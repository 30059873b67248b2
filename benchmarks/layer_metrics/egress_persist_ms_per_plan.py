"""D2H and egress: mean milliseconds of the egress worker's store
append a plan - window delta of ``pipeline.egress_persist_s`` (the
``egress.persist`` leg: ``append_columns``, its inline seal included)
over the plans egressed (observations of ``pipeline.stage_egress_s``).
A child of ``egress_host_ms_per_plan``.  None where the program keeps
no such timer, or no plan egressed in the window."""


def read(run):
    if "pipeline.egress_persist_s" not in run.marks1:
        return None
    seconds, _ = run.timer("pipeline.egress_persist_s")
    _, plans = run.timer("pipeline.stage_egress_s")
    return seconds / plans * 1e3 if plans else None
