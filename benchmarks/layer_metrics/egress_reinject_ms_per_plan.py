"""D2H and egress: mean milliseconds a plan's egress spent re-injecting
its derived alerts - window total of ``pipeline.egress_reinject_s``
(the ``egress.derived-alerts`` leg: the nested take under the intake
lock, then the alerts' staging and dispatch, all on the egress worker)
over the plans egressed, whether or not a plan fired.  A child of
``egress_host_ms_per_plan``.  None where the program keeps no such
timer, or no plan egressed."""


def read(run):
    if "pipeline.egress_reinject_s" not in run.marks1:
        return None
    seconds, _ = run.timer("pipeline.egress_reinject_s")
    _, plans = run.timer("pipeline.stage_egress_s")
    return seconds / plans * 1e3 if plans else None
