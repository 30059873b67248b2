"""D2H and egress: mean milliseconds of the egress worker's outbound
submit a plan - window delta of ``pipeline.egress_outbound_s`` (the
``egress.outbound`` leg: ``OutboundConnectorsManager.submit``) over
the plans egressed.  A child of ``egress_host_ms_per_plan``.  None
where the program keeps no such timer, or no plan egressed."""


def read(run):
    if "pipeline.egress_outbound_s" not in run.marks1:
        return None
    seconds, _ = run.timer("pipeline.egress_outbound_s")
    _, plans = run.timer("pipeline.stage_egress_s")
    return seconds / plans * 1e3 if plans else None
