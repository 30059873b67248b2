"""D2H and egress: the egress stage's milliseconds a plan that no named
leg holds - window delta of ``pipeline.stage_egress_s`` less its
children ``pipeline.device_wait_s``, ``pipeline.egress_persist_s``,
``pipeline.egress_outbound_s``, ``pipeline.egress_reinject_s`` and
``pipeline.stage_meter_s``, over the plans egressed: the fetch's
bookkeeping, the counters and the release.  None where the program
keeps no leg timers, or no plan egressed."""

LEGS = ("pipeline.device_wait_s", "pipeline.egress_persist_s",
        "pipeline.egress_outbound_s", "pipeline.egress_reinject_s",
        "pipeline.stage_meter_s")


def read(run):
    if "pipeline.egress_persist_s" not in run.marks1:
        return None
    egress, plans = run.timer("pipeline.stage_egress_s")
    if not plans:
        return None
    legs = sum(run.timer(name)[0] for name in LEGS)
    return (egress - legs) / plans * 1e3
