"""D2H and egress: the egress stage's SELF time per plan - the host's own
fan-out (store append, outbound submit, alert re-injection, accounting):
window delta of ``pipeline.stage_egress_s`` minus its child
``pipeline.device_wait_s``, over the plans egressed.  What stays of
``egress_wait_ms_per_plan`` when the device answers at once."""


def read(run):
    if "pipeline.device_wait_s" not in run.marks1:
        return None
    egress, plans = run.timer("pipeline.stage_egress_s")
    wait, _ = run.timer("pipeline.device_wait_s")
    return (egress - wait) / plans * 1e3 if plans else None
