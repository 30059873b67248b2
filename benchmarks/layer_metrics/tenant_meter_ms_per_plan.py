"""Tenant metering: mean host milliseconds the egress worker spends
folding one plan's per-tenant block into the usage ledger
(``dispatcher._meter_plan``), window delta of the timer
``pipeline.stage_meter_s`` over the plans metered.  None where the
program keeps no such timer, or metering is off."""


def read(run):
    seconds, count = run.timer("pipeline.stage_meter_s")
    return seconds / count * 1e3 if count else None
