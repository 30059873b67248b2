"""D2H and egress: mean milliseconds a plan's egress spent BLOCKED on the
device finishing its step and on D2H - window delta of
``pipeline.device_wait_s`` (the span around the views' one blocking
fetch) over the plans egressed (observations of
``pipeline.stage_egress_s``).  The child of ``egress_wait_ms_per_plan``:
that is this plus ``egress_host_ms_per_plan``.  A ring's shared fetch
counts once and is spread over its plans."""


def read(run):
    if "pipeline.device_wait_s" not in run.marks1:
        return None
    seconds, _ = run.timer("pipeline.device_wait_s")
    _, plans = run.timer("pipeline.stage_egress_s")
    return seconds / plans * 1e3 if plans else None
