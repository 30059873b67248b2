"""D2H + egress: mean host milliseconds in ``_egress`` per plan, window
delta of ``pipeline.stage_egress_s``.  A view from outside: it includes
the wait for the device to finish the plan's step."""


def read(run):
    seconds, count = run.timer("pipeline.stage_egress_s")
    return seconds / count * 1e3 if count else None
