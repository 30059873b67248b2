"""Batcher to ring: milliseconds over the WINDOW that the dispatch path
was blocked before it held the step lock - a full egress window
(``_stall_for_egress_room``) plus the wait for ``_step_lock`` - window
delta of ``pipeline.stage_dispatch_wait_s``.  A total, not a mean: one
4 s block among 25 plans must show."""


def read(run):
    if "pipeline.stage_dispatch_wait_s" not in run.marks1:
        return None
    seconds, _ = run.timer("pipeline.stage_dispatch_wait_s")
    return seconds * 1e3
