"""Batcher to ring: share of the window's steps that ran a program
narrower than the configured width (``pipeline.steps_narrow`` over
``steps``), in percent: partial plans on a narrow rung of the batcher's
width ladder.  Read in the open-loop wire cells, where every plan is a
partial and the step it pays stands inside each event's latency.  None
where the program keeps no such counter."""

NARROW = "pipeline.steps_narrow"


def read(run):
    steps = run.dispatcher("steps")
    if not steps or NARROW not in run.marks1:
        return None
    return 100.0 * run.counter(NARROW) / steps
