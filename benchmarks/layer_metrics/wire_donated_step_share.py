"""Device step: share of the window's steps that ran with the state
carry donated (``pipeline.steps_donated`` over ``steps``), in percent:
single steps handed the state manager's epoch under its lock, which
scatter into the carry in place instead of into a copy of it.  Read in
the open-loop wire cells, where every plan is a single step inside each
event's latency.  None where the program keeps no such counter."""

DONATED = "pipeline.steps_donated"


def read(run):
    steps = run.dispatcher("steps")
    if not steps or DONATED not in run.marks1:
        return None
    return 100.0 * run.counter(DONATED) / steps
