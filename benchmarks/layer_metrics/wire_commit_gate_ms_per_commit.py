"""Durability and background work: mean milliseconds of one journal
offset commit at the commit gate (``_maybe_commit_offset``: the event
store's flush, then the journal commit, with ``_step_lock`` and the
intake lock ``_lock`` held) - window delta of
``pipeline.commit_gate_s`` over its observations, one a commit.  None
where the program keeps no such timer, or nothing was committed."""


def read(run):
    seconds, count = run.timer("pipeline.commit_gate_s")
    return seconds / count * 1e3 if count else None
