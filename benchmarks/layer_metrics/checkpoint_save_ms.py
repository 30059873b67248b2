"""Durability and background work: mean milliseconds of a periodic
checkpoint, start to manifest swap - window delta of
``checkpoint.save_s``.  None when no checkpoint ended inside the window
(``checkpoints_in_window`` 0)."""


def read(run):
    seconds, count = run.timer("checkpoint.save_s")
    return seconds / count * 1e3 if count else None
