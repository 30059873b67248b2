"""Durability and background work: seconds from the end of the last
periodic checkpoint to the start of the traced slice.  Where this is
under ten or so, or missing (no checkpoint ended before the slice), the
slice shows the checkpoint's stall or the overload ladder's way back,
not the steady state, and ``device_idle_share`` and the breakdown read
accordingly.  It moves with ``setup_s``: the checkpointer's 30 s count
from the instance's start."""


def read(run):
    before = [t for t in run.checkpoints
              if run.trace_from is not None and t <= run.trace_from]
    return run.trace_from - before[-1] if before else None
