"""Durability and background work: mean milliseconds of the checkpoint's
``state`` phase - reading ``device_state.current``, the device-to-host
copy of every state field and the write - window delta of
``checkpoint.phase_state_s``.  None when no checkpoint ended inside the
window."""


def read(run):
    seconds, count = run.timer("checkpoint.phase_state_s")
    return seconds / count * 1e3 if count else None
