"""Process: milliseconds the process stood still in the window, as the
dispatcher loop's own waits witness it - window total of
``runtime.stall_s`` (how late each wake came that came
``runtime/process.py`` ``STALL_S`` or more past its timeout) in ms; 0
where no wake was that late.  None where the program keeps no such
timer."""


def read(run):
    if "runtime.stall_s" not in run.marks1:
        return None
    seconds, _ = run.timer("runtime.stall_s")
    return seconds * 1e3
