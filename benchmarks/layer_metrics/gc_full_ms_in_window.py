"""Process: milliseconds of full (generation 2) garbage collections in
the window - window total of ``runtime.gc_full_s`` (one span a
collection, on the thread that triggered it) in ms; 0 where none ran.
None where the program keeps no such timer."""


def read(run):
    if "runtime.gc_full_s" not in run.marks1:
        return None
    seconds, _ = run.timer("runtime.gc_full_s")
    return seconds * 1e3
