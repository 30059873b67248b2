"""H2D staging: bytes staged to the device per processed row, window
delta of ``pipeline.bytes_copied.h2d``."""


def read(run):
    rows = run.dispatcher("processed")
    if not rows:
        return None
    return run.counter("pipeline.bytes_copied.h2d") / rows
