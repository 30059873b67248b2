"""H2D staging: mean host milliseconds placing a packed plan on the
mesh (two arrays x ``n_shards`` ``device_put``s), window delta of
``pipeline.stage_place_s``.  A program on one chip, or one without the
timer, observes nothing."""


def read(run):
    seconds, count = run.timer("pipeline.stage_place_s")
    return seconds / count * 1e3 if count else None
