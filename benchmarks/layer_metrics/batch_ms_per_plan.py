"""Dispatcher intake: mean host milliseconds assembling a batch per
emitted plan, window delta of ``pipeline.stage_batch_s``."""


def read(run):
    seconds, count = run.timer("pipeline.stage_batch_s")
    return seconds / count * 1e3 if count else None
