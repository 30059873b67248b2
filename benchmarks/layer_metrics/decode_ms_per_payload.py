"""Dispatcher intake: mean host milliseconds in the native NDJSON decode
per payload, window delta of ``pipeline.stage_decode_s``."""


def read(run):
    seconds, count = run.timer("pipeline.stage_decode_s")
    return seconds / count * 1e3 if count else None
