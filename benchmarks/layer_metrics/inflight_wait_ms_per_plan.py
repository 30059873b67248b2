"""Batcher to ring: mean milliseconds a dispatched plan sat in the
in-flight window before the egress worker (or an inline drain) popped
it - window delta of ``pipeline.stage_inflight_wait_s``.  Of the order
of in-flight depth x step when the device is the bottleneck; the egress
worker's polling interval when it is idle."""


def read(run):
    seconds, count = run.timer("pipeline.stage_inflight_wait_s")
    return seconds / count * 1e3 if count else None
