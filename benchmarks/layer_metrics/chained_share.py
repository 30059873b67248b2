"""Batcher to ring: share of the window's steps that ran inside a K-step
chain (``ring_chains`` x K over ``steps``), in percent."""


def read(run):
    steps = run.dispatcher("steps")
    if not steps:
        return None
    return 100.0 * run.dispatcher("ring_chains") * run.ring_depth / steps
