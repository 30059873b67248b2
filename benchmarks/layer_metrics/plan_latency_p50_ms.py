"""Dispatcher, inside view: the median of its own per-plan samples (plan
creation plus its longest batching wait to egress done) over the plans
egressed inside the window - the newest of its ``latencies_s`` at the
window's end, as many as ``_egress`` ran.  The gap to the client's
median is intake queueing and fan-out."""

import numpy as np


def read(run):
    _, plans = run.timer("pipeline.stage_egress_s")
    samples = run.marks1["_plan_latencies_s"][-plans:] if plans else []
    return float(np.median(samples) * 1e3) if samples else None
