"""Load generator: 99th percentile of (actual send - due) over the
window's sends, the benchmark's own clock.  A starved generator voids
the run's latency reading."""

import numpy as np


def read(run):
    log = run.sends
    made = log.measured & (log.status > 0)
    if not made.any():
        return None
    return float(np.percentile(log.sent[made] - log.due[made], 99) * 1e3)
