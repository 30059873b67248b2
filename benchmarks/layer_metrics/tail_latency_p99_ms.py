"""The client's 99th percentile of (delivery - due) over the window's
events; a failed event takes the end of the final drain.  Per layer and
not end to end, because no bound the contract allows holds it (PERF.md
section 2): it sits on the edge of the window's one background stall,
and where sends are shed it is the censored time of failed events."""


def read(run):
    return run.latency_percentile_ms(99)
