"""Median over the window's source events of (delivery to the connector -
the time the send was due); a failed event takes the end of the final
drain.  Host clock, the client's side."""


def read(run):
    return run.latency_percentile_ms(50)
