"""``host_syncs_per_step`` in the open-loop wire cells, where it bears on
latency and not on events/s (the rate is fixed): each blocking fetch
stands between a step's end and the delivery of its rows."""

from benchmarks import cells

read = cells.reader("layer_metrics", "host_syncs_per_step")
