"""``egress_wait_ms_per_plan`` in the open-loop wire cells, where it bears
on latency and not on events/s (the rate is fixed): a row reaches the
client only when its plan's egress is done."""

from benchmarks import cells

read = cells.reader("layer_metrics", "egress_wait_ms_per_plan")
