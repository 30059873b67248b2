"""``device_wait_ms_per_plan`` in the open-loop wire cells, where it bears
on latency and not on events/s (the rate is fixed): a row reaches the
client only after its plan's step is done and fetched."""

from benchmarks import cells

read = cells.reader("layer_metrics", "device_wait_ms_per_plan")
