"""``batch_ms_per_plan`` in the open-loop wire cells, where it bears on
latency and not on events/s (the rate is fixed): a payload's rows wait
while their plan is assembled."""

from benchmarks import cells

read = cells.reader("layer_metrics", "batch_ms_per_plan")
