"""``device_step_ms`` in the open-loop wire cells, where it bears on
latency and not on events/s (the rate is fixed): a payload waits for one
whole step of the plan that holds it."""

from benchmarks import cells

read = cells.reader("layer_metrics", "device_step_ms")
