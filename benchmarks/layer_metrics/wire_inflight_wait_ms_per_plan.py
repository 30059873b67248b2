"""``inflight_wait_ms_per_plan`` in the open-loop wire cells, where it
bears on latency and not on events/s (the rate is fixed)."""

from benchmarks import cells

read = cells.reader("layer_metrics", "inflight_wait_ms_per_plan")
