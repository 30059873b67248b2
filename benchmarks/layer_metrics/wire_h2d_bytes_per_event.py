"""``h2d_bytes_per_event`` in the open-loop wire cells, where it bears on
latency and not on events/s (the rate is fixed): a partial plan stages a
whole-width buffer before its step can start."""

from benchmarks import cells

read = cells.reader("layer_metrics", "h2d_bytes_per_event")
