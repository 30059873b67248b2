"""``step_roofline`` in the open-loop wire cells, where it bears on latency
and not on events/s (the rate is fixed): how much of that step the
chip's peaks would let a kernel take away."""

from benchmarks import cells

read = cells.reader("layer_metrics", "step_roofline")
