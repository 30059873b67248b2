"""``plan_fill_share`` in the open-loop wire cells, where it bears on
latency and not on events/s (the rate is fixed): a partial plan pays a
whole step, so a payload's latency is one step whatever it holds."""

from benchmarks import cells

read = cells.reader("layer_metrics", "plan_fill_share")
