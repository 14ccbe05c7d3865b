"""Per-layer metric `fused_walk_roofline`: see `harness.walk_readers.fused_walk_roofline`.

Read in the cells of its `workloads` list in `BENCHMARK.json`: those
whose searches reach the fused engines."""

from harness.walk_readers import fused_walk_roofline as read  # noqa: F401

NAME = "fused_walk_roofline"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "scoring programs and aggs"
MOVES = "search_p50_ms"
