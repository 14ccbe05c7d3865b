"""Per-layer metric `tile_prune_pct`: see `harness.walk_readers.tile_prune_pct`.

Read in the cells of its `workloads` list in `BENCHMARK.json`: those
whose searches reach the fused engines."""

from harness.walk_readers import tile_prune_pct as read  # noqa: F401

NAME = "tile_prune_pct"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
LAYER = "scoring programs and aggs"
MOVES = "search_p50_ms"
