"""Per-layer metric `column_scan_roofline`: see `harness.readers.column_scan_roofline`.

Read in the cells of its `workloads` list in `BENCHMARK.json`: its bytes function counts
columnar passes and says nothing of postings."""

from harness.readers import column_scan_roofline as read  # noqa: F401

NAME = "column_scan_roofline"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "scoring programs and aggs"
MOVES = "search_p50_ms"
