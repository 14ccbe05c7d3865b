"""Per-layer metric `column_scan_roofline`: see `harness.readers.column_scan_roofline`.

Read in every cell that reports `search_p50_ms`."""

from harness.readers import column_scan_roofline as read  # noqa: F401

NAME = "column_scan_roofline"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "scoring programs and aggs"
MOVES = "search_p50_ms"
