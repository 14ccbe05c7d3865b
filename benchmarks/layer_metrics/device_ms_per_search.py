"""Per-layer metric `device_ms_per_search`: see `harness.readers.device_ms_per_search`.

Read in every cell that reports `search_p50_ms`."""

from harness.readers import device_ms_per_search as read  # noqa: F401

NAME = "device_ms_per_search"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "scoring programs and aggs"
MOVES = "search_p50_ms"
