"""Per-layer metric `front_end_ms`: see `harness.readers.front_end_ms`.

Read in every cell that reports `search_p50_ms`."""

from harness.readers import front_end_ms as read  # noqa: F401

NAME = "front_end_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
LAYER = "REST front end and node"
MOVES = "search_p50_ms"
