"""Per-layer metric `search_tail_p95_ms`: see `harness.readers.search_tail_p95_ms`.

Read in every cell that reports `search_p50_ms`."""

from harness.readers import search_tail_p95_ms as read  # noqa: F401

NAME = "search_tail_p95_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
LAYER = "REST front end and node"
MOVES = "search_p50_ms"
