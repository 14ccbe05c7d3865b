"""Per-layer metric `pool_wait_ms`: see `harness.phases.pool_wait_ms`.

Read in every cell that reports `search_p50_ms`."""

from harness.phases import pool_wait_ms as read  # noqa: F401

NAME = "pool_wait_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "REST front end and node"
MOVES = "search_p50_ms"
