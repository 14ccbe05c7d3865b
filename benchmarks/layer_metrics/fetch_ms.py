"""Per-layer metric `fetch_ms`: see `harness.phases.fetch_ms`.

Read in every cell that reports `search_p50_ms`."""

from harness.phases import fetch_ms as read  # noqa: F401

NAME = "fetch_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "dispatch scheduler and shard searcher"
MOVES = "search_p50_ms"
