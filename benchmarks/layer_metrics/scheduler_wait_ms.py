"""Per-layer metric `scheduler_wait_ms`: see `harness.phases.scheduler_wait_ms`.

Read in every cell that reports `search_p50_ms`."""

from harness.phases import scheduler_wait_ms as read  # noqa: F401

NAME = "scheduler_wait_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "dispatch scheduler and shard searcher"
MOVES = "search_p50_ms"
