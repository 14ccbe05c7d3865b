"""Per-layer metric `bind_ms`: see `harness.phases.bind_ms`.

Read in every cell that reports `search_p50_ms`."""

from harness.phases import bind_ms as read  # noqa: F401

NAME = "bind_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "executor"
MOVES = "search_p50_ms"
