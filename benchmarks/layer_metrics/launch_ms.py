"""Per-layer metric `launch_ms`: see `harness.phases.launch_ms`.

Read in every cell that reports `search_p50_ms`."""

from harness.phases import launch_ms as read  # noqa: F401

NAME = "launch_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "executor"
MOVES = "search_p50_ms"
