"""Per-layer metric `device_wait_ms`: see `harness.phases.device_wait_ms`.

Read in every cell that reports `search_p50_ms`."""

from harness.phases import device_wait_ms as read  # noqa: F401

NAME = "device_wait_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "executor"
MOVES = "search_p50_ms"
