"""Per-layer metric `device_launches_per_search`: see `harness.phases.device_launches_per_search`.

Read in every cell that reports `search_p50_ms`."""

from harness.phases import device_launches_per_search as read  # noqa: F401

NAME = "device_launches_per_search"
UNIT = "launches"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "executor"
MOVES = "search_p50_ms"
