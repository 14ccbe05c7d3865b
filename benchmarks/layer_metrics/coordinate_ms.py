"""Per-layer metric `coordinate_ms`: see `harness.phases.coordinate_ms`.

Read in every cell that reports `search_p50_ms`."""

from harness.phases import coordinate_ms as read  # noqa: F401

NAME = "coordinate_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "REST front end and node"
MOVES = "search_p50_ms"
