"""Per-layer metric `rest_respond_ms`: see `harness.phases.rest_respond_ms`.

Read in every cell that reports `search_p50_ms`."""

from harness.phases import rest_respond_ms as read  # noqa: F401

NAME = "rest_respond_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "REST front end and node"
MOVES = "search_p50_ms"
