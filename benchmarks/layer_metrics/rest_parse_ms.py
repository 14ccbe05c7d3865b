"""Per-layer metric `rest_parse_ms`: see `harness.phases.rest_parse_ms`.

Read in every cell that reports `search_p50_ms`."""

from harness.phases import rest_parse_ms as read  # noqa: F401

NAME = "rest_parse_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "REST front end and node"
MOVES = "search_p50_ms"
