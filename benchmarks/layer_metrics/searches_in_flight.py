"""Per-layer metric `searches_in_flight`: see `harness.phases.searches_in_flight`.

Read in every cell that reports `search_p50_ms`."""

from harness.phases import searches_in_flight as read  # noqa: F401

NAME = "searches_in_flight"
UNIT = "searches"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "REST front end and node"
MOVES = "search_p50_ms"
