"""Per-layer metric `span_coverage_pct`: see `harness.phases.span_coverage_pct`.

Read in every cell that reports `search_p50_ms`."""

from harness.phases import span_coverage_pct as read  # noqa: F401

NAME = "span_coverage_pct"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_span"
LAYER = "REST front end and node"
MOVES = "search_p50_ms"
