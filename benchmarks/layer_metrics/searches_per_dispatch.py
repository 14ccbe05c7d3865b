"""Per-layer metric `searches_per_dispatch`: see `harness.readers.searches_per_dispatch`.

Read in every cell that reports `search_p50_ms`."""

from harness.readers import searches_per_dispatch as read  # noqa: F401

NAME = "searches_per_dispatch"
UNIT = "searches"
BETTER = "higher"
SOURCE = "program_counter"
LAYER = "dispatch scheduler and shard searcher"
MOVES = "search_p50_ms"
