"""Per-layer metric `fused_admission_pct`: see `harness.readers.fused_admission_pct`.

Read in every cell that reports `search_p50_ms`."""

from harness.readers import fused_admission_pct as read  # noqa: F401

NAME = "fused_admission_pct"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
LAYER = "executor"
MOVES = "search_p50_ms"
