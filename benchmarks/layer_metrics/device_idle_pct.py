"""Per-layer metric `device_idle_pct`: see `harness.readers.device_idle_pct`.

Read in every cell that reports `search_p50_ms`."""

from harness.readers import device_idle_pct as read  # noqa: F401

NAME = "device_idle_pct"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "device"
MOVES = "search_p50_ms"
