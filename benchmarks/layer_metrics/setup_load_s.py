"""Per-layer metric `setup_load_s`: see `harness.readers.setup_load_s`.

Read in every cell that reports `setup_s`."""

from harness.readers import setup_load_s as read  # noqa: F401

NAME = "setup_load_s"
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"
LAYER = "write path and store"
MOVES = "setup_s"
