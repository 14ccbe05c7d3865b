"""Per-layer metric `setup_compile_s`: see `harness.readers.setup_compile_s`.

Read in every cell that reports `setup_s`."""

from harness.readers import setup_compile_s as read  # noqa: F401

NAME = "setup_compile_s"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "compile cache"
MOVES = "setup_s"
