"""Device programs. Every one that reads a pack's block-max summary
takes it as one argument, so the summary is a pytree from the first
import of anything in here: its arrays are leaves, its grid is static."""

import jax

from ..index.segment import TileSummary

jax.tree_util.register_dataclass(
    TileSummary, data_fields=["start", "tiles", "vals", "cols"],
    meta_fields=["grid"])
