"""Operations and bytes of the fused tile walk (BM25 scoring with top-k
and block-max pruning in one pass over the forward index), from shapes
alone. `workbytes.py` counts columnar passes; this counts postings.

The forward index holds, for every document, `slots` pairs of a term id
and an impact (4 bytes each; the width is the power of two at or above
the most distinct words any document has, 8 at the least, 256 at the
most). The walk visits the documents in tiles of 1,024 (the whole shard
where it is smaller). A tile whose bound says that no query of the batch
can match there is skipped unread; every other tile is read whole, once
for the batch, whichever of the XLA and the Pallas walk runs. The bounds
read, for each query term, one window of `n_tiles` entries of the
block-max summary: a tile number and an impact, 4 bytes each.

The walk is bounded by HBM bandwidth: each pair read is compared with
each query term and added where equal, on the VPU, for which no peak is
published; `walk_ops` counts those for the record.
"""

from __future__ import annotations

import math

SCORE_TILE = 1024       # documents a tile
PAIR_BYTES = 4 + 4      # a term id and an impact; a tile number and a max
MIN_SLOTS, MAX_SLOTS = 8, 256


def next_pow2(n: int) -> int:
    return 1 << (max(n, 1) - 1).bit_length()


def forward_slots(distinct_max: int) -> int:
    """The forward index's width where the document with the most
    distinct words has `distinct_max`."""
    return min(max(next_pow2(distinct_max), MIN_SLOTS), MAX_SLOTS)


def tile_grid(shard_docs: int) -> tuple[int, int]:
    """(documents a tile, tiles) of a shard of `shard_docs` documents:
    its capacity is the next power of two."""
    cap = next_pow2(shard_docs)
    tile = math.gcd(cap, SCORE_TILE)
    return tile, cap // tile


def walk_bytes(tiles_scored: float, tile: int, slots: int) -> float:
    """Bytes the walk reads of the forward index for `tiles_scored`
    tiles that were not skipped."""
    return tiles_scored * tile * slots * PAIR_BYTES


def bounds_bytes(query_terms: int, n_tiles: int) -> int:
    """Bytes of the block-max summary the bounds of `query_terms` terms
    read: a window of `n_tiles` entries each."""
    return query_terms * n_tiles * PAIR_BYTES


def walk_ops(tiles_scored: float, tile: int, slots: int,
             query_terms_a_search: float) -> float:
    """A compare, a select and an add for every pair read and query
    term."""
    return 3 * tiles_scored * tile * slots * query_terms_a_search
