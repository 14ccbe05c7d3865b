"""The readers of the fused tile walk's per-layer metrics (beside
`readers.py`, which holds those of the accepted metrics): each takes the
`Run` and returns a number, or None where it finds nothing to read."""

from __future__ import annotations

from . import walkbytes
from .readers import _traced, spec_of


def tiles_delta(run) -> dict | None:
    """How far the three counters of `fused_scoring.tiles` (`examined`,
    `hard_skipped`, `thresholded`: counted once a dispatch, at its
    collect) moved over the window; None on a program without them."""
    before = run.stats_before.get("fused_scoring", {}).get("tiles")
    after = run.stats_after.get("fused_scoring", {}).get("tiles")
    if not before or not after:
        return None
    return {k: after[k] - before[k] for k in after}


def tile_prune_pct(run):
    """Of the tiles the fused walks of the window looked at, the share
    they pruned: `hard_skipped` (the block-max bound says no query of
    the batch can match in the tile: it is not read) plus `thresholded`
    (no query's bound beats its running k-th best: the tile is scored
    for the exact total and its top-k extraction and merge are left
    out), over `examined`. Nothing where no fused walk ran in the
    window, as on a program that admits none of the cell's plans."""
    tiles = tiles_delta(run)
    if not tiles or not tiles["examined"]:
        return None
    return 100.0 * (tiles["hard_skipped"] + tiles["thresholded"]) \
        / tiles["examined"]


def walk_least_bytes(run) -> float | None:
    """Bytes the walks and bounds of the searches answered in the traced
    span could not avoid reading (`walkbytes.py`): the forward-index
    tiles the walks scored, which is the window's `examined` less
    `hard_skipped` (a thresholded tile is still read, for the exact
    total), in the share that the traced searches are of the window's
    (the program counts tiles over the whole window and the traffic
    does not change within it); and one window of the block-max summary
    for every term of every traced search, in every shard. The forward
    index's width is taken from the configuration's longest passage
    (`corpus.length_max`): at a cell's size some passage has more than
    half that many distinct words, so the builder's power of two is the
    one at or above it."""
    tiles = tiles_delta(run)
    traced, answered = _traced(run), run.answered()
    if not tiles or not traced or not answered:
        return None
    scored = tiles["examined"] - tiles["hard_skipped"]
    if scored <= 0:
        return None
    shards = run.config["number_of_shards"]
    tile, n_tiles = walkbytes.tile_grid(-(-run.docs // shards))
    slots = walkbytes.forward_slots(run.config["corpus"]["length_max"])
    spec = spec_of(run.mix)
    terms = sum(len(c["match"]) for r in traced
                for c in spec(r)["clauses"] if "match" in c)
    return walkbytes.walk_bytes(scored * len(traced) / len(answered),
                                tile, slots) \
        + shards * walkbytes.bounds_bytes(terms, n_tiles)


def fused_walk_roofline(run):
    """Least time the chip's memory could take for `walk_least_bytes`
    (HBM peak of `peaks.json`) over the device time of the scoring
    programs in the traced span. That time is of every scoring program,
    fused or not (they share one jitted function), so a search that was
    not admitted lowers the share and none can raise it. Bounded by HBM
    bandwidth: the compares and adds run on the VPU, which has no
    published peak."""
    if not run.trace or not run.trace["scoring_s"] or not run.peaks:
        return None
    least = walk_least_bytes(run)
    if not least:
        return None
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] \
        / run.trace["scoring_s"]
