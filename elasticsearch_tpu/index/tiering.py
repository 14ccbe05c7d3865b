"""Tiered tile residency: HBM as a cache over host-RAM pack tiles.

Today's pack contract is "everything uploads, once" (executor
`device_arrays`): a pack must fit in HBM, which caps corpus size per
device. This module relaxes that for the dominant fused-path column —
the per-field forward index (`fwd_tids`/`fwd_imps`, >= 64 bytes/doc at
the minimum slot width, vs ~4-5 bytes/doc for a doc-value column) — by
partitioning it into the SAME SCORE_TILE-aligned doc tiles the
block-max walk already reasons about:

  * the tiny per-tile summaries (`PostingsField.tile_max`, numeric
    tile extrema) stay PERMANENTLY device-resident — they are the
    pruning oracle and the paging oracle at once;
  * the bound computation runs over those summaries FIRST (host
    mirror: ops/scoring.bundle_tile_bounds_np) to produce the survivor
    tile set — a tile no query in the batch can match is never fetched
    at all, so WAND pruning becomes an I/O filter, not just a FLOP
    filter ("The Performance Envelope of Inverted Indexing on Modern
    Hardware", PAPERS.md);
  * cold survivor tiles stream host->device asynchronously
    (`jax.device_put` per tile slice), overlapped with scoring: the
    executor's chunked tiered walk uploads chunk N+1's tiles while
    chunk N's program executes;
  * residency is LRU per (segment, field, tile), every resident tile's
    bytes held on the fielddata breaker via `utils/breaker.Hold`, with
    a weakref GC backstop per segment (holds are idempotent, so the
    deterministic drop path and the finalizer can never double-release
    an evicted-then-GC'd tile).

Keying invariant: NOTHING here touches `Segment.fingerprint()` /
`Segment.cache_key()` — residency state is runtime-only, so autotune
choices, resident executables, and the shard request cache never
re-key on a page event (gated under trace_guarded in
tests/test_tiering.py).

Opt-in: `ES_TPU_TIERED_PACK` env or the `index.tiering.enabled` node
setting; when the whole pack fits the budget the fully-resident fast
path is preserved (counted, not paged). Stats surface under
`nodes_stats()["fused_scoring"]["tiering"]`, and the fielddata breaker
entry splits summary vs paged residency in `nodes_stats()["breakers"]`.
"""

from __future__ import annotations

import os
import threading
import weakref

import numpy as np

from .segment import Segment, next_pow2, score_tile_size, build_tile_minmax
from ..utils.metrics import CounterMetric, HighWaterMetric

_TRUE = ("1", "true", "on", "yes")

DEFAULT_CHUNK_TILES = 8

# module config (node startup: Node plumbs index.tiering.* through
# configure(); env vars override at read time so tests and the bench
# can flip modes without a node)
_cfg_lock = threading.Lock()
_cfg_enabled: bool | None = None
_cfg_budget: int | None = None
_cfg_chunk_tiles: int | None = None
# ownership token: minted fresh per configure() so a closing node can
# tear down ONLY its own install — value equality on the settings
# would alias two nodes configured identically
_cfg_token: object | None = None


def configure(enabled: bool | None = None,
              budget_bytes: int | None = None,
              chunk_tiles: int | None = None) -> object:
    """Node startup hook. Process-global (the executor serves every
    node in the process); last configured node wins. Returns an
    ownership token for reset(if_current=...) — the repack /
    process-stats teardown convention."""
    global _cfg_enabled, _cfg_budget, _cfg_chunk_tiles, _cfg_token
    with _cfg_lock:
        if enabled is not None:
            _cfg_enabled = bool(enabled)
        if budget_bytes is not None:
            _cfg_budget = int(budget_bytes)
        if chunk_tiles is not None:
            _cfg_chunk_tiles = max(1, int(chunk_tiles))
        _cfg_token = object()
        return _cfg_token


def config_snapshot() -> tuple:
    with _cfg_lock:
        return (_cfg_enabled, _cfg_budget, _cfg_chunk_tiles)


def reset(if_current: object | None = None) -> None:
    """Drop config AND every paged tile + counter (test/node-close
    hook). `if_current`: tear down only while the installed config is
    still the caller's own configure() token — a closing node must not
    clobber a later node's live tiering config (even an identically-
    valued one) or drop its paged tiles."""
    global _cfg_enabled, _cfg_budget, _cfg_chunk_tiles, _cfg_token, \
        stats
    with _cfg_lock:
        if if_current is not None and if_current is not _cfg_token:
            return
        _cfg_enabled = _cfg_budget = _cfg_chunk_tiles = None
        _cfg_token = None
        stats = TieringStats()
    pager.clear()


def enabled() -> bool:
    env = os.environ.get("ES_TPU_TIERED_PACK")
    if env is not None:
        return env.lower() in _TRUE
    return bool(_cfg_enabled)


def budget_bytes() -> int:
    """HBM byte budget for PAGED tile residency (summaries are not
    charged against it — they are the permanently-resident index of
    the tier). Default: half the fielddata breaker limit."""
    env = os.environ.get("ES_TPU_TIERED_BUDGET_BYTES")
    if env:
        try:
            return max(1, int(env))
        except ValueError:
            pass
    if _cfg_budget is not None:
        return max(1, _cfg_budget)
    from ..utils.breaker import breaker_service
    return max(1, breaker_service().breaker("fielddata").limit // 2)


def chunk_tiles() -> int:
    """Tiles per chunked-walk upload+score step. POW2-BUCKETED: the
    chunk tile count is a static shape of the tiered chunk programs,
    so a raw setting value would mint one compiled program per value
    (the graftlint recompile-hazard family)."""
    env = os.environ.get("ES_TPU_TIERED_CHUNK_TILES")
    raw = None
    if env:
        try:
            raw = int(env)
        except ValueError:
            raw = None
    if raw is None:
        raw = _cfg_chunk_tiles
    return next_pow2(max(raw or DEFAULT_CHUNK_TILES, 1), floor=1)


class TieringStats:
    """Process-wide tiered-residency counters."""

    def __init__(self):
        self.tile_hits = CounterMetric()
        self.tile_misses = CounterMetric()
        self.tile_evictions = CounterMetric()
        # tiles the bound computation pruned BEFORE any fetch — the
        # I/O-filter win (never uploaded, never scored)
        self.prune_skipped_fetches = CounterMetric()
        self.tiered_dispatches = CounterMetric()
        # packs that fit the budget and kept the fully-resident path
        self.fast_path_full_resident = CounterMetric()
        # non-fused plans against a paged pack: the fallback uploads
        # the forward index after all (counted, breaker-accounted)
        self.unfused_full_uploads = CounterMetric()
        # mesh rows that stayed fully resident despite tiering (the
        # mesh pack is one SPMD array set; per-row paging is a
        # documented limitation, made observable here)
        self.mesh_full_resident_rows = CounterMetric()
        # ms a chunk's tile staging overlapped with the PREVIOUS
        # chunk's in-flight scoring — the upload/compute overlap the
        # stepped walk buys (high-water)
        self.prefetch_overlap_ms = HighWaterMetric()


stats = TieringStats()


class TileStore:
    """Host-side tile partition of one segment's pageable columns.

    Holds zero-copy views into the segment's forward-index arrays plus
    the host-side numeric tile extrema the survivor computation reads.
    Creating a store does NOT move bytes anywhere; the pager does."""

    __slots__ = ("seg_id", "capacity", "tile", "n_tiles", "fields",
                 "_fwd", "tile_nbytes", "paged_bytes", "summary_bytes",
                 "_extrema", "__weakref__")

    def __init__(self, segment: Segment):
        self.seg_id = segment.seg_id
        self.capacity = segment.capacity
        self.tile = score_tile_size(segment.capacity)
        self.n_tiles = segment.capacity // max(self.tile, 1)
        self.fields: tuple[str, ...] = tuple(sorted(
            f for f, pf in segment.text.items()
            if pf.fwd_tids is not None
            and getattr(pf, "tile_max", None) is not None))
        self._fwd = {}
        self.tile_nbytes = {}
        self.paged_bytes = 0
        self.summary_bytes = 0
        for f in self.fields:
            pf = segment.text[f]
            pos = getattr(pf, "fwd_pos", None)
            self._fwd[f] = (pf.fwd_tids, pf.fwd_imps, pos)
            self.tile_nbytes[f] = (pf.fwd_tids[: self.tile].nbytes
                                   + pf.fwd_imps[: self.tile].nbytes
                                   + (pos[: self.tile].nbytes
                                      if pos is not None else 0))
            self.paged_bytes += pf.fwd_tids.nbytes + pf.fwd_imps.nbytes \
                + (pos.nbytes if pos is not None else 0)
            self.summary_bytes += pf.tile_max.nbytes
            if pos is not None:
                # the positional length norms stay permanently
                # device-resident next to tile_max (they are per-doc
                # scalars the chunk walk gathers, not paged columns)
                self.summary_bytes += pf.k1ln.nbytes + pf.lnorm.nbytes
        self._extrema: dict[str, tuple | None] = {}

    def pageable(self) -> bool:
        return bool(self.fields) and self.n_tiles > 1

    def tile_slices(self, field: str, tile_id: int) -> tuple:
        tids, imps, pos = self._fwd[field]
        lo, hi = tile_id * self.tile, (tile_id + 1) * self.tile
        return (tids[lo:hi], imps[lo:hi],
                pos[lo:hi] if pos is not None else None)

    def extrema(self, segment: Segment, field: str):
        """Host numeric tile extrema for the survivor computation —
        the same build_tile_minmax product ensure_num_tiles uploads
        (and the SAME host arrays, via the shared per-segment cache),
        so the host filter and the device kernel prune from identical
        numbers without recomputing the O(capacity) pass."""
        if field not in self._extrema:
            mm = host_extrema(segment, field)
            self._extrema[field] = mm
            if mm is not None:
                self.summary_bytes += mm[0].nbytes + mm[1].nbytes
        return self._extrema[field]


def host_extrema(segment: Segment, field: str):
    """Per-segment host cache of build_tile_minmax — ONE computation
    shared by the device upload (executor.ensure_num_tiles) and the
    tiered survivor oracle (TileStore.extrema), so a range-filtered
    query never pays the O(capacity) min/max pass twice. None when the
    column cannot carry extrema (absent, multi-valued, degenerate tile
    grid). Host-derived state like _host_perms: lives with the segment,
    untouched by drop_device."""
    cache = getattr(segment, "_host_tile_minmax", None)
    if cache is None:
        cache = {}
        segment._host_tile_minmax = cache  # type: ignore[attr-defined]
    if field not in cache:
        nc = segment.numerics.get(field)
        cache[field] = (None if nc is None or nc.mv_values is not None
                        else build_tile_minmax(nc.values, nc.exists,
                                               segment.capacity))
    return cache[field]


class _ResidentTile:
    """One device-resident (segment, field, tile) slice pair with its
    breaker hold (class-managed: released exactly once by whichever of
    evict/drop/backstop runs first — Hold.release is idempotent)."""

    __slots__ = ("tids", "imps", "pos", "nbytes", "hold")

    def __init__(self, tids, imps, nbytes, hold, pos=None):
        self.tids = tids
        self.imps = imps
        self.pos = pos
        self.nbytes = nbytes
        self.hold = hold

    def retire(self) -> None:
        """Release the breaker hold when the tile's device buffers
        actually DIE, not when the pager forgets them: an evicted tile
        may still be referenced by an in-flight chunk program, and
        releasing while the buffers are live would let new uploads
        overcommit real HBM past what the breaker accounts. CPython
        refcounting makes the release immediate for an unreferenced
        tile; Hold.release stays idempotent either way."""
        try:
            weakref.finalize(self.tids, self.hold.release)
        except TypeError:
            self.hold.release()


class TilePager:
    """Process-global LRU of device-resident pack tiles.

    The lock guards only the residency map bookkeeping; uploads
    (`jax.device_put`) and breaker holds happen OUTSIDE it, so a slow
    host->device transfer never convoys concurrent searches (graftlint
    lock-discipline: `tiering` is a hot-lock module)."""

    def __init__(self):
        from ..utils import race_guard
        self._mx = threading.Lock()
        # LRU order; every map is declared lock-guarded so the armed
        # race sanitizer trips on any mutation that slips the lock
        self._tiles: dict[tuple, _ResidentTile] = race_guard.guarded_dict(
            self._mx, "tiering.TilePager._tiles")
        self._resident_bytes = 0
        self._stores: dict[str, weakref.ref] = race_guard.guarded_dict(
            self._mx, "tiering.TilePager._stores")
        self._zero_tiles: dict[tuple, tuple] = race_guard.guarded_dict(
            self._mx, "tiering.TilePager._zero_tiles")

    # -- store registry (stats + GC backstop) ------------------------------

    def register_store(self, segment: Segment, store: TileStore) -> None:
        with self._mx:
            self._stores[store.seg_id] = weakref.ref(store)
        # GC backstop: a segment dropped without drop_device() still
        # releases every paged tile's breaker hold. seg_ids are minted
        # fresh per process, so a late finalizer can only ever drop
        # tiles of ITS segment; release is idempotent either way.
        weakref.finalize(segment, self.drop_segment, store.seg_id)

    # -- fetch / evict ------------------------------------------------------

    def fetch(self, store: TileStore, fields: tuple[str, ...],
              tiles: np.ndarray) -> dict:
        """Ensure `tiles` (int array, -1 = chunk padding) of every
        field are device-resident; returns {field: (tids_tuple,
        imps_tuple)} aligned with `tiles`. Misses upload asynchronously
        (device_put), hits reuse the LRU entry; eviction never touches
        the tiles of THIS fetch."""
        import jax
        from ..utils import faults
        from ..utils.breaker import breaker_service
        # fault boundary: breaker_trip / shard_error rules with
        # site=tiering fire here, BEFORE any hold is taken
        faults.on_dispatch("tiering", phase="fetch")
        want = [(f, int(t)) for f in fields for t in tiles if t >= 0]
        keep = {(store.seg_id, f, t) for f, t in want}
        hits: dict[tuple, _ResidentTile] = {}
        missing: list[tuple[str, int]] = []
        with self._mx:
            for f, t in want:
                key = (store.seg_id, f, t)
                if key in hits:
                    continue
                entry = self._tiles.pop(key, None)
                if entry is not None:
                    self._tiles[key] = entry           # LRU touch
                    hits[key] = entry
                else:
                    missing.append((f, t))
        stats.tile_hits.inc(len(hits))
        stats.tile_misses.inc(len(missing))
        fielddata = breaker_service().breaker("fielddata")
        uploaded: dict[tuple, _ResidentTile] = {}
        try:
            for f, t in dict.fromkeys(missing):
                slices = store.tile_slices(f, t)
                tids, imps = slices[0], slices[1]
                pos = slices[2] if len(slices) > 2 else None
                nb = store.tile_nbytes[f]
                hold = fielddata.hold(nb)
                try:
                    entry = _ResidentTile(
                        jax.device_put(tids), jax.device_put(imps), nb,
                        hold, pos=(jax.device_put(pos)
                                   if pos is not None else None))
                except BaseException:
                    hold.release()
                    raise
                uploaded[(store.seg_id, f, t)] = entry
        except BaseException:
            for entry in uploaded.values():
                entry.hold.release()
            raise
        evicted = []
        with self._mx:
            for key, entry in uploaded.items():
                old = self._tiles.pop(key, None)
                if old is not None:
                    # two threads raced the same miss: keep the winner,
                    # give the loser's bytes straight back
                    self._resident_bytes -= old.nbytes
                    evicted.append(old)
                self._tiles[key] = entry
                self._resident_bytes += entry.nbytes
            budget = budget_bytes()
            for key in list(self._tiles):
                if self._resident_bytes <= budget:
                    break
                if key in keep:
                    continue           # never evict the working chunk
                old = self._tiles.pop(key)
                self._resident_bytes -= old.nbytes
                evicted.append(old)
                stats.tile_evictions.inc()
        for old in evicted:
            old.retire()
        out = {}
        resident = {**hits, **uploaded}
        for f in fields:
            fwd = store._fwd[f]
            has_pos = len(fwd) > 2 and fwd[2] is not None
            tids_parts, imps_parts, pos_parts = [], [], []
            for t in tiles:
                if t < 0:
                    z_tids, z_imps, z_pos = self._zero_tile(store, f)
                    tids_parts.append(z_tids)
                    imps_parts.append(z_imps)
                    if has_pos:
                        pos_parts.append(z_pos)
                else:
                    entry = resident[(store.seg_id, f, int(t))]
                    tids_parts.append(entry.tids)
                    imps_parts.append(entry.imps)
                    if has_pos:
                        pos_parts.append(entry.pos)
            out[f] = (tuple(tids_parts), tuple(imps_parts),
                      tuple(pos_parts) if has_pos else None)
        return out

    def _zero_tile(self, store: TileStore, field: str):
        """Shared pad tile (tids -1 = absent term, imps 0, pos -1 =
        empty delta stream): scored docs there can never match, and the
        gathered live mask is False for pad slots anyway. Unaccounted:
        one tile per shape, bounded by the distinct (tile, slot-width,
        pos-width) triples in use."""
        fwd = store._fwd[field]
        tids = fwd[0]
        pos = fwd[2] if len(fwd) > 2 else None
        pos_w = pos.shape[1] if pos is not None else 0
        key = (store.tile, tids.shape[1], pos_w)
        with self._mx:
            z = self._zero_tiles.get(key)
        if z is None:
            import jax
            z = (jax.device_put(np.full((store.tile, tids.shape[1]), -1,
                                        np.int32)),
                 jax.device_put(np.zeros((store.tile, tids.shape[1]),
                                         np.float32)),
                 (jax.device_put(np.full((store.tile, pos_w), -1,
                                         pos.dtype))
                  if pos is not None else None))
            # upload OUTSIDE the lock (device_put under the pager lock
            # would convoy concurrent fetches), then publish under it:
            # two threads racing the same shape keep the first winner
            with self._mx:
                z = self._zero_tiles.setdefault(key, z)
        return z

    def drop_segment(self, seg_id: str) -> None:
        """Release every paged tile (and its breaker hold) of one
        segment — Segment.drop_device() / clear_cache path AND the
        per-segment weakref backstop. Idempotent."""
        with self._mx:
            dead = [k for k in self._tiles if k[0] == seg_id]
            dropped = []
            for k in dead:
                entry = self._tiles.pop(k)
                self._resident_bytes -= entry.nbytes
                dropped.append(entry)
            self._stores.pop(seg_id, None)
        for entry in dropped:
            entry.retire()

    def clear(self) -> None:
        with self._mx:
            dropped = list(self._tiles.values())
            self._tiles.clear()
            self._resident_bytes = 0
            self._stores.clear()
            self._zero_tiles.clear()
        for entry in dropped:
            entry.retire()

    # -- stats --------------------------------------------------------------

    @property
    def resident_bytes(self) -> int:
        with self._mx:
            return self._resident_bytes

    def resident_tiles(self) -> int:
        with self._mx:
            return len(self._tiles)

    def summary_bytes(self) -> int:
        with self._mx:
            refs = list(self._stores.values())
        total = 0
        for r in refs:
            st = r()
            if st is not None:
                total += st.summary_bytes
        return total


pager = TilePager()


# ---------------------------------------------------------------------------
# Segment-level activation
#
# The page/don't-page decision is STICKY per segment, recorded at first
# dispatch (before the first device upload) — flipping the env mid-life
# must not strand a pack whose forward index was never uploaded on the
# non-tiered read path, or vice versa.
# ---------------------------------------------------------------------------


def activate(segment: Segment) -> frozenset:
    """Decide (once) and return the segment's paged field set. Empty
    set = fully resident. The decision compares the WHOLE pack footprint
    (resident columns + forward index) against the budget, so a pack
    that fits keeps the fully-resident fast path."""
    rec = getattr(segment, "_tiering_paged", None)
    if rec is not None:
        return rec
    paged: frozenset = frozenset()
    if enabled():
        store = store_for(segment)
        if store is not None and store.pageable():
            pack_bytes = segment.nbytes() + store.paged_bytes
            if pack_bytes > budget_bytes():
                paged = frozenset(store.fields)
            else:
                stats.fast_path_full_resident.inc()
    segment._tiering_paged = paged  # type: ignore[attr-defined]
    return paged


def paged_fields(segment: Segment) -> frozenset:
    """The recorded paged field set (empty when undecided or fully
    resident) — readers that must not trigger a decision."""
    rec = getattr(segment, "_tiering_paged", None)
    return rec if rec is not None else frozenset()


def clear_paged(segment: Segment) -> None:
    """Un-page a segment (the unfused full-residency fallback uploaded
    its forward index): drop its tiles and record the empty set so
    later dispatches take the ordinary path."""
    pager.drop_segment(segment.seg_id)
    segment._tiering_paged = frozenset()  # type: ignore[attr-defined]


def store_for(segment: Segment) -> TileStore | None:
    """The segment's (cached) TileStore; None when it has no pageable
    column. Registration attaches the GC backstop exactly once."""
    store = getattr(segment, "_tile_store", None)
    if store is None:
        store = TileStore(segment)
        if not store.pageable():
            segment._tile_store = store  # type: ignore[attr-defined]
            return None
        segment._tile_store = store  # type: ignore[attr-defined]
        pager.register_store(segment, store)
    return store if store.pageable() else None


def drop_segment_tiles(seg_id: str) -> None:
    pager.drop_segment(seg_id)


def note_prune_skipped(n: int) -> None:
    if n > 0:
        stats.prune_skipped_fetches.inc(n)


def record_overlap_ms(ms: float) -> None:
    stats.prefetch_overlap_ms.record(round(float(ms), 3))


def stats_snapshot() -> dict:
    """nodes_stats()["fused_scoring"]["tiering"] block."""
    return {
        "enabled": enabled(),
        "budget_bytes": budget_bytes() if enabled() else None,
        "chunk_tiles": chunk_tiles(),
        "resident_bytes": pager.resident_bytes,
        "resident_tiles": pager.resident_tiles(),
        "summary_bytes": pager.summary_bytes(),
        "tile_hits": stats.tile_hits.count,
        "tile_misses": stats.tile_misses.count,
        "tile_evictions": stats.tile_evictions.count,
        "prune_skipped_fetches": stats.prune_skipped_fetches.count,
        "tiered_dispatches": stats.tiered_dispatches.count,
        "fast_path_full_resident": stats.fast_path_full_resident.count,
        "unfused_full_uploads": stats.unfused_full_uploads.count,
        "mesh_full_resident_rows": stats.mesh_full_resident_rows.count,
        "prefetch_overlap_ms": {
            "high_water": round(float(stats.prefetch_overlap_ms.max), 3),
            "last": round(float(stats.prefetch_overlap_ms.last), 3),
        },
    }


def breaker_split() -> dict:
    """Summary-vs-paged residency split for the fielddata breaker's
    node-stats entry (the summaries ride the ordinary device_arrays
    hold; the paged bytes ride per-tile pager holds)."""
    return {"summary_bytes": pager.summary_bytes(),
            "paged_bytes": pager.resident_bytes}
