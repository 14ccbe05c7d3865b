"""Immutable columnar segments — the TPU-native replacement for Lucene segments.

Reference analog: the per-shard Lucene index managed by
index/engine/InternalEngine.java (IndexWriter segments) plus the fielddata
layer (index/fielddata/ — columnar per-doc values, global ordinals). In
this framework a segment IS columnar from birth:

  * text fields   -> block-CSR postings: fixed 128-lane blocks of
                     (doc_id, bm25_impact) pairs, term -> block range.
                     BM25 impacts are precomputed at index time
                     (BM25S-style "eager scoring" — see PAPERS.md), so
                     query-time work is gather + scatter-add, which maps
                     onto the TPU VPU; there is no per-doc scoring loop.
  * keyword field -> int32 ordinal column + sorted term dictionary
                     (ref: global ordinals, index/fielddata/ordinals/)
  * numeric/date  -> int32/float32 doc-value columns + exists mask
  * _id/_source   -> host-side (fetch phase never touches the device)

A Segment is built once (host, numpy), is immutable afterwards, and can be
uploaded to the device as a DeviceSegment pytree. Deletions are a live
bitmask owned by the engine, not the segment (like Lucene liveDocs).

Shapes are padded to power-of-two buckets so XLA recompilation count is
logarithmic in segment size, and the last dim of posting blocks is 128 to
match the TPU lane width.
"""

from __future__ import annotations

import math
import threading
from array import array
from dataclasses import dataclass, field as dc_field
from typing import Iterable

import numpy as np

# guards lazy text-sort column materialization (rare, once per
# segment+field); searches arrive concurrently via ThreadingHTTPServer
_TEXT_SORT_LOCK = threading.Lock()

from .mapping import (
    ParsedDocument, TEXT, KEYWORD, DATE, BOOLEAN, IP,
    LONG, INTEGER, SHORT, BYTE, DOUBLE, FLOAT, DENSE_VECTOR, GEO_POINT,
)

BLOCK = 128  # TPU lane width; one posting block = 128 (doc, impact) lanes
MAX_FWD_SLOTS = 256  # forward-index width limit (beyond: scatter path)

# block-max pruning (the block-max WAND analog for the dense path):
# per-(term, doc-tile) upper-bound impact summaries built at pack time.
# A query's score upper bound over a tile is sum_q w_q * tile_max[q, j];
# tiles whose bound cannot beat the running top-k threshold are skipped
# by the fused score+top-k kernels (ops/scoring.py, ops/pallas_scoring.py).
SCORE_TILE = 1024           # docs per pruning tile (lane-width multiple)

# Lucene BM25Similarity defaults (ref: index/similarity/BM25SimilarityProvider.java)
BM25_K1 = 1.2
BM25_B = 0.75

# positional pack (third eager column family next to deltas and
# impacts): per-(doc, slot) position lists, delta-encoded int16, width
# pow2-bucketed like the forward slot width. A field whose max
# per-posting tf exceeds POS_CAP (or whose positions overflow int16)
# skips the pack and phrase/span queries take the host path (counted
# under fused_scoring.admission.positional).
POS_CAP = 64                   # max positions kept per (doc, term)
POS_MAX_ENC = 32767            # int16 ceiling for absolute positions
POS_PACK_BUDGET = 1 << 27      # max cap * L * P int16 elements (256MB)


def bm25_norms(doc_len: np.ndarray, avg_len: float,
               k1: float = BM25_K1, b: float = BM25_B
               ) -> tuple[np.ndarray, np.ndarray]:
    """The two per-doc BM25 length-norm columns of the positional pack,
    in the ONE f32 op order every consumer shares:

      lnorm[d] = (1 - b) + b * doc_len[d] / avg_len   (BM25F field norm)
      k1ln[d]  = k1 * lnorm[d]                        (phrase/span k_d)

    Computed in f64 then rounded ONCE to f32 — the device engines, the
    eval_node reference path, and the host phrase/BM25F oracles all
    read these exact values, which is what makes fused positional
    scores byte-identical to the host `search/phrase.py` oracle."""
    ln = (1.0 - b) + b * (doc_len.astype(np.float64) / float(avg_len))
    ln32 = ln.astype(np.float32)
    return ln32, (k1 * ln).astype(np.float32)


def next_pow2(n: int, floor: int = 1) -> int:
    n = max(n, floor)
    return 1 << (n - 1).bit_length()


def bm25_idf(df: np.ndarray | float, doc_count: int) -> np.ndarray | float:
    """idf = ln(1 + (N - df + 0.5) / (df + 0.5)) — Lucene BM25Similarity.idfExplain."""
    return np.log(1.0 + (doc_count - df + 0.5) / (df + 0.5))


def score_tile_size(cap: int) -> int:
    """Pruning-tile width for a capacity: the largest power-of-two
    divisor of cap, capped at SCORE_TILE (pow2 caps get SCORE_TILE, or
    the whole cap when smaller). ALWAYS divides cap exactly, so tiles
    never straddle the array end; build_tile_max rejects degenerate
    widths (< BLOCK) that an odd-factor cap would produce."""
    return math.gcd(cap, SCORE_TILE)


@dataclass(frozen=True, eq=False)
class TileSummary:
    """The block-max summary of one text field: for each term, the
    tiles it occurs in and its largest impact there, as a CSR over the
    (term, tile) pairs that occur. Its bytes follow the postings (a
    posting makes at most one pair), not terms x tiles, so it exists at
    any vocabulary.

    Row t is `tiles[start[t]:start[t + 1]]` (ascending, distinct) with
    `vals` beside it; a tile that is not in the row holds no posting of
    the term and bounds to 0, so `row > 0` is exactly "the term occurs
    in the tile". `tiles` and `vals` are padded to at least
    `start[-1] + grid` entries (tile = grid, value 0), so a reader may
    take a window of `grid` entries at any row's start. `cols`, where
    set, names the tiles of the grid a reader's rows are cut to (the
    tiered walk's compacted chunks; a column outside the grid reads
    0). ops/scoring.tile_max_rows reads it on the device, `rows`
    here on the host."""

    start: np.ndarray                      # int32 [T + 1]
    tiles: np.ndarray                      # int32 [>= E + grid]
    vals: np.ndarray                       # float32, as `tiles`
    grid: int                              # tiles of the pack's grid
    cols: np.ndarray | None = None         # int32 [n] tiles of the grid

    @property
    def n_tiles(self) -> int:
        """The width a reader's rows come out at."""
        return self.grid if self.cols is None else self.cols.shape[-1]

    @property
    def nbytes(self) -> int:
        return self.start.nbytes + self.tiles.nbytes + self.vals.nbytes

    @property
    def entries(self) -> int:
        """(term, tile) pairs that occur."""
        return int(self.start[-1])

    def take(self, cols) -> "TileSummary":
        return TileSummary(self.start, self.tiles, self.vals, self.grid,
                           cols)

    def padded(self, n_terms: int, n_entries: int) -> "TileSummary":
        """The same summary with term rows up to `n_terms` (empty rows:
        an absent term bounds to 0 and can never un-prune a tile) and
        `n_entries` stored entries, for shapes that several packs
        share."""
        start = np.concatenate([self.start, np.full(
            max(n_terms + 1 - len(self.start), 0), self.start[-1],
            np.int32)])
        more = max(n_entries - len(self.tiles), 0)
        return TileSummary(
            start,
            np.concatenate([self.tiles, np.full(more, self.grid, np.int32)]),
            np.concatenate([self.vals, np.zeros(more, np.float32)]),
            self.grid, self.cols)

    def rows(self, tids: np.ndarray) -> np.ndarray:
        """[n] term ids -> float32 [n, n_tiles] rows, on the host: what
        the dense [T, n_tiles] array held at `tile_max[tids]`."""
        tids = np.asarray(tids)
        out = np.zeros((tids.shape[0], self.grid), np.float32)
        for i, t in enumerate(tids):
            lo, hi = int(self.start[t]), int(self.start[t + 1])
            out[i, self.tiles[lo:hi]] = self.vals[lo:hi]
        if self.cols is None:
            return out
        return np.concatenate([out, np.zeros_like(out[:, :1])], axis=1)[
            :, np.minimum(self.cols, self.grid)]

    def dense(self) -> np.ndarray:
        """float32 [T, n_tiles]: every row (tests and small packs)."""
        return self.rows(np.arange(len(self.start) - 1))


def tile_runs(tids: np.ndarray, tiles: np.ndarray, n_terms: int,
              grid: int) -> tuple[np.ndarray, np.ndarray, np.ndarray,
                                  np.ndarray, np.ndarray]:
    """The integer half of a TileSummary build, shared by the host and
    the device builder: postings' (term, tile) -> (order, heads, run,
    start, run_tiles). `order` sorts the postings by (term, tile) and
    `heads` are the positions in that order at which an entry's run of
    postings begins; `run[i]` is the entry posting i falls in, in the
    postings' own order; `start` is the CSR over terms and `run_tiles`
    each entry's tile. The float half is a max of the impacts over each
    run, which is order-free, so both builders give the same bytes."""
    key = tids.astype(np.int64) * grid + tiles
    order = np.argsort(key, kind="stable")
    key_s = key[order]
    head = np.ones(len(key_s), bool)
    head[1:] = key_s[1:] != key_s[:-1]
    run = np.empty(len(key_s), np.int64)
    run[order] = np.cumsum(head) - 1
    keys = key_s[head]
    start = np.zeros(n_terms + 1, np.int32)
    np.cumsum(np.bincount(keys // grid, minlength=n_terms)[:n_terms],
              out=start[1:])
    return (order, np.flatnonzero(head), run, start,
            (keys % grid).astype(np.int32))


def tile_summary_pad(entries: int, grid: int) -> int:
    """Stored entries of a summary with `entries` pairs: the window pad
    (see TileSummary), rounded up so that nearby packs share shapes."""
    return -(-(entries + grid) // 1024) * 1024


def tile_summary(tids: np.ndarray, docs: np.ndarray, imps: np.ndarray,
                 n_terms: int, cap: int,
                 tile: int | None = None) -> TileSummary | None:
    """Postings (term, doc, impact), in any order -> the block-max
    summary consumed by the fused score+top-k kernels: the max impact
    of each (term, doc-tile) pair that occurs. None when there are no
    terms or the tile width is degenerate."""
    if tile is None:
        tile = score_tile_size(cap)
    # degenerate widths (below the lane width, e.g. from an odd-factor
    # cap) would build huge summaries that prune nothing useful
    if cap % tile != 0 or (tile < BLOCK and tile < cap) or n_terms <= 0:
        return None
    grid = cap // tile
    order, heads, _run, start, run_tiles = tile_runs(
        tids, docs // tile, n_terms, grid)
    vals = (np.maximum.reduceat(imps[order], heads) if len(heads)
            else np.zeros(0, np.float32))
    return TileSummary(start, run_tiles, vals.astype(np.float32),
                       grid).padded(n_terms,
                                    tile_summary_pad(len(vals), grid))


def build_tile_max(fwd_tids: np.ndarray, fwd_imps: np.ndarray,
                   n_terms: int, cap: int,
                   tile: int | None = None) -> TileSummary | None:
    """tile_summary of a [cap, L] forward index (tid pad -1)."""
    docs, slots = np.nonzero(fwd_tids[:cap] >= 0)
    return tile_summary(fwd_tids[docs, slots], docs, fwd_imps[docs, slots],
                        n_terms, cap, tile)


def build_tile_minmax(values: np.ndarray, exists: np.ndarray, cap: int,
                      tile: int | None = None
                      ) -> tuple[np.ndarray, np.ndarray] | None:
    """Per-tile [lo, hi] extrema of a single-valued numeric column over
    the SCORE_TILE grid — the per-clause pack-time summary that lets the
    fused bool engine prune tiles a range filter cannot match in
    (ops/scoring.bundle_tile_bounds). Tiles with no existing value get
    an empty interval (lo > hi: dtype max/min sentinels), so they always
    prune. None when the tile grid would be degenerate for this cap."""
    if tile is None:
        tile = score_tile_size(cap)
    if cap % tile != 0 or (tile < BLOCK and tile < cap):
        return None
    from . import devbuild
    if devbuild.enabled():
        try:
            return devbuild.tile_minmax_device(values, exists, cap, tile)
        except Exception as e:
            devbuild.on_fallback("tile_minmax", e)
    n_tiles = cap // tile
    v = values[:cap].reshape(n_tiles, tile)
    e = exists[:cap].reshape(n_tiles, tile)
    if values.dtype == np.float32:
        lo_pad, hi_pad = np.float32(np.inf), np.float32(-np.inf)
        # NaN values would poison the extrema (every comparison against
        # NaN is False, so the overlap test would prune tiles whose
        # OTHER docs legitimately match). A NaN doc itself can never
        # match a range, so excluding it from the extrema is exact.
        # +-inf stay in: they CAN match unbounded ranges.
        e = e & ~np.isnan(v)
    else:
        lo_pad = np.iinfo(values.dtype).max
        hi_pad = np.iinfo(values.dtype).min
    lo = np.where(e, v, lo_pad).min(axis=1)
    hi = np.where(e, v, hi_pad).max(axis=1)
    return lo, hi


# ---------------------------------------------------------------------------
# Host-side columnar structures
# ---------------------------------------------------------------------------


@dataclass
class PostingsField:
    """Inverted index for one analyzed text field, in block-CSR layout.

    terms[t] is sorted; postings of term t live in blocks
    block_start[t] : block_start[t+1] of (block_docs, block_imps), padded
    with doc_id == capacity (dropped by scatter) and impact 0.
    """

    name: str
    terms: list[str]                       # sorted
    term_index: dict[str, int]
    df: np.ndarray                         # int32 [T] document frequency
    indptr: np.ndarray                     # int64 [T+1] into doc_ids/tfs (host CSR)
    doc_ids: np.ndarray                    # int32 [nnz]
    tfs: np.ndarray                        # float32 [nnz]
    doc_len: np.ndarray                    # float32 [cap] field length per doc
    doc_count: int                         # docs containing this field
    avg_len: float
    # positional sidecar (host-side; phrase/span matching — ref: Lucene
    # postings positions consumed by PhraseQuery/SpanQuery). Positions of
    # posting j live in pos_data[pos_indptr[j] : pos_indptr[j+1]] and are
    # token indices in the (concatenated, position_increment_gap=0 as in
    # ES 2.0 StringFieldMapper) field token stream.
    pos_data: np.ndarray = dc_field(default=None, repr=False)   # int32 [sum tf]
    pos_indptr: np.ndarray = dc_field(default=None, repr=False)  # int64 [nnz+1]
    # device-layout block arrays (term-major: scatter path)
    block_docs: np.ndarray = dc_field(default=None, repr=False)  # int32 [NB,128]
    block_imps: np.ndarray = dc_field(default=None, repr=False)  # float32 [NB,128]
    block_start: np.ndarray = dc_field(default=None, repr=False)  # int32 [T+1]
    # forward index (doc-major: gather path) — score[d] for a few-term
    # query is a compare+FMA over the doc's own (term, impact) slots,
    # which vectorizes on the VPU with NO scatter. tid pad = -1, imp pad 0.
    fwd_tids: np.ndarray = dc_field(default=None, repr=False)    # int32 [cap, L]
    fwd_imps: np.ndarray = dc_field(default=None, repr=False)    # float32 [cap, L]
    # block-max summary for the fused score+top-k path: the max impact
    # of each term among the docs of each SCORE_TILE-doc tile it occurs
    # in. None when the field has no forward index.
    tile_max: TileSummary = dc_field(default=None, repr=False)
    # positional pack (third eager column family; device phrase/span/
    # BM25F — ops/scoring positional clause kinds). fwd_pos is forward-
    # aligned with fwd_tids: positions of the term in slot l of doc d
    # live in fwd_pos[d, l*P:(l+1)*P], delta-encoded (first entry
    # absolute, then gaps), pad -1. P = pos_width = next_pow2(max tf),
    # capped at POS_CAP. None when the field has no position sidecar,
    # no forward index, or exceeds a positional cap (host path serves).
    fwd_pos: np.ndarray = dc_field(default=None, repr=False)   # i16 [cap, L*P]
    pos_width: int = 0                                         # P (pow2)
    lnorm: np.ndarray = dc_field(default=None, repr=False)     # f32 [cap]
    k1ln: np.ndarray = dc_field(default=None, repr=False)      # f32 [cap]

    def lookup(self, term: str) -> int:
        return self.term_index.get(term, -1)

    def enc_positions(self, tid: int, stride: int) -> np.ndarray:
        """All (doc, position) pairs of a term encoded as doc*stride + pos,
        sorted ascending — the working set for vectorized phrase
        intersection (search/phrase.py)."""
        if self.pos_data is None or tid < 0:
            return np.empty(0, dtype=np.int64)
        s, e = int(self.indptr[tid]), int(self.indptr[tid + 1])
        if s == e:
            return np.empty(0, dtype=np.int64)
        ps, pe = int(self.pos_indptr[s]), int(self.pos_indptr[e])
        docs = np.repeat(self.doc_ids[s:e].astype(np.int64),
                         np.diff(self.pos_indptr[s:e + 1]).astype(np.int64))
        return docs * stride + self.pos_data[ps:pe]

    def nbytes(self) -> int:
        n = (self.block_docs.nbytes + self.block_imps.nbytes
             + self.block_start.nbytes + self.doc_len.nbytes)
        tm = getattr(self, "tile_max", None)
        if tm is not None:
            n += tm.nbytes
        fp = getattr(self, "fwd_pos", None)
        if fp is not None:
            n += fp.nbytes + self.lnorm.nbytes + self.k1ln.nbytes
        return n


@dataclass
class KeywordColumn:
    """Ordinal doc-value column for one keyword field.

    ords[d] = index into `terms` (sorted), or -1 when the doc has no value.
    Ref: index/fielddata/plain/SortedSetDVOrdinalsIndexFieldData.java +
    global ordinals (ordinals/GlobalOrdinalsBuilder.java) — here ordinals
    are segment-local; the shard maps them to shard-global ords at refresh.
    """

    name: str
    terms: list[str]                       # sorted unique values
    term_index: dict[str, int]
    ords: np.ndarray                       # int32 [cap], -1 = missing;
                                           # multi-valued docs: MIN ord
                                           # (MultiValueMode.MIN sort key)
    df: np.ndarray                         # int32 [card] docs per term
    # multi-valued sidecar: [cap, M] sorted unique ords per doc, pad -1
    # (ref: SortedSetDocValues — ordinal SETS per doc)
    mv_ords: np.ndarray = dc_field(default=None, repr=False)

    @property
    def cardinality(self) -> int:
        return len(self.terms)

    def lookup(self, term: str) -> int:
        return self.term_index.get(term, -1)

    def nbytes(self) -> int:
        n = self.ords.nbytes + self.df.nbytes
        if self.mv_ords is not None:
            n += self.mv_ords.nbytes
        return n


@dataclass
class NumericColumn:
    """Numeric/date/boolean/ip doc-value column.

    Device dtype is int32 when every value fits (exact range filters and
    exact sums for the common case — http_logs status/size, seconds-
    resolution dates); float32 otherwise. Exact int64/float64 originals
    stay host-side in `raw` for fetch/stats exactness.
    Dates are stored as epoch SECONDS in the int32 device column (covers
    1902..2038 exactly; millis precision kept in `raw`).
    """

    name: str
    kind: str                              # mapping type (long/double/date/...)
    values: np.ndarray                     # int32 or float32 [cap] device column
    exists: np.ndarray                     # bool [cap]
    raw: np.ndarray                        # int64 or float64 [cap] host-exact
    bias: int = 0                          # device value = raw - bias (ip: 2^31)
    # multi-valued sidecar (ref: SortedNumericDocValues): values beyond
    # the first live in [cap, M] arrays; mv_exists masks the pad
    mv_values: np.ndarray = dc_field(default=None, repr=False)
    mv_raw: np.ndarray = dc_field(default=None, repr=False)
    mv_exists: np.ndarray = dc_field(default=None, repr=False)

    def nbytes(self) -> int:
        n = self.values.nbytes + self.exists.nbytes
        if self.mv_values is not None:
            n += self.mv_values.nbytes + self.mv_exists.nbytes
        return n


@dataclass
class VectorColumn:
    """Dense embedding column: [capacity, dims] float32.

    The kNN read path is a single [B,dims]x[dims,cap] matmul on the MXU —
    exact search; at TPU batch throughput exact beats ANN-graph recall
    tradeoffs for shard-sized corpora (the ES analog is
    dense_vector/HNSW; ref BASELINE.json config[4]).
    """

    name: str
    values: np.ndarray                     # float32 [cap, dims]
    exists: np.ndarray                     # bool [cap]
    norms: np.ndarray                      # float32 [cap] L2 norms (0 if absent)

    @property
    def dims(self) -> int:
        return self.values.shape[1]

    def nbytes(self) -> int:
        return self.values.nbytes + self.exists.nbytes + self.norms.nbytes


@dataclass
class GeoColumn:
    """geo_point doc-value column: lat/lon float32 pairs.

    Ref: index/fielddata/plain/GeoPointDVIndexFieldData — ES stores
    encoded lat/lon doc values; here they are two flat device columns so
    haversine/bbox/polygon tests are one fused VPU pass (ops/geo.py).
    """

    name: str
    lat: np.ndarray                        # float32 [cap]
    lon: np.ndarray                        # float32 [cap]
    exists: np.ndarray                     # bool [cap]

    def nbytes(self) -> int:
        return self.lat.nbytes + self.lon.nbytes + self.exists.nbytes


@dataclass
class CompletionColumn:
    """Suggest dictionary for one completion field: per-row entry lists.

    Host-resident (suggest never needs the device — same as the
    reference, where Completion090PostingsFormat builds an FST per
    segment). entries[i] = (row, {input, output, weight, payload,
    context}).
    """

    name: str
    entries: list[tuple[int, dict]]

    def nbytes(self) -> int:
        return sum(len(i.encode()) + 16
                   for _, e in self.entries for i in e.get("input", []))


@dataclass
class Segment:
    """One immutable columnar segment."""

    seg_id: str
    num_docs: int
    capacity: int                          # next_pow2(num_docs)
    ids: list[str]
    id_map: dict[str, int]
    sources: list[bytes]
    versions: np.ndarray                   # int64 [num_docs]
    text: dict[str, PostingsField]
    keywords: dict[str, KeywordColumn]
    numerics: dict[str, NumericColumn]
    vectors: dict[str, VectorColumn] = dc_field(default_factory=dict)
    # IVF coarse indexes per dense_vector field (index/ann.AnnIndex),
    # built lazily at first eligible search (the ensure_* convention —
    # index/ann.ensure_ann) or restored by the store round-trip; delta
    # segments always serve the exact scan and never carry one
    ann: dict[str, object] = dc_field(default_factory=dict)
    geos: dict[str, GeoColumn] = dc_field(default_factory=dict)
    completions: dict[str, CompletionColumn] = dc_field(default_factory=dict)
    # block join: parent_of[d] = row of d's parent for nested sub-docs,
    # -1 for primary docs (ref: Lucene block join / ObjectMapper nested)
    parent_of: np.ndarray = dc_field(default=None, repr=False)  # int32 [cap]
    # streaming write path (index/engine.py delta mode): a DELTA segment
    # is the small append-only pack rebuilt at every refresh on top of
    # an immutable base generation. `delta_parent` is the base
    # generation key it rides on; `delta_epoch` counts rebuilds since
    # the last compaction. Base segments leave both at their defaults.
    delta_parent: str | None = None
    delta_epoch: int = 0
    # True for concat_segments products: their eager impacts were
    # PRESERVED from the source segments' field stats and cannot be
    # recomputed from this segment's own doc_count/avg_len — the store
    # must persist them (builder/merge-built segments recompute exactly)
    impacts_preserved: bool = False

    @property
    def has_nested(self) -> bool:
        return self.parent_of is not None and bool((self.parent_of >= 0).any())

    def primary_mask(self) -> np.ndarray:
        if self.parent_of is None:
            m = np.zeros(self.capacity, dtype=bool)
            m[: self.num_docs] = True
            return m
        return self.parent_of == -1

    def device_changed(self, rebuilt: bool = False) -> None:
        """Called by whatever writes to the uploaded column tree
        (`_device`): a new tree (`rebuilt`) or leaves added to the one
        that is there (the executor's ensure_* uploads). What was kept
        from the tree's shape (a reader's bound plans:
        search/bound_plans.py) is valid while `device_epoch` reads the
        same; the epoch outlives drop_device, so a tree uploaded anew
        never reads as the old one."""
        built, added = getattr(self, "_device_epoch", (0, 0))
        self._device_epoch = ((built + 1, 0) if rebuilt
                              else (built, added + 1))

    def device_epoch(self) -> tuple | None:
        """(trees built, leaves added since) of the uploaded column
        tree; None while nothing is uploaded."""
        if getattr(self, "_device", None) is None:
            return None
        return getattr(self, "_device_epoch", None)

    def drop_device(self) -> None:
        """Drop every piece of HBM-resident device state derived from
        this segment — uploaded columns, the cached live-mask upload,
        layout-permuted live views, any PAGED tile buffers the tiered
        pager holds (index/tiering.py; their fielddata breaker holds
        release here, idempotently — the per-segment weakref backstop
        finding them already gone is a no-op, never a double-release)
        — AND the resident executables pinned on them
        (search/resident.py): a pinned program holds references into
        the dropped column tree, so leaving it cached would defeat the
        cache clear (and serve arrays the caller just asked to free).
        The sticky page/don't-page decision also resets: a re-upload
        re-decides against the CURRENT budget."""
        # IVF probe arrays (index/ann.ensure_ann_device) release their
        # fielddata hold deterministically here; the weakref backstop
        # finding them already released is a no-op (idempotent holds)
        for entry in getattr(self, "_ann_device", {}).values():
            hold = entry.get("_breaker_hold")
            if hold is not None:
                hold.release()
        for attr in ("_device", "_live_dev", "_live_view_cache",
                     "_tile_store", "_tiering_paged", "_ann_device"):
            if hasattr(self, attr):
                delattr(self, attr)
        from .tiering import drop_segment_tiles
        drop_segment_tiles(self.seg_id)
        from ..search.resident import evict_segment
        evict_segment(self.seg_id)

    def nbytes(self) -> int:
        n = 0
        for f in self.text.values():
            n += f.nbytes()
        for f in self.keywords.values():
            n += f.nbytes()
        for f in self.numerics.values():
            n += f.nbytes()
        for f in self.vectors.values():
            n += f.nbytes()
        # NOTE: lazily-built IVF indexes (self.ann) are excluded — their
        # device upload is breaker-accounted separately at ensure time
        # (search/executor.ensure_ann_device), after this estimate was
        # already held
        for f in self.geos.values():
            n += f.nbytes()
        return n

    def fingerprint(self) -> str:
        """Content fingerprint for restart-stable caches (the fused
        autotuner persists backend choices under it). Derived from the
        pack's shape-and-statistics signature — cheap, deterministic,
        and different whenever a refresh/merge rebuilds the segment with
        different contents — NOT from seg_id, which is minted fresh
        every process start."""
        cached = getattr(self, "_fingerprint", None)
        if cached is not None:
            return cached
        import hashlib
        h = hashlib.blake2b(digest_size=12)
        h.update(f"{self.capacity}|{self.num_docs}".encode())
        for f in sorted(self.text):
            pf = self.text[f]
            h.update(f"|t:{f}:{len(pf.terms)}:{int(pf.df.sum())}:"
                     f"{float(pf.doc_len.sum()):.3f}".encode())
        for f in sorted(self.keywords):
            kc = self.keywords[f]
            h.update(f"|k:{f}:{kc.cardinality}:{int(kc.df.sum())}".encode())
        for f in sorted(self.numerics):
            nc = self.numerics[f]
            # value-sensitive, not just count-sensitive: a refresh that
            # rewrites values but not doc counts must still re-key
            vsum = float(np.where(nc.exists,
                                  np.nan_to_num(
                                      nc.values.astype(np.float64)),
                                  0.0).sum())
            h.update(f"|n:{f}:{nc.kind}:{int(nc.exists.sum())}:"
                     f"{vsum:.6g}".encode())
        fp = h.hexdigest()
        self._fingerprint = fp  # type: ignore[attr-defined]
        return fp

    def cache_key(self) -> str:
        """Key for fingerprint-keyed caches (autotune choices, resident
        executables). Base segments key on content (`fingerprint()`),
        so a compaction re-keys. DELTA segments key on the base
        generation plus the pow2 delta-extent bucket INSTEAD of
        content: a refresh rebuilds the delta with new docs but the
        same key until its capacity bucket grows, so every cache keyed
        here survives the epoch bump untouched — refresh is an epoch
        bump, not an eviction."""
        if self.delta_parent is None:
            return self.fingerprint()
        return f"delta({self.delta_parent}):c{next_pow2(self.capacity, floor=BLOCK)}"

    def ensure_text_sort_column(self, field: str) -> bool:
        """Materialize a sortable ordinal view of an analyzed text field:
        per-doc MIN term ordinal over the postings (ref: ES 2.0 allowed
        sorting on analyzed strings via string fielddata; Lucene
        SortedSetDVs MultiValueMode.MIN). Built lazily on first sort,
        registered as a keyword column so the device sort path applies
        unchanged. Returns True only when a NEW column was materialized
        (callers must then invalidate any global-ordinal caches)."""
        with _TEXT_SORT_LOCK:
            if field in self.keywords:
                return False
            pf = self.text.get(field)
            if pf is None:
                return False
            sentinel = np.iinfo(np.int64).max
            ords64 = np.full(self.capacity, sentinel, dtype=np.int64)
            tids = np.repeat(np.arange(len(pf.terms), dtype=np.int64),
                             np.diff(pf.indptr))
            np.minimum.at(ords64, pf.doc_ids, tids)
            ords = np.where(ords64 == sentinel, -1,
                            ords64).astype(np.int32)
            col = KeywordColumn(
                name=field, terms=list(pf.terms),
                term_index=dict(pf.term_index),
                ords=ords, df=pf.df.astype(np.int32))
            # copy-on-write: concurrent searches/stats iterate these
            # dicts (ThreadingHTTPServer), so swap whole objects rather
            # than mutating in place; in-flight readers keep a
            # consistent snapshot either way
            self.keywords = {**self.keywords, field: col}
            dev = getattr(self, "_device", None)
            if dev is not None:
                import jax.numpy as jnp
                self._device = {**dev, "kw": {**dev["kw"],
                                              field: jnp.asarray(ords)}}
                self.device_changed(rebuilt=True)
            return True

    def field_kind(self, name: str) -> str | None:
        if name in self.text:
            return "text"
        if name in self.keywords:
            return "keyword"
        if name in self.numerics:
            return "numeric"
        if name in self.vectors:
            return "vector"
        if name in self.geos:
            return "geo"
        return None


# ---------------------------------------------------------------------------
# Builder
# ---------------------------------------------------------------------------


class _TermNumbers(dict):
    """term -> its number in order of first sight; looking up a term
    that has none numbers it."""

    __slots__ = ()

    def __missing__(self, term: str) -> int:
        number = self[term] = len(self)
        return number


class _TextTokens:
    """One text field's tokens as `SegmentBuilder.build`'s document loop
    leaves them: the number of every token's term in `tids`, one after
    another in (row, position) order, and for each row that has the
    field how many tokens it holds. Rows and positions are not stored a
    token: `_build_postings` spreads them from `rows` and `counts`."""

    __slots__ = ("numbers", "tids", "rows", "counts")

    def __init__(self):
        self.numbers = _TermNumbers()
        self.tids = array("i")
        self.rows: list[int] = []
        self.counts: list[int] = []

    def add(self, row: int, tokens: list[str]) -> None:
        """Two values of one field in one row concatenate (positions run
        on), so a row contributes one posting a term."""
        if self.rows and self.rows[-1] == row:
            self.counts[-1] += len(tokens)
        else:
            self.rows.append(row)
            self.counts.append(len(tokens))
        self.tids.extend(map(self.numbers.__getitem__, tokens))


class SegmentBuilder:
    """Accumulates parsed documents, emits an immutable Segment.

    Ref analog: the indexing buffer + DocumentsWriter flush in Lucene
    (engine refresh path, index/engine/InternalEngine.java:549-555).

    `similarity` maps a text field name to the Similarity whose impacts
    get baked into that field's posting blocks (ref:
    index/similarity/SimilarityService.java resolved per FieldMapper);
    None = BM25 for every field.
    """

    _counter = 0

    def __init__(self, similarity=None):
        self.docs: list[ParsedDocument] = []
        self.versions: list[int] = []
        self.parent_of: list[int] = []
        self.similarity = similarity  # Callable[[str], Similarity] | None

    def add(self, doc: ParsedDocument, version: int = 1) -> None:
        """Nested sub-documents are laid out as hidden rows BEFORE their
        parent (Lucene block-join order) with a parent pointer."""
        from .mapping import ParsedField, KEYWORD
        n_children = len(doc.nested)
        parent_row = len(self.docs) + n_children
        for i, entry in enumerate(doc.nested):
            path, fields = entry[0], list(entry[1])
            src = entry[2] if len(entry) > 2 else b""
            if not any(f.name == "_nested_path" for f in fields):
                fields.append(ParsedField(name="_nested_path", type=KEYWORD,
                                          value=path))
            self.docs.append(ParsedDocument(
                doc_id=f"{doc.doc_id}\x00{path}\x00{i}", source=src,
                fields=fields))
            self.versions.append(version)
            self.parent_of.append(parent_row)
        self.docs.append(doc)
        self.versions.append(version)
        self.parent_of.append(-1)

    def __len__(self) -> int:
        return len(self.docs)

    @property
    def num_docs(self) -> int:
        return len(self.docs)

    def build(self, seg_id: str | None = None) -> Segment:
        if seg_id is None:
            SegmentBuilder._counter += 1
            seg_id = f"seg_{SegmentBuilder._counter}"
        n = len(self.docs)
        cap = next_pow2(n, floor=BLOCK)

        ids: list[str] = []
        id_map: dict[str, int] = {}
        sources: list[bytes] = []
        # field name -> accumulated data
        text_tokens: dict[str, _TextTokens] = {}
        kw_values: dict[str, dict[int, str]] = {}
        num_values: dict[str, tuple[str, dict[int, float | int]]] = {}
        vec_values: dict[str, dict[int, list[float]]] = {}
        geo_values: dict[str, dict[int, tuple[float, float]]] = {}
        comp_values: dict[str, list[tuple[int, dict]]] = {}

        for d, doc in enumerate(self.docs):
            ids.append(doc.doc_id)
            id_map[doc.doc_id] = d
            sources.append(doc.source)
            # accumulate per-field; multiple ParsedFields with same name =
            # array values (text concatenates tokens BEFORE tf counting so a
            # doc contributes exactly one postings entry per term; keyword/
            # numeric keep first — multi-valued columns land round 2)
            for pf in doc.fields:
                if pf.type == TEXT:
                    toks = text_tokens.get(pf.name)
                    if toks is None:
                        toks = text_tokens[pf.name] = _TextTokens()
                    toks.add(d, pf.tokens or ())
                elif pf.type == KEYWORD:
                    col = kw_values.setdefault(pf.name, {})
                    col.setdefault(d, []).append(str(pf.value))
                elif pf.type == DENSE_VECTOR:
                    vcol = vec_values.setdefault(pf.name, {})
                    if d not in vcol:
                        vcol[d] = pf.value  # type: ignore[assignment]
                elif pf.type == GEO_POINT:
                    gcol = geo_values.setdefault(pf.name, {})
                    if d not in gcol:
                        gcol[d] = pf.value  # (lat, lon)
                elif pf.type == "completion":
                    comp_values.setdefault(pf.name, []).append((d, pf.value))
                else:
                    kind, col = num_values.setdefault(pf.name, (pf.type, {}))
                    col.setdefault(d, []).append(pf.value)

        text = {
            name: self._build_postings(name, toks, n, cap,
                                       self._sim_for(name))
            for name, toks in text_tokens.items()
        }
        keywords = {
            name: self._build_keyword(name, col, cap)
            for name, col in kw_values.items()
        }
        numerics = {
            name: self._build_numeric(name, kind, col, cap)
            for name, (kind, col) in num_values.items()
        }
        vectors = {
            name: self._build_vector(name, col, cap)
            for name, col in vec_values.items()
        }
        geos = {
            name: self._build_geo(name, col, cap)
            for name, col in geo_values.items()
        }
        completions = {
            name: CompletionColumn(name=name, entries=entries)
            for name, entries in comp_values.items()
        }

        parent_of = None
        if any(p >= 0 for p in self.parent_of):
            parent_of = np.full(cap, -1, dtype=np.int32)
            parent_of[:n] = self.parent_of
        return Segment(
            seg_id=seg_id, num_docs=n, capacity=cap,
            ids=ids, id_map=id_map, sources=sources,
            versions=np.asarray(self.versions, dtype=np.int64),
            text=text, keywords=keywords, numerics=numerics, vectors=vectors,
            geos=geos, completions=completions, parent_of=parent_of,
        )

    def _sim_for(self, field: str):
        if self.similarity is None:
            return None
        return self.similarity(field)

    @staticmethod
    def _build_geo(name: str, col: dict[int, tuple[float, float]], cap: int
                   ) -> GeoColumn:
        lat = np.zeros(cap, dtype=np.float32)
        lon = np.zeros(cap, dtype=np.float32)
        exists = np.zeros(cap, dtype=bool)
        for d, (la, lo) in col.items():
            lat[d] = la
            lon[d] = lo
            exists[d] = True
        return GeoColumn(name=name, lat=lat, lon=lon, exists=exists)

    @staticmethod
    def _build_vector(name: str, col: dict[int, list[float]], cap: int
                      ) -> VectorColumn:
        dims = len(next(iter(col.values())))
        values = np.zeros((cap, dims), dtype=np.float32)
        exists = np.zeros(cap, dtype=bool)
        for d, vec in col.items():
            values[d, : len(vec)] = np.asarray(vec, dtype=np.float32)
            exists[d] = True
        norms = np.linalg.norm(values, axis=1).astype(np.float32)
        return VectorColumn(name=name, values=values, exists=exists,
                            norms=norms)

    # -- per-field builders ------------------------------------------------

    @staticmethod
    def _build_postings(name: str, toks: _TextTokens, n_docs: int, cap: int,
                        sim=None) -> PostingsField:
        """One field's postings from its tokens, all at once: the term
        ids are the ranks of the terms in sorted order; one stable sort
        of the tokens by term leaves them in (term, row, position) order
        (they came in (row, position) order), so the heads of its
        (term, row) runs are the postings, the runs' lengths the tfs and
        the sorted positions the positional sidecar."""
        terms = sorted(toks.numbers)
        T = len(terms)
        term_index = dict(zip(terms, range(T)))
        first_seen = np.fromiter(map(toks.numbers.__getitem__, terms),
                                 dtype=np.int64, count=T)
        rank = np.empty(T, dtype=np.int32)
        rank[first_seen] = np.arange(T, dtype=np.int32)
        rows = np.asarray(toks.rows, dtype=np.int32)
        counts = np.asarray(toks.counts, dtype=np.int64)
        doc_len = np.zeros(cap, dtype=np.float32)
        doc_len[rows] = counts
        n_tok = len(toks.tids)
        tok_row = np.repeat(rows, counts)
        tok_pos = (np.arange(n_tok, dtype=np.int64)
                   - np.repeat(np.cumsum(counts) - counts, counts)
                   ).astype(np.int32)
        tok_tid = rank[np.frombuffer(toks.tids, dtype=np.int32)]

        order = np.argsort(tok_tid, kind="stable")
        tid_s, row_s = tok_tid[order], tok_row[order]
        head = np.ones(n_tok, dtype=bool)
        head[1:] = (tid_s[1:] != tid_s[:-1]) | (row_s[1:] != row_s[:-1])
        heads = np.flatnonzero(head)
        doc_ids = row_s[heads]  # ascending inside a term: rows came in order
        pos_indptr = np.append(heads, n_tok).astype(np.int64, copy=False)
        tfs = np.diff(pos_indptr).astype(np.float32)
        pos_data = tok_pos[order]
        df = np.bincount(tid_s[heads], minlength=T).astype(np.int32)
        indptr = np.zeros(T + 1, dtype=np.int64)
        np.cumsum(df, out=indptr[1:])

        doc_count = int(np.count_nonzero(doc_len[:n_docs])) or n_docs
        total_len = float(doc_len.sum())
        avg_len = (total_len / doc_count) if doc_count else 1.0

        pf = PostingsField(
            name=name, terms=terms, term_index=term_index, df=df,
            indptr=indptr, doc_ids=doc_ids, tfs=tfs,
            doc_len=doc_len, doc_count=doc_count, avg_len=max(avg_len, 1e-9),
            pos_data=pos_data, pos_indptr=pos_indptr,
        )
        SegmentBuilder._layout_blocks(pf, cap, sim)
        return pf

    @staticmethod
    def _layout_blocks(pf: PostingsField, cap: int, sim=None) -> None:
        """Pack host CSR postings into 128-lane blocks with eager impacts.

        The impact formula comes from the field's Similarity (BM25 by
        default; index/similarity.py) — the only place a similarity
        choice touches the engine; every query path downstream consumes
        impacts uniformly."""
        _pack_layout(pf, cap, _flat_impacts(pf, sim))

    @staticmethod
    def _build_keyword(name: str, col: dict[int, list[str]], cap: int
                       ) -> KeywordColumn:
        terms = sorted({v for vs in col.values() for v in vs})
        term_index = {t: i for i, t in enumerate(terms)}
        per_doc = {d: sorted({term_index[v] for v in vs})
                   for d, vs in col.items()}
        ords = np.full(cap, -1, dtype=np.int32)
        for d, os_ in per_doc.items():
            ords[d] = os_[0]           # MIN ord (MultiValueMode.MIN)
        df = np.zeros(len(terms), dtype=np.int32)
        for os_ in per_doc.values():
            df[os_] += 1               # doc freq counts docs, not values
        mv = None
        max_len = max((len(o) for o in per_doc.values()), default=1)
        if max_len > 1:
            M = next_pow2(max_len, floor=2)
            mv = np.full((cap, M), -1, dtype=np.int32)
            for d, os_ in per_doc.items():
                mv[d, : len(os_)] = os_
        return KeywordColumn(name=name, terms=terms, term_index=term_index,
                             ords=ords, df=df, mv_ords=mv)

    @staticmethod
    def _build_numeric(name: str, kind: str, col: dict[int, list],
                       cap: int) -> NumericColumn:
        exists = np.zeros(cap, dtype=bool)
        is_int = kind in (LONG, INTEGER, SHORT, BYTE, DATE, BOOLEAN, IP)
        dt = np.int64 if is_int else np.float64
        raw = np.zeros(cap, dtype=dt)

        def norm(v):
            if kind == BOOLEAN:
                return 1 if v else 0
            return v

        for d, vs in col.items():
            exists[d] = True
            # MIN value, matching the keyword column's MIN-ord sort key
            # (MultiValueMode.MIN, the ES asc-sort default)
            raw[d] = min(norm(v) for v in vs)
        bias = 1 << 31 if kind == IP else 0
        vals = _device_vals(raw, kind, bias, is_int)
        mv_raw = mv_vals = mv_exists = None
        max_len = max((len(v) for v in col.values()), default=1)
        if max_len > 1:
            M = next_pow2(max_len, floor=2)
            mv_raw = np.zeros((cap, M), dtype=dt)
            mv_exists = np.zeros((cap, M), dtype=bool)
            for d, vs in col.items():
                for j, v in enumerate(vs[:M]):
                    mv_raw[d, j] = norm(v)
                    mv_exists[d, j] = True
            mv_vals = _device_vals(mv_raw, kind, bias, is_int)
        return NumericColumn(name=name, kind=kind, values=vals, exists=exists,
                             raw=raw, bias=bias, mv_values=mv_vals,
                             mv_raw=mv_raw, mv_exists=mv_exists)


def _flat_impacts(pf: PostingsField, sim=None) -> np.ndarray:
    """Per-posting eager impacts in CSR order ([nnz] f32), computed from
    the field's Similarity + field stats. Split out of the layout pass
    so an impact-PRESERVING repack (concat_segments, the streaming
    compaction) can feed recovered impacts through the same packer."""
    if sim is None:
        from .similarity import DEFAULT_SIMILARITY
        sim = DEFAULT_SIMILARITY
    return sim.field_impacts(
        pf.tfs.astype(np.float64), pf.doc_len[pf.doc_ids].astype(np.float64),
        pf.indptr, pf.df, doc_count=float(pf.doc_count),
        avg_len=float(pf.avg_len), total_len=float(pf.doc_len.sum()))


def _block_lanes(pf: PostingsField) -> np.ndarray:
    """[nnz] int64: where each posting of the CSR lies in the flattened
    block arrays. A term's blocks are contiguous and all but its last
    are full, so posting r of term t is lane `block_start[t] * BLOCK +
    r` (block `block_start[t] + r // BLOCK`, lane `r % BLOCK`)."""
    T = len(pf.terms)
    return (np.arange(len(pf.doc_ids), dtype=np.int64)
            + np.repeat(pf.block_start[:T].astype(np.int64) * BLOCK
                        - pf.indptr[:T], np.diff(pf.indptr)))


def extract_flat_impacts(pf: PostingsField) -> np.ndarray:
    """Recover the [nnz] CSR-order impacts from the packed block arrays
    — the inverse of _pack_layout's block fill, one gather at the lanes
    the packer wrote, exact by construction (no float math). The
    streaming compaction reads impacts back through this so a
    compacted base scores byte-identically to the packs it folded."""
    return pf.block_imps.ravel()[_block_lanes(pf)]


def _pack_layout(pf: PostingsField, cap: int, imps: np.ndarray) -> None:
    """Device layouts (128-lane blocks, forward index, block-max tile
    summary) from CSR postings + precomputed per-posting impacts.

    This is the ONE seam every pack build flows through — builder
    refresh, merge_segments (repack's build-aside) and concat_segments
    (compaction) all land here — so the device-parallel builder
    (index/devbuild.py) hooks in here: when enabled, the layout pass
    runs as exact device scatters (byte-identical output), and ANY
    device error falls back to the host form below."""
    from . import devbuild
    if devbuild.enabled():
        try:
            devbuild.pack_layout_device(pf, cap, imps)
            return
        except Exception as e:
            devbuild.on_fallback("pack_layout", e)
    _pack_layout_host(pf, cap, imps)


def _pack_layout_host(pf: PostingsField, cap: int,
                      imps: np.ndarray) -> None:
    """The layout pass on the host, as index stores over all postings at
    once — the default, the device path's fallback, and the identity
    oracle it is tested against (tests/pack_build_oracle.py holds the
    loops this has to match byte for byte)."""
    T = len(pf.terms)
    counts = np.diff(pf.indptr)
    block_start = np.zeros(T + 1, dtype=np.int32)
    np.cumsum((counts + BLOCK - 1) // BLOCK, out=block_start[1:])
    nb_pad = next_pow2(int(block_start[-1]), floor=1)
    pf.block_start = block_start
    lanes = _block_lanes(pf)
    imps32 = np.asarray(imps, dtype=np.float32)
    block_docs = np.full(nb_pad * BLOCK, cap, dtype=np.int32)  # cap = dropped
    block_docs[lanes] = pf.doc_ids
    block_imps = np.zeros(nb_pad * BLOCK, dtype=np.float32)
    block_imps[lanes] = imps32
    pf.block_docs = block_docs.reshape(nb_pad, BLOCK)
    pf.block_imps = block_imps.reshape(nb_pad, BLOCK)

    # forward (doc-major) layout from the same impacts. One doc with
    # thousands of unique terms would inflate the dense [cap, L]
    # arrays for the whole segment, so past MAX_FWD_SLOTS the field
    # skips the forward index and queries take the scatter path.
    lengths = np.bincount(pf.doc_ids, minlength=cap)
    L = next_pow2(int(lengths.max(initial=1)), floor=8)
    if L > MAX_FWD_SLOTS:
        pf.fwd_tids = None
        pf.fwd_imps = None
        return
    tids = np.repeat(np.arange(T, dtype=np.int64), counts)
    cells = pf.doc_ids.astype(np.int64) * L + forward_slot_ranks(pf.doc_ids)
    fwd_tids = np.full(cap * L, -1, dtype=np.int32)
    fwd_tids[cells] = tids
    fwd_imps = np.zeros(cap * L, dtype=np.float32)
    fwd_imps[cells] = imps32
    pf.fwd_tids = fwd_tids.reshape(cap, L)
    pf.fwd_imps = fwd_imps.reshape(cap, L)
    pf.tile_max = tile_summary(tids, pf.doc_ids, imps, T, cap)
    pack_positions(pf, cap)


def forward_slot_ranks(doc_ids: np.ndarray) -> np.ndarray:
    """Per-posting forward-index slot, CSR order — the rank of each
    posting among its doc's postings in term-major order (the device
    builder's ops/build.forward_slots gives the same). The forward fill
    puts a posting's (tid, impact) pair there and the positional pack
    its positions."""
    nnz = len(doc_ids)
    order = np.argsort(doc_ids, kind="stable")
    sorted_docs = doc_ids[order]
    per_doc = np.bincount(sorted_docs)
    first = (np.cumsum(per_doc) - per_doc)[sorted_docs]
    out = np.empty(nnz, dtype=np.int64)
    out[order] = np.arange(nnz, dtype=np.int64) - first
    return out


def position_deltas(pf: PostingsField) -> np.ndarray:
    """[sum tf] int16 delta stream of the position sidecar: per posting
    the first entry is the absolute token position, the rest are gaps
    (strictly positive — one token per position). Exact int math, so
    host and device packs are byte-identical by construction."""
    pd = pf.pos_data.astype(np.int64)
    d = pd.copy()
    d[1:] -= pd[:-1]
    counts = np.diff(pf.pos_indptr)
    starts = pf.pos_indptr[:-1][counts > 0]
    d[starts] = pd[starts]
    return d.astype(np.int16)


def pos_pack_width(pf: PostingsField, cap: int, L: int) -> int | None:
    """P (pow2 positions-per-slot bucket) for a field's positional
    pack, or None with the field staying host-served: no sidecar, tf
    over POS_CAP, positions past the int16 ceiling, or a pack bigger
    than POS_PACK_BUDGET elements. The pow2 bucket is the
    pad_delta_shapes convention: P only changes at pow2 boundaries, so
    delta growth within a bucket never re-shapes the pack."""
    if pf.pos_data is None or pf.pos_indptr is None:
        return None
    max_tf = int(np.diff(pf.pos_indptr).max(initial=0))
    if max_tf <= 0 or max_tf > POS_CAP:
        return None
    if pf.pos_data.size and int(pf.pos_data.max(initial=0)) > POS_MAX_ENC:
        return None
    P = next_pow2(max_tf, floor=2)
    if cap * L * P > POS_PACK_BUDGET:
        return None
    return P


def pack_positions(pf: PostingsField, cap: int) -> None:
    """Build the eager positional column family (fwd_pos + the BM25
    length-norm columns) from the position sidecar, forward-aligned
    with fwd_tids. Shared by the host layout pass and the device
    builder's fallback; ops/build.scatter_positions is the device
    scatter twin (identical int output)."""
    pf.fwd_pos = None
    pf.pos_width = 0
    pf.lnorm = None
    pf.k1ln = None
    if pf.fwd_tids is None:
        return
    L = pf.fwd_tids.shape[1]
    P = pos_pack_width(pf, cap, L)
    if P is None:
        return
    deltas = position_deltas(pf)
    doc_pp, flat_pp = _position_targets(pf, P)
    fwd_pos = np.full((cap, L * P), -1, dtype=np.int16)
    fwd_pos[doc_pp, flat_pp] = deltas
    pf.fwd_pos = fwd_pos
    pf.pos_width = P
    pf.lnorm, pf.k1ln = bm25_norms(pf.doc_len, pf.avg_len)


def _position_targets(pf: PostingsField, P: int
                      ) -> tuple[np.ndarray, np.ndarray]:
    """Per-POSITION (doc row, slot*P + k) scatter targets — host int
    vector math shared by pack_positions and the device builder."""
    counts = np.diff(pf.pos_indptr).astype(np.int64)
    slots = forward_slot_ranks(pf.doc_ids)
    doc_pp = np.repeat(pf.doc_ids.astype(np.int64), counts)
    slot_pp = np.repeat(slots, counts)
    k_pp = (np.arange(int(counts.sum()), dtype=np.int64)
            - np.repeat(pf.pos_indptr[:-1].astype(np.int64), counts))
    return doc_pp, slot_pp * P + k_pp


def pad_delta_shapes(seg: Segment) -> Segment:
    """Bucket every TERM-COUNT-derived device array of a delta segment
    to the next power of two, so the shape signature of the pack — and
    with it every jit program, pinned resident executable, and autotune
    shape bucket — stays constant while the delta grows within a
    bucket. Capacity, forward width L, and block counts are already
    pow2; term count T was the one content-proportional shape left.
    Padded tile_max rows are empty (an absent term bounds to 0 and can
    never un-prune a tile — the PackedShards convention) and its stored
    entries are bucketed the same way;
    padded block_start entries repeat the final block (zero postings).
    Mutates and returns `seg`."""
    for pf in seg.text.values():
        T = len(pf.terms)
        t_pad = next_pow2(max(T, 1), floor=8)
        if pf.tile_max is not None:
            pf.tile_max = pf.tile_max.padded(
                t_pad, next_pow2(len(pf.tile_max.tiles)))
        if pf.block_start is not None and len(pf.block_start) < t_pad + 1:
            pf.block_start = np.concatenate(
                [pf.block_start,
                 np.full(t_pad + 1 - len(pf.block_start),
                         pf.block_start[-1], dtype=pf.block_start.dtype)])
    return seg


def concat_segments(segments: Iterable[Segment], seg_id: str | None = None,
                    live_masks: dict[str, np.ndarray] | None = None
                    ) -> Segment:
    """Impact-PRESERVING columnar concatenation — the streaming write
    path's compaction (fold delta segments into a new base while the
    old generation keeps serving).

    Unlike merge_segments (which re-derives tokens and recomputes
    impacts under the merged field stats), this repack keeps every
    surviving posting's eager impact EXACTLY as the source pack scored
    it: term dictionaries union, doc rows renumber (dead rows drop),
    and the device layouts rebuild from the preserved impacts — so a
    search against the compacted base is byte-identical to the same
    search against the base+delta pair it folded, which is the
    correctness contract the background compaction swap relies on. It
    is also the throughput story (arxiv 1910.11028, BM25S eager
    scoring): compaction cost is a columnar copy, not a re-tokenize +
    re-score of the corpus."""
    from .mapping import ParsedField  # noqa: F401 (parity with merge_segments)
    segs = [s for s in segments if s.num_docs > 0]
    if seg_id is None:
        SegmentBuilder._counter += 1
        seg_id = f"seg_{SegmentBuilder._counter}"

    # -- row survival + renumbering ---------------------------------------
    keeps: list[np.ndarray] = []          # bool [num_docs] per seg
    row_maps: list[np.ndarray] = []       # old row -> new row (-1 dead)
    n = 0
    for s in segs:
        live = None if live_masks is None else live_masks.get(s.seg_id)
        keep = (np.ones(s.num_docs, dtype=bool) if live is None
                else np.array(live[: s.num_docs], dtype=bool, copy=True))
        if s.parent_of is not None:
            ch = s.parent_of[: s.num_docs] >= 0
            keep[ch] &= keep[s.parent_of[: s.num_docs][ch]]
        rm = np.full(s.num_docs, -1, dtype=np.int64)
        rm[keep] = n + np.arange(int(keep.sum()))
        keeps.append(keep)
        row_maps.append(rm)
        n += int(keep.sum())
    cap = next_pow2(n, floor=BLOCK)

    ids: list[str] = []
    sources: list[bytes] = []
    versions = np.ones(n, dtype=np.int64)
    parent_new = np.full(cap, -1, dtype=np.int32)
    any_nested = False
    for s, keep, rm in zip(segs, keeps, row_maps):
        for d in np.nonzero(keep)[0]:
            d = int(d)
            ids.append(s.ids[d])
            sources.append(s.sources[d])
            versions[rm[d]] = int(s.versions[d])
            if s.parent_of is not None and s.parent_of[d] >= 0:
                parent_new[rm[d]] = rm[int(s.parent_of[d])]
                any_nested = True

    # -- text fields: CSR merge with preserved impacts --------------------
    text: dict[str, PostingsField] = {}
    text_names = sorted({f for s in segs for f in s.text})
    for name in text_names:
        all_terms = sorted({t for s in segs for t in
                            (s.text[name].terms if name in s.text else ())})
        t_index = {t: i for i, t in enumerate(all_terms)}
        tid_parts, doc_parts, tf_parts, imp_parts = [], [], [], []
        pos_parts, plen_parts = [], []
        doc_len = np.zeros(cap, dtype=np.float32)
        # one legacy source without the positional sidecar poisons the
        # merged field's: an EMPTY pos array would make phrase queries
        # silently match nothing, where pos_data=None correctly
        # degrades them (QueryBinder's conjunctive approximation)
        have_positions = all(s.text[name].pos_data is not None
                             for s in segs if name in s.text)
        for s, keep, rm in zip(segs, keeps, row_maps):
            pf = s.text.get(name)
            if pf is None:
                continue
            kept_rows = np.nonzero(keep)[0]
            doc_len[rm[kept_rows]] += pf.doc_len[kept_rows]
            nnz = len(pf.doc_ids)
            if nnz == 0:
                continue
            sel = keep[pf.doc_ids]
            if not sel.any():
                continue
            tids = np.repeat(np.arange(len(pf.terms), dtype=np.int64),
                             np.diff(pf.indptr))
            remap = np.asarray([t_index[t] for t in pf.terms],
                               dtype=np.int64)
            flat = extract_flat_impacts(pf)
            tid_parts.append(remap[tids[sel]])
            doc_parts.append(rm[pf.doc_ids[sel]])
            tf_parts.append(pf.tfs[sel])
            imp_parts.append(flat[sel])
            if pf.pos_data is not None:
                plens = np.diff(pf.pos_indptr)[sel]
                plen_parts.append(plens)
                pos_sel = np.repeat(sel, np.diff(pf.pos_indptr))
                pos_parts.append(pf.pos_data[pos_sel])
            else:
                plen_parts.append(np.zeros(int(sel.sum()), dtype=np.int64))
                pos_parts.append(np.empty(0, dtype=np.int32))
        if tid_parts:
            tid_all = np.concatenate(tid_parts)
            doc_all = np.concatenate(doc_parts)
            tf_all = np.concatenate(tf_parts)
            imp_all = np.concatenate(imp_parts)
            plen_all = np.concatenate(plen_parts)
            pos_all = (np.concatenate(pos_parts) if pos_parts
                       else np.empty(0, dtype=np.int32))
        else:
            tid_all = doc_all = np.empty(0, dtype=np.int64)
            tf_all = imp_all = np.empty(0, dtype=np.float32)
            plen_all = np.empty(0, dtype=np.int64)
            pos_all = np.empty(0, dtype=np.int32)
        # stable (term, new-doc) order: per-seg runs are doc-ascending
        # and row renumbering is order-preserving, so lexsort == the
        # concat order a fresh build over the same rows would produce
        order = np.lexsort((doc_all, tid_all))
        tid_all, doc_all = tid_all[order], doc_all[order]
        tf_all, imp_all = tf_all[order], imp_all[order]
        plen_all = plen_all[order]
        # positions follow their posting through the permutation
        pos_off = np.zeros(len(plen_all) + 1, dtype=np.int64)
        if len(plen_all):
            pre = np.concatenate(plen_parts)  # pre-permutation lengths
            starts = np.zeros(len(pre) + 1, dtype=np.int64)
            np.cumsum(pre, out=starts[1:])
            chunks = [pos_all[starts[j]: starts[j + 1]] for j in order]
            pos_all = (np.concatenate(chunks) if chunks
                       else np.empty(0, dtype=np.int32))
            np.cumsum(plen_all, out=pos_off[1:])
        T = len(all_terms)
        df = np.bincount(tid_all, minlength=T).astype(np.int32)
        indptr = np.zeros(T + 1, dtype=np.int64)
        np.cumsum(df, out=indptr[1:])
        doc_count = int(np.count_nonzero(doc_len[:n])) or n
        total_len = float(doc_len.sum())
        avg_len = (total_len / doc_count) if doc_count else 1.0
        pf_new = PostingsField(
            name=name, terms=all_terms, term_index=t_index, df=df,
            indptr=indptr, doc_ids=doc_all.astype(np.int32),
            tfs=tf_all.astype(np.float32), doc_len=doc_len,
            doc_count=doc_count, avg_len=max(avg_len, 1e-9),
            pos_data=(pos_all.astype(np.int32) if have_positions
                      else None),
            pos_indptr=(pos_off if have_positions else None),
        )
        _pack_layout(pf_new, cap, imp_all.astype(np.float32))
        text[name] = pf_new

    # -- keyword columns ---------------------------------------------------
    keywords: dict[str, KeywordColumn] = {}
    kw_names = sorted({f for s in segs for f in s.keywords
                       if f not in s.text})  # text-sort views rebuild lazily
    for name in kw_names:
        all_terms = sorted({t for s in segs
                            for t in (s.keywords[name].terms
                                      if name in s.keywords else ())})
        t_index = {t: i for i, t in enumerate(all_terms)}
        ords = np.full(cap, -1, dtype=np.int32)
        mv_width = 0
        per_seg_remap = []
        for s in segs:
            kc = s.keywords.get(name)
            per_seg_remap.append(
                None if kc is None else
                np.asarray([t_index[t] for t in kc.terms], dtype=np.int32))
            if kc is not None and kc.mv_ords is not None:
                mv_width = max(mv_width, kc.mv_ords.shape[1])
        mv = (np.full((cap, next_pow2(mv_width, floor=2)), -1,
                      dtype=np.int32) if mv_width else None)
        df = np.zeros(len(all_terms), dtype=np.int32)
        for s, keep, rm, remap in zip(segs, keeps, row_maps,
                                      per_seg_remap):
            kc = s.keywords.get(name)
            if kc is None or remap is None:
                continue
            rows = np.nonzero(keep)[0]
            loc = kc.ords[rows]
            has = loc >= 0
            ords[rm[rows[has]]] = remap[loc[has]]
            if kc.mv_ords is not None and mv is not None:
                lmv = kc.mv_ords[rows]
                hmv = lmv >= 0
                vals = np.where(hmv, remap[np.clip(lmv, 0, None)], -1)
                mv[rm[rows], : lmv.shape[1]] = vals
                for r, row_vals in zip(rm[rows], vals):
                    u = np.unique(row_vals[row_vals >= 0])
                    df[u] += 1
            else:
                if mv is not None:
                    mv[rm[rows[has]], 0] = remap[loc[has]]
                u, c = np.unique(remap[loc[has]], return_counts=True)
                df[u] += c.astype(np.int32)
        keywords[name] = KeywordColumn(
            name=name, terms=all_terms, term_index=t_index, ords=ords,
            df=df, mv_ords=mv)

    # -- numeric / vector / geo / completion columns -----------------------
    numerics: dict[str, NumericColumn] = {}
    num_names = sorted({f for s in segs for f in s.numerics})
    for name in num_names:
        kind = next(s.numerics[name].kind for s in segs
                    if name in s.numerics)
        is_int = all(s.numerics[name].raw.dtype == np.int64
                     for s in segs if name in s.numerics)
        dt = np.int64 if is_int else np.float64
        raw = np.zeros(cap, dtype=dt)
        exists = np.zeros(cap, dtype=bool)
        mv_width = max((s.numerics[name].mv_raw.shape[1]
                        for s in segs if name in s.numerics
                        and s.numerics[name].mv_raw is not None),
                       default=0)
        mv_raw = (np.zeros((cap, mv_width), dtype=dt) if mv_width else None)
        mv_exists = (np.zeros((cap, mv_width), dtype=bool)
                     if mv_width else None)
        bias = 1 << 31 if kind == IP else 0
        for s, keep, rm in zip(segs, keeps, row_maps):
            nc = s.numerics.get(name)
            if nc is None:
                continue
            rows = np.nonzero(keep)[0]
            raw[rm[rows]] = nc.raw[rows].astype(dt)
            exists[rm[rows]] = nc.exists[rows]
            if mv_raw is not None:
                if nc.mv_raw is not None:
                    w = nc.mv_raw.shape[1]
                    mv_raw[rm[rows], :w] = nc.mv_raw[rows].astype(dt)
                    mv_exists[rm[rows], :w] = nc.mv_exists[rows]
                else:
                    has = nc.exists[rows]
                    mv_raw[rm[rows[has]], 0] = nc.raw[rows[has]].astype(dt)
                    mv_exists[rm[rows[has]], 0] = True
        numerics[name] = NumericColumn(
            name=name, kind=kind, values=_device_vals(raw, kind, bias,
                                                      is_int),
            exists=exists, raw=raw, bias=bias,
            mv_values=(None if mv_raw is None
                       else _device_vals(mv_raw, kind, bias, is_int)),
            mv_raw=mv_raw, mv_exists=mv_exists)

    vectors: dict[str, VectorColumn] = {}
    for name in sorted({f for s in segs for f in s.vectors}):
        dims = next(s.vectors[name].dims for s in segs if name in s.vectors)
        vals = np.zeros((cap, dims), dtype=np.float32)
        exists = np.zeros(cap, dtype=bool)
        for s, keep, rm in zip(segs, keeps, row_maps):
            vc = s.vectors.get(name)
            if vc is None:
                continue
            rows = np.nonzero(keep)[0]
            vals[rm[rows]] = vc.values[rows]
            exists[rm[rows]] = vc.exists[rows]
        vectors[name] = VectorColumn(
            name=name, values=vals, exists=exists,
            norms=np.linalg.norm(vals, axis=1).astype(np.float32))

    # -- ANN carry-over: skip the IVF rebuild when the source column is
    # unchanged. When exactly ONE source segment holds a vector field,
    # already has its IVF index, and every one of its rows survives at
    # the SAME ordinal (identity row map — the deletes-only / pure-
    # append compaction shape), the merged column is byte-equal to the
    # source column, so the source index (centroids, members, radii)
    # is still exact and transplants as-is instead of re-clustering.
    ann_carry: dict[str, object] = {}
    for name in vectors:
        srcs = [(s, keep, rm) for s, keep, rm
                in zip(segs, keeps, row_maps) if name in s.vectors]
        if len(srcs) != 1:
            continue
        s0, keep0, rm0 = srcs[0]
        src_ai = s0.ann.get(name)
        if src_ai is None or not bool(keep0.all()):
            continue
        if not np.array_equal(rm0, np.arange(s0.num_docs)):
            continue
        ann_carry[name] = src_ai
        from . import devbuild
        devbuild.count_skipped("ann")

    geos: dict[str, GeoColumn] = {}
    for name in sorted({f for s in segs for f in s.geos}):
        lat = np.zeros(cap, dtype=np.float32)
        lon = np.zeros(cap, dtype=np.float32)
        exists = np.zeros(cap, dtype=bool)
        for s, keep, rm in zip(segs, keeps, row_maps):
            gc = s.geos.get(name)
            if gc is None:
                continue
            rows = np.nonzero(keep)[0]
            lat[rm[rows]] = gc.lat[rows]
            lon[rm[rows]] = gc.lon[rows]
            exists[rm[rows]] = gc.exists[rows]
        geos[name] = GeoColumn(name=name, lat=lat, lon=lon, exists=exists)

    completions: dict[str, CompletionColumn] = {}
    for name in sorted({f for s in segs for f in s.completions}):
        entries: list[tuple[int, dict]] = []
        for s, keep, rm in zip(segs, keeps, row_maps):
            cc = s.completions.get(name)
            if cc is None:
                continue
            for row, entry in cc.entries:
                if row < len(keep) and keep[row]:
                    entries.append((int(rm[row]), entry))
        completions[name] = CompletionColumn(name=name, entries=entries)

    return Segment(
        seg_id=seg_id, num_docs=n, capacity=cap,
        ids=ids, id_map={i: j for j, i in enumerate(ids)},
        sources=sources, versions=versions,
        text=text, keywords=keywords, numerics=numerics, vectors=vectors,
        ann=ann_carry,
        geos=geos, completions=completions,
        parent_of=parent_new if any_nested else None,
        impacts_preserved=True,
    )


def _device_vals(raw: np.ndarray, kind: str, bias: int,
                 is_int: bool) -> np.ndarray:
    """Host-exact raw values -> device column dtype (see NumericColumn)."""
    if kind == DATE:
        return (raw // 1000).astype(np.int32)   # epoch seconds, int32-exact
    if kind == IP:
        # uint32 address space biased into int32 so adjacent IPs stay
        # exact (float32's 24-bit mantissa would smear /24 ranges)
        return (raw - bias).astype(np.int32)
    if is_int:
        lo, hi = raw.min(initial=0), raw.max(initial=0)
        if np.iinfo(np.int32).min <= lo and hi <= np.iinfo(np.int32).max:
            return raw.astype(np.int32)
        return raw.astype(np.float32)  # precision caveat: > 2^24 longs
    return raw.astype(np.float32)


class _ConstList:
    """O(1)-memory stand-in for per-doc host lists (sources of a
    columnar bulk load are synthesized, not stored)."""

    __slots__ = ("_value", "_n")

    def __init__(self, value, n: int):
        self._value = value
        self._n = n

    def __len__(self) -> int:
        return self._n

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [self._value] * len(range(*i.indices(self._n)))
        return self._value


class _RangeIds:
    """Virtual id list "0".."n-1" — 20M python strings would cost GBs."""

    __slots__ = ("_n",)

    def __init__(self, n: int):
        self._n = n

    def __len__(self) -> int:
        return self._n

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [str(j) for j in range(*i.indices(self._n))]
        if i < 0:
            i += self._n
        if not 0 <= i < self._n:
            raise IndexError(i)
        return str(i)

    def __iter__(self):
        return (str(i) for i in range(self._n))


class _RangeIdMap:
    """Virtual {str(i): i} map matching _RangeIds."""

    __slots__ = ("_n",)

    def __init__(self, n: int):
        self._n = n

    def get(self, key, default=None):
        try:
            i = int(key)
        except (TypeError, ValueError):
            return default
        if 0 <= i < self._n and str(i) == key:
            return i
        return default

    def __getitem__(self, key):
        v = self.get(key)
        if v is None:
            raise KeyError(key)
        return v

    def __contains__(self, key) -> bool:
        return self.get(key) is not None

    def __len__(self) -> int:
        return self._n


def build_columnar(seg_id: str, n: int, *,
                   keywords: dict[str, np.ndarray] | None = None,
                   numerics: dict[str, tuple[str, np.ndarray]] | None = None,
                   ids: list[str] | None = None,
                   sources: list[bytes] | None = None,
                   pad_multiple: int = 512) -> Segment:
    """Bulk columnar ingestion: build a Segment directly from numpy
    arrays, vectorized — the path for loading tens of millions of rows
    of analytics data in seconds instead of the doc-by-doc parse
    (which costs minutes at that scale).

    keywords: field -> array of values (any dtype; uniqued into the
    sorted term dictionary). numerics: field -> (mapping_kind, values)
    with values in the field's HOST unit (dates: epoch millis).
    Produces the exact structure SegmentBuilder.build would for the same
    single-valued data (verified by tests/test_columnar_build.py).

    Capacity pads to `pad_multiple` (not pow2): one big segment compiles
    once, and a 20M-row corpus must not pay pow2's up-to-2x padding in
    every per-query column scan.

    Ref analog: bulk indexing (action/bulk/TransportBulkAction) feeding
    DocumentsWriter — here the flush IS the load.
    """
    cap = max(-(-n // pad_multiple) * pad_multiple, BLOCK)
    kw_cols = {}
    for name, vals in (keywords or {}).items():
        if isinstance(vals, tuple):
            # pre-encoded (terms, ordinals): terms MUST already be in
            # sorted order — uniquing 20M strings is the slow part the
            # caller is skipping
            terms, inv = list(vals[0]), np.asarray(vals[1])
            if any(terms[i] >= terms[i + 1]
                   for i in range(len(terms) - 1)):
                raise ValueError(
                    f"pre-encoded terms for [{name}] must be strictly "
                    "sorted (ordinal order IS term sort order)")
            if inv.size and (inv.min() < 0 or inv.max() >= len(terms)):
                raise ValueError(
                    f"pre-encoded ordinals for [{name}] out of range")
        else:
            vals = np.asarray(vals)
            terms_arr, inv = np.unique(vals, return_inverse=True)
            terms = [str(t) for t in terms_arr]
        ords = np.full(cap, -1, dtype=np.int32)
        ords[:n] = inv.astype(np.int32)
        df = np.bincount(inv, minlength=len(terms)).astype(np.int32)
        kw_cols[name] = KeywordColumn(
            name=name, terms=terms,
            term_index={t: i for i, t in enumerate(terms)},
            ords=ords, df=df)
    num_cols = {}
    for name, (kind, vals) in (numerics or {}).items():
        is_int = kind in (LONG, INTEGER, SHORT, BYTE, DATE, BOOLEAN, IP)
        raw = np.zeros(cap, dtype=np.int64 if is_int else np.float64)
        raw[:n] = vals
        exists = np.zeros(cap, dtype=bool)
        exists[:n] = True
        bias = 1 << 31 if kind == IP else 0
        num_cols[name] = NumericColumn(
            name=name, kind=kind, values=_device_vals(raw, kind, bias,
                                                      is_int),
            exists=exists, raw=raw, bias=bias)
    return Segment(
        seg_id=seg_id, num_docs=n, capacity=cap,
        ids=ids if ids is not None else _RangeIds(n),
        id_map=({i: j for j, i in enumerate(ids)} if ids is not None
                else _RangeIdMap(n)),
        sources=sources if sources is not None else _ConstList(b"{}", n),
        versions=np.ones(n, dtype=np.int64),
        text={}, keywords=kw_cols, numerics=num_cols,
    )


def merge_segments(segments: Iterable[Segment], seg_id: str | None = None,
                   live_masks: dict[str, np.ndarray] | None = None,
                   similarity=None) -> "Segment":
    """Merge segments into one, dropping deleted docs.

    Ref analog: Lucene segment merging driven by TieredMergePolicy
    (index/merge/policy/TieredMergePolicyProvider.java). Columnar merge =
    re-parse-free rebuild from host CSR data.
    """
    from .mapping import ParsedField  # local import to avoid cycle at module load

    builder = SegmentBuilder(similarity=similarity)
    for seg in segments:
        live = None if live_masks is None else live_masks.get(seg.seg_id)
        # invert CSR once per text field: doc -> ordered token list, using
        # the positional sidecar so phrase/span queries survive merges
        doc_terms: dict[str, list[list[str]]] = {}
        for name, pf in seg.text.items():
            per_doc: list[list[str]] = [
                [None] * int(pf.doc_len[d]) for d in range(seg.num_docs)]
            for t_idx, term in enumerate(pf.terms):
                s, e = int(pf.indptr[t_idx]), int(pf.indptr[t_idx + 1])
                for j in range(s, e):
                    d = int(pf.doc_ids[j])
                    if pf.pos_data is not None:
                        ps, pe = int(pf.pos_indptr[j]), int(pf.pos_indptr[j + 1])
                        for p in pf.pos_data[ps:pe]:
                            per_doc[d][int(p)] = term
                    else:  # legacy segment without positions: order unknown
                        slots = per_doc[d]
                        tf = int(pf.tfs[j])
                        placed = 0
                        for i, v in enumerate(slots):
                            if v is None and placed < tf:
                                slots[i] = term
                                placed += 1
            doc_terms[name] = per_doc
        comp_by_row: dict[int, list[tuple[str, dict]]] = {}
        for name, cc in seg.completions.items():
            for row, entry in cc.entries:
                comp_by_row.setdefault(row, []).append((name, entry))

        def row_fields(d: int) -> list[ParsedField]:
            fields: list[ParsedField] = []
            for name in seg.text:
                toks = [t for t in doc_terms[name][d] if t is not None]
                if toks:
                    fields.append(ParsedField(name=name, type=TEXT, tokens=toks))
            for name, entry in comp_by_row.get(d, ()):
                fields.append(ParsedField(name=name, type="completion",
                                          value=entry))
            for name, kc in seg.keywords.items():
                if name in seg.text:
                    continue  # derived text-sort view; rebuilt lazily
                if kc.mv_ords is not None:
                    for o in kc.mv_ords[d]:
                        if o >= 0:
                            fields.append(ParsedField(
                                name=name, type=KEYWORD,
                                value=kc.terms[int(o)]))
                elif kc.ords[d] >= 0:
                    fields.append(ParsedField(name=name, type=KEYWORD,
                                              value=kc.terms[kc.ords[d]]))
            for name, nc in seg.numerics.items():
                if not nc.exists[d]:
                    continue
                if nc.mv_raw is not None:
                    vals = nc.mv_raw[d][nc.mv_exists[d]]
                else:
                    vals = [nc.raw[d]]
                for v in vals:
                    value = int(v) if nc.raw.dtype == np.int64 else float(v)
                    if nc.kind == BOOLEAN:
                        value = bool(v)
                    fields.append(ParsedField(name=name, type=nc.kind,
                                              value=value))
            for name, vc in seg.vectors.items():
                if vc.exists[d]:
                    fields.append(ParsedField(
                        name=name, type=DENSE_VECTOR,
                        value=[float(x) for x in vc.values[d]]))
            for name, gc in seg.geos.items():
                if gc.exists[d]:
                    fields.append(ParsedField(
                        name=name, type=GEO_POINT,
                        value=(float(gc.lat[d]), float(gc.lon[d]))))
            return fields

        # nested child rows re-attach to their parent (block order is
        # rebuilt by SegmentBuilder.add)
        children_of: dict[int, list[int]] = {}
        if seg.parent_of is not None:
            for d in range(seg.num_docs):
                p = int(seg.parent_of[d])
                if p >= 0:
                    children_of.setdefault(p, []).append(d)

        for d in range(seg.num_docs):
            if live is not None and not live[d]:
                continue
            if seg.parent_of is not None and seg.parent_of[d] >= 0:
                continue  # child rows ride with their parent
            doc = ParsedDocument(doc_id=seg.ids[d], source=seg.sources[d],
                                 fields=row_fields(d))
            for c in children_of.get(d, ()):
                cf = row_fields(c)
                path = next((f.value for f in cf
                             if f.name == "_nested_path"), "")
                cf = [f for f in cf if f.name != "_nested_path"]
                doc.nested.append((str(path), cf, seg.sources[c]))
            builder.add(doc, version=int(seg.versions[d]))
    return builder.build(seg_id)
