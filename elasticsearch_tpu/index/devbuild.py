"""Device-parallel pack build — the host driver (ops/build.py holds
the jitted programs).

Pack build is a single-host-thread stage of the engine: every refresh,
compaction, mesh repack and ANN build funnels through the host's sort
and index stores of `SegmentBuilder.build` and `segment._pack_layout`.
This module moves the heavy half onto the hardware as batched JAX
programs:

  host   tokenizes, hashes terms (np.unique) and finalizes term dicts;
  device sorts the (term-id, doc) occurrence stream, segments it into
         postings, packs 128-lane blocks + the forward index, and
         scatter-maxes the block-max tile summary;
  host   computes eager BM25 impacts in the CANONICAL path
         (`segment._flat_impacts`) — float math stays where its bits
         are already defined.

Identity contract: a device-built Segment is BYTE-IDENTICAL to the
host builder's — same `fingerprint()`/`cache_key()`, same eager
impacts bit-for-bit, same tile_max/extrema — because every device
program is exact (see ops/build.py). Every fingerprint-keyed cache,
the autotune store, resident entries and the streaming-delta keying
invariant are therefore untouched by the builder swap.

One path feeds all three consumers: `SegmentBuilder.build` (refresh +
merge_segments, which repack's build-aside uses) and
`concat_segments` (compaction) route their layout pass through
`segment._pack_layout`, whose dispatch seam lands here; the IVF
k-means of `ann.build_ann` promotes through `ops.build.kmeans_device`.

Opt-in: `index.build.device` setting / `ES_TPU_DEVICE_BUILD` env (the
`ann.configure` convention). Any device error falls back to the host
builder automatically (fault-injectable at `site=build`), counted
under `nodes_stats()["indexing"]["device_build"]`.
"""

from __future__ import annotations

import logging
import os
import threading
import time

import numpy as np

from ..utils import faults

logger = logging.getLogger("elasticsearch_tpu.devbuild")

_TRUE = ("1", "true", "on", "yes")

# guards the module config (configure/reset tokens, the ann.py idiom)
_cfg_lock = threading.Lock()
_cfg_enabled: bool | None = None
_cfg_token = 0

# per-thread scope override: the engine's compaction wraps its
# build-aside in enable_scope() so the per-index `index.build.device`
# setting reaches the _pack_layout dispatch seam without flipping the
# process-global flag under concurrent engines
_tls = threading.local()

# guards the build counters surfaced in nodes_stats
_stats_lock = threading.Lock()
_stats = {
    "builds_device": 0,        # full builder.build runs on the device path
    "builds_fallback": 0,      # device errors that fell back to host
    "build_skipped": 0,        # rebuilds short-circuited (deletes-only)
    "docs_device": 0,          # rows ingested through device builds
    "build_device_ms": 0.0,    # wall-time of device builds
    "pack_layout_device": 0,   # _pack_layout calls served by the device
    "kmeans_device": 0,        # IVF k-means loops run on the device
    "tile_minmax_device": 0,   # numeric tile summaries on the device
    "pack_positions_device": 0,  # positional column packs on the device
}


def configure(enabled: bool | None = None) -> int:
    """Set the process-global device-build default; returns a token for
    scoped reset (the ann.configure convention)."""
    global _cfg_enabled, _cfg_token
    with _cfg_lock:
        _cfg_enabled = enabled
        _cfg_token += 1
        return _cfg_token


def reset(if_current: int | None = None) -> None:
    global _cfg_enabled, _cfg_token
    with _cfg_lock:
        if if_current is not None and if_current != _cfg_token:
            return
        _cfg_enabled = None
        _cfg_token += 1


def device_build_default() -> bool:
    """The configured/env default — what an engine without an explicit
    `index.build.device` setting uses. Env wins (read at call time so
    tests can flip it)."""
    env = os.environ.get("ES_TPU_DEVICE_BUILD")
    if env is not None:
        return env.strip().lower() in _TRUE
    with _cfg_lock:
        return bool(_cfg_enabled)


def enabled() -> bool:
    """Whether the _pack_layout/_kmeans dispatch seams take the device
    path right now: a thread-scoped override (enable_scope) beats the
    process default."""
    ov = getattr(_tls, "override", None)
    if ov is not None:
        return bool(ov)
    return device_build_default()


class enable_scope:
    """Thread-scoped device-build override (nestable): the engine's
    per-index setting rides through module-level seams on this."""

    def __init__(self, on: bool = True):
        self._on = bool(on)
        self._prev = None

    def __enter__(self):
        self._prev = getattr(_tls, "override", None)
        _tls.override = self._on
        return self

    def __exit__(self, *exc):
        _tls.override = self._prev
        return False


def _bump(key: str, dv=1) -> None:
    with _stats_lock:
        _stats[key] += dv


def count_skipped(stage: str = "") -> None:
    """A rebuild that was short-circuited because only deletes changed
    (live-mask flips): the source column set is unchanged, so the
    existing pack/ANN index is still exact."""
    _bump("build_skipped")


def on_fallback(stage: str, err: BaseException | None = None) -> None:
    _bump("builds_fallback")
    logger.warning("device build fell back to host at %s: %s", stage,
                   err if err is not None else "error", exc_info=err)


def stats() -> dict:
    """Snapshot for nodes_stats()["indexing"]["device_build"]."""
    with _stats_lock:
        out = dict(_stats)
    ms = out["build_device_ms"]
    out["docs_per_s"] = (out["docs_device"] / (ms / 1000.0)) if ms else 0.0
    return out


def reset_stats() -> None:
    with _stats_lock:
        for k in _stats:
            _stats[k] = 0 if k != "build_device_ms" else 0.0


# ---------------------------------------------------------------------------
# full builder path (engine refresh / merge rebuild)
# ---------------------------------------------------------------------------


def build_segment(builder, seg_id: str | None = None, *,
                  index: str | None = None, shard: int | None = None):
    """Device-parallel SegmentBuilder.build: same accumulation
    semantics, postings construction on the device, automatic host
    fallback on any device error (fault site=build, phase=build)."""
    from .segment import SegmentBuilder
    if seg_id is None:
        SegmentBuilder._counter += 1
        seg_id = f"seg_{SegmentBuilder._counter}"
    try:
        faults.on_dispatch("build", index=index, shard=shard,
                           phase="build")
        t0 = time.monotonic()
        seg = _build_device(builder, seg_id)
        with _stats_lock:
            _stats["builds_device"] += 1
            _stats["docs_device"] += seg.num_docs
            _stats["build_device_ms"] += (time.monotonic() - t0) * 1000.0
        return seg
    except Exception as e:
        on_fallback("build_segment", e)
        return builder.build(seg_id)


def _build_device(builder, seg_id: str):
    """Mirror of SegmentBuilder.build with text fields accumulated as
    flat occurrence streams of token strings (the device sort's
    input). Every non-text column delegates to the
    vectorized builders below (or the host statics for the rare
    multi-valued/ragged shapes), so the resulting Segment is
    byte-identical to `builder.build(seg_id)`."""
    from .mapping import TEXT, KEYWORD, DENSE_VECTOR, GEO_POINT
    from .segment import (
        BLOCK, CompletionColumn, Segment, SegmentBuilder, next_pow2,
    )
    n = len(builder.docs)
    cap = next_pow2(n, floor=BLOCK)

    ids: list[str] = []
    id_map: dict[str, int] = {}
    sources: list[bytes] = []
    occ_tokens: dict[str, list[str]] = {}
    occ_docs: dict[str, list[np.ndarray]] = {}
    occ_pos: dict[str, list[np.ndarray]] = {}
    text_doclen: dict[str, np.ndarray] = {}
    kw_values: dict[str, dict[int, list[str]]] = {}
    num_values: dict[str, tuple[str, dict[int, list]]] = {}
    vec_values: dict[str, dict[int, list[float]]] = {}
    geo_values: dict[str, dict[int, tuple[float, float]]] = {}
    comp_values: dict[str, list[tuple[int, dict]]] = {}

    for d, doc in enumerate(builder.docs):
        ids.append(doc.doc_id)
        id_map[doc.doc_id] = d
        sources.append(doc.source)
        # same multi-field semantics as the host builder: text
        # concatenates tokens per doc; keyword/numeric accumulate
        # value lists; vector/geo keep first; completion appends
        doc_tokens: dict[str, list[str]] = {}
        for pf in doc.fields:
            if pf.type == TEXT:
                doc_tokens.setdefault(pf.name, []).extend(pf.tokens or [])
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
                    gcol[d] = pf.value
            elif pf.type == "completion":
                comp_values.setdefault(pf.name, []).append((d, pf.value))
            else:
                kind, col = num_values.setdefault(pf.name, (pf.type, {}))
                col.setdefault(d, []).append(pf.value)
        for fname, toks in doc_tokens.items():
            if fname not in text_doclen:
                text_doclen[fname] = np.zeros(cap, dtype=np.float32)
                occ_tokens[fname] = []
                occ_docs[fname] = []
                occ_pos[fname] = []
            text_doclen[fname][d] += float(len(toks))
            occ_tokens[fname].extend(toks)
            occ_docs[fname].append(np.full(len(toks), d, dtype=np.int32))
            occ_pos[fname].append(np.arange(len(toks), dtype=np.int32))

    text = {
        name: _build_postings_device(
            name, occ_tokens[name], occ_docs[name], occ_pos[name],
            text_doclen[name], n, cap, builder._sim_for(name))
        for name in occ_tokens
    }
    keywords = {
        name: _build_keyword_columnar(name, col, cap)
        for name, col in kw_values.items()
    }
    numerics = {
        name: _build_numeric_columnar(name, kind, col, cap)
        for name, (kind, col) in num_values.items()
    }
    vectors = {
        name: _build_vector_columnar(name, col, cap)
        for name, col in vec_values.items()
    }
    geos = {
        name: SegmentBuilder._build_geo(name, col, cap)
        for name, col in geo_values.items()
    }
    completions = {
        name: CompletionColumn(name=name, entries=entries)
        for name, entries in comp_values.items()
    }

    parent_of = None
    if any(p >= 0 for p in builder.parent_of):
        parent_of = np.full(cap, -1, dtype=np.int32)
        parent_of[:n] = builder.parent_of
    return Segment(
        seg_id=seg_id, num_docs=n, capacity=cap,
        ids=ids, id_map=id_map, sources=sources,
        versions=np.asarray(builder.versions, dtype=np.int64),
        text=text, keywords=keywords, numerics=numerics, vectors=vectors,
        geos=geos, completions=completions, parent_of=parent_of,
    )


def _build_postings_device(name: str, tokens: list[str],
                           doc_parts: list[np.ndarray],
                           pos_parts: list[np.ndarray],
                           doc_len: np.ndarray, n_docs: int, cap: int,
                           sim=None):
    """Postings for one text field from its flat occurrence stream:
    host np.unique interns the term dict ('<U' code-point order ==
    the host builder's sorted()), the device sorts + segments the
    (term-id, doc) stream, the host computes canonical impacts and the
    device packs the layouts."""
    from .segment import BLOCK, PostingsField, _flat_impacts, next_pow2
    from ..ops import build as ob

    doc_count = int(np.count_nonzero(doc_len[:n_docs])) or n_docs
    total_len = float(doc_len.sum())
    avg_len = (total_len / doc_count) if doc_count else 1.0
    n_occ = len(tokens)
    if n_occ == 0:
        # degenerate field (present but no tokens anywhere): nothing to
        # sort — emit the host builder's empty shapes directly
        pf = PostingsField(
            name=name, terms=[], term_index={},
            df=np.array([], dtype=np.int32),
            indptr=np.zeros(1, dtype=np.int64),
            doc_ids=np.empty(0, dtype=np.int32),
            tfs=np.empty(0, dtype=np.float32),
            doc_len=doc_len, doc_count=doc_count,
            avg_len=max(avg_len, 1e-9),
            pos_data=np.empty(0, dtype=np.int32),
            pos_indptr=np.zeros(1, dtype=np.int64),
        )
        pack_layout_device(pf, cap, np.empty(0, dtype=np.float32))
        return pf

    tok_arr = np.asarray(tokens, dtype=np.str_)
    terms_arr, tids = np.unique(tok_arr, return_inverse=True)
    terms = [str(t) for t in terms_arr]
    term_index = {t: i for i, t in enumerate(terms)}
    T = len(terms)
    doc_occ = np.concatenate(doc_parts)
    pos_occ = np.concatenate(pos_parts)

    pad = np.iinfo(np.int32).max
    batch_cap = next_pow2(n_occ, floor=BLOCK)
    vocab_buckets = next_pow2(T, floor=8)
    tid_p = np.full(batch_cap, pad, dtype=np.int32)
    tid_p[:n_occ] = tids
    doc_p = np.full(batch_cap, pad, dtype=np.int32)
    doc_p[:n_occ] = doc_occ
    pos_p = np.zeros(batch_cap, dtype=np.int32)
    pos_p[:n_occ] = pos_occ

    pos_s, tf, df_pad, _p_tid, p_doc = ob.sort_segment_postings(
        tid_p, doc_p, pos_p, batch_cap=batch_cap,
        vocab_buckets=vocab_buckets)
    df = np.asarray(df_pad)[:T].astype(np.int32, copy=False)
    indptr = np.zeros(T + 1, dtype=np.int64)
    np.cumsum(df, out=indptr[1:])
    nnz = int(indptr[-1])
    tf_h = np.asarray(tf)[:nnz]
    pf = PostingsField(
        name=name, terms=terms, term_index=term_index, df=df,
        indptr=indptr,
        doc_ids=np.asarray(p_doc)[:nnz].astype(np.int32, copy=False),
        tfs=tf_h.astype(np.float32),
        doc_len=doc_len, doc_count=doc_count,
        avg_len=max(avg_len, 1e-9),
        pos_data=np.asarray(pos_s)[:n_occ].astype(np.int32, copy=False),
        pos_indptr=np.concatenate(
            [np.zeros(1, dtype=np.int64),
             np.cumsum(tf_h.astype(np.int64))]),
    )
    # eager impacts: the canonical host path — bit-for-bit the numbers
    # the host builder would bake (see module docstring)
    pack_layout_device(pf, cap, _flat_impacts(pf, sim))
    return pf


# ---------------------------------------------------------------------------
# layout pass (the segment._pack_layout dispatch seam)
# ---------------------------------------------------------------------------


def pack_layout_device(pf, cap: int, imps: np.ndarray) -> None:
    """Device mirror of segment._pack_layout_host: 128-lane blocks,
    forward index and block-max tile summary, all as scatters over
    host-computed unique target indices — byte-identical output.
    Raises on any device error; the caller's seam falls back to the
    host form."""
    from .segment import (
        BLOCK, MAX_FWD_SLOTS, TileSummary, next_pow2, score_tile_size,
        tile_runs, tile_summary_pad,
    )
    from ..ops import build as ob

    faults.on_dispatch("build", phase="pack")
    T = len(pf.terms)
    nnz = len(pf.doc_ids)
    n_blocks_per_term = (np.diff(pf.indptr) + BLOCK - 1) // BLOCK
    block_start = np.zeros(T + 1, dtype=np.int32)
    np.cumsum(n_blocks_per_term, out=block_start[1:])
    nb = int(block_start[-1])
    nb_pad = next_pow2(nb, floor=1)
    if nb_pad * BLOCK >= np.iinfo(np.int32).max:
        raise OverflowError("pack exceeds int32 flat block indexing")

    # per-posting target lanes (host integer vector math, exact)
    tid_pp = np.repeat(np.arange(T, dtype=np.int64), np.diff(pf.indptr))
    r = np.arange(nnz, dtype=np.int64) - pf.indptr[tid_pp]
    flat = ((block_start[tid_pp].astype(np.int64) + r // BLOCK) * BLOCK
            + r % BLOCK)

    batch_cap = next_pow2(max(nnz, 1), floor=BLOCK)
    idx_p = np.full(batch_cap, nb_pad * BLOCK, dtype=np.int32)  # pad: OOB
    idx_p[:nnz] = flat
    docs_p = np.full(batch_cap, cap, dtype=np.int32)
    docs_p[:nnz] = pf.doc_ids
    imps_p = np.zeros(batch_cap, dtype=np.float32)
    imps_p[:nnz] = imps
    bd, bi = ob.pack_block_lanes(idx_p, docs_p, imps_p,
                                 np.int32(cap), nb_cap=nb_pad)
    pf.block_docs = np.asarray(bd).reshape(nb_pad, BLOCK)
    pf.block_imps = np.asarray(bi).reshape(nb_pad, BLOCK)
    pf.block_start = block_start
    _bump("pack_layout_device")

    lengths = np.bincount(pf.doc_ids, minlength=cap) if nnz else \
        np.zeros(cap, dtype=np.int64)
    n_slots = next_pow2(int(lengths.max(initial=1)), floor=8)
    if n_slots > MAX_FWD_SLOTS:
        pf.fwd_tids = None
        pf.fwd_imps = None
        return
    slot_in = np.full(batch_cap, np.iinfo(np.int32).max, dtype=np.int32)
    slot_in[:nnz] = pf.doc_ids
    slots = np.asarray(ob.forward_slots(slot_in))
    # pads ride doc = cap: the row index is out of bounds, so the whole
    # (row, slot) pair is dropped whatever garbage slot they carry
    ft, fi = ob.scatter_forward(docs_p, slots, _padded_i32(tid_pp, batch_cap),
                                imps_p, cap=cap, n_slots=n_slots)
    pf.fwd_tids = np.asarray(ft)
    pf.fwd_imps = np.asarray(fi)

    tile = score_tile_size(cap)
    if cap % tile != 0 or (tile < BLOCK and tile < cap) or T <= 0:
        pf.tile_max = None
        _pack_positions_device(pf, cap, n_slots)
        return
    grid = cap // tile
    _order, _heads, run, start, run_tiles = tile_runs(
        tid_pp, pf.doc_ids // tile, T, grid)
    entry_cap = next_pow2(len(run_tiles), floor=BLOCK)
    run_p = np.full(batch_cap, entry_cap, dtype=np.int32)  # pad: OOB entry
    run_p[:nnz] = run
    vals = ob.scatter_tile_max(run_p, imps_p, entry_cap=entry_cap)
    pf.tile_max = TileSummary(
        start, run_tiles, np.asarray(vals)[:len(run_tiles)], grid
    ).padded(T, tile_summary_pad(len(run_tiles), grid))
    _pack_positions_device(pf, cap, n_slots)


def _pack_positions_device(pf, cap: int, n_slots: int) -> None:
    """Device twin of segment.pack_positions: the same host-computed
    (doc, slot*P + k) unique targets, scattered by
    ops/build.scatter_positions — integer set, byte-identical to the
    host fill. The norm columns are two f64->f32 rounds over doc_len
    (segment.bm25_norms, the one shared op order)."""
    from .segment import (BLOCK, bm25_norms, next_pow2, pos_pack_width,
                          position_deltas, _position_targets)
    from ..ops import build as ob
    pf.fwd_pos = None
    pf.pos_width = 0
    pf.lnorm = None
    pf.k1ln = None
    if pf.fwd_tids is None:
        return
    P = pos_pack_width(pf, cap, n_slots)
    if P is None:
        return
    deltas = position_deltas(pf)
    doc_pp, flat_pp = _position_targets(pf, P)
    npos = len(deltas)
    pos_cap = next_pow2(max(npos, 1), floor=BLOCK)
    docs_p = np.full(pos_cap, cap, dtype=np.int32)
    docs_p[:npos] = doc_pp
    cols_p = np.zeros(pos_cap, dtype=np.int32)
    cols_p[:npos] = flat_pp
    vals_p = np.full(pos_cap, -1, dtype=np.int16)
    vals_p[:npos] = deltas
    fp = ob.scatter_positions(docs_p, cols_p, vals_p,
                              cap=cap, pos_cols=n_slots * P)
    pf.fwd_pos = np.asarray(fp)
    pf.pos_width = P
    pf.lnorm, pf.k1ln = bm25_norms(pf.doc_len, pf.avg_len)
    _bump("pack_positions_device")


def _padded_i32(vals: np.ndarray, batch_cap: int,
                fill: int = 0) -> np.ndarray:
    out = np.full(batch_cap, fill, dtype=np.int32)
    out[:len(vals)] = vals
    return out


def tile_minmax_device(values: np.ndarray, exists: np.ndarray, cap: int,
                       tile: int) -> tuple[np.ndarray, np.ndarray]:
    """Device half of segment.build_tile_minmax (caller already did the
    degenerate-grid gating): same NaN exclusion, same identity
    sentinels, min/max reductions are order-free → byte-identical."""
    from ..ops import build as ob
    n_tiles = cap // tile
    v = values[:cap]
    e = exists[:cap]
    if values.dtype == np.float32:
        lo_pad = np.float32(np.inf)
        hi_pad = np.float32(-np.inf)
        e = e & ~np.isnan(v)
    else:
        lo_pad = values.dtype.type(np.iinfo(values.dtype).max)
        hi_pad = values.dtype.type(np.iinfo(values.dtype).min)
    lo, hi = ob.tile_minmax(v, e, lo_pad, hi_pad, n_tiles=n_tiles)
    _bump("tile_minmax_device")
    return (np.asarray(lo).astype(values.dtype, copy=False),
            np.asarray(hi).astype(values.dtype, copy=False))


# ---------------------------------------------------------------------------
# vectorized doc-value builders (columnar layout without per-doc loops)
# ---------------------------------------------------------------------------


def _build_keyword_columnar(name: str, col: dict[int, list[str]],
                            cap: int):
    """Single-valued fast path: np.unique interns the dictionary
    ('<U' order == sorted()) and one scatter lays out the ordinal
    column. Multi-valued docs take the host static (identical by
    definition)."""
    from .segment import KeywordColumn, SegmentBuilder
    if any(len(vs) != 1 for vs in col.values()):
        return SegmentBuilder._build_keyword(name, col, cap)
    rows = np.fromiter(col.keys(), dtype=np.int64, count=len(col))
    vals = np.asarray([vs[0] for vs in col.values()], dtype=np.str_)
    terms_arr, inv = np.unique(vals, return_inverse=True)
    terms = [str(t) for t in terms_arr]
    ords = np.full(cap, -1, dtype=np.int32)
    ords[rows] = inv.astype(np.int32)
    df = np.bincount(inv, minlength=len(terms)).astype(np.int32)
    return KeywordColumn(name=name, terms=terms,
                         term_index={t: i for i, t in enumerate(terms)},
                         ords=ords, df=df, mv_ords=None)


def _build_numeric_columnar(name: str, kind: str, col: dict[int, list],
                            cap: int):
    """Single-valued fast path for the numeric doc-value layout. The
    host-exact int64/float64 raw column stays on the host — jax
    without x64 would downcast it, and `raw` backs fetch/stats
    exactness. Multi-valued docs take the host static."""
    from .mapping import BOOLEAN, BYTE, DATE, INTEGER, IP, LONG, SHORT
    from .segment import NumericColumn, SegmentBuilder, _device_vals
    if any(len(vs) != 1 for vs in col.values()):
        return SegmentBuilder._build_numeric(name, kind, col, cap)
    is_int = kind in (LONG, INTEGER, SHORT, BYTE, DATE, BOOLEAN, IP)
    dt = np.int64 if is_int else np.float64
    rows = np.fromiter(col.keys(), dtype=np.int64, count=len(col))
    if kind == BOOLEAN:
        flat = np.asarray([1 if vs[0] else 0 for vs in col.values()],
                          dtype=dt)
    else:
        flat = np.asarray([vs[0] for vs in col.values()], dtype=dt)
    exists = np.zeros(cap, dtype=bool)
    exists[rows] = True
    raw = np.zeros(cap, dtype=dt)
    raw[rows] = flat
    bias = 1 << 31 if kind == IP else 0
    return NumericColumn(name=name, kind=kind,
                         values=_device_vals(raw, kind, bias, is_int),
                         exists=exists, raw=raw, bias=bias,
                         mv_values=None, mv_raw=None, mv_exists=None)


def _build_vector_columnar(name: str, col: dict[int, list], cap: int):
    """Row-block copy of the embedding column (one assignment, no
    per-doc loop). Ragged inputs (shorter vectors zero-padded by the
    host builder) fall back to the host static."""
    from .segment import SegmentBuilder, VectorColumn
    dims = len(next(iter(col.values())))
    if any(len(v) != dims for v in col.values()):
        return SegmentBuilder._build_vector(name, col, cap)
    rows = np.fromiter(col.keys(), dtype=np.int64, count=len(col))
    mat = np.asarray(list(col.values()), dtype=np.float32)
    values = np.zeros((cap, dims), dtype=np.float32)
    values[rows] = mat
    exists = np.zeros(cap, dtype=bool)
    exists[rows] = True
    norms = np.linalg.norm(values, axis=1).astype(np.float32)
    return VectorColumn(name=name, values=values, exists=exists,
                        norms=norms)
