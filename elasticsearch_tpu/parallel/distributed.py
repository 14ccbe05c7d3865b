"""Mesh-distributed search: shard-parallel scoring with in-program reduce.

Reference analog: the distributed QUERY phase — TransportSearchAction
fanning out to one copy of every shard (TransportSearchTypeAction.java:
126-153) and SearchPhaseController merging shard top-k + agg trees on a
coordinating node (SearchPhaseController.java:147-282).

TPU-first redesign: instead of RPC fan-out + host merge, the WHOLE
distributed query is ONE jitted program over a ("replica", "shard")
mesh via shard_map:

    each device scores ITS shards' columns locally        (QueryPhase)
      (several shards on a device: vmap over its rows, then
      their top-k side by side and their buckets summed)
    lax.all_gather of local top-k over the "shard" axis   (ICI)
    global top-k with (score desc, shard asc, doc asc)    (sortDocs)
    lax.psum / pmin / pmax of aggregation bucket arrays   (agg reduce)

The query batch additionally splits over the "replica" axis (data
parallelism over requests). The same eval_node/eval_aggs interpreters
used by the single-chip executor run inside shard_map — one code path,
two placements.

Packing: every logical shard is force-merged to one columnar segment,
padded to COMMON shapes (cap, posting-block count), with keyword
ordinals remapped into a MESH-GLOBAL ordinal space at pack time so
bucket arrays reduce exactly across shards.
"""

from __future__ import annotations

import json
import pickle
import time
import weakref
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..index.mapping import MapperService
from ..index.segment import (Segment, SegmentBuilder, next_pow2,
                             merge_segments, pad_delta_shapes, BLOCK,
                             build_tile_max, build_tile_minmax,
                             score_tile_size)
from ..search.executor import (QueryBinder, finalize, eval_node,
                               eval_aggs, _agg_view_plan, _ViewMasks,
                               _bound_view_fields, _fused_plan_bundle,
                               _fused_params_ok, _bundle_pallas_reason,
                               _bundle_pos_width, _bundle_fwd_width,
                               _bundle_positional,
                               _FUSED_DENSE_KINDS, _FUSED_RANGE_KINDS,
                               _FUSED_VEC_KINDS,
                               eval_fused_topk, resolve_fused_backend,
                               autotune_persist_key, seg_cache_key,
                               _fused_stats,
                               _resident_step, _split_deadline,
                               _resident_chunk_tiles, _fused_walk,
                               _walk_steps, _collect, _launch,
                               _start_fetch, plan_switches)
from ..search import bound_plans
from ..search.query_dsl import QueryParser
from ..search.aggregations import (parse_aggs, ShardAggContext, AggSpec,
                                   merge_shard_partials, finalize_partials,
                                   shard_partials)
from ..ops.scoring import bundle_primary_field
from ..ops.topk import top_k_by_field, top_k_hits
from ..search.controller import shards_header
from ..search.shard_searcher import filter_source
from ..utils.errors import (QueryParsingError, SearchParseError,
                            SearchTimeoutError)
from ..utils.metrics import MetricsRegistry
from ..utils.profiler import phase, request_args

# request-shaped errors: every replica row would reject them the same
# way, so they never retry, never count toward device health, and
# surface unchanged
_PARSE_ERRORS = (SearchParseError, QueryParsingError)
_HETEROGENEOUS = ("distributed msearch requires structurally identical "
                  "queries (split heterogeneous batches)")

# bodies answered by one program over an index's chips, whichever mesh
# reader served them (`GET /_nodes/stats/dispatch` -> `mesh.searches`)
_mesh_counts = MetricsRegistry()
mesh_searches = _mesh_counts.counter("searches")

# mapped types whose values a field sort reads from the numeric column
SORT_TYPES = frozenset({"integer", "long", "short", "byte", "float",
                        "double", "half_float", "date"})


def mesh_sort(body: dict, mappers) -> tuple | None:
    """The body's sort as the program takes it: ("_score",), or
    ("field", name, descending, "num") for one numeric field; None for
    any other sort."""
    sort = body.get("sort")
    if sort in (None, [], "_score"):
        return ("_score",)
    entries = sort if isinstance(sort, list) else [sort]
    if len(entries) != 1:
        return None
    entry = entries[0]
    if isinstance(entry, str):
        field, order = entry, "asc"
    elif isinstance(entry, dict) and len(entry) == 1:
        field, spec = next(iter(entry.items()))
        if isinstance(spec, dict):
            if set(spec) - {"order"}:
                return None
            order = spec.get("order", "asc")
        else:
            order = spec
    else:
        return None
    if field == "_score":
        return ("_score",)
    fm = mappers.field(field)
    if fm is None or fm.type not in SORT_TYPES \
            or str(order).lower() not in ("asc", "desc"):
        return None
    return ("field", field, str(order).lower() == "desc", "num")


def _mesh_stepped_enabled() -> bool:
    """May a deadline-carrying mesh search run the STEPPED program form
    (the preemptive device-side timeout the single-chip resident loop
    already has)? The stepped form chunks the fused tile walk and polls
    the host clock between chunks via io_callback — callbacks inside
    shard_map are per-device host calls with NO collectives in the
    chunk loop, so devices may disagree transiently on the verdict
    without desyncing; the final verdict is psum'd over BOTH mesh axes,
    making the timeout decision collective. Multi-process meshes stay
    cooperative: each process would poll its OWN monotonic clock
    against a deadline minted on the coordinator's, which is
    meaningless cross-host."""
    import os
    if os.environ.get("ES_TPU_MESH_STEPPED", "auto").lower() in (
            "0", "false", "off"):
        return False
    try:
        return jax.process_count() == 1
    except Exception:  # pragma: no cover - uninitialized backend
        return False


class _UnionShardView:
    """Binding view of one shard exposing the UNION of all shards' fields
    (missing ones as empty stubs) so one query binds to ONE plan shape on
    every shard — per-shard structural differences (absent field, dense
    vs scatter) must not fork the compiled program."""

    def __init__(self, seg: Segment, text: dict, keywords: dict,
                 numerics: dict, num_docs: int | None = None,
                 vectors: dict | None = None):
        self._seg = seg
        self.text = text
        self.keywords = keywords
        self.numerics = numerics
        # vector stubs carry the pack dims so a knn clause binds to ONE
        # desc on every shard (a shard without the column still binds
        # knn_vec; its packed rows have exists=False everywhere)
        if vectors is not None:
            self.vectors = vectors
        # keyword idf binds against the GLOBAL df the view carries, so
        # the doc count must be mesh-global too (else df > num_docs on
        # a small shard flips idf negative)
        if num_docs is not None:
            self.num_docs = num_docs

    def __getattr__(self, name):
        return getattr(self._seg, name)

    def field_kind(self, name: str) -> str | None:
        if name in self.text:
            return "text"
        if name in self.keywords:
            return "keyword"
        if name in self.numerics:
            return "numeric"
        if name in getattr(self, "vectors", {}):
            return "vector"
        return None


def summarize_shards(shards: list[Segment]) -> dict:
    """JSON-able pack summary of a host's LOCAL shards — the control-
    plane message from which every host derives the IDENTICAL global
    pack spec (merge_summaries). Multi-host packing exchanges these
    over the cluster transport instead of shipping shard data."""
    text = {}
    for f in sorted({f for s in shards for f in s.text}):
        nb = max((s.text[f].block_docs.shape[0] if f in s.text else 1)
                 for s in shards)
        fwd_ok = all(s.text[f].fwd_tids is not None
                     for s in shards if f in s.text)
        fwd_l = max((s.text[f].fwd_tids.shape[1]
                     for s in shards
                     if f in s.text and s.text[f].fwd_tids is not None),
                    default=8)
        # positional sidecar: packable only when EVERY shard carries it
        # (a mixed pack would fork the SPMD program); pos_p is the
        # per-slot position capacity the mesh slab pads to
        pos_ok = fwd_ok and all(
            getattr(s.text[f], "fwd_pos", None) is not None
            for s in shards if f in s.text)
        pos_p = max((s.text[f].fwd_pos.shape[1]
                     // s.text[f].fwd_tids.shape[1]
                     for s in shards
                     if f in s.text
                     and getattr(s.text[f], "fwd_pos", None) is not None),
                    default=0)
        # term-dictionary width: sizes the mesh-global tile_max pad so
        # every host packs identically-shaped block-max summaries
        nt = max((len(s.text[f].terms) for s in shards if f in s.text),
                 default=0)
        text[f] = {"nb": int(nb), "fwd_ok": bool(fwd_ok),
                   "fwd_l": int(fwd_l), "nt": int(nt),
                   "pos_ok": bool(pos_ok), "pos_p": int(pos_p)}
    kw = {}
    for f in sorted({f for s in shards for f in s.keywords}):
        df: dict[str, int] = {}
        for s in shards:
            kc = s.keywords.get(f)
            if kc is None:
                continue
            for t, d in zip(kc.terms, kc.df):
                df[t] = df.get(t, 0) + int(d)
        mv = max((s.keywords[f].mv_ords.shape[1]
                  for s in shards
                  if f in s.keywords
                  and s.keywords[f].mv_ords is not None), default=0)
        kw[f] = {"df": df, "mv": int(mv)}
    num = {}
    for f in sorted({f for s in shards for f in s.numerics}):
        any_f32 = any(s.numerics[f].values.dtype == np.float32
                      for s in shards if f in s.numerics)
        mv = max((s.numerics[f].mv_values.shape[1]
                  for s in shards
                  if f in s.numerics
                  and s.numerics[f].mv_values is not None), default=0)
        nc0 = next(s.numerics[f] for s in shards if f in s.numerics)
        lo = hi = None
        for s in shards:
            nc = s.numerics.get(f)
            if nc is None:
                continue
            vals = (nc.mv_values[nc.mv_exists] if nc.mv_values is not None
                    else nc.values[: s.capacity][nc.exists])
            if vals.size:
                lo = float(vals.min()) if lo is None else min(
                    lo, float(vals.min()))
                hi = float(vals.max()) if hi is None else max(
                    hi, float(vals.max()))
        num[f] = {"f32": bool(any_f32), "mv": int(mv),
                  "kind": nc0.kind, "bias": int(nc0.bias),
                  "lo": lo, "hi": hi}
    vec = {}
    for f in sorted({f for s in shards for f in s.vectors}):
        dims = max(s.vectors[f].dims for s in shards if f in s.vectors)
        vec[f] = {"dims": int(dims)}
    return {"cap": int(max((s.capacity for s in shards), default=BLOCK)),
            "total_docs": int(sum(s.num_docs for s in shards)),
            "text": text, "kw": kw, "num": num, "vec": vec}


class PackSpec:
    """The global shape contract every host packs to. Deterministic
    function of the merged summaries, so independently-merging hosts
    agree bit-for-bit."""

    def __init__(self, summaries: list[dict], n_shards: int):
        self.n_shards = n_shards
        self.cap = max(next_pow2(
            max(s["cap"] for s in summaries), floor=BLOCK), BLOCK)
        self.total_docs = sum(s["total_docs"] for s in summaries)
        text_fields = sorted({f for s in summaries for f in s["text"]})
        self.text: dict[str, dict] = {}
        self.fwd_disabled: set[str] = set()
        for f in text_fields:
            entries = [s["text"][f] for s in summaries if f in s["text"]]
            if not all(e["fwd_ok"] for e in entries):
                self.fwd_disabled.add(f)
            # nt=0 (any summary from a peer without the field, or a
            # pre-tile_max summary) disables block-max packing for the
            # field rather than desyncing hosts on the summary shape
            nts = [e.get("nt", 0) for e in entries]
            # positions pack only when every host's shards carry the
            # sidecar (pos_ok everywhere, width agreed by pow2 pad);
            # absent/mixed summaries disable it rather than desync
            pps = [e.get("pos_p", 0) for e in entries]
            self.text[f] = {
                "nb": max(next_pow2(max(e["nb"] for e in entries),
                                    floor=1), 1),
                "fwd_l": max(next_pow2(max(e["fwd_l"] for e in entries),
                                       floor=8), 8),
                "nt": (next_pow2(max(nts), floor=1)
                       if all(n > 0 for n in nts) else 0),
                "pos_p": (next_pow2(max(pps), floor=1)
                          if all(e.get("pos_ok") for e in entries)
                          and all(p > 0 for p in pps) else 0)}
        self.kw_terms: dict[str, list[str]] = {}
        self.kw_df: dict[str, np.ndarray] = {}
        self.kw_mv: dict[str, int] = {}
        for f in sorted({f for s in summaries for f in s["kw"]}):
            df: dict[str, int] = {}
            mv = 0
            for s in summaries:
                e = s["kw"].get(f)
                if e is None:
                    continue
                mv = max(mv, e["mv"])
                for t, d in e["df"].items():
                    df[t] = df.get(t, 0) + d
            terms = sorted(df)
            self.kw_terms[f] = terms
            self.kw_df[f] = np.asarray([df[t] for t in terms],
                                       dtype=np.int32)
            self.kw_mv[f] = mv
        # dense_vector fields (mapping-fixed dims, so every summary
        # agrees; max is belt-and-braces against partial mappings)
        self.vec: dict[str, dict] = {}
        for f in sorted({f for s in summaries for f in s.get("vec", {})}):
            self.vec[f] = {"dims": max(s["vec"][f]["dims"]
                                       for s in summaries
                                       if f in s.get("vec", {}))}
        self.num: dict[str, dict] = {}
        for f in sorted({f for s in summaries for f in s["num"]}):
            entries = [s["num"][f] for s in summaries if f in s["num"]]
            los = [e["lo"] for e in entries if e.get("lo") is not None]
            his = [e["hi"] for e in entries if e.get("hi") is not None]
            self.num[f] = {
                "dtype": (np.float32 if any(e["f32"] for e in entries)
                          else np.int32),
                "mv": max(e["mv"] for e in entries),
                "kind": entries[0]["kind"],
                "bias": entries[0]["bias"],
                # MESH-GLOBAL extent: histogram origins / bucket counts
                # are static program shape, so every host must derive
                # them from the same numbers
                "ext": ((min(los), max(his)) if los else None)}


class PackedShards:
    """Host + device representation of S shards with aligned shapes.

    Every array holds one row a shard on its leading axis, split over
    the mesh's "shard" axis: a device holds `rows_per_device` rows, one
    where the shards lie one a device and all of them where the mesh is
    one device. The rows count the shards, not the devices, and must
    divide over them.

    `spec`/`shard_offset`/`placer` support multi-host packing: each
    host packs only its LOCAL shards against the GLOBAL PackSpec and
    places rows into the global mesh array via its own placer
    (parallel/multihost.py); single-host callers omit all three."""

    def __init__(self, index_name: str, shards: list[Segment],
                 mapper: MapperService, mesh: Mesh,
                 spec: PackSpec | None = None, shard_offset: int = 0,
                 placer=None, shard_stats: bool = False):
        """`shard_stats`: a keyword term scores with the statistics of
        its own shard (df and doc count: query_then_fetch, what
        `ShardReader` does), not the mesh-global ones (DFS)."""
        self.index_name = index_name
        self.mappers = mapper
        self.mesh = mesh
        if spec is None:
            spec = PackSpec([summarize_shards(shards)], len(shards))
        # rows: the index's shards; a device holds an equal share of them
        self.n_shards = spec.n_shards
        n_devices = mesh.shape["shard"]
        if self.n_shards % n_devices:
            raise ValueError(f"{self.n_shards} shard rows do not divide "
                             f"over a {n_devices}-device shard axis")
        self.rows_per_device = self.n_shards // n_devices
        if shard_offset + len(shards) > self.n_shards:
            raise ValueError(f"packed rows {shard_offset}+{len(shards)} "
                             f"exceed the {self.n_shards}-shard mesh")
        self.spec = spec
        self.shard_offset = shard_offset
        self.shards = shards
        self.cap = spec.cap
        # tiered tile residency (index/tiering.py): the mesh pack is
        # ONE SPMD array set over all rows, so per-row tile paging
        # would fork the shard_map program per residency state — mesh
        # rows stay fully resident for now (single-chip packs page).
        # Rows whose pack exceeds the tiering budget are COUNTED so an
        # oversubscribed mesh is observable in the stats instead of
        # silently un-tiered; their summaries still register with the
        # pager's stats surface through the per-segment stores.
        from ..index import tiering as _tiering
        if _tiering.enabled():
            budget = _tiering.budget_bytes()
            for s in shards:
                fwd_bytes = sum(
                    pf.fwd_tids.nbytes + pf.fwd_imps.nbytes
                    for pf in s.text.values()
                    if pf.fwd_tids is not None)
                if s.nbytes() + fwd_bytes > budget:
                    _tiering.stats.mesh_full_resident_rows.inc()
        # a field is dense-capable only if EVERY shard (on every host)
        # has its forward index (mixed plans would fork the program)
        self.fwd_disabled = spec.fwd_disabled

        # mesh-global keyword ordinal spaces
        self.kw_terms = spec.kw_terms
        kw_fields = sorted(spec.kw_terms)
        text_fields = sorted(spec.text)
        num_fields = sorted(spec.num)

        S, cap = len(shards), self.cap
        arrays: dict = {"text": {}, "kw": {}, "num": {}}
        # per keyword field, each shard's local ordinal -> mesh-global
        remaps: dict[str, list] = {}
        for f in text_fields:
            dense = f not in self.fwd_disabled
            nb = spec.text[f]["nb"]
            docs = np.full((S, nb, BLOCK), cap, dtype=np.int32)
            imps = np.zeros((S, nb, BLOCK), dtype=np.float32)
            dlen = np.zeros((S, cap), dtype=np.float32)
            entry = {"block_docs": docs, "block_imps": imps, "doc_len": dlen}
            pos_p = spec.text[f].get("pos_p", 0) if dense else 0
            if dense:
                fwd_l = spec.text[f]["fwd_l"]
                ftids = np.full((S, cap, fwd_l), -1, dtype=np.int32)
                fimps = np.zeros((S, cap, fwd_l), dtype=np.float32)
                entry["fwd_tids"] = ftids
                entry["fwd_imps"] = fimps
                if pos_p:
                    # positional slab rides the mesh pack next to the
                    # forward pair: [S, cap, fwd_l, P] padded with the
                    # -1 empty-delta sentinel, flattened to the same
                    # [*, L*P] slot layout the single-chip decode reads
                    fpos = np.full((S, cap, fwd_l, pos_p), -1,
                                   dtype=np.int16)
                    fk1ln = np.ones((S, cap), dtype=np.float32)
                    flnorm = np.ones((S, cap), dtype=np.float32)
            for i, s in enumerate(shards):
                pf = s.text.get(f)
                if pf is None:
                    continue
                bd = pf.block_docs
                docs[i, : bd.shape[0]] = np.where(bd >= s.capacity, cap, bd)
                imps[i, : bd.shape[0]] = pf.block_imps
                dlen[i, : s.capacity] = pf.doc_len
                if dense:
                    ftids[i, : s.capacity, : pf.fwd_tids.shape[1]] = pf.fwd_tids
                    fimps[i, : s.capacity, : pf.fwd_imps.shape[1]] = pf.fwd_imps
                    if pos_p:
                        l_s = pf.fwd_tids.shape[1]
                        p_s = pf.fwd_pos.shape[1] // l_s
                        fpos[i, : s.capacity, : l_s, : p_s] = \
                            pf.fwd_pos.reshape(s.capacity, l_s, p_s)
                        fk1ln[i, : s.capacity] = pf.k1ln
                        flnorm[i, : s.capacity] = pf.lnorm
            if dense and pos_p:
                entry["fwd_pos"] = fpos.reshape(S, cap, fwd_l * pos_p)
                entry["k1ln"] = fk1ln
                entry["lnorm"] = flnorm
            if dense and spec.text[f].get("nt", 0) > 0:
                # per-shard-row block-max summaries over the PACKED
                # forward index (shard-local term ids, mesh-common tile
                # grid) — what routes the shard_map program through the
                # fused score+top-k op. Term rows pad with zero impact:
                # absent terms bound to 0 and can never un-prune a tile.
                nt = spec.text[f]["nt"]
                # one shape for every row on every host: a posting
                # makes at most one entry, and nb * BLOCK bounds the
                # postings of any shard
                ne = spec.text[f]["nb"] * BLOCK \
                    + cap // score_tile_size(cap)
                tms = []
                for i in range(S):
                    tm = build_tile_max(ftids[i], fimps[i], nt, cap,
                                        tile=score_tile_size(cap))
                    if tm is None:
                        tms = None
                        break
                    tms.append(tm.padded(nt, ne))
                if tms is not None:
                    entry["tile_max"] = jax.tree_util.tree_map(
                        lambda *xs: np.stack(xs), *tms)
            arrays["text"][f] = entry
        for f in kw_fields:
            lookup = {t: i for i, t in enumerate(self.kw_terms[f])}
            ords = np.full((S, cap), -1, dtype=np.int32)
            remaps[f] = [None] * S
            for i, s in enumerate(shards):
                kc = s.keywords.get(f)
                if kc is None:
                    continue
                remap = np.asarray([lookup[t] for t in kc.terms],
                                   dtype=np.int32)
                remaps[f][i] = remap
                local = kc.ords[: s.capacity]
                if remap.size:
                    ords[i, : s.capacity] = np.where(
                        local >= 0, remap[np.clip(local, 0, None)], -1)
            arrays["kw"][f] = ords
            # multi-valued sidecar: remapped ord sets (same branch the
            # single-chip interpreter takes via seg["kw_mv"])
            M = spec.kw_mv[f]
            if M:
                mv = np.full((S, cap, M), -1, dtype=np.int32)
                for i, s in enumerate(shards):
                    kc = s.keywords.get(f)
                    if kc is None:
                        continue
                    remap = np.asarray([lookup[t] for t in kc.terms],
                                       dtype=np.int32)
                    if kc.mv_ords is not None:
                        local = kc.mv_ords[: s.capacity]
                        mv[i, : s.capacity, : local.shape[1]] = np.where(
                            local >= 0, remap[np.clip(local, 0, None)], -1)
                    else:
                        local = kc.ords[: s.capacity]
                        mv[i, : s.capacity, 0] = np.where(
                            local >= 0, remap[np.clip(local, 0, None)], -1)
                arrays.setdefault("kw_mv", {})[f] = mv
        # dense_vector columns, one [S, cap, D] slab per field: vectors
        # shard across the mesh shard axis exactly like postings do (a
        # shard row carries its own docs' vectors), so the PR 4/7/13
        # failover / eviction-repack / host-elasticity arcs cover
        # vector serving with no extra machinery. Host packs f32; the
        # similarity matmul casts to bf16 at eval, same math as the
        # single-chip column (ops/knn.knn_score_column).
        vec_fields = sorted(spec.vec)
        for f in vec_fields:
            D = spec.vec[f]["dims"]
            vvals = np.zeros((S, cap, D), dtype=np.float32)
            vexists = np.zeros((S, cap), dtype=bool)
            vnorms = np.zeros((S, cap), dtype=np.float32)
            for i, s in enumerate(shards):
                vc = s.vectors.get(f)
                if vc is None:
                    continue
                vvals[i, : s.capacity, : vc.dims] = vc.values
                vexists[i, : s.capacity] = vc.exists
                vnorms[i, : s.capacity] = vc.norms
            arrays.setdefault("vec", {})[f] = {
                "values": vvals, "exists": vexists, "norms": vnorms}
        for f in num_fields:
            dtype = spec.num[f]["dtype"]
            vals = np.zeros((S, cap), dtype=dtype)
            exists = np.zeros((S, cap), dtype=bool)
            for i, s in enumerate(shards):
                nc = s.numerics.get(f)
                if nc is None:
                    continue
                vals[i, : s.capacity] = nc.values.astype(dtype)
                exists[i, : s.capacity] = nc.exists
            entry = {"values": vals, "exists": exists}
            if not spec.num[f]["mv"]:
                # per-shard-row tile extrema on the mesh-common grid:
                # the fused bool engine's mask-density prune input for
                # range filter clauses (rows of absent shards have no
                # existing values -> empty intervals -> always pruned)
                mm = [build_tile_minmax(vals[i], exists[i], cap,
                                        tile=score_tile_size(cap))
                      for i in range(S)]
                if all(m is not None for m in mm):
                    entry["tile_lo"] = np.stack([m[0] for m in mm])
                    entry["tile_hi"] = np.stack([m[1] for m in mm])
            M = spec.num[f]["mv"]
            if M:
                mvv = np.zeros((S, cap, M), dtype=dtype)
                mve = np.zeros((S, cap, M), dtype=bool)
                for i, s in enumerate(shards):
                    nc = s.numerics.get(f)
                    if nc is None:
                        continue
                    if nc.mv_values is not None:
                        w = nc.mv_values.shape[1]
                        mvv[i, : s.capacity, :w] = \
                            nc.mv_values[: s.capacity].astype(dtype)
                        mve[i, : s.capacity, :w] = \
                            nc.mv_exists[: s.capacity]
                    else:
                        mvv[i, : s.capacity, 0] = nc.values.astype(dtype)
                        mve[i, : s.capacity, 0] = nc.exists
                entry["mv_values"] = mvv
                entry["mv_exists"] = mve
            arrays["num"][f] = entry
        live = np.zeros((S, cap), dtype=bool)
        for i, s in enumerate(shards):
            live[i, : s.num_docs] = True

        # placement hooks: single-host = plain device_put / numpy
        # passthrough; parallel/multihost.py swaps in callback placers
        # that serve only this host's shard rows. place_step places the
        # stepped-deadline scalar vector — HOST-LOCAL by design in a
        # multi-host mesh (each process polls its own offset-corrected
        # deadline; parallel/clocksync.py), identity elsewhere.
        self.place_params = lambda tree: tree
        self.place_aggs = lambda tree: tree
        self.place_step = lambda arr: arr
        if placer is None:
            def placer(a: np.ndarray):
                # from the host array: each row goes straight to its own
                # device, no whole copy lands on the first one
                pspec = P("shard", *([None] * (a.ndim - 1)))
                return jax.device_put(np.asarray(a),
                                      NamedSharding(mesh, pspec))

        num_dtypes = {f: np.dtype(spec.num[f]["dtype"])
                      for f in num_fields}
        self.dev = jax.tree_util.tree_map(placer, arrays)
        self._shard_put = placer
        for f, entry in arrays["text"].items():
            # the rows' block-max summaries are on the device now, as a
            # shard pack's are (`fused_scoring.summary`), for as long
            # as this pack or a view that shares its columns holds them
            tm = entry.get("tile_max")
            if tm is not None:
                held = (tm.nbytes, int(tm.start[:, -1].sum()))
                _fused_stats.record_summary(*held)
                weakref.finalize(self.dev["text"][f]["tile_max"],
                                 _fused_stats.record_summary,
                                 -held[0], -held[1])
        # sort permutations of the lazy agg layouts (kept host-side for
        # projection top-ups; one [S, cap] array per LAYOUT, not per
        # column — columns rebuild on demand from self.shards)
        self._layout_perms: dict[tuple[str, str], np.ndarray] = {}
        self.host_live = live          # host copy for incremental deletes
        self.live = placer(live)

        # per-shard union binding views (one plan shape for all shards)
        from ..index.segment import (PostingsField, KeywordColumn,
                                     NumericColumn, VectorColumn)
        import copy as _copy

        self.bind_views: list[_UnionShardView] = []
        for i, s in enumerate(shards):
            text = {}
            for f in text_fields:
                pf = s.text.get(f)
                if pf is None:
                    pf = PostingsField(
                        name=f, terms=[], term_index={},
                        df=np.zeros(0, np.int32), indptr=np.zeros(1, np.int64),
                        doc_ids=np.zeros(0, np.int32),
                        tfs=np.zeros(0, np.float32),
                        doc_len=np.zeros(s.capacity, np.float32),
                        doc_count=0, avg_len=1.0)
                    pf.block_start = np.zeros(1, np.int32)
                    pf.fwd_tids = (None if f in self.fwd_disabled
                                   else np.zeros((0, 0), np.int32))
                elif f in self.fwd_disabled and pf.fwd_tids is not None:
                    pf = _copy.copy(pf)
                    pf.fwd_tids = None
                    pf.fwd_imps = None
                text[f] = pf
            kws = {}
            for f in kw_fields:
                # the packed kw columns hold MESH-GLOBAL ordinals, so
                # term/range/set binds must resolve against the global
                # dictionary, not the shard's local one (local-ord binds
                # against global columns silently mis-match whenever
                # shard dictionaries differ). Global df + total docs
                # also give every shard the same idf — the DFS-mode
                # scoring the distributed path wants.
                terms = self.kw_terms[f]
                df = spec.kw_df[f]
                if shard_stats:
                    df = np.zeros(len(terms), np.int32)
                    remap = remaps.get(f, [None] * S)[i]
                    if remap is not None and remap.size:
                        df[remap] = s.keywords[f].df
                kc = KeywordColumn(
                    name=f, terms=terms,
                    term_index={t: o for o, t in enumerate(terms)},
                    ords=np.full(0, -1, np.int32),
                    df=df)
                kws[f] = kc
            nums = {}
            for f in num_fields:
                # dtype-signaling stub: range/term binds must pick the
                # PACK dtype on every shard, not the local column's
                nums[f] = NumericColumn(
                    name=f, kind=spec.num[f]["kind"],
                    values=np.zeros(0, num_dtypes[f]),
                    exists=np.zeros(0, bool), raw=np.zeros(0, np.int64),
                    bias=spec.num[f]["bias"])
            vecs = {}
            for f in vec_fields:
                # dims-signaling stub: a knn clause binds to ONE desc
                # (field, similarity, pack dims) on every shard
                D = spec.vec[f]["dims"]
                vecs[f] = VectorColumn(
                    name=f, values=np.zeros((0, D), np.float32),
                    exists=np.zeros(0, bool),
                    norms=np.zeros(0, np.float32))
            self.bind_views.append(_UnionShardView(
                s, text, kws, nums,
                num_docs=(s.num_docs if shard_stats
                          else max(spec.total_docs, 1)),
                vectors=vecs))

    def _stacked_kw(self, f: str) -> np.ndarray | None:
        """[S, cap] mesh-global ordinal column rebuilt from the
        segments (same remap as the pack loop); None for mv/absent."""
        if f not in self.kw_terms or self.spec.kw_mv.get(f, 0):
            return None
        lookup = {t: i for i, t in enumerate(self.kw_terms[f])}
        ords = np.full((len(self.shards), self.cap), -1, np.int32)
        for i, s in enumerate(self.shards):
            kc = s.keywords.get(f)
            if kc is None:
                continue
            remap = np.asarray([lookup[t] for t in kc.terms], np.int32)
            local = kc.ords[: s.capacity]
            if remap.size:
                ords[i, : s.capacity] = np.where(
                    local >= 0, remap[np.clip(local, 0, None)], -1)
        return ords

    def _stacked_num(self, f: str) -> tuple[np.ndarray, np.ndarray] | None:
        """([S, cap] values, exists) in the pack dtype; None for
        mv/absent columns."""
        e = self.spec.num.get(f)
        if e is None or e["mv"]:
            return None
        dtype = e["dtype"]
        vals = np.zeros((len(self.shards), self.cap), dtype=dtype)
        exists = np.zeros((len(self.shards), self.cap), dtype=bool)
        for i, s in enumerate(self.shards):
            nc = s.numerics.get(f)
            if nc is None:
                continue
            vals[i, : s.capacity] = nc.values.astype(dtype)
            exists[i, : s.capacity] = nc.exists
        return vals, exists

    def _top_up(self, store: dict, perms: np.ndarray,
                filter_kw: set[str], filter_num: set[str]) -> None:
        """Add MISSING filter-column projections to an existing layout
        (later queries may reference different fields than the first)."""
        for g in filter_num - set(store["vw_num"]):
            col = self._stacked_num(g)
            if col is None:
                continue
            vals, exists = col
            store["vw_num"][g] = {
                "values": self._shard_put(
                    np.take_along_axis(vals, perms, 1)),
                "exists": self._shard_put(
                    np.take_along_axis(exists, perms, 1))}
        for g in filter_kw - set(store["vw_kw"]):
            ords_g = self._stacked_kw(g)
            if ords_g is None:
                continue
            store["vw_kw"][g] = self._shard_put(
                np.take_along_axis(ords_g, perms, 1))

    def ensure_sorted_layouts(self, kw_layouts: set[str],
                              num_layouts: set[str],
                              filter_kw: set[str],
                              filter_num: set[str]) -> None:
        """Stacked per-shard-row sorted layouts + view projections — the
        mesh analog of the single-chip ensure_kw_sorted /
        ensure_num_sorted / ensure_agg_views. After this, the shard_map
        program's per-shard seg slice carries the SAME structure the
        single-chip view agg path keys on, so eval_aggs routes through
        the gather-free sorted-view kernels on the mesh too. Strictly
        additive and presence-gated: packs that never call this execute
        exactly as before; the jit cache retraces on the seg pytree
        structure change, so no manual invalidation is needed."""
        S = len(self.shards)
        for f in kw_layouts:
            store = self.dev.get("kw_sorted", {}).get(f)
            if store is None:
                ords = self._stacked_kw(f)
                if ords is None:
                    continue
                card = len(self.kw_terms.get(f, []))
                perms = np.argsort(ords, axis=1, kind="stable").astype(
                    np.int32)
                starts = np.empty((S, card + 1), dtype=np.int32)
                for i in range(S):
                    starts[i] = np.searchsorted(ords[i][perms[i]],
                                                np.arange(card + 1))
                store = {"perm": self._shard_put(perms),
                         "starts": self._shard_put(starts),
                         "vw_num": {}, "vw_kw": {}, "vw_kw_mv": {}}
                self.dev.setdefault("kw_sorted", {})[f] = store
                self._layout_perms[("kw", f)] = perms
            self._top_up(store, self._layout_perms[("kw", f)],
                         filter_kw, filter_num)
        for f in num_layouts:
            store = self.dev.get("num_sorted", {}).get(f)
            if store is None:
                col = self._stacked_num(f)
                if col is None:
                    continue
                vals, exists = col
                vals = vals.copy()
                sentinel = (np.iinfo(np.int32).max
                            if vals.dtype == np.int32
                            else np.float32(np.inf))
                vals[~exists] = sentinel
                perms = np.argsort(vals, axis=1, kind="stable").astype(
                    np.int32)
                store = {
                    "perm": self._shard_put(perms),
                    "vals": self._shard_put(
                        np.take_along_axis(vals, perms, 1)),
                    "sexists": self._shard_put(
                        np.take_along_axis(exists, perms, 1)),
                    "vw_num": {}, "vw_kw": {}, "vw_kw_mv": {}}
                self.dev.setdefault("num_sorted", {})[f] = store
                self._layout_perms[("num", f)] = perms
            self._top_up(store, self._layout_perms[("num", f)],
                         filter_kw, filter_num)

    def without_rows(self, rows_per_shard: dict[int, list[int]]
                     ) -> "PackedShards":
        """This pack with the live bits of deleted/updated docs cleared,
        WITHOUT repacking — an O(corpus bitmap) upload, not an O(corpus
        content) rebuild (the mesh analog of Lucene liveDocs). A new
        view that shares every column: a search still in flight on this
        one keeps its live mask. Shard ids are GLOBAL; each host may
        only deactivate rows it owns."""
        host_live = self.host_live.copy()
        for sid, rows in rows_per_shard.items():
            local = sid - self.shard_offset
            if not 0 <= local < len(self.shards):
                raise ValueError(
                    f"shard {sid} is outside this host's span "
                    f"[{self.shard_offset}:"
                    f"{self.shard_offset + len(self.shards)})")
            host_live[local, rows] = False
        if (host_live == self.host_live).all():
            return self
        import copy
        view = copy.copy(self)
        view.host_live = host_live
        view.live = self._shard_put(host_live)
        return view

    @classmethod
    def from_node_index(cls, node, index_name: str, mesh: Mesh) -> "PackedShards":
        """Pack a Node's index (force-merging each shard to one segment)."""
        svc = node.indices[index_name]
        return cls.from_views(index_name, engine_views(svc), svc.mappers,
                              mesh)

    @classmethod
    def from_views(cls, index_name: str, views: list, mappers,
                   mesh: Mesh, shard_stats: bool = False) -> "PackedShards":
        """Pack one (segments, live masks) view a shard, in shard order.
        A shard that is one segment with every row live is packed as it
        is (its impacts were baked with the mapping's similarity when it
        was built, and nothing here writes to it); any other is merged
        into a fresh segment, its impacts baked again with the mapped
        per-field similarity so mesh scores match the host path
        (index/similarity.py)."""
        shards = []
        for sid, (segs, live) in enumerate(views):
            segs = [s for s in segs if s.num_docs]
            if not segs:
                shards.append(SegmentBuilder().build(f"empty_{sid}"))
            elif len(segs) == 1 and segs[0].parent_of is None \
                    and live[segs[0].seg_id][: segs[0].num_docs].all():
                shards.append(segs[0])
            else:
                shards.append(merge_segments(
                    segs, f"packed_{sid}", live,
                    similarity=mappers.similarity_for))
        return cls(index_name, shards, mappers, mesh,
                   shard_stats=shard_stats)


def engine_views(svc) -> list:
    """Each shard's (segments, live masks) once its engine is refreshed:
    the write path's current state, what a mesh built outside the node's
    search path packs."""
    views = []
    for sid in range(svc.num_shards):
        eng = svc.shard(sid)
        eng.refresh()
        views.append((list(eng.segments), dict(eng.live)))
    return views


def view_docs(view) -> dict[str, tuple[int, bytes]]:
    """id -> (version, source) of the live documents of one view."""
    segs, live = view
    out = {}
    for seg in segs:
        for d in np.flatnonzero(live[seg.seg_id][: seg.num_docs]).tolist():
            out[seg.ids[d]] = (int(seg.versions[d]), seg.sources[d])
    return out


def _sort_rank(key, missing, total, descending: bool):
    """The order `top_k_by_field` took a shard's hits in, as one number
    that falls with the rank: the value (negated for ascending), then the
    documents without one, then the slots past the shard's matches."""
    filled = jnp.arange(key.shape[-1]) < total[..., None]
    if key.dtype == jnp.int32:
        i32 = jnp.iinfo(jnp.int32)
        r = key if descending else jnp.where(key == i32.min, i32.max, -key)
        return jnp.where(filled, jnp.where(missing, i32.min + 1, r), i32.min)
    r = key if descending else -key
    r = jnp.where(missing, jnp.finfo(jnp.float32).min, r)
    return jnp.where(filled, r, -jnp.inf)


def _reduce_shard_axis(agg_out: dict, rows: int = 1) -> dict:
    """psum counts/sums, pmin mins, pmax maxes over the shard axis. With
    `rows` > 1 every leaf leads with a device's rows, which reduce the
    same way on the device first."""
    def red(v, local, collective):
        return collective(local(v, axis=0) if rows > 1 else v, "shard")

    def walk(obj):
        if isinstance(obj, dict):
            out = {}
            for key, v in obj.items():
                if isinstance(v, dict):
                    out[key] = walk(v)
                elif key == "min":
                    out[key] = red(v, jnp.min, jax.lax.pmin)
                elif key == "max":
                    out[key] = red(v, jnp.max, jax.lax.pmax)
                else:
                    out[key] = red(v, jnp.sum, jax.lax.psum)
            return out
        return red(obj, jnp.sum, jax.lax.psum)

    return walk(agg_out)


class _PendingMesh:
    """In-flight half of a split mesh msearch (`submit_mesh`): the
    shard_map programs of every generation and signature group are
    enqueued; finish() collects in submission order, merges each body's
    generations and builds its response. Interface-compatible with
    shard_searcher._PendingMsearch so the dispatch scheduler pipelines
    mesh readers like shard readers (including the cooperative
    `deadline`: collection past it raises SearchTimeoutError instead of
    syncing the remaining groups)."""

    __slots__ = ("index_name", "bodies", "ids", "n_gens", "with_partials",
                 "deadline", "started", "parts", "group_sizes",
                 "dispatch_count", "fetch_s")

    def __init__(self, index_name: str, bodies: list[dict], ids: list,
                 n_gens: int, with_partials: bool,
                 deadline: float | None):
        self.index_name = index_name
        self.bodies = bodies
        self.ids = ids
        self.n_gens = n_gens
        self.with_partials = with_partials
        self.deadline = deadline
        self.started = time.monotonic()
        # (generation, searcher, body indices, launched state, span args)
        self.parts: list[tuple] = []
        self.group_sizes: list[int] = []
        self.dispatch_count = 0
        self.fetch_s = [0.0] * len(bodies)

    def finish(self) -> list[dict]:
        n = len(self.bodies)
        raws = [[None] * self.n_gens for _ in range(n)]
        sorts: list = [None] * n
        for gen, searcher, idxs, st, span_args in self.parts:
            if self.deadline is not None \
                    and time.monotonic() > self.deadline:
                raise SearchTimeoutError(self.index_name)
            with phase("collect", n, **span_args) as leaf:
                got = searcher._collect_with_failover(
                    [self.bodies[i] for i in idxs], st,
                    deadline=self.deadline, leaf=leaf)
            for i, raw in zip(idxs, got):
                raws[i][gen] = raw
                sorts[i] = st["sort"]
        with phase("fetch", n, **request_args(self.ids)) as fetch:
            out = [DistributedSearcher._build_response(
                body, raws[i], sorts[i], self.with_partials, self.started)
                for i, body in enumerate(self.bodies)]
        mesh_searches.inc(n)
        self.fetch_s = [fetch.seconds / max(n, 1)] * n
        return out


def submit_mesh(searchers: list, bodies: list[dict],
                with_partials: bool = False,
                deadline: float | None = None,
                requests: list | None = None,
                keys: list | None = None) -> _PendingMesh:
    """The split submit of every mesh reader: `searchers` are the
    generations it searches (one pack, or a `MeshIndex`'s base and
    tail), each a point-in-time view. Each launches one program a
    signature group of the bodies on its pack, without collecting
    (`DistributedSearcher.submit`). Like a `ShardReader`'s, the call is
    the `bind` phase but for the launches; `keys` are the bodies' keys
    where the caller has them (the scheduler's), else each is made."""
    bodies = searchers[0]._rewrite_knn(bodies)
    ids = list(requests) if requests else [None] * len(bodies)
    body_keys = [keys[i] if keys and keys[i] is not None
                 else bound_plans.body_key(b) for i, b in enumerate(bodies)]
    pend = _PendingMesh(searchers[0].packed.index_name, bodies, ids,
                        len(searchers), with_partials, deadline)
    with phase("bind", len(bodies), **request_args(ids)) as bind:
        for gen, searcher in enumerate(searchers):
            searcher.submit(pend, gen, bind, body_keys)
    pend.group_sizes = [len(p[2]) for p in pend.parts if p[0] == 0]
    pend.dispatch_count = len(pend.parts)
    return pend


class DistributedSearcher:
    """Executes searches as one shard_map program over the mesh.

    `replica_ids` maps mesh-local replica rows to PHYSICAL full-mesh
    row ids: a degraded repack (parallel/repack.py) serves from a
    reduced mesh whose row 0 may physically be the full mesh's row 1,
    and fault-injection selectors / per-row failover counters must keep
    addressing the physical row. `health` is the optional consecutive-
    failure tracker the eviction machinery wires in at the dispatch and
    collect boundaries (timeouts and parse errors never reach it,
    matching the failover retry rules)."""

    def __init__(self, packed: PackedShards, health=None,
                 replica_ids: tuple[int, ...] | None = None,
                 gather_out: bool = False):
        self.packed = packed
        self.mesh = packed.mesh
        self.n_replicas = self.mesh.shape["replica"]
        self.health = health
        # gather_out: all_gather results over the replica axis so EVERY
        # device (hence every process) holds the full batch's output —
        # required when replica rows live on different hosts (the
        # multihost replica layout: device_get of another host's output
        # shard is not addressable); wasted bytes on a single-host mesh,
        # so it stays off there
        self._gather_out = bool(gather_out)
        self.replica_ids = (tuple(replica_ids) if replica_ids is not None
                            else tuple(range(self.n_replicas)))
        if len(self.replica_ids) != self.n_replicas:
            raise ValueError(
                f"{len(self.replica_ids)} replica_ids for a "
                f"{self.n_replicas}-replica mesh")
        self._jit_cache: dict = {}
        # what `submit` made of a body and of a group of bodies on this
        # pack (search/bound_plans.py): the pack never changes under a
        # searcher, so a kept plan stays good for its life
        self._plans = bound_plans.BoundPlans()

    def _phys(self, replica: int) -> int:
        """Mesh-local replica row -> physical full-mesh row id."""
        return self.replica_ids[replica]

    def over(self, packed: PackedShards) -> "DistributedSearcher | None":
        """This searcher's pinned shard_map programs over another pack
        (the streaming tail's refresh epoch bump, the base with rows
        deactivated): a NEW searcher that shares the compiled programs,
        legal exactly when the new pack's device-tree avals match the
        old — the compiled programs take the pack as a runtime argument,
        so identical shapes/dtypes mean zero recompiles, they just read
        the new epoch's columns. This searcher and its pack stay as they
        are, so a search in flight on them, or on a reader that holds
        them, reads its own point in time. PackSpec pow2-buckets every
        content-proportional dimension (cap, nb, fwd_l, nt), so a
        growing tail only mismatches when a bucket overflows — then the
        caller builds a new searcher, paying the compile log-many times
        instead of once per refresh. None on any mismatch."""
        if packed.mesh is not self.mesh:
            return None
        old = (self.packed.dev, self.packed.live)
        new = (packed.dev, packed.live)
        if jax.tree_util.tree_structure(old) \
                != jax.tree_util.tree_structure(new):
            return None
        for a, b in zip(jax.tree_util.tree_leaves(old),
                        jax.tree_util.tree_leaves(new)):
            if tuple(a.shape) != tuple(b.shape) or a.dtype != b.dtype:
                return None
        searcher = DistributedSearcher(packed, self.health, self.replica_ids,
                                       self._gather_out)
        searcher._jit_cache = self._jit_cache
        from ..search import resident
        if resident.enabled() and self._jit_cache:
            # every pinned program that survived the epoch bump is one
            # avoided mesh recompile — reported through the same
            # counters the repack's drops go through
            resident.stats.refresh_reuses.inc(len(self._jit_cache))
        return searcher

    # -- public ------------------------------------------------------------
    def search(self, body: dict) -> dict:
        return self.msearch([body])[0]

    def msearch(self, bodies: list[dict],
                with_partials: bool = False,
                deadline: float | None = None) -> list[dict]:
        """Heterogeneous batch: bodies group by (plan signature, aggs,
        k, sort), one device program per group — the mesh analog of the
        host path's signature grouping in shard_searcher.msearch. Each
        body keeps its OWN aggregations. (with_partials is accepted for
        scheduler interface parity — the isolated retry of
        search/dispatch.py calls reader.msearch(bodies, wp) — and is
        ignored: mesh responses are always complete.)"""
        return self.msearch_submit(bodies, deadline=deadline).finish()

    def msearch_submit(self, bodies: list[dict],
                       with_partials: bool = False,
                       deadline: float | None = None) -> _PendingMesh:
        """The batched dispatch entry the scheduler (search/dispatch.py)
        expects: every signature group's shard_map program is enqueued
        WITHOUT a device sync; finish() collects in submission order
        (`submit_mesh` over this one pack). (with_partials is accepted
        for interface parity; mesh responses are always complete.)"""
        return submit_mesh([self], bodies, deadline=deadline)

    def submit(self, pend: _PendingMesh, gen: int, bind,
               body_keys: list) -> None:
        """Launch this pack's programs for `pend`'s bodies, one a
        signature group, without collecting; each launch joins `pend`
        as generation `gen`. A body is kept on this searcher
        (search/bound_plans.py): its parse, its bound trees on every
        shard's view, its group key, and with it its group of one and
        the last group of several it came first in, ready to launch
        with their parameters placed on the devices. A body served
        alone before, or such a group served again, launches from its
        key; another group of bodies each kept is assembled from their
        bound trees (`_assemble`: no parse or bind); a body not kept is
        parsed and bound, and only it. Not kept: a body without a key
        or whose parse or bind may not be, and the stepped program form
        a deadline asks for."""
        bodies = pend.bodies
        plans = self._plans
        plans.open((self.packed.mappers.version, plan_switches()))
        bound: list = []
        keys: list = []
        fresh: set = set()
        for i, body in enumerate(bodies):
            key = body_keys[i]
            bb = plans.get(key) if key is not None else None
            if bb is None:
                with bound_plans.keep_scope() as scope:
                    # a kept parse outlives the caller's dict: it is
                    # made from a copy (the key has shown the body to
                    # be plain JSON values)
                    bb = self._bind_body(pickle.loads(pickle.dumps(
                        body, -1)) if key is not None else body)
                if key is not None and scope.keep:
                    plans.put(key, bb)
                else:
                    key = None
                fresh.add(i)
            bound.append(bb)
            keys.append(key)
        groups: dict = {}
        for i, bb in enumerate(bound):
            groups.setdefault(bb.sig, []).append(i)
        stepped = pend.deadline is not None and _mesh_stepped_enabled()
        for gk, idxs in groups.items():
            span_args = bind.args = request_args(pend.ids[i] for i in idxs)
            group = [bodies[i] for i in idxs]
            gkeys = tuple(keys[i] for i in idxs)
            kept = None not in gkeys
            first = bound[idxs[0]]
            st = None
            if kept and len(idxs) == 1:
                st = first.alone
            elif kept and first.led is not None and first.led[0] == gkeys:
                st = first.led[1]
            if st is not None and not (stepped and st["fused"] is not None):
                bound_plans.hits.inc()
                from ..search import resident
                if resident.enabled():
                    # the kept plan holds its pinned program: reused
                    resident.stats.resident_hits.inc()
            else:
                st = self._assemble([bound[i] for i in idxs],
                                    deadline=pend.deadline, sort=gk[-1],
                                    place=True)
                if not kept or st["stepped"]:
                    bound_plans.bypassed.inc()
                else:
                    bound_plans.misses.inc()
                    if fresh.isdisjoint(idxs):
                        bound_plans.assembled.inc()
                    if len(idxs) == 1:
                        first.alone = st
                    else:
                        plans.lead(gkeys[0], first, (gkeys, st))
            try:
                out = self._dispatch_uniform(group, deadline=pend.deadline,
                                             st=st, bind=bind)
            except BaseException:
                if kept:
                    # kept values that do not launch are built anew by
                    # the next search
                    plans.drop(gkeys)
                raise
            pend.parts.append((gen, self, idxs, out, span_args))

    def _bind_body(self, body: dict) -> bound_plans.BoundBody:
        """Parse one body (its query and its aggregations) and bind its
        query on every shard's view: one plan shape on every shard (the
        views are the union of the shards' fields). Its `sig` is
        `_group_key`'s."""
        pk = self.packed
        query = QueryParser(pk.mappers).parse(body.get("query"))
        bounds = [QueryBinder(view, pk.mappers).bind(query)  # type: ignore
                  for view in pk.bind_views]
        sig = bounds[0].signature()
        if any(bnd.signature() != sig for bnd in bounds[1:]):
            raise SearchParseError(_HETEROGENEOUS)
        agg_specs = parse_aggs(body.get("aggs") or body.get("aggregations"))
        for spec in agg_specs:
            fm = pk.mappers.field(spec.field)
            if spec.kind in ("terms", "cardinality", "value_count") and \
                    fm is not None and fm.type == "text" and \
                    pk.mappers.field(f"{spec.field}.keyword") is not None:
                spec.field = f"{spec.field}.keyword"
        return bound_plans.BoundBody(
            {"query": query, "agg_specs": agg_specs}, bounds,
            self._group_key(body, sig))

    def _group_key(self, body: dict, sig: tuple | None = None) -> tuple:
        """What groups a body with others into one program on this
        pack: its plan's signature (`sig`, else bound on the first
        shard's view), aggregations, k and sort (last; a sort the
        program cannot reduce by is the score's)."""
        pk = self.packed
        if sig is None:
            sig = QueryBinder(pk.bind_views[0], pk.mappers).bind(
                QueryParser(pk.mappers).parse(body.get("query"))
            ).signature()
        aggs = json.dumps(body.get("aggs") or body.get("aggregations") or {},
                          sort_keys=True, default=str)
        size = int(body.get("size", 10)) + int(body.get("from", 0))
        return (sig, aggs, size, mesh_sort(body, pk.mappers) or ("_score",))

    def _rewrite_knn(self, bodies: list[dict]) -> list[dict]:
        """Top-level `knn` sections rewrite onto the knn SCORING CLAUSE
        (search/shard_searcher.rewrite_knn_body — one rewrite, both
        substrates): the mesh serves vector search through the same
        shard_map program as everything else, so sharding, replica
        failover, eviction-repack, and host elasticity cover it with
        no dedicated path. Pure-knn bodies clamp size to k (the knn
        candidate-window contract) but report `hits.total` as the
        MATCH count (every live doc carrying a vector) — the mesh has
        no candidates path, so totals/aggs are query-shaped here where
        the single-chip candidates path reports the k-window
        (documented divergence; the hit window itself is identical)."""
        if not any((b or {}).get("knn") for b in bodies):
            return bodies
        from ..search.shard_searcher import rewrite_knn_body
        out = []
        for b in bodies:
            if (b or {}).get("knn"):
                _fused_stats.record_knn("mesh:query_rewrite")
                nb = rewrite_knn_body(b)
                if not b.get("query"):
                    k = int(b["knn"].get("k",
                                         b["knn"].get("num_candidates",
                                                      10)))
                    nb["size"] = min(int(b.get("size", 10)), k)
                b = nb
            out.append(b)
        return out

    def raw_msearch(self, bodies: list[dict],
                    deadline: float | None = None,
                    allow_stepped: bool | None = None) -> list[dict]:
        """Per-body raw results (candidates + agg partials) for callers
        that merge across generations (MeshIndex) or fetch across hosts
        (MultiHostIndex). `deadline` is absolute LOCAL monotonic
        seconds (a multihost caller passes its offset-corrected copy of
        the driver's deadline); `allow_stepped` overrides the stepped-
        program auto-gate — the multihost driver decides ONCE and
        broadcasts the decision so every process compiles the same
        program form (a per-host decision could diverge and deadlock
        the mesh in a collective)."""
        bodies = self._rewrite_knn(bodies)
        out: list[dict | None] = [None] * len(bodies)
        for idxs in self._signature_groups(bodies).values():
            raws = self._raw_uniform([bodies[i] for i in idxs],
                                     deadline=deadline,
                                     allow_stepped=allow_stepped)
            for i, raw in zip(idxs, raws):
                out[i] = raw
        return out  # type: ignore[return-value]

    def _signature_groups(self, bodies: list[dict]) -> dict:
        """`raw_msearch`'s groups: `_group_key` but for the sort, which
        raw results leave to the caller's merge (score order)."""
        groups: dict[tuple, list[int]] = {}
        for i, b in enumerate(bodies):
            groups.setdefault(self._group_key(b)[:3], []).append(i)
        return groups

    def _raw_uniform(self, bodies: list[dict],
                     deadline: float | None = None,
                     allow_stepped: bool | None = None) -> list[dict]:
        """One compiled program for structurally identical bodies ->
        per-body {"score", "shard", "doc", "total", "partials",
        "agg_specs", "packed"}."""
        return self._collect_with_failover(
            bodies, self._dispatch_uniform(bodies, deadline=deadline,
                                           allow_stepped=allow_stepped),
            deadline=deadline, allow_stepped=allow_stepped)

    def _collect_with_failover(self, bodies: list[dict], st: dict,
                               deadline: float | None = None,
                               allow_stepped: bool | None = None,
                               leaf=None) -> list[dict]:
        """Collect with the OTHER half of replica failover: jax
        dispatch is asynchronous, so a real device failure (preemption,
        runtime drop, OOM) usually surfaces at the device_get inside
        _collect_uniform, not at enqueue — on such an error the whole
        dispatch+collect is re-entered once per remaining replica row.
        Deadline and request-shaped errors never retry, and a deadline
        that passes MID-failover stops the retry loop with the same
        SearchTimeoutError the pending path raises (re-dispatching
        cannot un-pass the cutoff; it only burns device time) — with no
        holds retained, so the failover-exhaustion exit leaks nothing.
        `leaf`: the caller's `collect` phase, for the first collect."""
        rep0 = int(st.get("replica", 0))
        try:
            out = self._collect_uniform(st, leaf)
        except (SearchTimeoutError, *_PARSE_ERRORS):
            raise
        except Exception as e:  # noqa: BLE001 — device/injected
            from ..search.dispatch import failover_stats
            if self.health is not None:
                self.health.record_failure(self._phys(rep0), e)
            last: Exception = e
            for rep in range(rep0 + 1, self.n_replicas):
                if deadline is not None and time.monotonic() > deadline:
                    raise SearchTimeoutError(self.packed.index_name)
                failover_stats.record_retry(self._phys(rep))
                try:
                    out = self._collect_uniform(
                        self._dispatch_uniform_attempt(
                            bodies, rep, deadline=deadline,
                            allow_stepped=allow_stepped))
                except (SearchTimeoutError, *_PARSE_ERRORS):
                    raise
                except Exception as e2:  # noqa: BLE001
                    if self.health is not None:
                        self.health.record_failure(self._phys(rep), e2)
                    last = e2
                    continue
                failover_stats.record_succeeded(self._phys(rep))
                if self.health is not None:
                    self.health.record_success(self._phys(rep))
                return out
            if self.n_replicas > 1:
                failover_stats.record_failed(self._phys(rep0))
            raise last
        if self.health is not None:
            self.health.record_success(self._phys(rep0))
        return out

    def _check_shard_rows(self, replica: int) -> None:
        """Mesh dispatch boundary of the fault-injection registry
        (utils/faults.py): one probe per LOCAL shard row, carrying the
        PHYSICAL replica row this attempt runs against so rules can pin
        a fault to one copy (`shard_error:shard=2:replica=0:site=mesh`)
        and a rule pinned to an evicted row never re-fires against the
        survivor that inherited its mesh-local index after a repack."""
        from ..utils import faults
        if not faults.enabled():
            return
        pk = self.packed
        for local in range(len(pk.shards)):
            faults.on_dispatch("mesh", index=pk.index_name,
                               shard=pk.shard_offset + local,
                               replica=self._phys(replica))

    def _dispatch_uniform(self, bodies: list[dict],
                          deadline: float | None = None,
                          allow_stepped: bool | None = None,
                          st: dict | None = None, bind=None) -> dict:
        """Dispatch half of _raw_uniform with replica failover
        (TransportSearchTypeAction.onFirstPhaseResult's retry of the
        next shard routing, mapped onto the mesh): when an attempt
        fails (real device/dispatch error OR injected fault) and the
        mesh has more replica rows (n_replicas > 1), the dispatch is
        re-entered once per extra replica row before giving up.
        Request-shaped errors (parse) never retry: every copy would
        reject them the same way.

        Scope note: a retry RE-ENTERS the same SPMD program — the
        collective spans every replica row, so this recovers TRANSIENT
        failures (preempted queue, runtime drop, an injected fault
        pinned to one replica row via `replica=`), which is what
        replication buys without resharding. A device that is
        permanently dead fails every re-entry; the wired-in `health`
        tracker counts those consecutive failures and, past
        `mesh.eviction.failure_threshold`, triggers the degraded repack
        onto the surviving rows (parallel/repack.py) that removes the
        per-search tax. Counters:
        nodes_stats()["dispatch"]["failover"]. `st`: the plan prepared
        for replica row 0 (`submit`'s, kept or new), launched first;
        `bind`: the caller's `bind` phase, which each launch pauses."""
        from ..search.dispatch import failover_stats
        last: Exception | None = None
        for rep in range(self.n_replicas):
            if rep > 0:
                failover_stats.record_retry(self._phys(rep))
            try:
                if rep == 0 and st is not None:
                    self._check_shard_rows(0)
                    out = self.launch(st, bind)
                else:
                    out = self._dispatch_uniform_attempt(
                        bodies, rep, deadline=deadline,
                        allow_stepped=allow_stepped, bind=bind)
            except _PARSE_ERRORS:
                raise
            except Exception as e:  # noqa: BLE001 — device/injected
                if self.health is not None:
                    self.health.record_failure(self._phys(rep), e)
                last = e
                continue
            if rep > 0:
                failover_stats.record_succeeded(self._phys(rep))
            return out
        if self.n_replicas > 1:
            failover_stats.record_failed(self._phys(0))
        assert last is not None
        raise last

    def _dispatch_uniform_attempt(self, bodies: list[dict],
                                  replica: int,
                                  deadline: float | None = None,
                                  allow_stepped: bool | None = None,
                                  bind=None) -> dict:
        """One dispatch attempt against one replica row's copies: bind,
        admit, and enqueue the shard_map program WITHOUT syncing, so
        several groups' (or several searchers') programs can be in
        flight at once. A `deadline` (absolute monotonic seconds) on a
        fused-admitted plan arms the STEPPED program form — the chunked
        tile walk with the collective-safe per-chunk deadline check —
        so a laggard mesh search exits early from the device instead of
        completing its whole walk (the cooperative _PendingMesh check
        only fires once results are already computed)."""
        self._check_shard_rows(replica)
        return self.launch(self.prepare(bodies, replica, deadline=deadline,
                                        allow_stepped=allow_stepped), bind)

    def prepare(self, bodies: list[dict], replica: int = 0,
                deadline: float | None = None,
                allow_stepped: bool | None = None,
                sort: tuple = ("_score",), place: bool = False) -> dict:
        """Everything of a dispatch but the launch: parse and bind on
        every shard's view (`_bind_body`), then `_assemble`. The result
        launches with `launch` as often as wanted. `sort` is ("_score",)
        or ("field", name, descending, "num"): a numeric field's values,
        reduced across the shards by (value, shard, doc). `place` puts
        the parameters on the mesh's devices now, in the program's
        layout, so that a kept dispatch uploads nothing."""
        return self._assemble([self._bind_body(b) for b in bodies],
                              replica, deadline, allow_stepped, sort, place)

    def _assemble(self, bound: list, replica: int = 0,
                  deadline: float | None = None,
                  allow_stepped: bool | None = None,
                  sort: tuple = ("_score",), place: bool = False) -> dict:
        """A dispatch from bound bodies (`_bind_body`'s, of one group):
        finalize, the aggregation context of the first body's
        aggregations, the fused admission, the compiled program, and
        the pack they are for."""
        pk = self.packed
        n = len(bound)
        # the window over all shards; a shard gives at most its rows
        k_all = next_pow2(max(max(bb.sig[2] for bb in bound), 1), floor=1)
        k = min(k_all, pk.cap)
        agg_specs = bound[0].parsed["agg_specs"]

        # pad the batch to a power of two (the compiled programs are
        # keyed on log-many batch sizes, as the single-chip executor's)
        # that is a replica-axis multiple
        R = self.n_replicas
        B = ((next_pow2(max(n, 1), floor=1) + R - 1) // R) * R
        rows = [bb.bounds for bb in bound] + [bound[0].bounds] * (B - n)

        # the bodies' bound trees on the UNION views, per (shard, query);
        # ONE finalize over the flattened batch guarantees identical desc
        # across shards
        sig0 = bound[0].sig[0]
        if any(bb.sig[0] != sig0 for bb in bound[1:]):
            raise SearchParseError(_HETEROGENEOUS)
        flat_bounds = [r[v] for v in range(len(pk.bind_views)) for r in rows]
        desc, flat_params = finalize(flat_bounds)  # leaves [S_local*B, ...]
        params = jax.tree_util.tree_map(
            lambda a: a.reshape(len(pk.bind_views), B, *a.shape[1:]),
            flat_params)
        params = pk.place_params(params)

        agg_desc, agg_params = self._build_aggs(agg_specs)
        agg_params = pk.place_aggs(agg_params)
        if place:
            params = jax.device_put(
                params, NamedSharding(self.mesh, P("shard", "replica")))
            agg_params = jax.device_put(
                agg_params, NamedSharding(self.mesh, P("shard")))

        # sorted-view agg layouts (presence-gated, like single-chip):
        # when the query is view-compatible, pack stacked sorted layouts
        # + filter-column projections so the in-program agg mask never
        # rides a per-query permutation gather
        filter_kw: set = set()
        filter_num: set = set()
        if agg_specs and pk.shard_offset == 0 \
                and len(pk.shards) == pk.n_shards \
                and _bound_view_fields(flat_bounds[0], filter_kw,
                                       filter_num):
            kw_layouts = {s.field for s in agg_specs if s.kind == "terms"}
            num_layouts = {s.field for s in agg_specs
                           if s.kind in ("date_histogram", "histogram",
                                         "percentiles",
                                         "percentile_ranks")}
            sub_nums = {m.field for s in agg_specs
                        for m in getattr(s, "sub_metrics", ())}
            pk.ensure_sorted_layouts(kw_layouts, num_layouts, filter_kw,
                                     filter_num | sub_nums)

        # fused block-max score+top-k routing: the SAME plan classifier
        # as the single-chip executor (a field sort and, since the mesh
        # fused branch computes no aggs, agg plans fall back), over a
        # pack that carries per-shard-row tile summaries, with positive
        # bool boosts. Every admission input is identical on every host,
        # so the SPMD entry stays collective.
        fused = pallas_reason = None
        bundle, reject = _fused_plan_bundle(desc, min(k, pk.cap),
                                            agg_specs, sort,
                                            allow_aggs=False)
        if bundle is not None:
            from ..ops.scoring import positional_prefix, clause_fields
            for _r, kd, f, _w in bundle:
                if kd in _FUSED_DENSE_KINDS:
                    if "tile_max" not in pk.dev["text"].get(f, {}):
                        bundle, reject = None, "missing_tile_max"
                        break
                elif isinstance(kd, str) and positional_prefix(kd):
                    # every clause field needs the packed positional
                    # slab AND tile summaries (spec packs them only
                    # when every shard on every host carries positions)
                    if any("fwd_pos" not in pk.dev["text"].get(cf, {})
                           or "tile_max" not in pk.dev["text"].get(cf, {})
                           for cf in clause_fields(f)):
                        bundle, reject = None, "missing_positions_pack"
                        break
                elif kd in _FUSED_VEC_KINDS:
                    if f not in pk.dev.get("vec", {}):
                        bundle, reject = None, "missing_vector_column"
                        break
                elif "tile_lo" not in pk.dev["num"].get(f, {}):
                    bundle, reject = None, "missing_tile_minmax"
                    break
        if bundle is not None and not _fused_params_ok(desc, flat_params,
                                                       bundle):
            bundle, reject = None, "nonpositive_boost"
        if bundle is not None:
            ck = min(min(k, pk.cap), score_tile_size(pk.cap))
            pallas_reason = _bundle_pallas_reason(
                bundle, (), ck, _bundle_pos_width(bundle, pk.dev["text"]),
                _bundle_fwd_width(bundle, pk.dev["text"]))
            # an SPMD program cannot wall-clock itself per host without
            # desyncing the collective (run_backend=None), but it CAN
            # reuse a choice the single-chip executor timed + persisted
            # for an identical pack: the per-shard fingerprints key the
            # same canonical store entries (autotune_persist_key)
            backend = resolve_fused_backend(
                ("mesh", pk.index_name, pk.cap, desc, k), ck,
                pallas_candidate=pallas_reason is None,
                # keyed by each shard's OWN capacity: that is the cap a
                # single-chip execution of the content-identical segment
                # persisted under (capacity is content-derived, so it
                # matches exactly when the fingerprint does — pk.cap is
                # the mesh-wide pad and would silently never match).
                # seg_cache_key (not fingerprint): a streaming TAIL
                # shard keys on its (base generation, pow2 extent), so
                # a refreshed tail keeps hitting the same entry
                persist_keys=tuple(autotune_persist_key(
                    seg_cache_key(s), s.capacity, desc, k, False)
                    for s in pk.shards))
            fused = (bundle, backend)
        stepped = (fused is not None and deadline is not None
                   and (allow_stepped if allow_stepped is not None
                        else _mesh_stepped_enabled()))
        walks = ()
        if fused is not None:
            n_tiles = pk.dev["text"][bundle_primary_field(bundle)][
                "tile_max"].n_tiles
            walks = (_fused_walk(
                bundle, fused[1], n_tiles, min(k, pk.cap),
                _resident_chunk_tiles(n_tiles) if stepped else None),)
        st = {"run": self._compiled(desc, agg_desc, k_all, B // R, fused,
                                    stepped=stepped, sort=sort),
              "params": params, "agg_params": agg_params,
              "stepped": stepped, "fused": fused, "reject": reject,
              "pallas_reason": pallas_reason, "walks": walks,
              "agg_specs": agg_specs, "sort": sort,
              # captured NOW: a later _build_aggs (another group's
              # dispatch before this one collects) must not clobber it
              "agg_ctx": self._agg_ctx, "n": n, "B": B, "packed": pk,
              # which replica row's copies this attempt ran against —
              # the collect probe and collect-time failover key on it
              "replica": replica}
        if stepped:
            hi, lo = _split_deadline(deadline)
            st["step"] = pk.place_step(
                jnp.asarray([hi, lo, 0.0, 0.0], jnp.float32))
        return st

    def launch(self, st: dict, bind=None) -> dict:
        """Enqueue a prepared dispatch without syncing: counts its fused
        admission (and why the Pallas kernel was passed over) and
        returns the state `_collect_uniform` reads, the
        program's output under "out". The program call and the request
        for its result's copy to the host are the `dispatch` phase,
        counted in `launches` (search/executor.py `_launch`, which
        pauses the caller's `bind`). A plan without aggregations is
        given the pack without its sorted agg layouts, which only
        aggregations read: a layout that another plan's aggregations
        add later then changes none of its programs."""
        pk = st["packed"]
        if st["fused"] is not None:
            _fused_stats.record_admit(
                positional=_bundle_positional(st["fused"][0]))
            if st["pallas_reason"] is not None:
                _fused_stats.record_pallas_reject(st["pallas_reason"])
        else:
            _fused_stats.record_reject(st["reject"])
        seg = pk.dev if st["agg_specs"] else {
            key: v for key, v in pk.dev.items()
            if key not in ("kw_sorted", "num_sorted")}
        args = (seg, pk.live, st["params"], st["agg_params"])
        if st["stepped"]:
            args += (st["step"],)
        backend = "unfused" if st["fused"] is None \
            else "fused_" + st["fused"][1]
        with _launch(bind, backend):
            out = st["run"](*args)
            fetch = _start_fetch(out)
        return dict(st, out=out, **fetch)

    def _collect_uniform(self, st: dict, leaf=None) -> list[dict]:
        """Collect half of _raw_uniform: sync + build per-body raws.
        With `leaf` (the caller's `collect` phase) the sync is the
        executor's `_collect`: counted in `collects`, with `st` as its
        layout, after which `leaf` is the `unpack` phase. It reads the
        pack the plan was prepared for, which a refresh never changes."""
        pk = st["packed"]
        # collect-phase fault boundary (mirrors the reader's): straggler
        # rules (shard_delay defaults to phase=collect) burn wall-clock
        # here, where the caller waits on the collective's results —
        # _PendingMesh.finish's deadline check then times out the
        # still-uncollected groups
        from ..utils import faults
        if faults.enabled():
            for local in range(len(pk.shards)):
                faults.on_dispatch("mesh", index=pk.index_name,
                                   shard=pk.shard_offset + local,
                                   replica=self._phys(
                                       int(st.get("replica", 0))),
                                   phase="collect")
        n, B = st["n"], st["B"]
        agg_specs = st["agg_specs"]
        host = (jax.device_get(st["out"]) if leaf is None
                else _collect(st["out"], st, leaf))
        (m_score, m_shard, m_doc, total, prune, m_key, m_missing), \
            agg_out = host[:2]
        if st.get("stepped") and int(host[2]) > 0:
            # the psum'd device-side verdict: ANY shard's chunk walk
            # crossing the deadline times the whole search out — its
            # skipped chunks make the gathered results unusable, which
            # is exactly the discard-on-timeout contract the
            # cooperative path already has
            from ..search import resident as _resident
            _resident.stats.preempted_by_deadline.inc()
            raise SearchTimeoutError(pk.index_name)
        if st["fused"] is not None:
            # each shard row's (hard, thresholded, examined), replica
            # psum'd and replicated per query row: the launch's totals
            # once, and each row's under its shard number
            rows = np.asarray(prune[0], np.float64)
            hard, thr, examined = rows.sum(0)
            _fused_stats.record_prune(
                hard, thr, examined,
                positional=_bundle_positional(st["fused"][0]),
                steps=_walk_steps(examined, st["walks"]))
            _fused_stats.record_mesh_rows(rows)

        per_query_partials = [None] * B
        if agg_specs:
            per_query_partials = shard_partials(
                agg_specs, st["agg_ctx"],
                [jax.tree_util.tree_map(np.asarray, agg_out)], batch=B)
        return [{"score": m_score[i], "shard": m_shard[i],
                 "doc": m_doc[i], "total": int(total[i]),
                 "key": m_key[i], "missing": m_missing[i],
                 "partials": per_query_partials[i],
                 "agg_specs": agg_specs, "packed": pk}
                for i in range(n)]

    @staticmethod
    def _build_response(body: dict, raws: list[dict],
                        sort: tuple = ("_score",),
                        with_partials: bool = False,
                        started: float | None = None) -> dict:
        """Merge one body's raw results from 1+ generations (base/tail
        packs) into a response — the cross-generation sortDocs + agg
        reduce: candidates by (key, generation, rank), a generation's
        ranks being the program's (shard, document) order; then the
        hits' `_source`. `sort` is the one the program reduced by (see
        `prepare`); `with_partials` leaves the aggregations as the
        merged partials (`_agg_partials`) for the node's reduce;
        `started` (monotonic seconds) gives `took`."""
        frm = int(body.get("from", 0))
        size = int(body.get("size", 10))
        by_score = sort[0] == "_score"
        sign = -1.0 if by_score or sort[2] else 1.0
        cands = []
        total = 0
        for gen, raw in enumerate(raws):
            total += raw["total"]
            for j in range(int(min(raw["total"], raw["score"].shape[0]))):
                missing = not by_score and bool(raw["missing"][j])
                key = float(raw["score"][j] if by_score else raw["key"][j])
                cands.append((missing, sign * key, gen, j))
        cands.sort()
        src = body.get("_source", True)
        hits = []
        for missing, _key, gen, j in cands[frm: frm + size]:
            raw = raws[gen]
            pk = raw["packed"]
            s = int(raw["shard"][j])
            local = s - pk.shard_offset
            if not 0 <= local < len(pk.shards):
                raise RuntimeError(
                    f"hit on shard {s} lives on another host — fetch "
                    "multi-host results through MultiHostIndex, not "
                    "DistributedSearcher directly")
            seg = pk.shards[local]
            d = int(raw["doc"][j])
            score = float(raw["score"][j])
            hit = {"_index": pk.index_name, "_type": "_doc",
                   "_id": seg.ids[d],
                   "_score": score if by_score else (score or None)}
            if not by_score:
                key = float(raw["key"][j])
                hit["sort"] = [None] if missing else [
                    int(key) if key.is_integer() else key]
            if src is not False:
                source = filter_source(json.loads(seg.sources[d]), src)
                if source is not None:
                    hit["_source"] = source
            hits.append(hit)
        pk0 = raws[0]["packed"]
        resp = {
            "took": (0 if started is None
                     else int((time.monotonic() - started) * 1000)),
            "timed_out": False,
            "_shards": shards_header(pk0.n_shards, pk0.n_shards),
            # as a shard reader's: no score where no hit was asked for
            "hits": {"total": total,
                     "max_score": (-cands[0][1])
                     if by_score and cands and frm + size else None,
                     "hits": hits},
        }
        agg_specs = raws[0]["agg_specs"]
        if agg_specs:
            merged = merge_shard_partials(
                agg_specs, [r["partials"] for r in raws
                            if r["partials"] is not None])
            if with_partials:
                resp["_agg_partials"] = merged
            else:
                resp["aggregations"] = finalize_partials(agg_specs, merged)
        return resp

    # -- aggs --------------------------------------------------------------
    def _build_aggs(self, specs: list[AggSpec]):
        pk = self.packed
        self._agg_ctx = None
        if not specs:
            return (), ()
        global_ords = {}
        for s in specs:
            if s.kind in ("terms", "cardinality"):
                terms = pk.kw_terms.get(s.field, [])
                ident = np.arange(max(len(terms), 1), dtype=np.int32)
                # identity maps: packed columns already hold mesh-global ords
                global_ords[s.field] = (terms, [ident] * pk.n_shards)
        extents = {
            f: (None if e["ext"] is None
                else (e["ext"][0], e["ext"][1],
                      np.dtype(e["dtype"]) == np.int32))
            for f, e in pk.spec.num.items()}
        self._agg_ctx = ShardAggContext(pk.shards, global_ords,
                                        allow_device_topk=False,
                                        extent_override=extents)
        agg_desc, per_seg = self._agg_ctx.build(specs)
        if not per_seg:
            return agg_desc, ()
        stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *per_seg)
        return agg_desc, stacked

    # -- the distributed program ------------------------------------------
    def _compiled(self, desc, agg_desc, k: int, b_loc: int,
                  fused: tuple | None = None, stepped: bool = False,
                  sort: tuple = ("_score",)):
        """One pinned shard_map program per (plan signature, agg sig,
        pow2 k, local batch, stepped?, sort) — k arrives pow2-bucketed from
        _dispatch_uniform_attempt, so this cache IS the mesh's resident
        entry table, scoped to one immutable pack: a repack rebuilds
        PackedShards AND this searcher, so a stale program dies with
        the instance and can never serve the new pack (no fingerprint
        key needed — the per-shard fingerprints are constant for the
        life of the cache). With ES_TPU_RESIDENT_LOOP set, reuse is
        reported through the resident counters.

        The STEPPED variant (deadline-carrying fused searches) takes an
        extra replicated step_arr input and returns the psum'd
        device-side timed_out verdict: the fused tile walk runs in
        _RESIDENT_CHUNKS chunks with a host-clock poll between chunks —
        the same chunked form (XLA fori span or chunked pallas_call
        grid) the resident loop pins — and NO collectives inside the
        chunk loop, so a per-device verdict cannot desync the mesh; the
        final psum over BOTH axes makes the timeout decision
        collective. Deadline-less searches keep the callback-free
        single-walk program.

        A device holds `rows_per_device` shard rows of the pack. Where
        that is one, the program takes the row as it is; where it is
        more (several shards on one device), it runs the one-shard body
        over its rows with `jax.vmap` and reduces them on the device
        first: totals and buckets summed (min / max for those), the
        rows' candidates and prune counts side by side so that row r of
        device d is shard d * rows + r. Then the collectives over the
        "shard" axis, of size 1 on a mesh of one device."""
        from ..search import resident as _resident
        key = (desc, agg_desc, k, b_loc, fused, stepped, sort)
        fn = self._jit_cache.get(key)
        if fn is not None:
            if _resident.enabled():
                _resident.stats.resident_hits.inc()
            return fn
        if _resident.enabled():
            _resident.stats.cold_dispatches.inc()
        pk = self.packed
        mesh = self.mesh
        cap = pk.cap
        chunk_tiles = 1
        if stepped:
            chunk_tiles = _resident_chunk_tiles(
                pk.dev["text"][bundle_primary_field(fused[0])][
                    "tile_max"].n_tiles)

        gather_out = self._gather_out
        in_specs = (P("shard"), P("shard"), P("shard", "replica"),
                    P("shard"))
        if gather_out:
            # results all_gather'd over "replica" in-program: every
            # device (hence every HOST of a multi-process replica
            # layout) holds the full batch's output, so collect never
            # reads a non-addressable shard
            out_specs = ((P(),) * 7, P())
        else:
            out_specs = ((P("replica"),) * 7, P("replica"))
        if stepped:
            in_specs = in_specs + (P(),)
            out_specs = out_specs + (P(),)

        rows = pk.rows_per_device

        def per_shard(seg, live_l, prm_l, agg_l, step=None):
            """One shard row's top-k, total, prune counts, aggregation
            buckets and (stepped) timed-out verdict."""
            timed = l_key = l_missing = None
            if fused is not None:
                # same fused block-max score+top-k engine as the
                # single-chip executor; each shard prunes against its
                # own per-clause tile summaries and never materializes
                # [B, cap] (admission guarantees no aggs, so the match
                # mask is never needed)
                f_bundle, f_backend = fused
                if stepped:
                    l_score, l_idx, l_total, pruned, timed = \
                        eval_fused_topk(seg, desc, prm_l, live_l,
                                        min(k, cap), f_bundle,
                                        f_backend, step=step)
                else:
                    l_score, l_idx, l_total, pruned = eval_fused_topk(
                        seg, desc, prm_l, live_l, min(k, cap), f_bundle,
                        f_backend)
                agg_out = {}
            else:
                score, match = eval_node(desc, prm_l, seg, cap, b_loc)
                valid = match & live_l[None, :]
                score = jnp.where(valid, score, 0.0)
                if sort[0] == "_score":
                    l_score, l_idx, l_total = top_k_hits(score, valid,
                                                         min(k, cap))
                else:
                    # a numeric field's own values, as the single-chip
                    # program sorts them (missing after every value)
                    col = seg["num"].get(sort[1])
                    if col is None:
                        keys = jnp.zeros((cap,), jnp.int32)
                        missing = jnp.ones((cap,), bool)
                    else:
                        keys, missing = col["values"], ~col["exists"]
                    l_key, l_idx, l_total, l_missing = top_k_by_field(
                        keys, valid, missing, min(k, cap), sort[2])
                    l_score = jnp.take_along_axis(score, l_idx, axis=1)
                pruned = jnp.zeros((3,), jnp.float32)

                # sorted-view agg path (same machinery as the
                # single-chip executor): live masks permuted into each
                # layout's order in-program (once per dispatch), plan
                # gates per agg node
                live_views = {}
                for f, store in seg.get("kw_sorted", {}).items():
                    live_views[("kw", f)] = jnp.take(live_l, store["perm"])
                for f, store in seg.get("num_sorted", {}).items():
                    live_views[("num", f)] = jnp.take(live_l,
                                                      store["perm"])
                plan = _agg_view_plan(desc, agg_desc, agg_l, seg,
                                      live_views)
                views = _ViewMasks(desc, prm_l, seg, live_views, cap,
                                   b_loc)
                agg_out = eval_aggs(agg_desc, agg_l, seg, valid,
                                    views=views, plan=plan)
            return l_score, l_idx, l_total, l_key, l_missing, pruned, \
                agg_out, timed

        @partial(shard_map, mesh=mesh, in_specs=in_specs,
                 out_specs=out_specs, check_vma=False)
        def _segment_program_mesh(seg, live, prm, agg_prm, *step_in):
            # b_loc is STATIC (B / replicas): param-less plans (e.g. a
            # term absent from every shard binds to a constant) carry
            # no leaf to infer the batch from
            step = (_resident_step(step_in[0], chunk_tiles) if stepped
                    else None)
            if rows == 1:
                def row(x):
                    return jax.tree_util.tree_map(lambda a: a[0], x)
                got = per_shard(row(seg), live[0], row(prm), row(agg_prm),
                                step)
            else:
                # a device that holds several shards runs the one
                # shard's body over its rows: every output then leads
                # with [rows], reduced on the device before the
                # collectives below
                got = jax.vmap(partial(per_shard, step=step))(
                    seg, live, prm, agg_prm)
            l_score, l_idx, l_total, l_key, l_missing, pruned, agg_out, \
                timed = got

            # ---- cross-shard reduce over ICI (SearchPhaseController) ----
            with jax.named_scope("shard_reduce"):
                if sort[0] == "_score":
                    # a slot past a shard's matches scores -inf
                    l_key, rank = l_score, l_score
                    l_missing = jnp.zeros(l_score.shape, bool)
                else:
                    rank = _sort_rank(l_key, l_missing, l_total, sort[2])
                kk = l_score.shape[-1]

                def flat(x):
                    # [b, S * k], shard-major: a device's rows follow
                    # one another, so device d's row r is shard
                    # d * rows + r
                    g = jax.lax.all_gather(x, "shard",
                                           tiled=rows > 1)  # [S, b, k]
                    return jnp.moveaxis(g, 0, 1).reshape(b_loc, -1)

                # top_k is stable, so equal ranks come shard ascending,
                # then in the shard's own order (document ascending); a
                # window wider than a shard's rows takes every shard's
                _, m_pos = jax.lax.top_k(flat(rank),
                                         min(k, pk.n_shards * kk))

                def pick(x):
                    return jnp.take_along_axis(flat(x), m_pos, axis=1)

                m_score, m_doc = pick(l_score), pick(l_idx)
                m_key, m_missing = pick(l_key), pick(l_missing)
                m_shard = (m_pos // kk).astype(jnp.int32)
                if rows > 1:
                    l_total = l_total.sum(0)
                total = jax.lax.psum(l_total, "shard")

            # each shard row's prune counts, [S, 3] in shard order like
            # the candidates, psum'd over "replica": each replica prunes
            # against its own sub-batch, so one replica's counts would
            # drop every other's. An unfused plan counts none.
            if fused is None:
                prune = jnp.zeros((mesh.shape["shard"] * rows, 3),
                                  jnp.float32)
            else:
                prune = jax.lax.psum(
                    jax.lax.all_gather(pruned, "shard", tiled=rows > 1),
                    "replica")
            prune = jnp.broadcast_to(prune[None], (b_loc,) + prune.shape)
            agg_out = _reduce_shard_axis(agg_out, rows)
            if gather_out:
                # batch-axis gather over the replica rows (tiled: row
                # r's [b_loc] slice lands at rows r*b_loc..): identical
                # host-side shapes to the sharded out_specs, now
                # replicated on every device
                def _rep(x):
                    return jax.lax.all_gather(x, "replica", axis=0,
                                              tiled=True)
                m_score, m_shard, m_doc, total, prune, m_key, \
                    m_missing = (_rep(m_score), _rep(m_shard),
                                 _rep(m_doc), _rep(total), _rep(prune),
                                 _rep(m_key), _rep(m_missing))
                agg_out = jax.tree_util.tree_map(_rep, agg_out)
            out = ((m_score, m_shard, m_doc, total, prune, m_key,
                    m_missing), agg_out)
            if stepped:
                # collective verdict: any device's walk crossing the
                # deadline times out the whole search (both axes — a
                # replica row's laggard is as fatal as a shard's)
                timed = timed.astype(jnp.int32)
                if rows > 1:
                    timed = timed.sum(0)
                out = out + (jax.lax.psum(timed, ("shard", "replica")),)
            return out

        fn = jax.jit(_segment_program_mesh)
        self._jit_cache[key] = fn
        return fn


class MeshIndex:
    """A LIVE mesh-resident index: big immutable base pack + small tail
    pack + liveDocs-style deletes, so the distributed path serves an
    index that is still being written to.

    Refresh semantics (the mesh analog of InternalEngine.refresh
    :549-555 — Lucene's big-segments-plus-small-segments shape mapped
    onto PackedShards):

    * docs deleted or updated since the base pack: their base rows are
      DEACTIVATED in a new view of the base pack (one bitmap upload, no
      repack);
    * docs new or updated since the base pack: rebuilt into a TAIL
      PackedShards whose cost is proportional to the DELTA, not the
      corpus;
    * when the tail outgrows `repack_ratio` of the base, everything
      folds into a fresh base pack (the merge/force-merge analog).

    A refresh changes no pack or searcher in place: it makes new ones
    (sharing the compiled programs where the shapes allow), so what
    `searchers()` returned before it is a point-in-time view that a
    search in flight, or a reader that holds it, keeps reading.

    Searches run on base and tail programs and merge per body:
    candidates by (score desc, generation, shard, doc), totals summed,
    agg partials merged by bucket key (ordinal spaces differ between
    packs; partials are keyed by term strings / numeric keys exactly so
    they can meet).
    """

    REPACK_MIN = 4096

    def __init__(self, node, index_name: str, mesh: Mesh,
                 repack_ratio: float = 0.25, svc=None,
                 readers: list | None = None, shard_stats: bool = False):
        """`node` (or `svc`, the index's service itself) holds the index.
        Without `readers` the mesh packs the engines' state once they
        are refreshed; with them (one `ShardReader` a shard, as the node's
        search path acquires them) it packs what those readers see, and
        so does `refresh(readers)`. `shard_stats`: see `PackedShards`."""
        self.node = node
        self.index_name = index_name
        self.mesh = mesh
        self.repack_ratio = repack_ratio
        self._svc = svc
        self.shard_stats = shard_stats
        self.last_refresh_stats: dict = {}
        self._full_pack(self._views(readers))

    def _views(self, readers: list | None) -> list:
        if readers is not None:
            # what a search of the node sees
            return [(r.segments, r.live_all) for r in readers]
        svc = self._svc or self.node.indices[self.index_name]
        return engine_views(svc)

    # -- packing -----------------------------------------------------------

    def repack(self, readers: list | None = None) -> None:
        """Pack what `readers` see (or the engines' state) anew as the
        base, with no tail."""
        self._full_pack(self._views(readers))

    def _full_pack(self, views: list) -> None:
        svc = self._svc or self.node.indices[self.index_name]
        self.base = PackedShards.from_views(
            self.index_name, views, svc.mappers, self.mesh,
            shard_stats=self.shard_stats)
        # the old base's programs serve the new one where its shapes
        # agree (they take the pack as an argument)
        prev = getattr(self, "base_searcher", None)
        self.base_searcher = (prev.over(self.base) if prev is not None
                              else None) or DistributedSearcher(self.base)
        # per-shard id -> (row, version) of the packed docs
        self.base_docs: list[dict[str, tuple[int, int]]] = []
        for seg in self.base.shards:
            self.base_docs.append({
                did: (row, int(seg.versions[row]))
                for did, row in seg.id_map.items()})
        self.tail: PackedShards | None = None
        self.tail_searcher: DistributedSearcher | None = None
        # signature of the delta the current tail pack was built from:
        # an unchanged delta skips the rebuild AND keeps the compiled
        # programs warm
        self._tail_sig: tuple | None = None
        # tail generation key: the mesh analog of the engine's
        # (base generation, delta epoch) — tail shards carry it as
        # their delta_parent so every fingerprint-keyed cache they
        # touch (autotune persist keys) survives the per-refresh
        # rebuild; a repack mints a new one
        import hashlib
        h = hashlib.blake2b(digest_size=8)
        for seg in self.base.shards:
            h.update(seg.fingerprint().encode())
        self._base_gen = f"mesh:{h.hexdigest()}"
        self._tail_epoch = 0

    def refresh(self, readers: list | None = None) -> dict:
        """Fold engine changes (or what `readers` see) into the mesh
        view. Returns stats: {"mode": "noop"|"tail"|"repack",
        "tail_docs": n, "deactivated": n}."""
        svc = self._svc or self.node.indices[self.index_name]
        views = self._views(readers)
        deactivate: dict[int, list[int]] = {}
        deltas: list[list[tuple[str, int, bytes]]] = []
        total_delta = 0
        base_total = sum(s.num_docs for s in self.base.shards)
        for sid, view in enumerate(views):
            current = view_docs(view)
            packed = self.base_docs[sid]
            base_seg = self.base.shards[sid]

            def changed(did: str, ver: int, src: bytes) -> bool:
                entry = packed.get(did)
                if entry is None:
                    return True
                row, base_ver = entry
                if base_ver != ver:
                    return True
                # force/external_gte writes can REPLACE a doc keeping
                # the same version — the bytes are the tiebreaker
                return base_seg.sources[row] != src

            dead = [row for did, (row, ver) in packed.items()
                    if did not in current
                    or changed(did, *current[did])]
            if dead:
                deactivate[sid] = dead
            delta = [(did, ver, src)
                     for did, (ver, src) in current.items()
                     if changed(did, ver, src)]
            deltas.append(delta)
            total_delta += len(delta)

        threshold = max(base_total * self.repack_ratio, self.REPACK_MIN)
        if total_delta > threshold:
            self._full_pack(views)
            self.last_refresh_stats = {"mode": "repack",
                                       "tail_docs": total_delta,
                                       "deactivated": 0}
            return self.last_refresh_stats

        n_dead = sum(len(v) for v in deactivate.values())
        base = self.base.without_rows(deactivate)
        if base is not self.base:
            self.base = base
            self.base_searcher = self.base_searcher.over(base)
        if total_delta == 0:
            if self.tail is not None:
                # deletions may have emptied the tail
                self.tail = None
                self.tail_searcher = None
                self._tail_sig = None
            self.last_refresh_stats = {"mode": "noop",
                                       "tail_docs": 0,
                                       "deactivated": n_dead}
            return self.last_refresh_stats

        import zlib
        sig = tuple(tuple(sorted((did, ver, zlib.crc32(s))
                                 for did, ver, s in delta))
                    for delta in deltas)
        if sig == self._tail_sig and self.tail is not None:
            # nothing changed since the current tail pack was built —
            # keep it (and its compiled programs) instead of rebuilding
            self.last_refresh_stats = {"mode": "noop",
                                       "tail_docs": total_delta,
                                       "deactivated": n_dead}
            return self.last_refresh_stats

        svc_mappers = svc.mappers
        self._tail_epoch += 1
        tail_segs = []
        for sid, delta in enumerate(deltas):
            builder = SegmentBuilder(similarity=svc_mappers.similarity_for)
            for did, ver, src in sorted(delta):
                builder.add(svc_mappers.parse(did, src), version=ver)
            seg = builder.build(f"tail_{sid}")
            # generation-preserving refresh: the tail shard keys its
            # caches on (base generation, pow2 extent) and its term-
            # count-derived shapes bucket to pow2, so the rebuilt pack
            # usually lands on the SAME avals and the searcher below
            # runs it with the old one's pinned shard_map programs
            seg.delta_parent = self._base_gen
            seg.delta_epoch = self._tail_epoch
            pad_delta_shapes(seg)
            tail_segs.append(seg)
        new_tail = PackedShards(self.index_name, tail_segs,
                                svc_mappers, self.mesh,
                                shard_stats=self.shard_stats)
        searcher = (self.tail_searcher.over(new_tail)
                    if self.tail_searcher is not None else None)
        reused = searcher is not None
        self.tail = new_tail
        # first tail, or a pow2 bucket overflowed: one rebuild
        self.tail_searcher = searcher or DistributedSearcher(new_tail)
        self._tail_sig = sig
        self.last_refresh_stats = {"mode": "tail",
                                   "tail_docs": total_delta,
                                   "deactivated": n_dead,
                                   "tail_programs_reused": bool(reused)}
        return self.last_refresh_stats

    # -- search ------------------------------------------------------------

    def searchers(self) -> list:
        """The generations a search reads now: the base pack's searcher,
        and the tail's where there is one."""
        return [self.base_searcher] + (
            [self.tail_searcher] if self.tail_searcher is not None else [])

    def search(self, body: dict) -> dict:
        return self.msearch([body])[0]

    def msearch(self, bodies: list[dict],
                with_partials: bool = False) -> list[dict]:
        return submit_mesh(self.searchers(), bodies,
                           with_partials).finish()
