"""Mesh-distributed search: shard-parallel scoring with in-program reduce.

Reference analog: the distributed QUERY phase — TransportSearchAction
fanning out to one copy of every shard (TransportSearchTypeAction.java:
126-153) and SearchPhaseController merging shard top-k + agg trees on a
coordinating node (SearchPhaseController.java:147-282).

TPU-first redesign: instead of RPC fan-out + host merge, the WHOLE
distributed query is ONE jitted program over a ("replica", "shard")
mesh via shard_map:

    each device scores ITS shard's columns locally        (QueryPhase)
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
                               _RESIDENT_CHUNKS)
from ..search.query_dsl import QueryParser
from ..search.aggregations import (parse_aggs, ShardAggContext, AggSpec,
                                   merge_shard_partials, finalize_partials,
                                   shard_partials)
from ..ops.topk import top_k_hits
from ..search.controller import shards_header
from ..utils.errors import (QueryParsingError, SearchParseError,
                            SearchTimeoutError)

# request-shaped errors: every replica row would reject them the same
# way, so they never retry, never count toward device health, and
# surface unchanged
_PARSE_ERRORS = (SearchParseError, QueryParsingError)


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

    `spec`/`shard_offset`/`placer` support multi-host packing: each
    host packs only its LOCAL shards against the GLOBAL PackSpec and
    places rows into the global mesh array via its own placer
    (parallel/multihost.py); single-host callers omit all three."""

    def __init__(self, index_name: str, shards: list[Segment],
                 mapper: MapperService, mesh: Mesh,
                 spec: PackSpec | None = None, shard_offset: int = 0,
                 placer=None):
        self.index_name = index_name
        self.mappers = mapper
        self.mesh = mesh
        self.n_shards = mesh.shape["shard"]
        if spec is None:
            spec = PackSpec([summarize_shards(shards)], self.n_shards)
        if spec.n_shards != self.n_shards:
            raise ValueError(f"spec for {spec.n_shards} shards on a "
                             f"{self.n_shards}-shard mesh")
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
            for i, s in enumerate(shards):
                kc = s.keywords.get(f)
                if kc is None:
                    continue
                remap = np.asarray([lookup[t] for t in kc.terms],
                                   dtype=np.int32)
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
                pspec = P("shard", *([None] * (a.ndim - 1)))
                return jax.device_put(jnp.asarray(a),
                                      NamedSharding(mesh, pspec))

        num_dtypes = {f: np.dtype(spec.num[f]["dtype"])
                      for f in num_fields}
        self.dev = jax.tree_util.tree_map(placer, arrays)
        self._shard_put = placer
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
        for s in shards:
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
                kc = KeywordColumn(
                    name=f, terms=terms,
                    term_index={t: i for i, t in enumerate(terms)},
                    ords=np.full(0, -1, np.int32),
                    df=spec.kw_df[f])
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
                s, text, kws, nums, num_docs=max(spec.total_docs, 1),
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

    def deactivate_rows(self, rows_per_shard: dict[int, list[int]]) -> None:
        """Clear live bits for deleted/updated docs WITHOUT repacking —
        an O(corpus bitmap) upload, not an O(corpus content) rebuild
        (the mesh analog of Lucene liveDocs). Shard ids are GLOBAL;
        each host may only deactivate rows it owns."""
        changed = False
        for sid, rows in rows_per_shard.items():
            local = sid - self.shard_offset
            if not 0 <= local < len(self.shards):
                raise ValueError(
                    f"shard {sid} is outside this host's span "
                    f"[{self.shard_offset}:"
                    f"{self.shard_offset + len(self.shards)})")
            for r in rows:
                if self.host_live[local, r]:
                    self.host_live[local, r] = False
                    changed = True
        if changed:
            self.live = self._shard_put(self.host_live)

    @classmethod
    def from_node_index(cls, node, index_name: str, mesh: Mesh) -> "PackedShards":
        """Pack a Node's index (force-merging each shard to one segment)."""
        svc = node.indices[index_name]
        shards = []
        for sid in range(svc.num_shards):
            eng = svc.shard(sid)
            eng.refresh()
            if len(eng.segments) == 0:
                shards.append(SegmentBuilder().build(f"empty_{sid}"))
            else:
                # always a fresh copy: PackedShards owns its segments (it
                # may normalize forward-index availability across shards);
                # re-bake impacts with the mapped per-field similarity so
                # mesh scores match the host path (index/similarity.py)
                shards.append(merge_segments(
                    eng.segments, f"packed_{sid}", eng.live,
                    similarity=svc.mappers.similarity_for))
        return cls(index_name, shards, svc.mappers, mesh)


def _reduce_shard_axis(agg_out: dict) -> dict:
    """psum counts/sums, pmin mins, pmax maxes over the shard axis."""
    def walk(obj):
        if isinstance(obj, dict):
            out = {}
            for key, v in obj.items():
                if isinstance(v, dict):
                    out[key] = walk(v)
                elif key == "min":
                    out[key] = jax.lax.pmin(v, "shard")
                elif key == "max":
                    out[key] = jax.lax.pmax(v, "shard")
                else:
                    out[key] = jax.lax.psum(v, "shard")
            return out
        return jax.lax.psum(obj, "shard")

    return walk(agg_out)


class _PendingMesh:
    """In-flight half of a split mesh msearch: the shard_map programs of
    every signature group are enqueued; finish() collects in submission
    order. Interface-compatible with shard_searcher._PendingMsearch so
    the dispatch scheduler can pipeline mesh searchers like readers
    (including the cooperative `deadline`: collection past it raises
    SearchTimeoutError instead of syncing the remaining groups)."""

    __slots__ = ("searcher", "bodies", "parts", "group_sizes",
                 "dispatch_count", "deadline")

    def __init__(self, searcher: "DistributedSearcher", bodies: list[dict],
                 parts: list[tuple], group_sizes: list[int],
                 deadline: float | None = None):
        self.searcher = searcher
        self.bodies = bodies
        self.parts = parts
        self.group_sizes = group_sizes
        self.dispatch_count = len(parts)
        self.deadline = deadline

    def finish(self) -> list[dict]:
        import time
        out: list[dict | None] = [None] * len(self.bodies)
        for idxs, st in self.parts:
            if self.deadline is not None \
                    and time.monotonic() > self.deadline:
                raise SearchTimeoutError(
                    self.searcher.packed.index_name)
            raws = self.searcher._collect_with_failover(
                [self.bodies[i] for i in idxs], st,
                deadline=self.deadline)
            for i, raw in zip(idxs, raws):
                out[i] = DistributedSearcher._build_response(
                    self.bodies[i], [raw])
        return out  # type: ignore[return-value]


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

    def _phys(self, replica: int) -> int:
        """Mesh-local replica row -> physical full-mesh row id."""
        return self.replica_ids[replica]

    def adopt_pack(self, packed: PackedShards) -> bool:
        """Swap in a REBUILT pack (the streaming tail's refresh epoch
        bump) while keeping every pinned shard_map program: legal
        exactly when the new pack's device-tree avals match the old —
        the compiled programs take the pack as a runtime argument, so
        identical shapes/dtypes mean zero recompiles, they just read
        the new epoch's columns. PackSpec pow2-buckets every content-
        proportional dimension (cap, nb, fwd_l, nt), so a growing tail
        only mismatches when a bucket overflows — then the caller
        rebuilds the searcher, paying the compile log-many times
        instead of once per refresh. Returns False on any mismatch."""
        if packed.mesh is not self.mesh:
            return False
        old = (self.packed.dev, self.packed.live)
        new = (packed.dev, packed.live)
        if jax.tree_util.tree_structure(old) \
                != jax.tree_util.tree_structure(new):
            return False
        for a, b in zip(jax.tree_util.tree_leaves(old),
                        jax.tree_util.tree_leaves(new)):
            if tuple(a.shape) != tuple(b.shape) or a.dtype != b.dtype:
                return False
        self.packed = packed
        from ..search import resident
        if resident.enabled() and self._jit_cache:
            # every pinned program that survived the epoch bump is one
            # avoided mesh recompile — reported through the same
            # counters the repack's drops go through
            resident.stats.refresh_reuses.inc(len(self._jit_cache))
        return True

    # -- public ------------------------------------------------------------
    def search(self, body: dict) -> dict:
        return self.msearch([body])[0]

    def msearch(self, bodies: list[dict],
                with_partials: bool = False,
                deadline: float | None = None) -> list[dict]:
        """Heterogeneous batch: bodies group by (plan signature, aggs),
        one device program per group — the mesh analog of the host
        path's signature grouping in shard_searcher.msearch. Each body
        keeps its OWN aggregations. (with_partials is accepted for
        scheduler interface parity — the isolated retry of
        search/dispatch.py calls reader.msearch(bodies, wp) — and is
        ignored: mesh responses are always complete.)"""
        return self.msearch_submit(bodies, deadline=deadline).finish()

    def msearch_submit(self, bodies: list[dict],
                       with_partials: bool = False,
                       deadline: float | None = None) -> "_PendingMesh":
        """The batched dispatch entry the scheduler (search/dispatch.py)
        expects: every signature group's shard_map program is enqueued
        WITHOUT a device sync; finish() collects in submission order.
        Group dispatches are pipelined exactly like the single-chip
        executor's — the mesh accepts the same batched entry.
        (with_partials is accepted for interface parity; mesh responses
        are always complete.)"""
        bodies = self._rewrite_knn(bodies)
        parts = []
        groups = self._signature_groups(bodies)
        for idxs in groups.values():
            parts.append((idxs,
                          self._dispatch_uniform([bodies[i]
                                                  for i in idxs],
                                                 deadline=deadline)))
        return _PendingMesh(self, bodies, parts,
                            group_sizes=[len(i) for i in groups.values()],
                            deadline=deadline)

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
        pk = self.packed
        parser = QueryParser(pk.mappers)
        binder = QueryBinder(pk.bind_views[0], pk.mappers)  # type: ignore
        groups: dict[tuple, list[int]] = {}
        for i, b in enumerate(bodies):
            sig = binder.bind(parser.parse(b.get("query"))).signature()
            aggs_key = json.dumps(b.get("aggs") or b.get("aggregations")
                                  or {}, sort_keys=True, default=str)
            k = int(b.get("size", 10)) + int(b.get("from", 0))
            groups.setdefault((sig, aggs_key, k), []).append(i)
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
                               allow_stepped: bool | None = None
                               ) -> list[dict]:
        """Collect with the OTHER half of replica failover: jax
        dispatch is asynchronous, so a real device failure (preemption,
        runtime drop, OOM) usually surfaces at the device_get inside
        _collect_uniform, not at enqueue — on such an error the whole
        dispatch+collect is re-entered once per remaining replica row.
        Deadline and request-shaped errors never retry, and a deadline
        that passes MID-failover stops the retry loop with the same
        SearchTimeoutError the pending path raises (re-dispatching
        cannot un-pass the cutoff; it only burns device time) — with no
        holds retained, so the failover-exhaustion exit leaks nothing."""
        import time
        rep0 = int(st.get("replica", 0))
        try:
            out = self._collect_uniform(st)
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
                          allow_stepped: bool | None = None) -> dict:
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
        nodes_stats()["dispatch"]["failover"]."""
        from ..search.dispatch import failover_stats
        last: Exception | None = None
        for rep in range(self.n_replicas):
            if rep > 0:
                failover_stats.record_retry(self._phys(rep))
            try:
                out = self._dispatch_uniform_attempt(
                    bodies, rep, deadline=deadline,
                    allow_stepped=allow_stepped)
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
                                  allow_stepped: bool | None = None
                                  ) -> dict:
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
        pk = self.packed
        n = len(bodies)
        parser = QueryParser(pk.mappers)
        queries = [parser.parse(b.get("query")) for b in bodies]
        sizes = [int(b.get("size", 10)) + int(b.get("from", 0))
                 for b in bodies]
        k = min(next_pow2(max(max(sizes), 1), floor=1), pk.cap)
        agg_specs = parse_aggs(bodies[0].get("aggs")
                               or bodies[0].get("aggregations"))
        for spec in agg_specs:
            fm = pk.mappers.field(spec.field)
            if spec.kind in ("terms", "cardinality", "value_count") and \
                    fm is not None and fm.type == "text" and \
                    pk.mappers.field(f"{spec.field}.keyword") is not None:
                spec.field = f"{spec.field}.keyword"

        # pad batch to a replica-axis multiple
        R = self.n_replicas
        B = ((max(n, 1) + R - 1) // R) * R
        queries = queries + [queries[0]] * (B - n)

        # bind per (shard, query) against the UNION views; ONE finalize
        # over the flattened batch guarantees identical desc across shards
        flat_bounds = []
        for view in pk.bind_views:
            binder = QueryBinder(view, pk.mappers)  # type: ignore[arg-type]
            flat_bounds.extend(binder.bind(q) for q in queries)
        sig0 = flat_bounds[0].signature()
        for bnd in flat_bounds[1:]:
            if bnd.signature() != sig0:
                raise SearchParseError(
                    "distributed msearch requires structurally identical "
                    "queries (split heterogeneous batches)")
        desc, flat_params = finalize(flat_bounds)  # leaves [S_local*B, ...]
        params = jax.tree_util.tree_map(
            lambda a: a.reshape(len(pk.bind_views), B, *a.shape[1:]),
            flat_params)
        params = pk.place_params(params)

        agg_desc, agg_params = self._build_aggs(agg_specs)
        agg_params = pk.place_aggs(agg_params)

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
        # as the single-chip executor (the mesh program is
        # score-sort-only, hence the literal sort_spec; the mesh fused
        # branch computes no aggs, so agg plans fall back), over a pack
        # that carries per-shard-row tile summaries, with positive bool
        # boosts. Every admission input is identical on every host, so
        # the SPMD entry stays collective.
        fused = None
        bundle, reject = _fused_plan_bundle(desc, min(k, pk.cap),
                                            agg_specs, ("_score",),
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
            if pallas_reason is not None:
                _fused_stats.record_pallas_reject(pallas_reason)
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
            _fused_stats.record_admit(
                positional=_bundle_positional(bundle))
        else:
            _fused_stats.record_reject(reject)
        stepped = (fused is not None and deadline is not None
                   and (allow_stepped if allow_stepped is not None
                        else _mesh_stepped_enabled()))
        run = self._compiled(desc, agg_desc, k, B // R, fused,
                             stepped=stepped)
        if stepped:
            hi, lo = _split_deadline(deadline)
            step_arr = pk.place_step(
                jnp.asarray([hi, lo, 0.0, 0.0], jnp.float32))
            out = run(pk.dev, pk.live, params, agg_params, step_arr)
        else:
            out = run(pk.dev, pk.live, params, agg_params)
        return {"out": out, "stepped": stepped,
                "fused": fused, "agg_specs": agg_specs,
                # captured NOW: a later _build_aggs (another group's
                # dispatch before this one collects) must not clobber it
                "agg_ctx": self._agg_ctx, "n": n, "B": B,
                # which replica row's copies this attempt ran against —
                # the collect probe and collect-time failover key on it
                "replica": replica}

    def _collect_uniform(self, st: dict) -> list[dict]:
        """Collect half of _raw_uniform: sync + build per-body raws."""
        pk = self.packed
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
        if st.get("stepped"):
            # the psum'd device-side verdict: ANY shard's chunk walk
            # crossing the deadline times the whole search out — its
            # skipped chunks make the gathered results unusable, which
            # is exactly the discard-on-timeout contract the
            # cooperative path already has
            (m_score, m_shard, m_doc, total, prune), agg_out, timed = \
                jax.device_get(st["out"])
            if int(timed) > 0:
                from ..search import resident as _resident
                _resident.stats.preempted_by_deadline.inc()
                raise SearchTimeoutError(pk.index_name)
        else:
            (m_score, m_shard, m_doc, total, prune), agg_out = \
                jax.device_get(st["out"])
        if st["fused"] is not None:
            # prune rows are the mesh-wide (shard AND replica psum'd)
            # dispatch totals, replicated per query row — one record
            # per dispatch
            _fused_stats.record_prune(
                *(float(x) for x in prune[0]),
                positional=_bundle_positional(st["fused"][0]))

        per_query_partials = [None] * B
        if agg_specs:
            per_query_partials = shard_partials(
                agg_specs, st["agg_ctx"],
                [jax.tree_util.tree_map(np.asarray, agg_out)], batch=B)
        return [{"score": m_score[i], "shard": m_shard[i],
                 "doc": m_doc[i], "total": int(total[i]),
                 "partials": per_query_partials[i],
                 "agg_specs": agg_specs, "packed": pk}
                for i in range(n)]

    @staticmethod
    def _build_response(body: dict, raws: list[dict]) -> dict:
        """Merge one body's raw results from 1+ generations (base/tail
        packs) into a response — the cross-generation sortDocs + agg
        reduce."""
        frm = int(body.get("from", 0))
        size = int(body.get("size", 10))
        cands = []
        total = 0
        for gen, raw in enumerate(raws):
            total += raw["total"]
            nvalid = int(min(raw["total"], raw["score"].shape[0]))
            for j in range(nvalid):
                cands.append((-float(raw["score"][j]), gen,
                              int(raw["shard"][j]), int(raw["doc"][j])))
        cands.sort()
        hits = []
        for negs, gen, s, d in cands[frm: frm + size]:
            pk = raws[gen]["packed"]
            local = s - pk.shard_offset
            if not 0 <= local < len(pk.shards):
                raise RuntimeError(
                    f"hit on shard {s} lives on another host — fetch "
                    "multi-host results through MultiHostIndex, not "
                    "DistributedSearcher directly")
            seg = pk.shards[local]
            hits.append({
                "_index": raws[gen]["packed"].index_name,
                "_type": "_doc",
                "_id": seg.ids[d],
                "_score": -negs,
                "_source": json.loads(seg.sources[d]),
            })
        pk0 = raws[0]["packed"]
        resp = {
            "took": 0, "timed_out": False,
            "_shards": shards_header(pk0.n_shards, pk0.n_shards),
            "hits": {"total": total,
                     "max_score": (-cands[0][0]) if cands else None,
                     "hits": hits},
        }
        agg_specs = raws[0]["agg_specs"]
        if agg_specs:
            merged = merge_shard_partials(
                agg_specs, [r["partials"] for r in raws
                            if r["partials"] is not None])
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
                  fused: tuple | None = None, stepped: bool = False):
        """One pinned shard_map program per (plan signature, agg sig,
        pow2 k, local batch, stepped?) — k arrives pow2-bucketed from
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
        single-walk program."""
        from ..search import resident as _resident
        key = (desc, agg_desc, k, b_loc, fused, stepped)
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
            f0 = next(f for _r, kd, f, _w in fused[0]
                      if kd in _FUSED_DENSE_KINDS)
            n_tiles = pk.dev["text"][f0]["tile_max"].n_tiles
            chunk_tiles = max(1, -(-n_tiles // _RESIDENT_CHUNKS))

        gather_out = self._gather_out
        in_specs = (P("shard"), P("shard"), P("shard", "replica"),
                    P("shard"))
        if gather_out:
            # results all_gather'd over "replica" in-program: every
            # device (hence every HOST of a multi-process replica
            # layout) holds the full batch's output, so collect never
            # reads a non-addressable shard
            out_specs = ((P(), P(), P(), P(), P()), P())
        else:
            out_specs = ((P("replica"), P("replica"), P("replica"),
                          P("replica"), P("replica")), P("replica"))
        if stepped:
            in_specs = in_specs + (P(),)
            out_specs = out_specs + (P(),)

        @partial(shard_map, mesh=mesh, in_specs=in_specs,
                 out_specs=out_specs, check_vma=False)
        def program(seg, live, prm, agg_prm, *step_in):
            # b_loc is STATIC (B / replicas): param-less plans (e.g. a
            # term absent from every shard binds to a constant) carry
            # no leaf to infer the batch from
            seg = jax.tree_util.tree_map(lambda a: a[0], seg)
            live_l = live[0]
            prm_l = jax.tree_util.tree_map(lambda a: a[0], prm)
            agg_l = jax.tree_util.tree_map(lambda a: a[0], agg_prm)
            timed = None

            if fused is not None:
                # same fused block-max score+top-k engine as the
                # single-chip executor; each shard prunes against its
                # own per-clause tile summaries and never materializes
                # [B, cap] (admission guarantees no aggs, so the match
                # mask is never needed)
                f_bundle, f_backend = fused
                if stepped:
                    step = _resident_step(step_in[0], chunk_tiles)
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
                l_score, l_idx, l_total = top_k_hits(score, valid,
                                                     min(k, cap))
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

            # ---- cross-shard reduce over ICI (SearchPhaseController) ----
            g_score = jax.lax.all_gather(l_score, "shard")   # [S, b, k]
            g_idx = jax.lax.all_gather(l_idx, "shard")
            S = g_score.shape[0]
            kk = l_score.shape[1]
            # shard-major flatten => top_k tie-break = (shard asc, rank asc)
            flat_score = jnp.moveaxis(g_score, 0, 1).reshape(b_loc, S * kk)
            flat_idx = jnp.moveaxis(g_idx, 0, 1).reshape(b_loc, S * kk)
            shard_of = jnp.repeat(jnp.arange(S, dtype=jnp.int32), kk)[None, :]
            m_score, m_pos = jax.lax.top_k(flat_score, kk)
            m_shard = jnp.take_along_axis(
                jnp.broadcast_to(shard_of, flat_idx.shape), m_pos, axis=1)
            m_doc = jnp.take_along_axis(flat_idx, m_pos, axis=1)
            total = jax.lax.psum(l_total, "shard")

            # psum over BOTH axes: each replica prunes against its own
            # sub-batch, so shard-only totals would drop every replica
            # but the one whose rows land first in the gathered output
            prune = jnp.broadcast_to(
                jax.lax.psum(pruned, ("shard", "replica"))[None, :],
                (b_loc, 3))
            agg_out = _reduce_shard_axis(agg_out)
            if gather_out:
                # batch-axis gather over the replica rows (tiled: row
                # r's [b_loc] slice lands at rows r*b_loc..): identical
                # host-side shapes to the sharded out_specs, now
                # replicated on every device
                def _rep(x):
                    return jax.lax.all_gather(x, "replica", axis=0,
                                              tiled=True)
                m_score, m_shard, m_doc, total, prune = (
                    _rep(m_score), _rep(m_shard), _rep(m_doc),
                    _rep(total), _rep(prune))
                agg_out = jax.tree_util.tree_map(_rep, agg_out)
            out = ((m_score, m_shard, m_doc, total, prune), agg_out)
            if stepped:
                # collective verdict: any device's walk crossing the
                # deadline times out the whole search (both axes — a
                # replica row's laggard is as fatal as a shard's)
                out = out + (jax.lax.psum(timed.astype(jnp.int32),
                                          ("shard", "replica")),)
            return out

        fn = jax.jit(program)
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
      DEACTIVATED in place (one bitmap upload, no repack);
    * docs new or updated since the base pack: rebuilt into a TAIL
      PackedShards whose cost is proportional to the DELTA, not the
      corpus;
    * when the tail outgrows `repack_ratio` of the base, everything
      folds into a fresh base pack (the merge/force-merge analog).

    Searches run on base and tail programs and merge per body:
    candidates by (score desc, generation, shard, doc), totals summed,
    agg partials merged by bucket key (ordinal spaces differ between
    packs; partials are keyed by term strings / numeric keys exactly so
    they can meet).
    """

    REPACK_MIN = 4096

    def __init__(self, node, index_name: str, mesh: Mesh,
                 repack_ratio: float = 0.25):
        self.node = node
        self.index_name = index_name
        self.mesh = mesh
        self.repack_ratio = repack_ratio
        self.last_refresh_stats: dict = {}
        self._full_pack()

    # -- packing -----------------------------------------------------------

    def _full_pack(self) -> None:
        self.base = PackedShards.from_node_index(
            self.node, self.index_name, self.mesh)
        self.base_searcher = DistributedSearcher(self.base)
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

    def refresh(self) -> dict:
        """Fold engine changes into the mesh view. Returns stats:
        {"mode": "noop"|"tail"|"repack", "tail_docs": n,
        "deactivated": n}."""
        svc = self.node.indices[self.index_name]
        n_shards = self.base.n_shards
        deactivate: dict[int, list[int]] = {}
        deltas: list[list[tuple[str, int, bytes]]] = []
        total_delta = 0
        base_total = sum(s.num_docs for s in self.base.shards)
        for sid in range(n_shards):
            eng = svc.shard(sid)
            eng.refresh()
            current = {did: (ver, src)
                       for did, ver, src in eng.snapshot_docs()}
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
            self._full_pack()
            self.last_refresh_stats = {"mode": "repack",
                                       "tail_docs": total_delta,
                                       "deactivated": 0}
            return self.last_refresh_stats

        n_dead = sum(len(v) for v in deactivate.values())
        if deactivate:
            self.base.deactivate_rows(deactivate)
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
            # ADOPTS it — pinned shard_map programs survive untouched
            seg.delta_parent = self._base_gen
            seg.delta_epoch = self._tail_epoch
            pad_delta_shapes(seg)
            tail_segs.append(seg)
        new_tail = PackedShards(self.index_name, tail_segs,
                                svc_mappers, self.mesh)
        reused = (self.tail_searcher is not None
                  and self.tail_searcher.adopt_pack(new_tail))
        self.tail = new_tail
        if not reused:
            # first tail, or a pow2 bucket overflowed: one rebuild
            self.tail_searcher = DistributedSearcher(new_tail)
        self._tail_sig = sig
        self.last_refresh_stats = {"mode": "tail",
                                   "tail_docs": total_delta,
                                   "deactivated": n_dead,
                                   "tail_programs_reused": bool(reused)}
        return self.last_refresh_stats

    # -- search ------------------------------------------------------------

    def search(self, body: dict) -> dict:
        return self.msearch([body])[0]

    def msearch(self, bodies: list[dict],
                with_partials: bool = False) -> list[dict]:
        base_raw = self.base_searcher.raw_msearch(bodies)
        if self.tail_searcher is None:
            return [DistributedSearcher._build_response(b, [r])
                    for b, r in zip(bodies, base_raw)]
        tail_raw = self.tail_searcher.raw_msearch(bodies)
        return [DistributedSearcher._build_response(b, [rb, rt])
                for b, rb, rt in zip(bodies, base_raw, tail_raw)]
