"""Per-segment query execution: bind -> device program -> top-k + aggs.

Reference analog: search/query/QueryPhase.java:92-168 — the per-shard
Lucene execution (BulkScorer loop, TopScoreDocCollector, then
AggregationPhase collectors). Here the whole phase is ONE jitted device
program per (query structure, segment shape) pair:

    eval query AST  -> dense per-doc scores [B, cap] + match mask
    top-k           -> lax.top_k with Lucene-compatible tie-breaking
    aggregations    -> masked scatter-add bucket kernels

Two-step execution:
  * bind (host): resolve terms against the segment dictionary to block
    ranges / ordinals / bounds; produces a hashable static `desc` tree
    (compiled into the program) + dynamic param arrays (traced), so
    different terms with the same query SHAPE reuse the compiled program.
    Queries binding to the same desc can be batched (leading dim B).
  * eval (device): recursive desc interpreter building the XLA program.

Static shapes everywhere: posting-gather budgets and bucket counts are
padded to power-of-two buckets, so XLA compile count stays logarithmic.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field as dc_field
from functools import partial
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..index.mapping import (MapperService, parse_date_millis, parse_ip,
                             MapperParsingError, DATE, BOOLEAN, IP)
from ..index.segment import (Segment, BLOCK, next_pow2, bm25_idf,
                             BM25_K1, BM25_B, POS_MAX_ENC)
from ..ops.scoring import (score_term, score_terms_fused,
                           score_topk_bundle_fused, bundle_tile_bounds,
                           match_mask_bundle_fused, bundle_primary_field,
                           BOUND_SLACK, positional_prefix, clause_fields,
                           bundle_text_fields, bundle_pos_fields,
                           positional_tile_scores, phrase_kind, span_kind,
                           bm25f_kind, parse_positional_kind,
                           walk_step_tiles)
from ..ops.knn import knn_score_column, SIMILARITIES as _KNN_SIMILARITIES
from ..ops.pallas_scoring import (pallas_enabled, interpret_mode,
                                  score_term_pallas,
                                  score_terms_fused_pallas,
                                  score_terms_dense_pallas,
                                  fused_topk_bundle_pallas,
                                  match_mask_bundle_pallas,
                                  resident_step_ok)
from ..ops.topk import top_k_hits, top_k_by_field
from ..ops import aggs as agg_ops
from ..utils.errors import (QueryParsingError, SearchParseError,
                            SearchTimeoutError)
from ..utils.metrics import (MeanMetric as _MeanMetric,
                             MetricsRegistry as _MetricsRegistry)
from ..utils.profiler import enclosing as _enclosing, phase as _phase
from ..utils import trace_guard as _trace_guard
from . import resident as _resident
from .bound_plans import note_volatile as _note_volatile
from .query_dsl import (
    Query, MatchAllQuery, MatchNoneQuery, TermQuery, RangeQuery, ExistsQuery,
    IdsQuery, PrefixQuery, WildcardQuery, FuzzyQuery, BoolQuery,
    ConstantScoreQuery, BoostingQuery, FunctionScoreQuery, ScoreFunction,
    ScriptQuery, GeoDistanceQuery, GeoBoundingBoxQuery, GeoPolygonQuery,
    GeoShapeQuery, ShapeTokensQuery, KnnQuery,
)

_F32_MIN_WEIGHT = 1e-30  # keeps score>0 as the match signal even at boost~0
_DENSE_GROUP_MAX = 16    # should-groups up to this many terms take the
                         # forward-index gather path instead of scatter
                         # (a question of ten analysed terms is a plain
                         # match; expansions run to hundreds)
# fused positional clause caps: n is compiled into the clause kind
# string (phrase_pos:{n}:..., bm25f:{nf}:{nt}), so these bound the
# distinct-kind explosion the same way _FUSED_MAX_CLAUSES bounds the
# per-tile unroll; wider shapes take the host phrase/span/BM25F path
_POS_CLAUSE_TERMS_MAX = 8
_POS_FIELDS_MAX = 4


# ---------------------------------------------------------------------------
# Device view of a segment
# ---------------------------------------------------------------------------


def device_arrays(segment: Segment) -> dict:
    """Upload (once) and return the segment's device-resident columns.

    The upload is accounted against the fielddata breaker (columns are
    the HBM-resident fielddata analog) and released when the segment is
    garbage collected — ref: RamAccountingTermsEnum + the fielddata
    breaker of HierarchyCircuitBreakerService."""
    dev = getattr(segment, "_device", None)
    if dev is None:
        import weakref
        from ..utils.breaker import breaker_service
        # tiered residency (index/tiering.py): a pack over the HBM
        # budget pages its forward-index columns per SCORE_TILE tile
        # instead of uploading them here — only the tiny tile_max
        # summaries (the paging/pruning oracle) stay permanently
        # resident. The decision is sticky per segment.
        from ..index import tiering as _tiering_mod
        paged = _tiering_mod.activate(segment)
        fielddata = breaker_service().breaker("fielddata")
        nbytes = segment.nbytes()
        hold = fielddata.hold(nbytes)
        weakref.finalize(segment, hold.release)
        dev = {
            "text": {
                name: {
                    "block_docs": jnp.asarray(pf.block_docs),
                    "block_imps": jnp.asarray(pf.block_imps),
                    "doc_len": jnp.asarray(pf.doc_len),
                    **({"fwd_tids": jnp.asarray(pf.fwd_tids),
                        "fwd_imps": jnp.asarray(pf.fwd_imps)}
                       if pf.fwd_tids is not None and name not in paged
                       else {}),
                    # positions column family: the big [cap, L*P] delta
                    # pack pages with the forward columns; the tiny
                    # per-doc norm columns stay permanently resident
                    # (the tiered chunk walk gathers them like tile_max)
                    **({"fwd_pos": jnp.asarray(pf.fwd_pos)}
                       if getattr(pf, "fwd_pos", None) is not None
                       and name not in paged
                       else {}),
                    **({"k1ln": jnp.asarray(pf.k1ln),
                        "lnorm": jnp.asarray(pf.lnorm)}
                       if getattr(pf, "fwd_pos", None) is not None
                       else {}),
                    **({"tile_max": jax.device_put(pf.tile_max)}
                       if pf.fwd_tids is not None
                       and getattr(pf, "tile_max", None) is not None
                       else {}),
                }
                for name, pf in segment.text.items()
            },
            "kw": {name: jnp.asarray(kc.ords) for name, kc in segment.keywords.items()},
            "kw_mv": {name: jnp.asarray(kc.mv_ords)
                      for name, kc in segment.keywords.items()
                      if kc.mv_ords is not None},
            "num": {
                name: {"values": jnp.asarray(nc.values),
                       "exists": jnp.asarray(nc.exists),
                       **({"mv_values": jnp.asarray(nc.mv_values),
                           "mv_exists": jnp.asarray(nc.mv_exists)}
                          if nc.mv_values is not None else {})}
                for name, nc in segment.numerics.items()
            },
            "vec": {
                # bf16 HBM residency: the MXU consumes bf16 anyway
                # (knn_topk casts), so f32 storage would double both
                # the footprint and the matmul's HBM read; norms stay
                # f32 for the similarity denominators
                name: {"values": jnp.asarray(vc.values,
                                             dtype=jnp.bfloat16),
                       "exists": jnp.asarray(vc.exists),
                       "norms": jnp.asarray(vc.norms)}
                for name, vc in segment.vectors.items()
            },
            "geo": {
                name: {"lat": jnp.asarray(gc.lat),
                       "lon": jnp.asarray(gc.lon),
                       "exists": jnp.asarray(gc.exists)}
                for name, gc in segment.geos.items()
            },
        }
        if segment.has_nested:
            # block-join projection: child row -> parent row (self for
            # primary rows so scatter indices stay in-bounds)
            target = np.where(segment.parent_of >= 0, segment.parent_of,
                              np.arange(segment.capacity, dtype=np.int32))
            dev["nested"] = {
                "target": jnp.asarray(target.astype(np.int32)),
                "is_child": jnp.asarray(segment.parent_of >= 0),
            }
        for name, entry in dev["text"].items():
            if "tile_max" in entry:
                tm = segment.text[name].tile_max
                _fused_stats.record_summary(tm.nbytes, tm.entries)
                weakref.finalize(segment, _fused_stats.record_summary,
                                 -tm.nbytes, -tm.entries)
        segment._device = dev  # type: ignore[attr-defined]
        segment.device_changed(rebuilt=True)
    return dev


def ensure_kw_sorted(segment: Segment, field: str) -> None:
    """Lazily upload the ordinal-sort permutation + group boundaries for
    a keyword column — the static layout behind scatter-free terms
    aggregation (ops/aggs.sorted_group_reduce). The local->global remap
    stays a (small, G-sized) runtime scatter because global ordinals are
    a READER property while this layout is a SEGMENT property."""
    dev = device_arrays(segment)
    if field in dev.get("kw_sorted", {}):
        return
    kc = segment.keywords.get(field)
    if kc is None:
        return
    perm = np.argsort(kc.ords, kind="stable").astype(np.int32)
    sorted_ords = kc.ords[perm]
    starts = np.searchsorted(
        sorted_ords, np.arange(kc.cardinality + 1)).astype(np.int32)
    _host_perms(segment)[("kw", field)] = perm
    dev.setdefault("kw_sorted", {})[field] = {
        "perm": jnp.asarray(perm), "starts": jnp.asarray(starts)}
    segment.device_changed()


def ensure_num_sorted(segment: Segment, field: str) -> None:
    """Lazily upload the value-sort permutation for a single-valued
    numeric column (scatter-free histograms; missing docs sort last via
    the dtype max sentinel and are excluded by the exists mask)."""
    dev = device_arrays(segment)
    if field in dev.get("num_sorted", {}):
        return
    nc = segment.numerics.get(field)
    if nc is None or nc.mv_values is not None:
        return
    vals = nc.values.copy()
    sentinel = (np.iinfo(np.int32).max if vals.dtype == np.int32
                else np.float32(np.inf))
    vals[~nc.exists] = sentinel
    perm = np.argsort(vals, kind="stable").astype(np.int32)
    _host_perms(segment)[("num", field)] = perm
    dev.setdefault("num_sorted", {})[field] = {
        "perm": jnp.asarray(perm),
        "vals": jnp.asarray(vals[perm]),
        "sexists": jnp.asarray(nc.exists[perm])}
    segment.device_changed()


def ensure_num_tiles(segment: Segment, field: str) -> bool:
    """Lazily build + upload the per-tile [lo, hi] extrema of a
    single-valued numeric column (index/segment.build_tile_minmax) —
    the mask-density prune input for fused range filter clauses. The
    changed dev-tree structure keys fresh compiled programs, exactly
    like the other ensure_* lazy uploads. Returns False when the column
    cannot carry extrema (absent, multi-valued, degenerate tile grid)."""
    nc = segment.numerics.get(field)
    if nc is None or nc.mv_values is not None:
        return False
    dev = device_arrays(segment)
    entry = dev["num"].get(field)
    if entry is None:
        return False
    if "tile_lo" in entry:
        return True
    # shared per-segment host cache (index/tiering.host_extrema): the
    # tiered survivor oracle reads the SAME arrays, so a paged pack's
    # range clause computes the extrema once, not once per consumer
    mm = _tiering.host_extrema(segment, field)
    if mm is None:
        return False
    entry["tile_lo"] = jnp.asarray(mm[0])
    entry["tile_hi"] = jnp.asarray(mm[1])
    segment.device_changed()
    return True


def ensure_script_vals(segment: Segment, fields) -> None:
    """Lazily upload the natural-unit float32 view ("script_vals":
    dates in epoch millis, ip unbiased) for the numeric columns a
    script references — scripts are rare, so this HBM copy must not tax
    script-free workloads. Mutates the cached device dict; the changed
    pytree structure keys a separate compiled program, which a scripted
    query needs anyway."""
    dev = device_arrays(segment)
    for f in fields:
        nc = segment.numerics.get(f)
        if nc is not None and "script_vals" not in dev["num"][f]:
            dev["num"][f]["script_vals"] = \
                jnp.asarray(nc.raw.astype(np.float32))
            segment.device_changed()


# ---------------------------------------------------------------------------
# Sorted-space query views
#
# At HBM-resident corpus scale the per-query permutation gather that
# carries a doc-space match mask into an agg layout's sort order costs
# ~17ms per 20M-row query on this TPU (a flat 1-D gather), while
# evaluating the SAME filter directly against sorted copies of the
# referenced columns costs ~0.2ms. So for view-compatible queries
# (elementwise column predicates: range/term/terms/exists/bool —
# i.e. the filter context of every analytics workload) the engine keeps
# lazily-projected sorted copies of the filter columns per agg layout
# and re-evaluates the query desc in sorted space; the per-doc gather
# never happens. Text scoring descs keep the doc-space path.
# ---------------------------------------------------------------------------

_VIEW_KW_KINDS = ("term_kw", "ord_set", "range_kw", "exists_kw")
_VIEW_NUM_KINDS = ("term_num", "range_int", "range_f32", "exists_num")


def _host_perms(segment: Segment) -> dict:
    hp = getattr(segment, "_host_perms", None)
    if hp is None:
        hp = {}
        segment._host_perms = hp  # type: ignore[attr-defined]
    return hp


def _bound_view_fields(bound: "Bound", kw: set, num: set) -> bool:
    """Walk a bound tree: True if every node is view-compatible,
    collecting the kw/num fields its mask evaluation reads."""
    k = bound.kind
    if k in ("none", "match_all"):
        return True
    if k in _VIEW_KW_KINDS:
        kw.add(bound.field)
        return True
    if k in _VIEW_NUM_KINDS:
        num.add(bound.field)
        return True
    if k == "bool":
        return all(_bound_view_fields(c, kw, num)
                   for grp in ("must", "should", "must_not", "filter")
                   for c in bound.children[grp])
    if k == "const":
        return _bound_view_fields(bound.children["q"][0], kw, num)
    return False


def ensure_agg_views(segment: Segment, bound: "Bound", agg_desc: tuple,
                     ) -> None:
    """Project the filter columns `bound` references into the sort order
    of every agg layout `agg_desc` uses on this segment (plus the
    sub-metric source columns). One-time numpy work per
    (layout, column) pair; no-op for non-view-compatible queries."""
    kw_f: set = set()
    num_f: set = set()
    if not _bound_view_fields(bound, kw_f, num_f):
        return
    dev = device_arrays(segment)
    perms = _host_perms(segment)
    for name, node in agg_desc:
        kind = node[0]
        if kind == "terms_kw":
            layouts = [("kw", node[1], node[3])]
        elif kind in ("hist_fixed", "hist_edges"):
            layouts = [("num", node[1], node[3])]
        elif kind == "pctl":
            layouts = [("num", node[1], ())]
        else:
            continue
        for lkind, lfield, subs in layouts:
            store_name = "kw_sorted" if lkind == "kw" else "num_sorted"
            store = dev.get(store_name, {}).get(lfield)
            perm = perms.get((lkind, lfield))
            if store is None or perm is None:
                continue
            need_num = num_f | {f for _n, f, mk in subs
                                if mk in ("avg", "sum", "value_count")}
            if "vw_kw_mv" not in store:
                # the three (empty) view dicts below are new nodes of
                # the tree
                segment.device_changed()
            vw_num = store.setdefault("vw_num", {})
            for f in need_num:
                nc = segment.numerics.get(f)
                if nc is None or f in vw_num:
                    continue
                col = {"values": jnp.asarray(nc.values[perm]),
                       "exists": jnp.asarray(nc.exists[perm])}
                if nc.mv_values is not None:
                    col["mv_values"] = jnp.asarray(nc.mv_values[perm])
                    col["mv_exists"] = jnp.asarray(nc.mv_exists[perm])
                vw_num[f] = col
                segment.device_changed()
            vw_kw = store.setdefault("vw_kw", {})
            vw_kw_mv = store.setdefault("vw_kw_mv", {})
            for f in kw_f:
                kc = segment.keywords.get(f)
                if kc is None or f in vw_kw:
                    continue
                vw_kw[f] = jnp.asarray(kc.ords[perm])
                if kc.mv_ords is not None:
                    vw_kw_mv[f] = jnp.asarray(kc.mv_ords[perm])
                segment.device_changed()


def _desc_view_ok(desc: tuple, store: dict, seg: dict) -> bool:
    """Trace-time check: can `desc`'s match mask be evaluated against the
    projections present in `store`? (Multi-valued sidecar presence must
    mirror the doc-space column so eval_node takes the same branch.)"""
    kind = desc[0]
    if kind in ("none", "match_all"):
        return True
    if kind in _VIEW_KW_KINDS:
        f = desc[1]
        return (f in store.get("vw_kw", {})
                and ((f in seg.get("kw_mv", {}))
                     == (f in store.get("vw_kw_mv", {}))))
    if kind in _VIEW_NUM_KINDS:
        f = desc[1]
        col = store.get("vw_num", {}).get(f)
        if col is None:
            return False
        return ("mv_values" in seg["num"].get(f, {})) == ("mv_values" in col)
    if kind == "bool":
        _, must, should, must_not, filt = desc
        return all(_desc_view_ok(d, store, seg)
                   for grp in (must, should, must_not, filt) for d in grp)
    if kind == "const":
        return _desc_view_ok(desc[1], store, seg)
    return False


def _sub_view_ok(store: dict, seg: dict, mfield: str, mkind: str) -> bool:
    if mfield not in seg["num"]:
        return True  # column absent from segment: empty metric either way
    if mkind not in ("avg", "sum", "value_count"):
        return False  # min/max/stats keep the doc-space path
    col = store.get("vw_num", {}).get(mfield)
    return col is not None and "mv_values" not in col \
        and "mv_values" not in seg["num"][mfield]


def _agg_view_plan(desc: tuple, agg_desc: tuple, agg_params: tuple,
                   seg: dict, live_views: dict) -> tuple:
    """Per-agg-node static decision: evaluate in sorted view space?"""
    plan = []
    for (name, node), params in zip(agg_desc, agg_params):
        kind = node[0]
        ok = False
        if kind == "terms_kw":
            _, field, n_global, subs, top_s = node
            store = seg.get("kw_sorted", {}).get(field)
            if (store is not None and ("kw", field) in live_views
                    and field in seg["kw"]
                    and field not in seg.get("kw_mv", {})
                    and store["starts"].shape[0] - 1 == params[0].shape[0]
                    and _desc_view_ok(desc, store, seg)):
                ok = all(_sub_view_ok(store, seg, f, mk)
                         for _n, f, mk in subs)
        elif kind in ("hist_fixed", "hist_edges", "pctl"):
            field = node[1]
            subs = node[3] if kind != "pctl" else ()
            store = seg.get("num_sorted", {}).get(field)
            col = seg["num"].get(field)
            if (store is not None and ("num", field) in live_views
                    and col is not None and "mv_values" not in col
                    and "sexists" in store
                    and _desc_view_ok(desc, store, seg)):
                ok = all(_sub_view_ok(store, seg, f, mk)
                         for _n, f, mk in subs)
        plan.append(ok)
    return tuple(plan)


class _ViewMasks:
    """Lazily evaluates (and caches) the query's valid mask in each agg
    layout's sorted space: eval_node against projected columns, ANDed
    with the layout-permuted live mask."""

    def __init__(self, desc, params, seg, live_views, cap, B):
        self.desc = desc
        self.params = params
        self.seg = seg
        self.live_views = live_views
        self.cap = cap
        self.B = B
        self._cache: dict = {}

    def mask(self, key: tuple) -> jax.Array:
        hit = self._cache.get(key)
        if hit is not None:
            return hit
        lkind, lfield = key
        store_name = "kw_sorted" if lkind == "kw" else "num_sorted"
        store = self.seg[store_name][lfield]
        view_seg = {**self.seg,
                    "kw": store.get("vw_kw", {}),
                    "kw_mv": store.get("vw_kw_mv", {}),
                    "num": store.get("vw_num", {}),
                    "text": {}, "geo": {}, "vec": {}}
        _, match = eval_node(self.desc, self.params, view_seg,
                             self.cap, self.B)
        vm = match & self.live_views[key][None, :]
        self._cache[key] = vm
        return vm


# ---------------------------------------------------------------------------
# Bound query tree (host-side intermediate; finalize() -> desc + params)
# ---------------------------------------------------------------------------


@dataclass
class Bound:
    kind: str
    field: str | None = None
    scalars: dict[str, float | int] = dc_field(default_factory=dict)
    arrays: dict[str, np.ndarray] = dc_field(default_factory=dict)
    children: dict[str, list["Bound"]] = dc_field(default_factory=dict)

    def signature(self) -> tuple:
        return (
            self.kind, self.field,
            tuple(sorted(self.arrays)),
            tuple((g, tuple(c.signature() for c in cs))
                  for g, cs in sorted(self.children.items())),
        )


class QueryBinder:
    """Resolves a query AST against ONE segment. Ref analog: Lucene query
    rewrite + Weight creation (createWeight) per IndexReader."""

    def __init__(self, segment: Segment, mapper: MapperService,
                 live: np.ndarray | None = None,
                 dfs: dict | None = None):
        self.seg = segment
        self.mappers = mapper
        self.live = live   # primary live mask (parents_match liveness)
        self.dfs = dfs     # {"field\x00term": [global_df, global_N]} from
                           # the DFS pre-phase (aggregateDfs)

    def _dfs_ratio(self, field: str, term: str, df_local: float,
                   n_local: float) -> float:
        """Scale factor turning a locally-idf'd eager impact into the
        globally-idf'd score, delegated to the field's Similarity
        (idf_global/idf_local for BM25, squared for classic TF/IDF, 1.0
        where df isn't a separable factor — index/similarity.py)."""
        if not self.dfs:
            return 1.0
        entry = self.dfs.get(f"{field}\x00{term}")
        if not entry or entry[1] <= 0:
            return 1.0
        sim = self.mappers.similarity_for(field)
        return sim.df_scale(df_local, n_local,
                            float(entry[0]), float(entry[1]))

    def bind(self, q: Query) -> Bound:
        m = getattr(self, f"_bind_{type(q).__name__}", None)
        if m is None:
            raise QueryParsingError(f"unsupported query node [{type(q).__name__}]")
        return m(q)

    # -- leaves ------------------------------------------------------------

    def _no_match(self) -> Bound:
        return Bound("none")

    def _bind_MatchAllQuery(self, q: MatchAllQuery) -> Bound:
        return Bound("match_all", scalars={"boost": q.boost})

    def _bind_MatchNoneQuery(self, q: MatchNoneQuery) -> Bound:
        return self._no_match()

    def _term_text(self, field: str, term: str, boost: float) -> Bound:
        pf = self.seg.text.get(field)
        if pf is None:
            return self._no_match()
        t = pf.lookup(term)
        if t < 0:
            lo, nb = 0, 0
        else:
            lo = int(pf.block_start[t])
            nb = int(pf.block_start[t + 1]) - lo
            if self.dfs:
                boost = boost * self._dfs_ratio(
                    field, term, float(pf.df[t]), float(pf.doc_count))
        kind = "term_text" if pf.fwd_tids is not None else "term_text_sc"
        return Bound(kind, field,
                     scalars={"block_lo": lo, "nb": nb, "tid": t,
                              "weight": max(boost, _F32_MIN_WEIGHT)})

    def _terms_text_expanded(self, field: str, term_ids: Sequence[int],
                             boost: float) -> Bound:
        """Multi-term expansion (prefix/wildcard/fuzzy/terms) as one fused
        gather: absolute block indices of all expanded terms."""
        pf = self.seg.text[field]
        blocks: list[int] = []
        for t in term_ids:
            blocks.extend(range(int(pf.block_start[t]), int(pf.block_start[t + 1])))
        return Bound("terms_fused", field,
                     scalars={"weight": max(boost, _F32_MIN_WEIGHT)},
                     arrays={"blocks": np.asarray(blocks, dtype=np.int32)})

    def _bind_TermQuery(self, q: TermQuery) -> Bound:
        kind = self.seg.field_kind(q.field)
        if kind == "text":
            # term queries are NOT analyzed (ref: TermQueryParser.java) —
            # exact term lookup; `match` handles analysis at parse time
            return self._term_text(q.field, str(q.value), q.boost)
        if kind == "keyword":
            kc = self.seg.keywords[q.field]
            o = kc.lookup(str(q.value))
            score = 0.0
            if o >= 0:
                # keyword fields carry no norms: BM25 degenerates to idf
                # (tf=1, (k1+1)/(1+k1) with b=0 -> idf), ref BM25Similarity
                score = float(bm25_idf(float(kc.df[o]), self.seg.num_docs))
                if self.dfs:
                    entry = self.dfs.get(f"{q.field}\x00{q.value}")
                    if entry and entry[1] > 0:
                        score = float(bm25_idf(float(entry[0]),
                                               float(entry[1])))
            return Bound("term_kw", q.field,
                         scalars={"ord": o, "score": max(score * q.boost,
                                                         _F32_MIN_WEIGHT)})
        if kind == "numeric":
            nc = self.seg.numerics[q.field]
            try:
                if nc.kind == DATE:
                    v = parse_date_millis(q.value) // 1000
                elif nc.kind == BOOLEAN:
                    v = 1 if (q.value in (True, "true", "1", 1)) else 0
                elif nc.kind == IP:
                    v = parse_ip(q.value) - nc.bias
                else:
                    v = float(q.value) if nc.values.dtype == np.float32 else int(q.value)
            except (ValueError, TypeError, MapperParsingError):
                return self._no_match()
            return Bound("term_num", q.field,
                         scalars={"value": v, "score": max(q.boost, _F32_MIN_WEIGHT)})
        return self._no_match()

    def _bind_RangeQuery(self, q: RangeQuery) -> Bound:
        kind = self.seg.field_kind(q.field)
        if kind == "numeric":
            nc = self.seg.numerics[q.field]
            is_int = nc.values.dtype == np.int32

            def conv(v):
                if v is None:
                    return None
                try:
                    if nc.kind == DATE:
                        return parse_date_millis(v) // 1000 if not isinstance(v, bool) else None
                    if nc.kind == IP:
                        return parse_ip(v) - nc.bias
                    return float(v)
                except Exception:
                    raise QueryParsingError(
                        f"failed to parse range bound [{v}] on [{q.field}]")

            i32 = np.iinfo(np.int32)
            lo, hi = conv(q.gte), conv(q.lte)
            lo_x, hi_x = conv(q.gt), conv(q.lt)
            if is_int:
                lo_i = i32.min if lo is None and lo_x is None else int(
                    math.ceil(lo) if lo is not None else math.floor(lo_x) + 1)
                hi_i = i32.max if hi is None and hi_x is None else int(
                    math.floor(hi) if hi is not None else math.ceil(hi_x) - 1)
                lo_i = max(min(lo_i, i32.max), i32.min)
                hi_i = max(min(hi_i, i32.max), i32.min)
                return Bound("range_int", q.field,
                             scalars={"lo": lo_i, "hi": hi_i, "boost": q.boost})
            lo_f = -np.inf if lo is None and lo_x is None else (
                lo if lo is not None else np.nextafter(np.float32(lo_x), np.float32(np.inf)))
            hi_f = np.inf if hi is None and hi_x is None else (
                hi if hi is not None else np.nextafter(np.float32(hi_x), np.float32(-np.inf)))
            return Bound("range_f32", q.field,
                         scalars={"lo": float(lo_f), "hi": float(hi_f), "boost": q.boost})
        if kind == "keyword":
            kc = self.seg.keywords[q.field]
            terms = kc.terms
            lo_o = 0
            hi_o = len(terms) - 1
            if q.gte is not None:
                lo_o = int(np.searchsorted(terms, str(q.gte), side="left"))
            elif q.gt is not None:
                lo_o = int(np.searchsorted(terms, str(q.gt), side="right"))
            if q.lte is not None:
                hi_o = int(np.searchsorted(terms, str(q.lte), side="right")) - 1
            elif q.lt is not None:
                hi_o = int(np.searchsorted(terms, str(q.lt), side="left")) - 1
            return Bound("range_kw", q.field,
                         scalars={"lo": lo_o, "hi": hi_o, "boost": q.boost})
        return self._no_match()

    def _bind_ExistsQuery(self, q: ExistsQuery) -> Bound:
        kind = self.seg.field_kind(q.field)
        if kind == "text":
            return Bound("exists_text", q.field, scalars={"boost": 1.0})
        if kind == "keyword":
            return Bound("exists_kw", q.field, scalars={"boost": 1.0})
        if kind == "numeric":
            return Bound("exists_num", q.field, scalars={"boost": 1.0})
        if kind in ("geo", "vector"):
            return Bound("exists_gv", f"{kind}\x00{q.field}",
                         scalars={"boost": 1.0})
        return self._no_match()

    def _bind_KnnQuery(self, q: KnnQuery) -> Bound:
        """Vector similarity as a scoring clause: every live doc with a
        vector matches, scored by the field similarity's transform
        (ops/knn.knn_score_column) times boost. The similarity rides
        the desc (static — it compiles into the program); the query
        vector and boost are dynamic params."""
        vc = self.seg.vectors.get(q.field)
        if vc is None:
            return self._no_match()
        fm = self.mappers.field(q.field)
        sim = fm.similarity if fm is not None and fm.similarity else "cosine"
        if sim not in _KNN_SIMILARITIES:
            raise QueryParsingError(
                f"[knn] unsupported similarity [{sim}] on [{q.field}]")
        qv = np.asarray(q.vector, dtype=np.float32)
        if qv.shape[0] != vc.dims:
            raise QueryParsingError(
                f"[knn] query_vector has {qv.shape[0]} dims, field "
                f"[{q.field}] has {vc.dims}")
        return Bound("knn_vec", q.field,
                     scalars={"boost": max(float(q.boost),
                                           _F32_MIN_WEIGHT),
                              "sim": sim},
                     arrays={"qv": qv})

    def _bind_IdsQuery(self, q: IdsQuery) -> Bound:
        mask = np.zeros(self.seg.capacity, dtype=bool)
        for v in q.values:
            d = self.seg.id_map.get(v)
            if d is not None:
                mask[d] = True
        return Bound("ids", arrays={"mask": mask})

    def _expand_terms(self, field: str, pred, boost: float,
                      max_expansions: int) -> Bound:
        kind = self.seg.field_kind(field)
        if kind == "text":
            pf = self.seg.text[field]
            tids = [i for i, t in enumerate(pf.terms) if pred(t)][:max_expansions]
            if not tids:
                return self._no_match()
            return self._terms_text_expanded(field, tids, boost)
        if kind == "keyword":
            kc = self.seg.keywords[field]
            ords = np.asarray([i for i, t in enumerate(kc.terms) if pred(t)][:max_expansions],
                              dtype=np.int32)
            if ords.size == 0:
                return self._no_match()
            return Bound("ord_set", field,
                         scalars={"boost": max(boost, _F32_MIN_WEIGHT),
                                  "card_total": kc.cardinality},
                         arrays={"ords": ords})
        return self._no_match()

    def _bind_PrefixQuery(self, q: PrefixQuery) -> Bound:
        # sorted dictionary: prefix = contiguous term range (Lucene TermsEnum seek)
        return self._expand_terms(q.field, lambda t: t.startswith(q.value),
                                  q.boost, q.max_expansions)

    def _bind_WildcardQuery(self, q: WildcardQuery) -> Bound:
        import fnmatch
        import re as _re
        rx = _re.compile(fnmatch.translate(q.value))
        return self._expand_terms(q.field, lambda t: rx.match(t) is not None,
                                  q.boost, q.max_expansions)

    def _bind_FuzzyQuery(self, q: FuzzyQuery) -> Bound:
        target = q.value

        def within_edit(t: str) -> bool:
            if abs(len(t) - len(target)) > q.fuzziness:
                return False
            return _edit_distance_le(t, target, q.fuzziness)

        return self._expand_terms(q.field, within_edit, q.boost, q.max_expansions)

    def _bind_RegexpQuery(self, q: RegexpQuery) -> Bound:
        import re as _re
        try:
            rx = _re.compile(q.value)
        except _re.error as e:
            raise QueryParsingError(f"invalid regexp [{q.value}]: {e}")
        return self._expand_terms(q.field, lambda t: rx.fullmatch(t) is not None,
                                  q.boost, q.max_expansions)

    # -- positional (phrase / span) — host match -> device scatter ---------

    def _docs_w(self, docs: np.ndarray, imps: np.ndarray) -> Bound:
        if docs.size == 0:
            return self._no_match()
        return Bound("docs_w",
                     arrays={"docs": docs.astype(np.int32),
                             "imps": imps.astype(np.float32)})

    # -- fused positional admission (device phrase/span/BM25F) -------------

    def _positional_fallback(self, why: str) -> None:
        """Count one positional query taking the host path, by reason —
        nodes_stats()["fused_scoring"].admission.positional_fallbacks."""
        _fused_stats.record_positional(why)

    def _default_bm25(self, field: str) -> bool:
        """Positional clause kinds evaluate the packed k1ln/lnorm
        columns, which bake the DEFAULT BM25 parameters — any other
        configured Similarity keeps the host oracle path."""
        from ..index.similarity import BM25Similarity
        sim = self.mappers.similarity_for(field)
        return sim is None or (isinstance(sim, BM25Similarity)
                               and sim.k1 == BM25_K1 and sim.b == BM25_B)

    def _positional_field_ok(self, pf) -> bool:
        return (getattr(pf, "fwd_pos", None) is not None
                and getattr(pf, "tile_max", None) is not None
                and pf.fwd_tids is not None)

    def _phrase_fused(self, q, pf, tid_groups) -> Bound | None:
        """Fused-engine Bound for an eligible match_phrase, or None to
        take the host phrase_match -> docs_w path (reason counted).
        Eligibility mirrors the device algorithm's assumptions; the
        host path stays the byte-identity oracle for everything else."""
        from .phrase import terms_idf_sum
        if not _positional_enabled():
            return None                        # A/B lever: exact either way
        if q.prefix_last:
            self._positional_fallback("phrase_prefix")
            return None
        if not self._positional_field_ok(pf):
            self._positional_fallback("missing_positions_pack")
            return None
        if not self._default_bm25(q.field):
            self._positional_fallback("similarity")
            return None
        n = len(tid_groups)
        if n > _POS_CLAUSE_TERMS_MAX:
            self._positional_fallback("too_many_terms")
            return None
        if q.slop > POS_MAX_ENC:
            self._positional_fallback("slop_cap")
            return None
        if not q.boost > 0.0:
            # host docs_w at boost <= 0 yields score 0 => no match; the
            # fused leaf's match is freq > 0 — semantics diverge, and
            # boost <= 0 breaks the monotone tile bound anyway
            self._positional_fallback("nonpositive_boost")
            return None
        tids = [g[0] for g in tid_groups]
        idf_sum = terms_idf_sum(pf, tid_groups)
        wb = [idf_sum / float(bm25_idf(float(pf.df[t]), pf.doc_count))
              for t in tids]
        return Bound(phrase_kind(n, q.slop > 0), q.field,
                     scalars={"idf_sum": float(idf_sum),
                              "slop": int(q.slop),
                              "boost": float(q.boost)},
                     arrays={"qt": np.asarray(tids, np.int32),
                             "wb": np.asarray(wb, np.float32)})

    def _span_fused(self, q) -> Bound | None:
        """Fused-engine Bound for an eligible span tree — a bare
        span_term or a depth-1 span_near of same-field span_terms — or
        None for the host Spans path. span_or / span_first / span_not
        and nested span_near trees stay host-side, counted. Child
        boosts are ignored exactly as the host Spans algebra ignores
        them. Declines (returns None) on a positions-less field so the
        host path raises the identical QueryParsingError."""
        from .query_dsl import SpanTermQuery, SpanNearQuery
        if not _positional_enabled():
            return None
        if isinstance(q, SpanTermQuery):
            field, terms = q.field, [str(q.value)]
            in_order, slop = False, 0
        elif isinstance(q, SpanNearQuery) and q.clauses and all(
                isinstance(c, SpanTermQuery) for c in q.clauses):
            if len({c.field for c in q.clauses}) > 1:
                return None          # host raises the same-field error
            field = q.clauses[0].field
            terms = [str(c.value) for c in q.clauses]
            in_order, slop = q.in_order, q.slop
        else:
            self._positional_fallback(f"span_{type(q).__name__}")
            return None
        pf = self.seg.text.get(field)
        if pf is None or pf.pos_data is None:
            return None      # host: no_match / positions-less error
        if not self._positional_field_ok(pf):
            self._positional_fallback("missing_positions_pack")
            return None
        if not self._default_bm25(field):
            self._positional_fallback("similarity")
            return None
        n = len(terms)
        if n > _POS_CLAUSE_TERMS_MAX:
            self._positional_fallback("too_many_terms")
            return None
        if slop > POS_MAX_ENC:
            self._positional_fallback("slop_cap")
            return None
        if not q.boost > 0.0:
            self._positional_fallback("nonpositive_boost")
            return None
        tids = [pf.lookup(t) for t in terms]
        if any(t < 0 for t in tids):
            return self._no_match()  # host: empty spans -> no_match
        idf = [float(bm25_idf(float(pf.df[t]), pf.doc_count))
               for t in tids]
        idf_sum = sum(idf)
        # n == 1 degenerates to plain occurrence counting either way;
        # the unordered kind keeps the tight per-term bound
        kind = span_kind(n, in_order if n > 1 else False)
        return Bound(kind, field,
                     scalars={"idf_sum": float(idf_sum), "slop": int(slop),
                              "boost": float(q.boost)},
                     arrays={"qt": np.asarray(tids, np.int32),
                             "wb": np.asarray([idf_sum / v for v in idf],
                                              np.float32)})

    def _bind_BM25FQuery(self, q) -> Bound:
        """multi_match type=cross_fields as true BM25F: shared max-df
        IDF per term, per-field weighted tf and length norms, ONE
        saturation across fields. Binder computes the statistics once
        and feeds the SAME numbers to whichever path serves the query:
        the fused bm25f clause kind, or the host oracle
        (search/phrase.bm25f_scores) scattered through docs_w."""
        from .phrase import bm25f_scores
        pairs = [(f, w) for f, w in q.fields
                 if self.seg.text.get(f) is not None]
        if not pairs or not q.terms:
            return self._no_match()
        pfs = [self.seg.text[f] for f, _w in pairs]
        nf, nt = len(pairs), len(q.terms)
        tids = np.full((nf, nt), -1, np.int32)
        for fi, pf in enumerate(pfs):
            for ti, term in enumerate(q.terms):
                tids[fi, ti] = pf.lookup(term)
        if (tids < 0).all():
            return self._no_match()
        # shared IDF: rarest interpretation is per-term max df across
        # the fields (the BM25F "one virtual document" view); N is the
        # widest field's doc count so idf stays well-defined
        n_docs = max(pf.doc_count for pf in pfs)
        idf = [float(bm25_idf(float(max(
                   (pf.df[t] for pf, t in zip(pfs, tids[:, ti]) if t >= 0),
                   default=0.0)), n_docs)) for ti in range(nt)]
        weights = np.asarray([max(w, _F32_MIN_WEIGHT) for _f, w in pairs],
                             np.float32)
        fused_ok = (_positional_enabled() and q.boost > 0.0
                    and nf <= _POS_FIELDS_MAX
                    and nt <= _POS_CLAUSE_TERMS_MAX
                    and all(self._positional_field_ok(pf)
                            and self._default_bm25(f)
                            for (f, _w), pf in zip(pairs, pfs)))
        if fused_ok:
            return Bound(bm25f_kind(nf, nt), tuple(f for f, _w in pairs),
                         scalars={"boost": float(q.boost)},
                         arrays={"qt": tids,
                                 "idf": np.asarray(idf, np.float32),
                                 "wf": weights})
        if _positional_enabled():
            self._positional_fallback(
                "bm25f_boost" if not q.boost > 0.0 else
                "bm25f_shape" if (nf > _POS_FIELDS_MAX
                                  or nt > _POS_CLAUSE_TERMS_MAX) else
                "missing_positions_pack")
        col = bm25f_scores(pfs, tids, idf, weights, self.seg.capacity)
        docs = np.nonzero(col > 0.0)[0].astype(np.int32)
        return self._docs_w(docs, col[docs] * np.float32(q.boost))

    def _bind_PhraseQuery(self, q) -> Bound:
        from .phrase import phrase_match, phrase_impacts, terms_idf_sum
        pf = self.seg.text.get(q.field)
        if pf is None:
            return self._no_match()
        if pf.pos_data is None:
            # legacy segment persisted without the positional sidecar:
            # degrade to the conjunctive approximation (all terms must
            # match) rather than silently returning nothing
            from .query_dsl import BoolQuery, TermQuery
            return self.bind(BoolQuery(
                must=tuple(TermQuery(q.field, t) for t in q.terms),
                boost=q.boost))
        tid_groups: list[list[int]] = []
        for i, term in enumerate(q.terms):
            if q.prefix_last and i == len(q.terms) - 1:
                tids = [j for j, t in enumerate(pf.terms)
                        if t.startswith(term)][: q.max_expansions]
                tid_groups.append(tids)
            else:
                t = pf.lookup(term)
                if t < 0:
                    return self._no_match()
                tid_groups.append([t])
        fused = self._phrase_fused(q, pf, tid_groups)
        if fused is not None:
            return fused
        docs, freqs = phrase_match(pf, tid_groups, q.slop)
        imps = phrase_impacts(
            pf, docs, freqs, terms_idf_sum(pf, tid_groups),
            sim=self.mappers.similarity_for(q.field),
            tids=[t for g in tid_groups for t in g]) * q.boost
        return self._docs_w(docs, imps)

    def _span_tree(self, q):
        """Query AST -> (phrase.Spans, field, [tids]) for span evaluation."""
        from . import phrase as ph
        from .query_dsl import (SpanTermQuery, SpanNearQuery, SpanOrQuery,
                                SpanFirstQuery, SpanNotQuery)
        if isinstance(q, SpanTermQuery):
            pf = self.seg.text.get(q.field)
            if pf is not None and pf.pos_data is None:
                # ref: Lucene errors when positions were not indexed
                raise QueryParsingError(
                    f"field [{q.field}] was indexed without position data; "
                    f"cannot run span queries")
            if pf is None:
                return ph.Spans.empty(), q.field, []
            tid = pf.lookup(str(q.value))
            return ph.span_term(pf, tid), q.field, [tid] if tid >= 0 else []
        if isinstance(q, SpanNearQuery):
            parts = [self._span_tree(c) for c in q.clauses]
            field = self._span_same_field(parts, "span_near")
            tids = [t for _, _, ts in parts for t in ts]
            return (ph.span_near([p for p, _, _ in parts], q.slop,
                                 q.in_order), field, tids)
        if isinstance(q, SpanOrQuery):
            parts = [self._span_tree(c) for c in q.clauses]
            field = self._span_same_field(parts, "span_or")
            tids = [t for _, _, ts in parts for t in ts]
            return ph.span_or([p for p, _, _ in parts]), field, tids
        if isinstance(q, SpanFirstQuery):
            spans, field, tids = self._span_tree(q.match)
            return ph.span_first(spans, q.end), field, tids
        if isinstance(q, SpanNotQuery):
            inc, field, tids = self._span_tree(q.include)
            exc, _, _ = self._span_tree(q.exclude)
            return ph.span_not(inc, exc, q.pre, q.post), field, tids
        raise QueryParsingError(
            f"unsupported span clause [{type(q).__name__}]")

    @staticmethod
    def _span_same_field(parts, ctx: str) -> str:
        # Lucene SpanNearQuery/SpanOrQuery require all clauses on one
        # field ("Clauses must have same field")
        fields = {f for _, f, _ in parts}
        if len(fields) > 1:
            raise QueryParsingError(
                f"[{ctx}] clauses must have same field, got {sorted(fields)}")
        return parts[0][1]

    def _bind_span(self, q) -> Bound:
        from .phrase import phrase_impacts
        from ..index.segment import bm25_idf
        fused = self._span_fused(q)
        if fused is not None:
            return fused
        spans, field, tids = self._span_tree(q)
        pf = self.seg.text.get(field)
        if pf is None or spans.size == 0:
            return self._no_match()
        docs, freqs = spans.doc_freqs()
        idf_sum = sum(float(bm25_idf(float(pf.df[t]), pf.doc_count))
                      for t in tids)
        imps = phrase_impacts(
            pf, docs, freqs, idf_sum,
            sim=self.mappers.similarity_for(field), tids=tids) * q.boost
        return self._docs_w(docs, imps)

    _bind_SpanTermQuery = _bind_span
    _bind_SpanNearQuery = _bind_span
    _bind_SpanOrQuery = _bind_span
    _bind_SpanFirstQuery = _bind_span
    _bind_SpanNotQuery = _bind_span

    # -- block join (nested) ------------------------------------------------

    _NESTED_SCORE_MODES = ("none", "sum", "avg", "max", "min")

    def _bind_NestedQuery(self, q) -> Bound:
        """ToParentBlockJoinQuery analog: evaluate the child query over
        hidden nested rows, project match/score onto parent rows with a
        device scatter. Ref: index/query/NestedQueryParser.java."""
        if not self.seg.has_nested:
            return self._no_match()
        kc = self.seg.keywords.get("_nested_path")
        if kc is None:
            return self._no_match()
        o = kc.lookup(q.path)
        if o < 0:
            return self._no_match()
        path_mask = kc.ords == o
        mode = q.score_mode if q.score_mode in self._NESTED_SCORE_MODES \
            else "avg"
        return Bound("nested", field=mode,
                     scalars={"boost": max(q.boost, _F32_MIN_WEIGHT)},
                     arrays={"path_mask": path_mask},
                     children={"q": [self.bind(q.query)]})

    def _bind_ParentsMatchQuery(self, q) -> Bound:
        """Matches nested child rows whose PARENT matches the inner query
        (the nested-aggregation scope filter; ref: the parentDocs bitset
        in search/aggregations/bucket/nested/NestedAggregator.java)."""
        if not self.seg.has_nested:
            return self._no_match()
        plive = (self.live if self.live is not None
                 else self.seg.primary_mask())
        return Bound("parents_match",
                     arrays={"plive": np.asarray(plive, dtype=bool)},
                     children={"q": [self.bind(q.query)]})

    def _bind_MoreLikeThisQuery(self, q) -> Bound:
        """Lucene MoreLikeThis term selection against THIS segment's
        statistics: tokens of the like-texts ranked by tf*idf, top
        max_query_terms become a bool-should of term queries."""
        from .query_dsl import (BoolQuery, TermQuery, IdsQuery, resolve_msm)
        tf_by_field: dict[str, dict[str, int]] = {}
        for fld in q.fields:
            analyzer = self.mappers.search_analyzer_for(fld)
            counts = tf_by_field.setdefault(fld, {})
            for text in q.like_texts:
                for tok in analyzer.analyze(text):
                    counts[tok] = counts.get(tok, 0) + 1
            # ignore_like/unlike: terms of the unliked docs never make
            # the query (ref: MoreLikeThisQueryParser "unlike" handling)
            for text in getattr(q, "unlike_texts", ()) or ():
                for tok in analyzer.analyze(text):
                    counts.pop(tok, None)
        scored: list[tuple[float, str, str]] = []
        for fld, counts in tf_by_field.items():
            pf = self.seg.text.get(fld)
            if pf is None:
                continue
            for term, tf in counts.items():
                if tf < q.min_term_freq:
                    continue
                t = pf.lookup(term)
                if t < 0:
                    continue
                df = int(pf.df[t])
                if df < min(q.min_doc_freq, pf.doc_count):
                    continue
                idf = float(bm25_idf(float(df), pf.doc_count))
                scored.append((tf * idf, fld, term))
        scored.sort(reverse=True)
        selected = scored[: q.max_query_terms]
        if not selected:
            return self._no_match()
        shoulds = tuple(TermQuery(fld, term, q.boost)
                        for _, fld, term in selected)
        msm = resolve_msm(q.minimum_should_match, len(shoulds)) or 1
        must_not = (IdsQuery(q.exclude_ids),) if q.exclude_ids else ()
        return self.bind(BoolQuery(should=shoulds,
                                   minimum_should_match=max(msm, 1),
                                   must_not=must_not))

    # -- compound ----------------------------------------------------------

    def _bind_BoolQuery(self, q: BoolQuery) -> Bound:
        children = {
            "must": [self.bind(c) for c in q.must],
            "should": [self.bind(c) for c in q.should],
            "must_not": [self.bind(c) for c in q.must_not],
            "filter": [self.bind(c) for c in q.filter],
        }
        # Lucene-style BooleanQuery simplification: splice a nested pure
        # disjunction into the parent's should list (and pure conjunction
        # into must) so e.g. a multi-term match inside `should` binds to
        # the same flat plan as bare term clauses.
        parent_msm = q.minimum_should_match
        if parent_msm is None:
            parent_msm = 1 if (q.should and not q.must and not q.filter) else 0
        if parent_msm <= 1:
            # only valid when the parent needs at most one should vote:
            # then "child bool matched" == "any spliced term matched" and
            # scores are identical (sum of matching terms)
            spliced = []
            for c in children["should"]:
                if (c.kind == "bool" and c.scalars.get("boost") == 1.0
                        and c.scalars.get("msm", 0) == 1
                        and not c.children.get("must")
                        and not c.children.get("must_not")
                        and not c.children.get("filter")):
                    spliced.extend(c.children.get("should", []))
                else:
                    spliced.append(c)
            children["should"] = spliced
        spliced_m = []
        for c in children["must"]:
            if (c.kind == "bool" and c.scalars.get("boost") == 1.0
                    and not c.children.get("should")
                    and not c.children.get("must_not")):
                spliced_m.extend(c.children.get("must", []))
                # child FILTER clauses stay non-scoring: route to parent filter
                children["filter"] = children["filter"] + c.children.get("filter", [])
            else:
                spliced_m.append(c)
        children["must"] = spliced_m
        # fuse same-field text-term should clauses into one scatter
        # (the match-query fast path; only valid when msm <= 1)
        msm = q.minimum_should_match
        if msm is None:
            msm = 1 if (q.should and not q.must and not q.filter) else 0
        if msm <= 1:
            fused: dict[str, list[Bound]] = {}
            rest: list[Bound] = []
            for c in children["should"]:
                if c.kind in ("term_text", "term_text_sc"):
                    fused.setdefault((c.field, c.kind), []).append(c)
                else:
                    rest.append(c)
            for (fld, ckind), group in fused.items():
                # fuse even a single term so a match query binds to the
                # same plan whatever its term count. Few-term groups take
                # the forward-index GATHER path (VPU compare+FMA, no
                # scatter); many-term groups (prefix expansions etc.) and
                # fields without a forward index stay on posting-scatter.
                if ckind == "term_text" and len(group) <= _DENSE_GROUP_MAX:
                    tids: list[int] = []
                    weights: list[float] = []
                    for c in group:
                        tids.append(c.scalars.get("tid", -1))
                        weights.append(c.scalars["weight"])
                    rest.append(Bound(
                        "terms_dense", fld,
                        arrays={"tids": np.asarray(tids, dtype=np.int32),
                                "weights": np.asarray(weights, dtype=np.float32)}))
                else:
                    blocks: list[int] = []
                    weights = []
                    for c in group:
                        for b in range(c.scalars["nb"]):
                            blocks.append(c.scalars["block_lo"] + b)
                            weights.append(c.scalars["weight"])
                    rest.append(Bound(
                        "terms_fused_w", fld,
                        arrays={"blocks": np.asarray(blocks, dtype=np.int32),
                                "weights": np.asarray(weights, dtype=np.float32)}))
            children["should"] = rest
        return Bound("bool", scalars={"msm": msm, "boost": q.boost},
                     children=children)

    def _bind_GeoDistanceQuery(self, q: GeoDistanceQuery) -> Bound:
        if q.field not in self.seg.geos:
            return self._no_match()
        return Bound("geo_distance", q.field,
                     scalars={"lat": q.lat, "lon": q.lon,
                              "to_m": q.distance_m, "from_m": q.from_m,
                              "boost": q.boost})

    def _bind_GeoBoundingBoxQuery(self, q: GeoBoundingBoxQuery) -> Bound:
        if q.field not in self.seg.geos:
            return self._no_match()
        return Bound("geo_bbox", q.field,
                     scalars={"top": q.top, "left": q.left,
                              "bottom": q.bottom, "right": q.right,
                              "boost": q.boost})

    def _bind_GeoPolygonQuery(self, q: GeoPolygonQuery) -> Bound:
        if q.field not in self.seg.geos:
            return self._no_match()
        lats = np.asarray([p[0] for p in q.points], dtype=np.float32)
        lons = np.asarray([p[1] for p in q.points], dtype=np.float32)
        return Bound("geo_polygon", q.field,
                     scalars={"boost": q.boost, "n": len(q.points)},
                     arrays={"lats": lats, "lons": lons})

    def _bind_GeoShapeQuery(self, q: GeoShapeQuery) -> Bound:
        """Decompose a shape relation into cell-token disjunctions over
        the field's prefix tree (ops/geo_shape.py; ref:
        GeoShapeQueryParser + RecursivePrefixTreeStrategy):
        intersects -> one ShapeTokensQuery; within -> intersects AND NOT
        complement-covering; disjoint -> exists AND NOT intersects."""
        from ..index.mapping import GEO_SHAPE, shape_tree_config
        from ..ops.geo_shape import (shape_intersect_tokens,
                                     shape_complement_tokens)
        from .query_dsl import BoolQuery, ExistsQuery, ShapeTokensQuery
        fm = self.mappers.field(q.field)
        if fm is None:
            return self._no_match()
        if fm.type != GEO_SHAPE:
            raise QueryParsingError(
                f"Field [{q.field}] is not a geo_shape")
        tree, tree_levels, err_pct = shape_tree_config(fm)
        tokens = shape_intersect_tokens(q.shape_json, tree.name,
                                        tree_levels, err_pct)
        if q.relation == "intersects":
            return self.bind(ShapeTokensQuery(q.field, tokens, q.boost))
        if q.relation == "disjoint":
            return self.bind(BoolQuery(
                must=(ExistsQuery(q.field),),
                must_not=(ShapeTokensQuery(q.field, tokens),),
                boost=q.boost))
        # within: the bool node applies q.boost, so inner clauses stay 1.0
        comp = shape_complement_tokens(q.shape_json, tree.name,
                                       tree_levels, err_pct)
        return self.bind(BoolQuery(
            must=(ShapeTokensQuery(q.field, tokens),),
            must_not=(ShapeTokensQuery(q.field, comp),),
            boost=q.boost))

    def _bind_ShapeTokensQuery(self, q: ShapeTokensQuery) -> Bound:
        pf = self.seg.text.get(q.field)
        if pf is None:
            return self._no_match()
        tids = [t for t in (pf.lookup(tok) for tok in q.tokens) if t >= 0]
        if not tids:
            return self._no_match()
        # constant score (Lucene ConstantScore over the prefix-tree
        # filter): the fused terms disjunction provides the match mask,
        # `const` flattens its scores to the boost
        return Bound("const", scalars={"boost": q.boost},
                     children={"q": [self._terms_text_expanded(
                         q.field, tids, 1.0)]})

    def _bind_ScriptQuery(self, q: ScriptQuery) -> Bound:
        from ..script import compile_script
        from ..script.service import numeric_param
        cs = compile_script(q.script)  # validate (raises ScriptException)
        _note_volatile()  # the bind uploads
        ensure_script_vals(self.seg, cs.fields)
        pnames = ",".join(n for n, _ in q.params)
        scalars = {"boost": q.boost}
        for name, val in q.params:
            scalars[f"p_{name}"] = numeric_param(name, val)
        return Bound("script_q", f"{q.script}\x00{pnames}", scalars=scalars)

    def _bind_ConstantScoreQuery(self, q: ConstantScoreQuery) -> Bound:
        return Bound("const", scalars={"boost": q.boost},
                     children={"q": [self.bind(q.query)]})

    def _bind_BoostingQuery(self, q: BoostingQuery) -> Bound:
        return Bound("boosting", scalars={"negative_boost": q.negative_boost},
                     children={"pos": [self.bind(q.positive)],
                               "neg": [self.bind(q.negative)]})

    # -- function_score (ref: functionscore/FunctionScoreQueryParser) -------

    def _resolve_decay_value(self, field: str, v, is_span: bool) -> float:
        """origin/scale/offset -> column units (date cols: epoch seconds /
        second spans; numeric: float)."""
        nc = self.seg.numerics.get(field)
        if nc is not None and nc.kind == DATE:
            if is_span:
                from ..utils.settings import parse_time_value
                return parse_time_value(v) / 1000.0
            if v == "now" or v is None:
                import time as _t
                _note_volatile()
                return float(_t.time())
            return parse_date_millis(v) / 1000.0
        try:
            return float(v)
        except (TypeError, ValueError):
            # date strings against a long column hold epoch MILLIS
            from ..utils.settings import parse_time_value
            if is_span:
                return float(parse_time_value(v))
            if v == "now" or v is None:
                import time as _t
                _note_volatile()
                return _t.time() * 1000.0
            return float(parse_date_millis(v))

    def _bind_fn(self, fn: ScoreFunction) -> Bound:
        children = {"filter": [self.bind(fn.filter)]
                    if fn.filter is not None else []}
        if fn.kind == "weight":
            return Bound("fn_weight", scalars={"weight": fn.weight},
                         children=children)
        if fn.kind == "field_value_factor":
            has_col = fn.field in self.seg.numerics
            return Bound("fn_fvf", f"{fn.field}|{fn.modifier}|{int(has_col)}",
                         scalars={"factor": fn.factor, "missing": fn.missing,
                                  "weight": fn.weight}, children=children)
        if fn.kind == "random_score":
            return Bound("fn_random", scalars={"seed": fn.seed,
                                               "weight": fn.weight},
                         children=children)
        if fn.kind in ("gauss", "exp", "linear"):
            if fn.scale is None:
                raise QueryParsingError(
                    f"decay function on [{fn.field}] requires [scale]")
            has_col = fn.field in self.seg.numerics
            origin = self._resolve_decay_value(fn.field, fn.origin, False) \
                if has_col else 0.0
            scale = self._resolve_decay_value(fn.field, fn.scale, True) \
                if has_col else 1.0
            offset = self._resolve_decay_value(fn.field, fn.offset, True) \
                if has_col else 0.0
            return Bound("fn_decay", f"{fn.field}|{fn.kind}|{int(has_col)}",
                         scalars={"origin": origin, "scale": scale,
                                  "offset": offset, "decay": fn.decay,
                                  "weight": fn.weight}, children=children)
        if fn.kind == "script_score":
            from ..script import compile_script
            from ..script.service import numeric_param
            cs = compile_script(fn.script)
            _note_volatile()  # the bind uploads
            ensure_script_vals(self.seg, cs.fields)
            pnames = ",".join(n for n, _ in fn.script_params)
            scalars = {"weight": fn.weight}
            for name, val in fn.script_params:
                scalars[f"p_{name}"] = numeric_param(name, val)
            return Bound("fn_script", f"{fn.script}\x00{pnames}",
                         scalars=scalars, children=children)
        raise QueryParsingError(f"unknown score function [{fn.kind}]")

    def _bind_FunctionScoreQuery(self, q: FunctionScoreQuery) -> Bound:
        mode_tag = (f"{q.score_mode}|{q.boost_mode}|"
                    f"{int(q.min_score is not None)}")
        return Bound(
            "fnscore", mode_tag,
            scalars={"max_boost": q.max_boost,
                     "min_score": (q.min_score if q.min_score is not None
                                   else 0.0),
                     "boost": q.boost},
            children={"q": [self.bind(q.query)],
                      "fns": [self._bind_fn(f) for f in q.functions]})


def _edit_distance_le(a: str, b: str, k: int) -> bool:
    """Banded Levenshtein <= k (host-side fuzzy expansion)."""
    la, lb = len(a), len(b)
    if abs(la - lb) > k:
        return False
    prev = list(range(lb + 1))
    for i in range(1, la + 1):
        cur = [i] + [0] * lb
        lo = max(1, i - k)
        hi = min(lb, i + k)
        if lo > 1:
            cur[lo - 1] = k + 1
        for j in range(lo, hi + 1):
            cur[j] = min(prev[j] + 1, cur[j - 1] + 1,
                         prev[j - 1] + (a[i - 1] != b[j - 1]))
        if min(cur[max(0, lo - 1):hi + 1]) > k:
            return False
        prev = cur
    return prev[lb] <= k


# ---------------------------------------------------------------------------
# finalize: Bound trees (a batch with identical structure) -> (desc, params)
# ---------------------------------------------------------------------------


def finalize(bounds: Sequence[Bound]) -> tuple[tuple, tuple]:
    """Stack a batch of structurally-identical bound queries.

    Returns (desc, params): desc is the hashable static program structure;
    params is a pytree of stacked np arrays with leading dim B.
    """
    sig = bounds[0].signature()
    for b in bounds[1:]:
        if b.signature() != sig:
            raise ValueError("cannot batch queries with different plans")
    return _finalize_node(bounds)


def _finalize_node(bounds: Sequence[Bound]) -> tuple[tuple, tuple]:
    b0 = bounds[0]
    kind = b0.kind
    B = len(bounds)

    def stack_scalar(name, dtype):
        return np.asarray([b.scalars[name] for b in bounds], dtype=dtype)

    if kind == "none":
        return ("none",), ()
    if kind == "match_all":
        return ("match_all",), (stack_scalar("boost", np.float32),)
    if kind == "term_text":
        return (("term_text", b0.field),
                (stack_scalar("tid", np.int32),
                 stack_scalar("weight", np.float32)))
    if kind == "term_text_sc":
        nb_pad = next_pow2(max(b.scalars["nb"] for b in bounds), floor=1)
        return (("term_text_sc", b0.field, nb_pad),
                (stack_scalar("block_lo", np.int32),
                 stack_scalar("nb", np.int32),
                 stack_scalar("weight", np.float32)))
    if kind == "terms_dense":
        q_pad = next_pow2(max(b.arrays["tids"].size for b in bounds), floor=1)
        qt = np.full((B, q_pad), -1, dtype=np.int32)
        wq = np.zeros((B, q_pad), dtype=np.float32)
        for i, b in enumerate(bounds):
            t = b.arrays["tids"]
            qt[i, : t.size] = t
            wq[i, : t.size] = b.arrays["weights"]
        return ("terms_dense", b0.field, q_pad), (qt, wq)
    if kind in ("terms_fused", "terms_fused_w"):
        m_pad = next_pow2(max(b.arrays["blocks"].size for b in bounds), floor=1)
        gather = np.full((B, m_pad), -1, dtype=np.int32)
        weights = np.zeros((B, m_pad), dtype=np.float32)
        for i, b in enumerate(bounds):
            blocks = b.arrays["blocks"]
            gather[i, :blocks.size] = blocks
            if kind == "terms_fused_w":
                weights[i, :blocks.size] = b.arrays["weights"]
            else:
                weights[i, :blocks.size] = b.scalars["weight"]
        return ("terms_fused", b0.field, m_pad), (gather, weights)
    if kind == "term_kw":
        return (("term_kw", b0.field),
                (stack_scalar("ord", np.int32), stack_scalar("score", np.float32)))
    if kind == "ord_set":
        card = next_pow2(max(b.arrays["ords"].size for b in bounds), floor=1)
        card_total = int(b0.scalars["card_total"])
        ords = np.full((B, card), card_total, dtype=np.int32)  # pad -> sentinel col
        for i, b in enumerate(bounds):
            o = b.arrays["ords"]
            ords[i, :o.size] = o
        return (("ord_set", b0.field, card, card_total),
                (ords, stack_scalar("boost", np.float32)))
    if kind == "term_num":
        return (("term_num", b0.field),
                (np.asarray([b.scalars["value"] for b in bounds]),
                 stack_scalar("score", np.float32)))
    if kind == "range_int":
        return (("range_int", b0.field),
                (stack_scalar("lo", np.int32), stack_scalar("hi", np.int32),
                 stack_scalar("boost", np.float32)))
    if kind == "range_f32":
        return (("range_f32", b0.field),
                (stack_scalar("lo", np.float32), stack_scalar("hi", np.float32),
                 stack_scalar("boost", np.float32)))
    if kind == "range_kw":
        return (("range_kw", b0.field),
                (stack_scalar("lo", np.int32), stack_scalar("hi", np.int32),
                 stack_scalar("boost", np.float32)))
    if kind == "knn_vec":
        # similarity is static (compiled into the transform); the query
        # vector + boost are the dynamic params, so coalesced knn
        # searches with different vectors share one compiled program
        return (("knn_vec", b0.field, b0.scalars["sim"]),
                (np.stack([b.arrays["qv"] for b in bounds]),
                 stack_scalar("boost", np.float32)))
    if kind in ("exists_text", "exists_kw", "exists_num", "exists_gv"):
        return ((kind, b0.field), ())
    if kind == "ids":
        return ("ids",), (np.stack([b.arrays["mask"] for b in bounds]),)
    if kind == "docs_w":
        # precomputed host posting list (phrase/span matches): pad with
        # doc 0 / impact 0 — scatter-adding zero is a no-op
        n_pad = next_pow2(max(b.arrays["docs"].size for b in bounds), floor=1)
        docs = np.zeros((B, n_pad), dtype=np.int32)
        imps = np.zeros((B, n_pad), dtype=np.float32)
        for i, b in enumerate(bounds):
            d = b.arrays["docs"]
            docs[i, : d.size] = d
            imps[i, : d.size] = b.arrays["imps"]
        return ("docs_w", n_pad), (docs, imps)
    head = positional_prefix(kind) if isinstance(kind, str) else None
    if head in ("phrase_pos", "span_pos"):
        # n rides in the kind string (a static), so every bound in the
        # batch shares qt/wb width; slop is DYNAMIC — sloppiness only
        # (slop > 0) is compiled in, the slop value is a traced param
        return ((kind, b0.field),
                (np.stack([b.arrays["qt"] for b in bounds]),
                 np.stack([b.arrays["wb"] for b in bounds]),
                 stack_scalar("idf_sum", np.float32),
                 stack_scalar("slop", np.int32),
                 stack_scalar("boost", np.float32)))
    if head == "bm25f":
        return ((kind, b0.field),
                (np.stack([b.arrays["qt"] for b in bounds]),
                 np.stack([b.arrays["idf"] for b in bounds]),
                 np.stack([b.arrays["wf"] for b in bounds]),
                 stack_scalar("boost", np.float32)))
    if kind == "bool":
        descs = {}
        params = {}
        for group in ("must", "should", "must_not", "filter"):
            pairs = [_finalize_node([b.children[group][i] for b in bounds])
                     for i in range(len(b0.children[group]))]
            descs[group] = tuple(d for d, _ in pairs)
            params[group] = tuple(p for _, p in pairs)
        return (("bool", descs["must"], descs["should"], descs["must_not"],
                 descs["filter"]),
                (params["must"], params["should"], params["must_not"],
                 params["filter"],
                 stack_scalar("msm", np.int32), stack_scalar("boost", np.float32)))
    if kind == "const":
        d, p = _finalize_node([b.children["q"][0] for b in bounds])
        return ("const", d), (p, stack_scalar("boost", np.float32))
    if kind == "nested":
        d, p = _finalize_node([b.children["q"][0] for b in bounds])
        return (("nested", d, b0.field),        # field = score_mode (static)
                (p, np.stack([b.arrays["path_mask"] for b in bounds]),
                 stack_scalar("boost", np.float32)))
    if kind == "parents_match":
        d, p = _finalize_node([b.children["q"][0] for b in bounds])
        return (("parents_match", d),
                (p, np.stack([b.arrays["plive"] for b in bounds])))
    if kind == "boosting":
        dp, pp = _finalize_node([b.children["pos"][0] for b in bounds])
        dn, pn = _finalize_node([b.children["neg"][0] for b in bounds])
        return (("boosting", dp, dn),
                (pp, pn, stack_scalar("negative_boost", np.float32)))
    if kind == "fnscore":
        qd, qp = _finalize_node([b.children["q"][0] for b in bounds])
        fn_descs = []
        fn_params = []
        for i in range(len(b0.children["fns"])):
            fd, fp = _finalize_node([b.children["fns"][i] for b in bounds])
            fn_descs.append(fd)
            fn_params.append(fp)
        return (("fnscore", qd, tuple(fn_descs), b0.field),
                (qp, tuple(fn_params),
                 stack_scalar("max_boost", np.float32),
                 stack_scalar("min_score", np.float32),
                 stack_scalar("boost", np.float32)))
    if kind == "geo_distance":
        return (("geo_distance", b0.field),
                (stack_scalar("lat", np.float32),
                 stack_scalar("lon", np.float32),
                 stack_scalar("to_m", np.float32),
                 stack_scalar("from_m", np.float32),
                 stack_scalar("boost", np.float32)))
    if kind == "geo_bbox":
        return (("geo_bbox", b0.field),
                (stack_scalar("top", np.float32),
                 stack_scalar("left", np.float32),
                 stack_scalar("bottom", np.float32),
                 stack_scalar("right", np.float32),
                 stack_scalar("boost", np.float32)))
    if kind == "geo_polygon":
        # pad to pow2 vertices +1 closing vertex; padding repeats the
        # last vertex so padded edges are degenerate (no ray crossings)
        p_pad = next_pow2(max(b.scalars["n"] for b in bounds) + 1, floor=4)
        lats = np.zeros((B, p_pad), dtype=np.float32)
        lons = np.zeros((B, p_pad), dtype=np.float32)
        for i, b in enumerate(bounds):
            la, lo = b.arrays["lats"], b.arrays["lons"]
            n = la.size
            lats[i, :n] = la
            lons[i, :n] = lo
            lats[i, n:] = la[0]  # close the ring, then repeat
            lons[i, n:] = lo[0]
        return (("geo_polygon", b0.field, p_pad),
                (lats, lons, stack_scalar("boost", np.float32)))
    if kind == "script_q":
        pnames = [n for n in b0.field.split("\x00", 1)[1].split(",") if n]
        own = tuple(stack_scalar(f"p_{n}", np.float32) for n in pnames) + \
            (stack_scalar("boost", np.float32),)
        return (("script_q", b0.field), own)
    if kind == "fn_script":
        flt = b0.children.get("filter", [])
        fdesc, fparams = (None, ())
        if flt:
            fdesc, fparams = _finalize_node([b.children["filter"][0]
                                             for b in bounds])
        pnames = [n for n in b0.field.split("\x00", 1)[1].split(",") if n]
        own = tuple(stack_scalar(f"p_{n}", np.float32) for n in pnames) + \
            (stack_scalar("weight", np.float32),)
        return (("fn_script", b0.field, fdesc), (own, fparams))
    if kind in ("fn_weight", "fn_fvf", "fn_random", "fn_decay"):
        flt = b0.children.get("filter", [])
        fdesc, fparams = (None, ())
        if flt:
            fdesc, fparams = _finalize_node([b.children["filter"][0]
                                             for b in bounds])
        if kind == "fn_weight":
            own = (stack_scalar("weight", np.float32),)
        elif kind == "fn_fvf":
            own = (stack_scalar("factor", np.float32),
                   stack_scalar("missing", np.float32),
                   stack_scalar("weight", np.float32))
        elif kind == "fn_random":
            own = (stack_scalar("seed", np.uint32),
                   stack_scalar("weight", np.float32))
        else:
            own = (stack_scalar("origin", np.float32),
                   stack_scalar("scale", np.float32),
                   stack_scalar("offset", np.float32),
                   stack_scalar("decay", np.float32),
                   stack_scalar("weight", np.float32))
        return ((kind, b0.field, fdesc), (own, fparams))
    raise QueryParsingError(f"unknown bound node [{kind}]")


# ---------------------------------------------------------------------------
# Device evaluation (desc interpreter — runs under jit)
# ---------------------------------------------------------------------------


def eval_node(desc: tuple, params: tuple, seg: dict, cap: int, B: int
              ) -> tuple[jax.Array, jax.Array]:
    """Returns (score [B, cap] f32, match [B, cap] bool)."""
    kind = desc[0]
    if kind == "none":
        z = jnp.zeros((B, cap), jnp.float32)
        return z, jnp.zeros((B, cap), bool)
    if kind == "match_all":
        (boost,) = params
        ones = jnp.ones((B, cap), bool)
        return jnp.broadcast_to(boost[:, None], (B, cap)).astype(jnp.float32), ones
    if kind == "term_text":
        # forward-index gather (see terms_dense); tid -1 = absent term,
        # which only matches zero-impact padding slots -> no match
        _, field = desc
        tid, weight = params
        t = seg["text"][field]
        tids, imps = t["fwd_tids"], t["fwd_imps"]
        if pallas_enabled():
            score = score_terms_dense_pallas(tids, imps, tid[:, None],
                                             weight[:, None],
                                             interpret=interpret_mode())
        else:
            contrib = jnp.sum(jnp.where(tids[None] == tid[:, None, None],
                                        imps[None], 0.0), axis=-1)
            score = contrib * weight[:, None]
        return score, score > 0
    if kind == "term_text_sc":
        # posting-scatter path (fields whose forward index exceeded the
        # width cap)
        _, field, nb_pad = desc
        block_lo, nb, weight = params
        t = seg["text"][field]
        if pallas_enabled():
            score = score_term_pallas(t["block_docs"], t["block_imps"],
                                      block_lo, nb, weight, nb_pad, cap,
                                      interpret=interpret_mode())
        else:
            score = score_term(t["block_docs"], t["block_imps"],
                               block_lo, nb, weight, nb_pad, cap)
        return score, score > 0
    if kind == "terms_fused":
        _, field, _m = desc
        gather, weights = params
        t = seg["text"][field]
        if pallas_enabled():
            score = score_terms_fused_pallas(
                t["block_docs"], t["block_imps"], gather, weights, cap,
                interpret=interpret_mode())
        else:
            score = score_terms_fused(t["block_docs"], t["block_imps"],
                                      gather, weights, cap)
        return score, score > 0
    if kind == "terms_dense":
        # forward-index gather path: per doc slot, compare its term id to
        # each query term and FMA the eager impact — no scatter, pure VPU
        _, field, q_pad = desc
        qt, wq = params                           # [B, Qp]
        t = seg["text"][field]
        tids, imps = t["fwd_tids"], t["fwd_imps"]  # [cap, L]
        if pallas_enabled():
            score = score_terms_dense_pallas(tids, imps, qt, wq,
                                             interpret=interpret_mode())
            return score, score > 0
        score = jnp.zeros((B, cap), jnp.float32)
        for qi in range(q_pad):
            tq = qt[:, qi][:, None, None]          # [B,1,1]
            contrib = jnp.sum(
                jnp.where(tids[None] == tq, imps[None], 0.0), axis=-1)
            score = score + contrib * wq[:, qi][:, None]
        return score, score > 0
    if kind == "docs_w":
        docs, imps = params                         # [B, n] each
        score = jnp.zeros((B, cap), jnp.float32).at[
            jnp.arange(B)[:, None], docs].add(imps)
        return score, score > 0
    if isinstance(kind, str) and positional_prefix(kind):
        # positional clause (phrase/span/BM25F), unfused reference: the
        # SAME per-doc leaf evaluator the fused tile walk runs, applied
        # to the whole capacity as one "tile" — elementwise over docs,
        # so full-cap == tile-by-tile bit-identically
        _, field = desc
        ones_i = jnp.ones((B,), jnp.int32)
        ones_f = jnp.ones((B,), jnp.float32)
        inp = tuple(params) + (ones_i, ones_f)
        text_tiles = {}
        pos_tiles = {}
        for f in clause_fields(field):
            t = seg["text"][f]
            text_tiles[f] = (t["fwd_tids"], t["fwd_imps"])
            pos_tiles[f] = (t["fwd_pos"], t["k1ln"], t["lnorm"])
        s_leaf, m_leaf = positional_tile_scores(kind, field, inp,
                                                text_tiles, pos_tiles)
        return jnp.where(m_leaf, s_leaf, 0.0), m_leaf
    if kind == "knn_vec":
        # vector similarity clause: one whole-capacity MXU matmul —
        # the SAME column the fused bundle engine slices per tile
        # (_vec_clause_inputs), so fused and unfused hybrid scores are
        # bit-identical
        _, field, sim = desc
        qv, boost = params                          # [B, D], [B]
        v = seg["vec"][field]
        col = knn_score_column(v["values"], v["norms"], v["exists"], qv,
                               similarity=sim)
        match = jnp.broadcast_to(v["exists"][None, :], (B, cap))
        return col * boost[:, None], match
    if kind == "nested":
        # block-join to-parent projection (ToParentBlockJoinQuery)
        _, inner_desc, score_mode = desc
        inner_params, path_mask, boost = params
        c_score, c_match = eval_node(inner_desc, inner_params, seg, cap, B)
        ok = c_match & path_mask & seg["nested"]["is_child"][None, :]
        target = seg["nested"]["target"]
        cs = jnp.where(ok, c_score, 0.0)
        cnt = jnp.zeros((B, cap), jnp.float32).at[:, target].add(
            ok.astype(jnp.float32))
        match = cnt > 0
        if score_mode == "none":
            score = jnp.where(match, boost[:, None], 0.0)
        elif score_mode == "max":
            mx = jnp.full((B, cap), -jnp.inf).at[:, target].max(
                jnp.where(ok, cs, -jnp.inf))
            score = jnp.where(match, mx, 0.0) * boost[:, None]
        elif score_mode == "min":
            mn = jnp.full((B, cap), jnp.inf).at[:, target].min(
                jnp.where(ok, cs, jnp.inf))
            score = jnp.where(match, mn, 0.0) * boost[:, None]
        else:
            total = jnp.zeros((B, cap), jnp.float32).at[:, target].add(cs)
            if score_mode == "avg":
                total = total / jnp.maximum(cnt, 1.0)
            score = jnp.where(match, total, 0.0) * boost[:, None]
        return score, match
    if kind == "parents_match":
        (inner_desc,) = desc[1:]
        inner_params, plive = params
        p_score, p_match = eval_node(inner_desc, inner_params, seg, cap, B)
        pm = p_match & plive
        target = seg["nested"]["target"]
        match = jnp.take_along_axis(
            pm, jnp.broadcast_to(target[None, :], (B, cap)), axis=1) \
            & seg["nested"]["is_child"][None, :]
        return match.astype(jnp.float32), match
    if kind == "term_kw":
        _, field = desc
        ordv, scorev = params
        if field in seg.get("kw_mv", {}):
            mv = seg["kw_mv"][field]          # [cap, M]
            match = jnp.any(mv[None] == ordv[:, None, None], axis=-1) \
                & (ordv[:, None] >= 0)
        else:
            ords = seg["kw"][field]
            match = (ords[None, :] == ordv[:, None]) & (ordv[:, None] >= 0)
        return jnp.where(match, scorev[:, None], 0.0), match
    if kind == "ord_set":
        # membership via a [B, card_total+1] table instead of a
        # [B, cap, set] broadcast compare (which would blow HBM)
        _, field, _card, card_total = desc
        ord_sets, boost = params           # [B, card] (pad = card_total), [B]
        tbl = jnp.zeros((B, card_total + 1), bool).at[
            jnp.arange(B)[:, None], ord_sets].set(True)
        if field in seg.get("kw_mv", {}):
            mv = seg["kw_mv"][field]        # [cap, M]
            safe = jnp.clip(mv, 0, None)
            hit = jax.vmap(lambda t: t[safe])(tbl) & (mv >= 0)[None]
            match = jnp.any(hit, axis=-1)
        else:
            ords = seg["kw"][field]
            safe = jnp.clip(ords, 0, None)
            match = jax.vmap(lambda t: t[safe])(tbl) & (ords >= 0)[None, :]
        return jnp.where(match, boost[:, None], 0.0), match
    if kind == "term_num":
        _, field = desc
        value, scorev = params
        col = seg["num"][field]
        if "mv_values" in col:
            match = jnp.any((col["mv_values"][None] == value[:, None, None])
                            & col["mv_exists"][None], axis=-1)
        else:
            match = (col["values"][None, :] == value[:, None]) \
                & col["exists"][None, :]
        return jnp.where(match, scorev[:, None], 0.0), match
    if kind in ("range_int", "range_f32"):
        _, field = desc
        lo, hi, boost = params
        col = seg["num"][field]
        if "mv_values" in col:
            v = col["mv_values"][None]      # [1, cap, M]
            match = jnp.any((v >= lo[:, None, None])
                            & (v <= hi[:, None, None])
                            & col["mv_exists"][None], axis=-1)
        else:
            v = col["values"][None, :]
            match = (v >= lo[:, None]) & (v <= hi[:, None]) \
                & col["exists"][None, :]
        return jnp.where(match, boost[:, None], 0.0), match
    if kind == "range_kw":
        _, field = desc
        lo, hi, boost = params
        if field in seg.get("kw_mv", {}):
            mv = seg["kw_mv"][field][None]  # [1, cap, M]
            match = jnp.any((mv >= lo[:, None, None])
                            & (mv <= hi[:, None, None]), axis=-1)
        else:
            ords = seg["kw"][field][None, :]
            match = (ords >= lo[:, None]) & (ords <= hi[:, None]) \
                & (ords >= 0)
        return jnp.where(match, boost[:, None], 0.0), match
    if kind == "exists_text":
        _, field = desc
        m = (seg["text"][field]["doc_len"] > 0)[None, :]
        m = jnp.broadcast_to(m, (B, cap))
        return m.astype(jnp.float32), m
    if kind == "exists_kw":
        _, field = desc
        m = (seg["kw"][field] >= 0)[None, :]
        m = jnp.broadcast_to(m, (B, cap))
        return m.astype(jnp.float32), m
    if kind == "exists_num":
        _, field = desc
        m = seg["num"][field]["exists"][None, :]
        m = jnp.broadcast_to(m, (B, cap))
        return m.astype(jnp.float32), m
    if kind == "exists_gv":
        _, tag = desc
        col_kind, field = tag.split("\x00", 1)
        group = "geo" if col_kind == "geo" else "vec"
        m = seg[group][field]["exists"][None, :]
        m = jnp.broadcast_to(m, (B, cap))
        return m.astype(jnp.float32), m
    if kind == "ids":
        (mask,) = params
        return mask.astype(jnp.float32), mask
    if kind == "bool":
        _, d_must, d_should, d_not, d_filter = desc
        p_must, p_should, p_not, p_filter, msm, boost = params
        score = jnp.zeros((B, cap), jnp.float32)
        must_ok = jnp.ones((B, cap), bool)
        for d, p in zip(d_must, p_must):
            s, m = eval_node(d, p, seg, cap, B)
            score = score + jnp.where(m, s, 0.0)
            must_ok = must_ok & m
        for d, p in zip(d_filter, p_filter):
            _, m = eval_node(d, p, seg, cap, B)
            must_ok = must_ok & m
        not_any = jnp.zeros((B, cap), bool)
        for d, p in zip(d_not, p_not):
            _, m = eval_node(d, p, seg, cap, B)
            not_any = not_any | m
        should_cnt = jnp.zeros((B, cap), jnp.int32)
        for d, p in zip(d_should, p_should):
            s, m = eval_node(d, p, seg, cap, B)
            score = score + jnp.where(m, s, 0.0)
            should_cnt = should_cnt + m.astype(jnp.int32)
        match = must_ok & (~not_any) & (should_cnt >= msm[:, None])
        return score * boost[:, None], match
    if kind == "const":
        _, d_child = desc
        p_child, boost = params
        _, m = eval_node(d_child, p_child, seg, cap, B)
        return jnp.where(m, boost[:, None], 0.0), m
    if kind == "boosting":
        _, d_pos, d_neg = desc
        p_pos, p_neg, nboost = params
        s, m = eval_node(d_pos, p_pos, seg, cap, B)
        _, mn = eval_node(d_neg, p_neg, seg, cap, B)
        s = jnp.where(mn, s * nboost[:, None], s)
        return s, m
    if kind == "fnscore":
        # ref: common/lucene/search/function/FunctionScoreQuery.java —
        # combine the child score with per-doc function factors
        _, qdesc, fn_descs, mode_tag = desc
        qparams, fn_params, max_boost, min_score, boost = params
        score_mode, boost_mode, has_min = mode_tag.split("|")
        s, m = eval_node(qdesc, qparams, seg, cap, B)
        factors: list[jax.Array] = []
        applies: list[jax.Array] = []
        seg_fn = dict(seg)
        seg_fn["_score_ctx"] = s  # script_score's _score binding
        for fd, fp in zip(fn_descs, fn_params):
            f, a = _eval_score_fn(fd, fp, seg_fn, cap, B)
            factors.append(f)
            applies.append(a)
        if not factors:
            combined = jnp.ones((B, cap), jnp.float32)
        elif score_mode == "sum":
            combined = sum(jnp.where(a, f, 0.0)
                           for f, a in zip(factors, applies))
        elif score_mode == "avg":
            tot = sum(jnp.where(a, f, 0.0) for f, a in zip(factors, applies))
            cnt = sum(a.astype(jnp.float32) for a in applies)
            combined = jnp.where(cnt > 0, tot / jnp.maximum(cnt, 1.0), 1.0)
        elif score_mode == "max":
            stk = jnp.stack([jnp.where(a, f, -jnp.inf)
                             for f, a in zip(factors, applies)])
            mx = jnp.max(stk, axis=0)
            combined = jnp.where(jnp.isfinite(mx), mx, 1.0)
        elif score_mode == "min":
            stk = jnp.stack([jnp.where(a, f, jnp.inf)
                             for f, a in zip(factors, applies)])
            mn_ = jnp.min(stk, axis=0)
            combined = jnp.where(jnp.isfinite(mn_), mn_, 1.0)
        elif score_mode == "first":
            combined = jnp.ones((B, cap), jnp.float32)
            for f, a in zip(reversed(factors), reversed(applies)):
                combined = jnp.where(a, f, combined)
        else:  # multiply (default)
            combined = jnp.ones((B, cap), jnp.float32)
            for f, a in zip(factors, applies):
                combined = combined * jnp.where(a, f, 1.0)
        combined = jnp.minimum(combined, max_boost[:, None])
        if boost_mode == "replace":
            new = combined
        elif boost_mode == "sum":
            new = s + combined
        elif boost_mode == "avg":
            new = (s + combined) / 2.0
        elif boost_mode == "max":
            new = jnp.maximum(s, combined)
        elif boost_mode == "min":
            new = jnp.minimum(s, combined)
        else:  # multiply
            new = s * combined
        new = new * boost[:, None]
        if has_min == "1":
            m = m & (new >= min_score[:, None])
        # keep the positive-score match invariant of the scoring paths
        new = jnp.where(m, jnp.maximum(new, _F32_MIN_WEIGHT), 0.0)
        return new, m
    if kind == "script_q":
        _, tag = desc
        boost = params[-1]
        val = _eval_device_script(tag, params[:-1], seg, cap, B)
        m = val != 0 if val.dtype != bool else val
        score = jnp.where(m, jnp.maximum(boost[:, None], _F32_MIN_WEIGHT), 0.0)
        return score, m
    if kind == "geo_distance":
        from ..ops.geo import haversine_m
        _, field = desc
        lat_q, lon_q, to_m, from_m, boost = params
        g = seg["geo"][field]
        d = haversine_m(g["lat"][None, :], g["lon"][None, :],
                        lat_q[:, None], lon_q[:, None])
        m = g["exists"][None, :] & (d <= to_m[:, None]) & \
            (d >= from_m[:, None])
        return jnp.where(m, jnp.maximum(boost[:, None], _F32_MIN_WEIGHT),
                         0.0), m
    if kind == "geo_bbox":
        _, field = desc
        top, left, bottom, right, boost = params
        g = seg["geo"][field]
        lat = g["lat"][None, :]
        lon = g["lon"][None, :]
        lat_ok = (lat <= top[:, None]) & (lat >= bottom[:, None])
        # date-line crossing: left > right means the box wraps
        wraps = (left > right)[:, None]
        in_plain = (lon >= left[:, None]) & (lon <= right[:, None])
        in_wrap = (lon >= left[:, None]) | (lon <= right[:, None])
        m = g["exists"][None, :] & lat_ok & \
            jnp.where(wraps, in_wrap, in_plain)
        return jnp.where(m, jnp.maximum(boost[:, None], _F32_MIN_WEIGHT),
                         0.0), m
    if kind == "geo_polygon":
        _, field, p_pad = desc
        lats, lons, boost = params                  # [B, P], [B, P], [B]
        g = seg["geo"][field]
        y = g["lat"][None, :]                       # [1, cap]
        x = g["lon"][None, :]
        inside = jnp.zeros((B, cap), bool)
        # ray cast edge-by-edge (static unroll over padded vertex count;
        # arrays stay [B, cap] so HBM use is independent of P)
        for i in range(p_pad - 1):
            yi = lats[:, i][:, None]
            yj = lats[:, i + 1][:, None]
            xi = lons[:, i][:, None]
            xj = lons[:, i + 1][:, None]
            straddles = (yi > y) != (yj > y)
            denom = jnp.where(yj - yi == 0.0, 1e-12, yj - yi)
            x_cross = (xj - xi) * (y - yi) / denom + xi
            inside = inside ^ (straddles & (x < x_cross))
        m = g["exists"][None, :] & inside
        return jnp.where(m, jnp.maximum(boost[:, None], _F32_MIN_WEIGHT),
                         0.0), m
    raise QueryParsingError(f"unknown desc node [{kind}]")


def _eval_device_script(tag: str, own: tuple, seg: dict, cap: int, B: int,
                        score: jax.Array | None = None) -> jax.Array:
    """Run a compiled expression inside the device program.

    `tag` = "source\\x00p1,p2" (static, part of the jit cache key); `own`
    = stacked [B] param arrays in tag order (+ trailing weight/boost the
    caller consumes). Columns broadcast [cap] x params [B,1] -> [B,cap].
    """
    from ..script import compile_script, ColumnDocAccessor
    src, pname_str = tag.split("\x00", 1)
    pnames = [n for n in pname_str.split(",") if n]
    cs = compile_script(src)
    params = {n: own[i][:, None] for i, n in enumerate(pnames)}
    bindings = {}
    if score is not None:
        bindings["_score"] = score
    val = cs.run(doc=ColumnDocAccessor(seg, jnp), params=params,
                 bindings=bindings, xp=jnp)
    val = jnp.asarray(val)
    return jnp.broadcast_to(val, (B, cap))


def _eval_agg_script(tag: str, seg: dict, cap: int, B: int) -> jax.Array:
    """Aggregation-script variant of _eval_device_script: params are
    static floats encoded in the tag ("src\\x00k=v,...")."""
    from ..script import compile_script, ColumnDocAccessor
    src, ptag = tag.split("\x00", 1)
    params = {}
    for pair in ptag.split(","):
        if pair:
            k, v = pair.split("=", 1)
            params[k] = float(v)
    cs = compile_script(src)
    val = cs.run(doc=ColumnDocAccessor(seg, jnp), params=params,
                 bindings={}, xp=jnp)
    return jnp.broadcast_to(jnp.asarray(val), (B, cap))


def _eval_score_fn(desc: tuple, params: tuple, seg: dict, cap: int, B: int
                   ) -> tuple[jax.Array, jax.Array]:
    """One score function -> (factor [B,cap], applicable [B,cap])."""
    kind, tag, fdesc = desc
    own, fparams = params
    if fdesc is not None:
        _, applicable = eval_node(fdesc, fparams, seg, cap, B)
    else:
        applicable = jnp.ones((B, cap), bool)
    if kind == "fn_weight":
        (weight,) = own
        return jnp.broadcast_to(weight[:, None], (B, cap)), applicable
    if kind == "fn_script":
        weight = own[-1]
        # _score binding: scripts in function_score see the inner query
        # score — passed via seg["_score_ctx"] set by the fnscore branch
        val = _eval_device_script(tag, own[:-1], seg, cap, B,
                                  score=seg.get("_score_ctx"))
        return val.astype(jnp.float32) * weight[:, None], applicable
    if kind == "fn_random":
        seed, weight = own
        idx = jnp.arange(cap, dtype=jnp.uint32)[None, :]
        h = idx * jnp.uint32(2654435761) + seed[:, None] * jnp.uint32(40503)
        h = h ^ (h >> 15)
        h = h * jnp.uint32(2246822519)
        h = h ^ (h >> 13)
        u = h.astype(jnp.float32) / jnp.float32(2 ** 32)
        return u * weight[:, None], applicable
    field, shape_or_mod, has_col = tag.split("|")
    if has_col == "0":
        # column absent in this segment: fvf -> missing value; decay -> 1
        if kind == "fn_fvf":
            factor, missing, weight = own
            val = jnp.broadcast_to(missing[:, None], (B, cap))
            return _apply_fvf_modifier(val, shape_or_mod) * weight[:, None], \
                applicable
        weight = own[-1]
        return jnp.ones((B, cap), jnp.float32) * weight[:, None], applicable
    col = seg["num"][field]
    vals = col["values"].astype(jnp.float32)[None, :]
    exists = col["exists"][None, :]
    if kind == "fn_fvf":
        factor, missing, weight = own
        val = jnp.where(exists, vals * factor[:, None], missing[:, None])
        return _apply_fvf_modifier(val, shape_or_mod) * weight[:, None], \
            applicable
    # decay functions (ref: functionscore/DecayFunctionBuilder.java)
    origin, scale, offset, decay, weight = own
    d = jnp.maximum(jnp.abs(vals - origin[:, None]) - offset[:, None], 0.0)
    ln_decay = jnp.log(decay[:, None])
    if shape_or_mod == "gauss":
        sigma2 = -(scale[:, None] ** 2) / (2.0 * ln_decay)
        f = jnp.exp(-(d ** 2) / (2.0 * sigma2))
    elif shape_or_mod == "exp":
        lam = ln_decay / scale[:, None]
        f = jnp.exp(lam * d)
    else:  # linear
        s_ = scale[:, None] / (1.0 - decay[:, None])
        f = jnp.maximum((s_ - d) / s_, 0.0)
    f = jnp.where(exists, f, 1.0)
    return f * weight[:, None], applicable


def _apply_fvf_modifier(val: jax.Array, modifier: str) -> jax.Array:
    """Ref: common/lucene/search/function/FieldValueFactorFunction.Modifier."""
    if modifier == "none":
        return val
    if modifier == "log":
        return jnp.log10(jnp.maximum(val, 1e-9))
    if modifier == "log1p":
        return jnp.log10(jnp.maximum(val, 0.0) + 1.0)
    if modifier == "log2p":
        return jnp.log10(jnp.maximum(val, 0.0) + 2.0)
    if modifier == "ln":
        return jnp.log(jnp.maximum(val, 1e-9))
    if modifier == "ln1p":
        return jnp.log1p(jnp.maximum(val, 0.0))
    if modifier == "ln2p":
        return jnp.log(jnp.maximum(val, 0.0) + 2.0)
    if modifier == "square":
        return val * val
    if modifier == "sqrt":
        return jnp.sqrt(jnp.maximum(val, 0.0))
    if modifier == "reciprocal":
        return 1.0 / jnp.maximum(val, 1e-9)
    raise SearchParseError(f"unknown field_value_factor modifier [{modifier}]")


# ---------------------------------------------------------------------------
# Fused block-max score + top-k: plan classifier, backend autotuner, stats
#
# The unfused program materializes a full [B, cap] score matrix, then
# lax.top_k's it. Plans the classifier below can express as a CLAUSE
# BUNDLE (ops/scoring.py: dense-text must/should scoring clauses incl.
# boosted single-should wrappers, dense or numeric-range filter /
# must_not masks, dynamic msm/boost) instead route through the fused
# block-max-WAND ops (ops/scoring.score_topk_bundle_fused /
# ops/pallas_scoring.fused_topk_bundle_pallas): SCORE_TILE-doc tiles
# with a running top-k and block-max pruning off the pack-time tile_max
# summaries. Both engines take the same calling convention and cover
# the same matrix — multi-field bundles, range masks, emit-match (k>0
# plans that ALSO carry aggregations have the tile loop write the exact
# match mask, which feeds the ordinary aggregation pass — still never
# materializing the [B, cap] score matrix), and the mask-only k == 0
# pass. Which backend wins is shape- and data-dependent (the round-5
# bench had Pallas LOSING to XLA on http_logs), so the first execution
# of each (pack, shape-bucket) key warms both backends and takes the
# best-of-N wall clock of each; choices AND both timings persist
# across restarts under the node data path, keyed by the pack
# fingerprint (a refreshed pack re-tunes under its new fingerprint),
# and shapes where an admitted pallas candidate lost by >10% surface
# in nodes_stats()["fused_scoring"].loss_audit.
# ---------------------------------------------------------------------------

import contextlib as _contextlib
import json as _json
import os as _os
import threading as _threading
import time as _time

# the clause-kind partition is owned by ops/scoring.py — importing it
# keeps the admission classifier and the bundle engine from drifting
from ..ops.scoring import (DENSE_CLAUSE_KINDS as _FUSED_DENSE_KINDS,
                           RANGE_CLAUSE_KINDS as _FUSED_RANGE_KINDS,
                           VEC_CLAUSE_KINDS as _FUSED_VEC_KINDS)
# tiered tile residency (index/tiering.py): HBM as a cache over
# host-RAM forward-index tiles, paged by the block-max bound oracle
from ..index import tiering as _tiering

# compile-time unroll budget of the per-tile clause loop; plans beyond
# it fall back rather than minting pathological programs (a match with
# operator and is one must clause a term: ten for a long question)
_FUSED_MAX_CLAUSES = 16


def _fused_leaf_inputs(desc: tuple, params: tuple
                       ) -> tuple[jax.Array, jax.Array]:
    if desc[0] == "terms_dense":
        qt, wq = params
        return qt, wq
    tid, weight = params                     # term_text: single-term Q=1
    return tid[:, None], weight[:, None]


def fused_enabled() -> bool:
    return _os.environ.get("ES_TPU_FUSED", "auto").lower() not in (
        "0", "false", "off")


def _positional_enabled() -> bool:
    """Gate for the fused positional clause kinds (phrase/span/BM25F on
    device). Off forces the host phrase.py path — responses are
    byte-identical either way; this is the bench A/B lever."""
    return _os.environ.get("ES_TPU_POSITIONAL", "1").lower() not in (
        "0", "false", "off")


def _leaf_scoring_kind(d0) -> bool:
    return d0 in _FUSED_DENSE_KINDS or (isinstance(d0, str)
                                        and positional_prefix(d0))


def _classify_fused_leaf(desc: tuple):
    """(kind, field, wrapped) of a scoring clause the bundle engine
    evaluates per tile — a bare terms_dense/term_text or positional
    (phrase/span/BM25F) leaf, or one wrapped in a single-should bool
    that carries its own dynamic (msm, boost), e.g. a boosted match
    inside an explicit bool (bool-in-bool). None for anything else."""
    if _leaf_scoring_kind(desc[0]):
        return (desc[0], desc[1], False)
    if desc[0] == "bool":
        _, must, should, must_not, filt = desc
        if not must and not must_not and not filt and len(should) == 1 \
                and _leaf_scoring_kind(should[0][0]):
            return (should[0][0], should[0][1], True)
    return None


def _fused_plan_bundle(desc: tuple, k: int, agg_desc, sort_spec: tuple,
                       allow_aggs: bool = True, allow_k0: bool = False):
    """SHARED plan-level admission (single-chip executor AND the mesh
    searcher route through this — keep the predicates from drifting).

    Returns (bundle, reject_reason): a static clause-bundle tuple in
    eval_node order (must, filter, must_not, should — see
    ops/scoring.py) when the fused score+top-k path may serve the plan,
    else (None, reason) for the rejection counters. Requires a pure
    score sort; aggregations are fine where the caller can run the
    emit-match engine (allow_aggs). k == 0 plans (size-0 counts /
    filtered aggs) are admitted only where the caller runs the
    match-mask-only engine (allow_k0) — there is no k-th slot for the
    running top-k, so the score matrix is skipped entirely. Callers
    still check the pack carries the tile summaries and that every bool
    boost is positive."""
    if not fused_enabled():
        return None, "disabled"
    if k <= 0 and not allow_k0:
        return None, "k_zero"
    if tuple(sort_spec) != ("_score",):
        return None, "sort"
    if agg_desc and not allow_aggs:
        return None, "aggs_unsupported"
    if _leaf_scoring_kind(desc[0]):
        return (("should", desc[0], desc[1], False),), None
    if desc[0] != "bool":
        return None, f"clause:{desc[0]}"
    _, d_must, d_should, d_not, d_filter = desc
    clauses = []
    for role, group in (("must", d_must), ("filter", d_filter),
                        ("must_not", d_not), ("should", d_should)):
        for c in group:
            leaf = _classify_fused_leaf(c)
            if leaf is not None:
                clauses.append((role,) + leaf)
            elif role in ("filter", "must_not") \
                    and c[0] in _FUSED_RANGE_KINDS:
                clauses.append((role, c[0], c[1], False))
            elif role in ("must", "should") \
                    and c[0] in _FUSED_VEC_KINDS:
                # vector similarity clause (hybrid BM25+knn): scored
                # per tile from the in-program similarity column
                clauses.append((role, c[0], c[1], False))
            else:
                return None, f"clause:{c[0]}"
    if not any(_leaf_scoring_kind(kd) for _r, kd, _f, _w in clauses):
        return None, "no_scoring_clause"
    if len(clauses) > _FUSED_MAX_CLAUSES:
        return None, "too_many_clauses"
    return tuple(clauses), None


def _bundle_inputs(desc: tuple, params: tuple, bundle: tuple):
    """Per-clause dynamic inputs for a classified plan (runs under jit
    on the traced params): (cl_inputs, msm [B] i32, boost [B] f32|None)
    in the ops/scoring.py bundle contract. Walks desc/params in the
    exact group order the classifier emitted the bundle in."""
    B = _batch_size(params)
    ones_i = jnp.ones((B,), jnp.int32)
    ones_f = jnp.ones((B,), jnp.float32)
    if desc[0] != "bool":
        if isinstance(desc[0], str) and positional_prefix(desc[0]):
            return (tuple(params) + (ones_i, ones_f),), ones_i, None
        qt, wq = _fused_leaf_inputs(desc, params)
        return ((qt, wq, ones_i, ones_f),), ones_i, None
    _, d_must, d_should, d_not, d_filter = desc
    p_must, p_should, p_not, p_filter, msm, boost = params
    groups = {"must": (d_must, p_must), "should": (d_should, p_should),
              "must_not": (d_not, p_not), "filter": (d_filter, p_filter)}
    nxt = {r: 0 for r in groups}
    out = []
    for role, kind, _field, wrapped in bundle:
        dg, pg = groups[role]
        d, p = dg[nxt[role]], pg[nxt[role]]
        nxt[role] += 1
        if kind in _FUSED_RANGE_KINDS:
            lo, hi, _boost_r = p
            out.append((lo, hi))
        elif kind in _FUSED_VEC_KINDS:
            # (qv [B, D], boost [B], similarity) — the raw clause
            # inputs; eval_fused_topk/match substitute the computed
            # (col, exists, ub) before the scoring ops see them
            qv, boost_c = p
            out.append((qv, boost_c, d[2]))
        elif wrapped:
            _, _cm, c_should, _cn, _cf = d
            _pm, pc_should, _pn, _pf, msm_c, boost_c = p
            if positional_prefix(kind):
                # positional finalize params ride whole (the 5/4-tuple
                # contract of ops/scoring.positional_tile_scores), the
                # wrapper's (msm, boost) appended last
                out.append(tuple(pc_should[0]) + (msm_c, boost_c))
            else:
                qt, wq = _fused_leaf_inputs(c_should[0], pc_should[0])
                out.append((qt, wq, msm_c, boost_c))
        elif positional_prefix(kind):
            out.append(tuple(p) + (ones_i, ones_f))
        else:
            qt, wq = _fused_leaf_inputs(d, p)
            out.append((qt, wq, ones_i, ones_f))
    return tuple(out), msm, boost


def _fused_pack_ok(segment: Segment, bundle: tuple) -> str | None:
    """Pack-level admission: every dense clause field needs a forward
    index + tile_max block-max summary; every range clause field needs
    (lazily built) per-tile extrema. Returns a reject reason or None."""
    for _role, kind, field, _w in bundle:
        if kind in _FUSED_DENSE_KINDS:
            pf = segment.text.get(field)
            if pf is None or pf.fwd_tids is None \
                    or getattr(pf, "tile_max", None) is None:
                return "missing_tile_max"
        elif positional_prefix(kind):
            # binder admission already checked the BINDING segment; this
            # re-check covers the cross-segment callers (pack pairs,
            # mesh) where another segment may lack the positions pack
            for f in clause_fields(field):
                pf = segment.text.get(f)
                if pf is None or pf.fwd_tids is None \
                        or getattr(pf, "fwd_pos", None) is None \
                        or getattr(pf, "tile_max", None) is None:
                    return "missing_positions_pack"
        elif kind in _FUSED_VEC_KINDS:
            if segment.vectors.get(field) is None:
                return "missing_vector_column"
        elif not ensure_num_tiles(segment, field):
            return "missing_tile_minmax"
    return None


def _fused_params_ok(desc: tuple, params: tuple, bundle: tuple) -> bool:
    """Positive-boost admission, host-side on the numpy params: the
    outer bool boost and every wrapped clause's boost must be > 0 —
    scores are applied pre-selection in eval_node's op order (exact
    doc-id/tie parity for any positive boost), but boost <= 0 breaks
    the monotone-bound argument the pruning relies on."""
    if desc[0] != "bool":
        return True
    if not bool((np.asarray(params[5]) > 0).all()):
        return False
    p_groups = {"must": params[0], "should": params[1],
                "must_not": params[2], "filter": params[3]}
    nxt = {r: 0 for r in p_groups}
    for role, kind, _field, wrapped in bundle:
        p = p_groups[role][nxt[role]]
        nxt[role] += 1
        if wrapped and not bool((np.asarray(p[5]) > 0).all()):
            return False
        # knn clause boost must be positive too: its tile bound is the
        # max of the boost-folded column — monotone only for boost > 0
        if kind in _FUSED_VEC_KINDS \
                and not bool((np.asarray(p[1]) > 0).all()):
            return False
    return True


def _fused_row_elems(cap: int, n_tiles: int, k: int,
                     emit_match: bool = False,
                     vec_clauses: int = 0,
                     pos_width: int = 0,
                     chunk_tiles: int | None = None) -> int:
    """Per-row transient of a fused dispatch in elements — one [*, slab]
    scoring slab (a step of the walk: walk_step_tiles of the shapes and
    of a resident step's chunk_tiles, times the tile; one tile where
    k == 0, whose mask-only walk steps a tile at a time) plus the
    [*, steps*ck] candidate strip (ck = min(k, slab)), plus the
    [*, cap] bool match mask in emit-match (fused+aggs) mode, plus one
    [*, cap] similarity column per knn clause (the in-program vector
    preamble), plus the decoded [*, slab, n*P] i32 position slab of the
    widest positional clause (pos_width = its n * P; the per-clause
    decodes are sequential, so the widest bounds the live transient).
    The breaker estimate (execute_segment_async) and the chunking
    decision (_segment_body) MUST size from this one definition."""
    g = walk_step_tiles(n_tiles, chunk_tiles) if k > 0 else 1
    slab = g * (cap // n_tiles)
    return slab + (n_tiles // g) * min(k, slab) \
        + (cap if emit_match else 0) + vec_clauses * cap + pos_width * slab


def _fused_engine(bundle: tuple, backend: str) -> str:
    """The engine a fused plan runs on: the kernel has no knn-clause
    form (the similarity-column preamble is XLA-only) and no positional
    form on the chip (_positional_needs_xla), so such a bundle runs the
    XLA walk even where the kernel was chosen or forced — results are
    identical either way, crashing is not."""
    if any(kd in _FUSED_VEC_KINDS for _r, kd, _f, _w in bundle) \
            or _positional_needs_xla(bundle):
        return "xla"
    return backend


def _fused_walk(bundle: tuple, backend: str, n_tiles: int, k: int,
                chunk_tiles: int | None = None) -> tuple[int, int]:
    """(tiles, tiles a step) of the fused walk a launched plan makes,
    for `fused_scoring.steps`: the XLA top-k walk steps as
    ops/scoring.walk_step_tiles says, the mask-only walk (k == 0) and
    the kernel a tile at a time."""
    stepped = k > 0 and _fused_engine(bundle, backend) == "xla"
    return n_tiles, walk_step_tiles(n_tiles, chunk_tiles) if stepped else 1


def _walk_steps(examined: float, walks: tuple) -> tuple[float, float]:
    """(steps, tiles) that the stepped walks among `walks` ((tiles,
    tiles a step) of each walk of one launch) took, out of the
    `examined` tiles the device counted for them all: every repetition
    of the launch's walks (a batch's lax.map chunks, a mesh's shards)
    repeats them alike."""
    tiles = sum(n for n, _g in walks)
    if not tiles:
        return 0.0, 0.0
    reps = float(examined) / tiles
    return (reps * sum(n / g for n, g in walks if g > 1),
            reps * sum(n for n, g in walks if g > 1))


def _bundle_pos_width(bundle: tuple, text_cols) -> int:
    """Widest positional clause's decoded position slab in elements per
    doc (n_terms * P for phrase/span; P for bm25f, whose per-(field,
    term) decodes are sequential). text_cols is either Segment.text
    (host PostingsField objects) or a device seg["text"] dict."""
    w = 0
    for _r, kd, fld, _w2 in bundle:
        if not (isinstance(kd, str) and positional_prefix(kd)):
            continue
        head, n, _v = parse_positional_kind(kd)
        for f in clause_fields(fld):
            c = text_cols[f]
            if isinstance(c, dict):
                fwd_pos, fwd_tids = c.get("fwd_pos"), c.get("fwd_tids")
            else:
                fwd_pos, fwd_tids = c.fwd_pos, c.fwd_tids
            if fwd_pos is None or fwd_tids is None:
                continue
            # trailing axis: works for host [cap, L] / mesh [S, cap, L]
            p = fwd_pos.shape[-1] // fwd_tids.shape[-1]
            w = max(w, (1 if head == "bm25f" else n) * p)
    return w


def _bundle_fwd_width(bundle: tuple, text_cols) -> int:
    """Widest forward index (slots a doc) among the bundle's dense
    clause fields; text_cols as for _bundle_pos_width."""
    w = 0
    for _r, kd, fld, _w2 in bundle:
        if kd in _FUSED_DENSE_KINDS:
            c = text_cols[fld]
            fwd = c.get("fwd_tids") if isinstance(c, dict) else c.fwd_tids
            if fwd is not None:
                w = max(w, fwd.shape[-1])
    return w


def _bundle_positional(bundle: tuple) -> bool:
    return any(isinstance(kd, str) and positional_prefix(kd)
               for _r, kd, _f, _w in bundle)


class _FusedScoringStats:
    """Autotuner choices, block-prune counters, and per-reason admission
    rejections for the fused score+top-k path; surfaced via the node
    stats API (node.nodes_stats()["fused_scoring"])."""

    def __init__(self):
        self._lock = _threading.Lock()
        self._choices: dict[str, dict] = {}
        self._hard = 0.0
        self._thresholded = 0.0
        self._examined = 0.0
        self._dispatches = 0
        # launches whose walk took several tiles a step, those steps and
        # their tiles (a part of `examined`: _walk_steps)
        self._stepped_walks = 0
        self._steps = 0.0
        self._stepped_tiles = 0.0
        self._admitted = 0
        self._rejected: dict[str, int] = {}
        # positional (phrase/span/BM25F) observability: queries whose
        # positional clause fell back to the host path, by reason;
        # fused-admitted plans CARRYING positional clauses; and the
        # tile-prune counters of exactly those dispatches (the
        # position-aware prune signal the bench leg gates on)
        self._positional: dict[str, int] = {}
        self._positional_admitted = 0
        self._pos_hard = 0.0
        self._pos_thresholded = 0.0
        self._pos_examined = 0.0
        self._pos_dispatches = 0
        # fused-ADMITTED plans where the Pallas kernel was not even a
        # candidate, by reason tag — the remaining kernel-coverage gaps
        # made observable instead of inferred from bench diffs
        self._pallas_rejected: dict[str, int] = {}
        # top-level `knn` section admission, by reason (record_knn)
        self._knn: dict[str, int] = {}
        # IVF cluster-prune counters (record_ann_prune)
        self._ann_probed = 0
        self._ann_pruned = 0
        self._ann_scored = 0
        # block-max summaries held on the device (record_summary): a
        # gauge of what is resident, which `reset` leaves alone
        self._summary_bytes = 0
        self._summary_entries = 0

    def record_choice(self, key: tuple, backend: str, reason: str,
                      timings: dict | None = None,
                      keep_existing: bool = False) -> None:
        """keep_existing: record only when the key has no entry yet —
        the forced-env resolve path runs per dispatch and must not
        clobber a tuned entry's timings (which would silently drop the
        shape from the loss audit)."""
        entry = {"backend": backend, "reason": reason}
        if timings:
            entry["timings_ms"] = {b: round(t * 1e3, 3)
                                   for b, t in timings.items()}
        with self._lock:
            if keep_existing and repr(key) in self._choices:
                return
            # keys embed pack fingerprints, which refreshes/merges mint
            # forever: bounded so the stats payload cannot grow
            # monotonically
            _bounded_put(self._choices, repr(key), entry)

    def record_admit(self, positional: bool = False) -> None:
        with self._lock:
            self._admitted += 1
            if positional:
                self._positional_admitted += 1

    def record_reject(self, reason: str) -> None:
        with self._lock:
            self._rejected[reason] = self._rejected.get(reason, 0) + 1

    def record_positional(self, reason: str) -> None:
        """One positional query bound to the HOST phrase/span/BM25F
        path, by reason — plan-level positional admission made
        observable (admission.positional_fallbacks)."""
        with self._lock:
            self._positional[reason] = self._positional.get(reason, 0) + 1

    def record_pallas_reject(self, reason: str) -> None:
        with self._lock:
            self._pallas_rejected[reason] = \
                self._pallas_rejected.get(reason, 0) + 1

    def record_knn(self, reason: str) -> None:
        """Per-reason admission of top-level `knn` search sections
        (search/shard_searcher.py): how each vector search was served
        — "query_rewrite" (bundle clause, rides the dispatch
        scheduler), "ivf" (coarse-quantized probe), "exact" (pure-knn
        scan: below the IVF crossover OR a degraded/skipped build), or
        a "host_fallback:<why>" tag for shapes the device paths cannot
        take (e.g. unsupported similarity) — so unfused vector shapes
        are visible instead of silent."""
        with self._lock:
            self._knn[reason] = self._knn.get(reason, 0) + 1

    def record_prune(self, hard: float, thresholded: float,
                     examined: float, positional: bool = False,
                     steps: tuple[float, float] = (0.0, 0.0)) -> None:
        """One launch's tile counters; `steps` = (steps, tiles) of its
        walks that took several tiles a step (_walk_steps)."""
        with self._lock:
            self._hard += float(hard)
            self._thresholded += float(thresholded)
            self._examined += float(examined)
            self._dispatches += 1
            if steps[1]:
                self._stepped_walks += 1
                self._steps += float(steps[0])
                self._stepped_tiles += float(steps[1])
            if positional:
                self._pos_hard += float(hard)
                self._pos_thresholded += float(thresholded)
                self._pos_examined += float(examined)
                self._pos_dispatches += 1

    def record_summary(self, nbytes: int, entries: int) -> None:
        """A pack's block-max summary went to the device (or, negative,
        left it with its segment): what the stored form costs there."""
        with self._lock:
            self._summary_bytes += nbytes
            self._summary_entries += entries

    def record_ann_prune(self, probed: int, pruned: int,
                         scored: int) -> None:
        """IVF probe counters (ops/ann.ivf_topk stats, per-(query,
        cluster) units): `pruned` is the cluster-prune skip count — a
        probed cluster whose bound could not beat the running k-th
        best, skipped without touching its members."""
        with self._lock:
            self._ann_probed += int(probed)
            self._ann_pruned += int(pruned)
            self._ann_scored += int(scored)

    def snapshot(self) -> dict:
        with self._lock:
            pruned = self._hard + self._thresholded
            considered = self._admitted + sum(self._rejected.values())
            # autotuner loss-audit (the ROADMAP item-3 regression
            # signal): every TIMED tune kept both backends' best-of-N;
            # any shape where the Pallas candidate lost to XLA by >10%
            # is a kernel-coverage/perf gap, reported here whichever
            # backend actually won
            audit = []
            for key, entry in self._choices.items():
                t = entry.get("timings_ms")
                if not t or "pallas" not in t or "xla" not in t:
                    continue
                if t["xla"] > 0 and t["pallas"] > 1.1 * t["xla"]:
                    audit.append({"key": key, "backend": entry["backend"],
                                  "pallas_ms": t["pallas"],
                                  "xla_ms": t["xla"],
                                  "ratio": round(t["pallas"] / t["xla"],
                                                 3)})
            return {
                "backend_choices": {k: dict(v)
                                    for k, v in self._choices.items()},
                "dispatches": self._dispatches,
                "tiles": {"examined": round(self._examined, 3),
                          "hard_skipped": round(self._hard, 3),
                          "thresholded": round(self._thresholded, 3)},
                "steps": {"walks": self._stepped_walks,
                          "steps": round(self._steps, 3),
                          "stepped_tiles": round(self._stepped_tiles, 3)},
                "prune_rate": (pruned / self._examined
                               if self._examined else 0.0),
                "summary": {"bytes": self._summary_bytes,
                            "entries": self._summary_entries},
                "loss_audit": {"shapes": audit, "count": len(audit)},
                "ann": {"clusters_probed": self._ann_probed,
                        "clusters_pruned": self._ann_pruned,
                        "clusters_scored": self._ann_scored},
                # why plans fell back, by reason — so a bench run can
                # see WHY a workload missed the fused path; the
                # pallas_rejected sub-map counts fused-admitted plans
                # the KERNEL could not serve, by reason tag
                "admission": {
                    "admitted": self._admitted,
                    "rejected": dict(self._rejected),
                    "pallas_rejected": dict(self._pallas_rejected),
                    "knn": dict(self._knn),
                    "positional_fallbacks": dict(self._positional),
                    "positional_admitted": self._positional_admitted,
                    "rate": (self._admitted / considered
                             if considered else 0.0)},
                "positional": {
                    "dispatches": self._pos_dispatches,
                    "tiles": {
                        "examined": round(self._pos_examined, 3),
                        "hard_skipped": round(self._pos_hard, 3),
                        "thresholded": round(self._pos_thresholded, 3)},
                    "prune_rate": (
                        (self._pos_hard + self._pos_thresholded)
                        / self._pos_examined
                        if self._pos_examined else 0.0)},
            }

    def reset(self) -> None:
        with self._lock:
            self._choices.clear()
            self._hard = self._thresholded = self._examined = 0.0
            self._dispatches = 0
            self._stepped_walks = 0
            self._steps = self._stepped_tiles = 0.0
            self._admitted = 0
            self._rejected.clear()
            self._pallas_rejected.clear()
            self._knn.clear()
            self._positional.clear()
            self._positional_admitted = 0
            self._pos_hard = self._pos_thresholded = self._pos_examined = 0.0
            self._pos_dispatches = 0
            self._ann_probed = self._ann_pruned = self._ann_scored = 0


_fused_stats = _FusedScoringStats()

# device-program launches by backend, counted where the program is
# launched (`fused_scoring.dispatches` counts fused launches only, at
# collect); process-wide like _fused_stats, read as deltas through
# `_nodes/stats/dispatch` -> "launches"
LAUNCH_BACKENDS = ("unfused", "fused_xla", "fused_pallas", "resident",
                   "tiered")
_launches = _MetricsRegistry()
for _backend in LAUNCH_BACKENDS:
    _launches.counter(_backend)


def launch_counts() -> dict:
    return _launches.snapshot()


# collects, and those whose launch had already asked for the result's
# device-to-host copy (`_start_fetch`); and the `collect` phase in two
# parts that tile it (`_collect`), weighted like the phase: `compute`
# up to the program's end, `copy` the rest. Process-wide, read as
# deltas through `_nodes/stats/dispatch` -> "collects", "collect_parts"
# (beside "launches", never inside "phases": the first is no time, the
# second time `collect` counts already)
_collects = _MetricsRegistry()
for _name in ("total", "prefetched"):
    _collects.counter(_name)
_collect_compute = _MeanMetric()
_collect_copy = _MeanMetric()


def collect_counts() -> dict:
    return _collects.snapshot()


def collect_parts() -> dict:
    return {"compute": _collect_compute.snapshot(),
            "copy": _collect_copy.snapshot()}


def _start_fetch(buf) -> dict:
    """Ask for a launched program's result on the host now, so that the
    copy runs behind whatever the launching thread does next (the next
    shard's bind and launch, the previous shard's unpack and fetch) and
    `_collect`'s device_get finds the bytes there or in flight. Called
    right after the program call, inside its `_launch` block; `buf` may
    be a tree of arrays (the mesh program's outputs). Returns the
    per-launch field of the layout that `_collect` counts by."""
    try:
        for leaf in jax.tree_util.tree_leaves(buf):
            leaf.copy_to_host_async()
        prefetched = True
    except (AttributeError, RuntimeError):
        prefetched = False
    return {"_prefetched": prefetched}


def _span_args(bind) -> dict:
    """What a phase of this dispatch takes from the reader's `bind`
    phase (None for callers that bring none): its weight, the searches
    the reader's call serves, and the `request=` / `requests=` span
    arguments."""
    return {"weight": bind.weight, **bind.args} if bind is not None else {}


@_contextlib.contextmanager
def _launch(bind, backend: str, span: str = "dispatch"):
    """The phase around one device-program launch, counted under its
    backend. It takes its time out of the reader's `bind` block
    (utils/profiler.phase: parse, bind, wire params and layout ran up
    to here, the breaker accounting follows), so that the spans stay
    leaves."""
    _launches.counter(backend).inc()
    if bind is not None:
        bind.pause()
    try:
        with _phase(span, **_span_args(bind)):
            yield
    finally:
        if bind is not None:
            bind.resume()


def fused_scoring_stats() -> dict:
    """Snapshot for the node stats API (+ the tiered-residency block:
    resident vs summary bytes, tile hit/miss/eviction counters, and
    the prune-skipped fetch count proving the I/O filter)."""
    out = _fused_stats.snapshot()
    out["tiering"] = _tiering.stats_snapshot()
    return out


# hard cap on the per-tile selection depth the kernel will attempt:
# up to ops/pallas_scoring._CK_UNROLL the selection passes unroll; past
# it a fori_loop runs the same passes (the multi-pass form that lifted
# the old 128 hard cap), and past THIS the O(ck * tile) per-tile
# selection work loses to XLA's tile-wide lax.top_k regardless
_FUSED_PALLAS_CK_MAX = 1024

_autotune_choices: dict = {}
# serializes first-execution tuning: concurrent searches timing
# different keys would dispatch onto the same (serially executing)
# device and corrupt each other's wall clocks — and the corrupted
# winner would be cached for the life of the process
_autotune_lock = _threading.Lock()
# bound on cached choices/stats entries: keys embed seg_ids, which a
# long-lived node's refresh/merge cycle mints without end — evicting
# oldest-inserted only costs a re-tune if an evicted pack comes back
_AUTOTUNE_CACHE_CAP = 512


def _bounded_put(d: dict, key, value) -> None:
    """Insert under the shared FIFO cap (caller holds the dict's lock).
    ONE policy for the tuner cache and its stats mirror, so the two
    stay in lockstep; re-recording an existing key never evicts."""
    if key not in d:
        while len(d) >= _AUTOTUNE_CACHE_CAP:
            d.pop(next(iter(d)))
    d[key] = value


def seg_cache_key(segment: Segment) -> str:
    """The key every fingerprint-keyed cache (autotune choices, the
    persisted store, resident entries) indexes a pack under. Base
    segments key on content; DELTA segments (streaming write path) key
    on their (base generation, pow2 delta-extent bucket) instead —
    Segment.cache_key — so a refresh's delta rebuild lands on the SAME
    key and performs zero re-tunes and zero evictions. Only compaction
    (which mints a new base fingerprint) re-keys."""
    return segment.cache_key()


def fused_pallas_ok(ck: int) -> bool:
    """May the Pallas fused kernel be a candidate? Real-TPU lowering
    only (interpret mode is a validation tool, not a serving backend)
    and a bounded per-tile selection depth; ck == 0 is the mask-only
    k == 0 grid (no selection at all)."""
    return (pallas_enabled() and not interpret_mode()
            and 0 <= ck <= _FUSED_PALLAS_CK_MAX)


def _pallas_coverage() -> str:
    """Kernel coverage mode: "full" (default — the kernel serves the
    whole bundle admission matrix) or "legacy" (the PR 2 single-field
    all-dense no-aggs matrix; an A/B and bisection tool — with it set,
    the per-reason pallas_rejected counters show exactly which plans the
    restriction costs)."""
    return _os.environ.get("ES_TPU_PALLAS_COVERAGE", "full").lower()


# widest positions pack (L*P int16 elements per doc row) the kernel
# will stage into VMEM next to the forward block: past this the
# [tile, L*P] position ref alone approaches the VMEM budget and the
# XLA engine (which streams the decode through HBM) wins anyway
_POS_PALLAS_WIDTH_MAX = 4096
# widest forward index (slots a doc) the kernel will take: its dense
# clause compares every slot with every query term in a static unroll,
# and at 256 slots (passages of up to 256 distinct words) the Mosaic
# compile of any batch of two or more runs out of scoped VMEM on a v5e
# (128 slots compile); wider packs run the XLA engine, visibly
_FWD_PALLAS_SLOTS_MAX = 128


def _positional_needs_xla(bundle: tuple) -> bool:
    """The kernel's positional variant (phrase / span / BM25F) evaluates
    ops/scoring.positional_tile_scores in the kernel body — cumsum,
    int16 reductions, minor-dim reshapes and gathers, none of which the
    Mosaic TPU lowering implements (tests/test_tpu_compile.py pins the
    refusal). It exists in interpret mode only; on a real TPU a
    positional bundle runs the fused XLA engine — same tile walk, same
    bytes — and is counted under pallas_rejected["positional_mosaic"].
    Even a FORCED pallas choice demotes (eval_fused_topk/_match), like
    knn clauses: results are identical either way, crashing is not."""
    return _bundle_positional(bundle) and not interpret_mode()


def _bundle_pallas_reason(bundle: tuple, agg_desc, ck: int,
                          pos_width: int = 0,
                          fwd_width: int = 0) -> str | None:
    """Why the Pallas kernel is NOT a candidate for a fused-admitted
    bundle (None = it is): reason tags feed
    nodes_stats()["fused_scoring"].admission.pallas_rejected so the
    remaining coverage gaps are observable, not inferred from bench
    diffs. Shape reasons are computed before availability so they
    surface on every backend. pos_width is the widest positional
    field's packed L*P (0 = caller has no positional clauses or no
    shape info — the VMEM gate is then skipped); fwd_width the widest
    dense field's forward slots (_bundle_fwd_width)."""
    if fwd_width > _FWD_PALLAS_SLOTS_MAX:
        return "forward_width"
    if any(kd in _FUSED_VEC_KINDS for _r, kd, _f, _w in bundle):
        # the similarity-column preamble (whole-capacity MXU matmul) has
        # no kernel form yet: hybrid BM25+vector bundles run the XLA
        # engine, visibly
        return "knn_clause"
    if ck > _FUSED_PALLAS_CK_MAX:
        return "ck_cap"
    if _bundle_positional(bundle) and pos_width > _POS_PALLAS_WIDTH_MAX:
        return "positional_vmem"
    if _positional_needs_xla(bundle):
        return "positional_mosaic"
    if _pallas_coverage() == "legacy":
        if _bundle_positional(bundle):
            return "positional_clause"
        if agg_desc:
            return "agg_emit_match"
        if ck == 0:
            return "k_zero"
        fields = {f for _r, kd, f, _w in bundle
                  if kd in _FUSED_DENSE_KINDS}
        if len(fields) != 1:
            return "multi_field"
        if any(kd in _FUSED_RANGE_KINDS for _r, kd, _f, _w in bundle):
            return "range_mask"
    if not fused_pallas_ok(ck):
        return "kernel_unavailable"
    return None


def _bundle_pallas_ok(bundle: tuple, agg_desc, ck: int,
                      pos_width: int = 0, fwd_width: int = 0) -> bool:
    """Bundle-level Pallas candidacy: the kernel now covers the full
    bundle admission matrix — multi-text-field bundles, positional
    (phrase/span/BM25F) clause kinds, dense/numeric range filter &
    must_not masks, emit-match (k>0 + aggs), and the mask-only k == 0
    grid — so candidacy reduces to availability plus the
    selection-depth and positional-VMEM caps (see _bundle_pallas_reason
    for the tags)."""
    return _bundle_pallas_reason(bundle, agg_desc, ck, pos_width,
                                 fwd_width) is None


# -- persisted autotuner choices (satellite: survive restarts) --------------
#
# Keys embed the pack FINGERPRINT (index/segment.Segment.fingerprint),
# which is stable across process restarts for identical content and
# changes whenever a refresh/merge rebuilds the pack — so invalidation
# is by construction: a refreshed pack re-tunes under its new key and
# stale entries age out of the FIFO cap.

_autotune_persist_path: str | None = None
# key -> {"choice": "pallas"|"xla", "timings_ms": {...}|None}: the
# loss-audit satellite keeps BOTH backends' best-of-N, not just the
# winner, so a restart can still answer "by how much did pallas lose"
_autotune_persisted: dict[str, dict] = {}
_AUTOTUNE_PERSIST_CAP = 4096


def _persist_entry(value) -> dict | None:
    """Normalize one on-disk store value: current dict entries and the
    pre-timings plain-string format both load (a legacy entry just has
    no timings to audit)."""
    if isinstance(value, str) and value in ("pallas", "xla"):
        return {"choice": value, "timings_ms": None}
    if isinstance(value, dict) and value.get("choice") in ("pallas",
                                                           "xla"):
        t = value.get("timings_ms")
        return {"choice": value["choice"],
                "timings_ms": dict(t) if isinstance(t, dict) else None}
    return None


def autotune_persistence_path() -> str | None:
    return _autotune_persist_path


def autotune_persist_key(fingerprint: str, cap: int, desc: tuple,
                         k: int, agg: bool) -> str:
    """Canonical persisted-store key shared by the single-chip executor
    and the mesh path: (pack fingerprint, cap, desc, pow2-bucketed k,
    aggs?). k is bucketed to its next power of two so the single-chip
    convention (k_eff = from+size) and the mesh convention (k already
    pow2-padded) land on the SAME key — that is what lets an SPMD mesh
    program (which cannot wall-clock itself without desyncing the
    collective) reuse the choice a single-chip execution of the
    identical pack timed and persisted. Entries persisted under the
    pre-canonical format (repr of the full tune key incl. b_pad) are
    inert: they never match, cost one re-tune per pack, and age out of
    the store's FIFO cap."""
    return repr((fingerprint, cap, desc, next_pow2(max(int(k), 1),
                                                   floor=1), bool(agg)))


def configure_autotune_persistence(path: str | None,
                                   if_owner: str | None = None,
                                   only_if_unset: bool = False) -> bool:
    """Point the autotuner at an on-disk choice store (the node passes
    <data_path>/fused_autotune.json at startup; None disables). The
    store is process-global, so with several in-process nodes the FIRST
    configured store wins (the breaker_service convention):
    only_if_unset claims the store atomically (returns False when
    another store is already configured), and if_owner tears down only
    the store you configured (a closing node must not disable
    persistence for nodes still running)."""
    global _autotune_persist_path, _autotune_persisted
    with _autotune_lock:
        if only_if_unset and _autotune_persist_path is not None:
            return False
        if if_owner is not None and _autotune_persist_path != if_owner:
            return False
        _autotune_persist_path = path
        _autotune_persisted = {}
        if path is None:
            return True
        try:
            # graftlint: ok(lock-discipline): node-startup store load —
            # must be atomic with claiming the store path, never on the
            # query path
            with open(path) as f:
                data = _json.load(f)
            _autotune_persisted = {
                str(k): e for k, v in data.items()
                if (e := _persist_entry(v)) is not None}
            # a store written before the FIFO cap existed (or by a
            # larger-capped build) must not smuggle an unbounded map
            # back in: drop oldest-inserted down to the cap on load
            while len(_autotune_persisted) > _AUTOTUNE_PERSIST_CAP:
                _autotune_persisted.pop(next(iter(_autotune_persisted)))
        except (OSError, ValueError):
            _autotune_persisted = {}
    return True


def _persisted_key_fingerprint(key_str: str) -> str | None:
    """First element (the pack fingerprint / cache key) of a persisted
    autotune store key — keys are repr() of tuples whose head is that
    string. None for unparseable (pre-canonical) keys."""
    import ast
    try:
        key = ast.literal_eval(key_str)
    except (ValueError, SyntaxError):
        return None
    if isinstance(key, tuple) and key and isinstance(key[0], str):
        return key[0]
    return None


def sweep_autotune_store(live_keys) -> int:
    """Prune persisted autotuner entries whose pack no longer exists
    (satellite: without this, every refresh/merge/compaction in a
    node's life leaves its dead fingerprints in fused_autotune.json
    forever — the FIFO cap bounds the count, but dead entries crowd
    out live ones and the file never shrinks). `live_keys` is the set
    of cache keys of every segment currently recovered on this node
    (node startup calls this after recovery); pack-pair keys
    ("fp_a+fp_b", the base+delta dispatch) survive when EVERY half is
    live, and unparseable legacy keys are swept with the dead. Returns
    the number of entries dropped and rewrites the store when any
    were."""
    live = set(live_keys)
    with _autotune_lock:
        if _autotune_persist_path is None or not _autotune_persisted:
            return 0
        dead = []
        for key_str in _autotune_persisted:
            fp = _persisted_key_fingerprint(key_str)
            if fp is None or not all(p in live for p in fp.split("+")):
                dead.append(key_str)
        if not dead:
            return 0
        for key_str in dead:
            _autotune_persisted.pop(key_str, None)
        tmp = _autotune_persist_path + ".tmp"
        try:
            # graftlint: ok(lock-discipline): node-startup sweep, never
            # on the query path — same discipline as the store load
            with open(tmp, "w") as f:
                _json.dump(_autotune_persisted, f)
            _os.replace(tmp, _autotune_persist_path)
        except OSError:
            pass
    return len(dead)


def _autotune_persist_locked(key_str: str, choice: str,
                             timings: dict | None = None) -> None:
    """Write-through one choice plus both backends' best-of-N timings
    (caller holds _autotune_lock). Atomic replace; write failures
    degrade to in-memory-only, never raise."""
    if _autotune_persist_path is None:
        return
    if key_str not in _autotune_persisted:
        while len(_autotune_persisted) >= _AUTOTUNE_PERSIST_CAP:
            _autotune_persisted.pop(next(iter(_autotune_persisted)))
    _autotune_persisted[key_str] = {
        "choice": choice,
        "timings_ms": ({b: round(t * 1e3, 3) for b, t in timings.items()}
                       if timings else None)}
    tmp = _autotune_persist_path + ".tmp"
    try:
        _os.makedirs(_os.path.dirname(_autotune_persist_path) or ".",
                     exist_ok=True)
        with open(tmp, "w") as f:
            _json.dump(_autotune_persisted, f)
        _os.replace(tmp, _autotune_persist_path)
    except OSError:
        pass


def resolve_fused_backend(key: tuple, ck: int, run_backend=None,
                          pallas_candidate: bool = True,
                          persist_keys: tuple[str, ...] | None = None
                          ) -> str:
    """Per-(pack fingerprint, shape-bucket) backend choice.
    ES_TPU_FUSED_BACKEND forces; a choice persisted under the node data
    path is reused across restarts; otherwise the first execution of a
    key times both backends via `run_backend(name)` (dispatch + block)
    — one compile pass, one steady-state warmup pass, then best-of-N
    (ES_TPU_AUTOTUNE_REPS, default 3) so a first-execution hiccup on
    either side cannot commit the wrong backend for the life of the
    pack — and caches + persists the winner. Callers with no way to
    time (mesh programs) pass run_backend=None and get a persisted
    choice when any of their `persist_keys` (autotune_persist_key — one
    per shard for a mesh pack) has one, else the static choice. Timed
    winners are written under persist_keys[0] (defaults to repr(key))."""
    forced = _os.environ.get("ES_TPU_FUSED_BACKEND", "").lower()
    if forced in ("pallas", "xla"):
        # forced outranks even an already-cached tuned choice, and is
        # never cached itself: flipping the env mid-process switches
        # EVERY path — cold, resident (_resident_backend mirrors this
        # precedence), mesh — onto one engine, and unsetting it
        # restores the tuned choice. Cache-first here would let a
        # pre-flip tuned choice serve one engine cold while the
        # resident path pins the other. keep_existing: this branch
        # runs per dispatch and must not overwrite a tuned entry's
        # timings (that would drop the shape from the loss audit).
        _fused_stats.record_choice(key, forced, "forced", None,
                                   keep_existing=True)
        return forced
    cached = _autotune_choices.get(key)
    if cached is not None:
        return cached
    with _autotune_lock:
        cached = _autotune_choices.get(key)
        if cached is not None:
            return cached
        key_str = repr(key)
        if persist_keys is None:
            persist_keys = (key_str,)
        persisted = next((c for pk in persist_keys
                          if (c := _autotune_persisted.get(pk))
                          is not None), None)
        if not pallas_candidate or not fused_pallas_ok(ck):
            choice, reason, timings = "xla", "pallas-unavailable", None
        elif persisted is not None:
            # reloaded timings (when the store has them) re-enter the
            # stats mirror so the loss audit survives a restart
            choice, reason = persisted["choice"], "persisted"
            timings = ({b: t / 1e3 for b, t
                        in persisted["timings_ms"].items()}
                       if persisted["timings_ms"] else None)
        elif run_backend is None:
            choice, reason, timings = "pallas", "static", None
        else:
            reps = max(1, int(_os.environ.get("ES_TPU_AUTOTUNE_REPS",
                                              "3")))
            timings = {}
            for b in ("xla", "pallas"):
                run_backend(b)                   # compile
                run_backend(b)                   # steady-state warmup:
                # the first post-compile execution still pays one-time
                # costs (transfer-cache fills, lazy device init) that
                # skewed an earlier round's http_logs choice toward pallas
                best = None
                for _ in range(reps):
                    t0 = _time.perf_counter()
                    run_backend(b)
                    dt = _time.perf_counter() - t0
                    best = dt if best is None else min(best, dt)
                timings[b] = best
            choice = min(timings, key=timings.get)
            reason = "timed"
            # graftlint: ok(lock-discipline): write-through must commit
            # under the same hold as the in-memory choice (a racing
            # tuner could persist the loser); first-execution-only per
            # (pack, shape) — never the steady-state query path
            _autotune_persist_locked(persist_keys[0], choice, timings)
        _bounded_put(_autotune_choices, key, choice)
    _fused_stats.record_choice(key, choice, reason, timings)
    return choice


def _vec_clause_inputs(seg: dict, bundle: tuple, cl_inputs: tuple,
                       n_tiles: int) -> tuple:
    """Substitute every knn clause's raw (qv, boost, similarity) input
    with the (col, exists, ub) triple the bundle ops consume (runs
    traced, inside the ONE fused program):

      col — the whole-capacity transformed-similarity column, boost
            folded in: the same `knn_score_column(...) * boost` ops, in
            the same order, as eval_node's knn_vec leaf, so fused and
            unfused hybrid scores are bit-identical;
      ub  — per-tile max of col (+ one BOUND_SLACK, mirroring the
            dense clauses' per-clause inflation): an EXACT query-time
            tile bound — the tile walk prunes vector tiles against the
            very numbers it would have scored."""
    out = []
    for (role, kind, field, _w), inp in zip(bundle, cl_inputs):
        if kind not in _FUSED_VEC_KINDS:
            out.append(inp)
            continue
        qv, boost_c, sim = inp
        v = seg["vec"][field]
        col = knn_score_column(v["values"], v["norms"], v["exists"], qv,
                               similarity=sim) * boost_c[:, None]
        b, cap = col.shape
        tile = cap // n_tiles
        ub = col.reshape(b, n_tiles, tile).max(axis=2)
        # sign-guarded slack (the ops/ann._slacked rule): dot_product
        # on non-unit vectors can transform NEGATIVE — multiplying a
        # negative max up would LOWER the bound below the true best
        # score and wrongly prune the tile
        ub = jnp.where(ub >= 0.0, ub * jnp.float32(BOUND_SLACK),
                       ub / jnp.float32(BOUND_SLACK))
        out.append((col, v["exists"], ub))
    return tuple(out)


def eval_fused_topk(seg: dict, desc: tuple, params: tuple,
                    live: jax.Array, k: int, bundle: tuple, backend: str,
                    emit_match: bool = False, step=None,
                    init_topk=None, idx_offset: int = 0):
    """Shared fused score+top-k entry (single-chip program AND the mesh
    shard_map program route through here). Returns (top_s [B,k],
    top_i [B,k], total [B], prune_stats [3] f32) plus the exact match
    mask [B, cap] when emit_match (the fused+aggs mode), plus the
    device-side timed_out scalar when a stepped `step` (see
    ops/scoring._stepped_tile_loop) is given. Both engines take the
    SAME calling convention and share bundle_tile_bounds, so they prune
    identically and responses stay byte-identical whichever the
    autotuner picked — including through a stepped chunk boundary."""
    cl_inputs, msm, boost = _bundle_inputs(desc, params, bundle)
    if boost is None:
        boost = jnp.ones_like(msm, dtype=jnp.float32)
    text_cols = {f: seg["text"][f] for f in bundle_text_fields(bundle)}
    num_cols = {f: seg["num"][f] for _r, kd, f, _w in bundle
                if kd in _FUSED_RANGE_KINDS}
    if any(kd in _FUSED_VEC_KINDS for _r, kd, _f, _w in bundle):
        n_tiles = text_cols[bundle_primary_field(bundle)][
            "tile_max"].n_tiles
        cl_inputs = _vec_clause_inputs(seg, bundle, cl_inputs, n_tiles)
    if _fused_engine(bundle, backend) == "pallas":
        out = fused_topk_bundle_pallas(
            text_cols, num_cols, bundle, cl_inputs, msm, boost, live, k,
            emit_match=emit_match, step=step, interpret=interpret_mode(),
            init_topk=init_topk, idx_offset=idx_offset)
    else:
        out = score_topk_bundle_fused(
            text_cols, num_cols, bundle, cl_inputs, msm, boost, live, k,
            emit_match=emit_match, step=step, init_topk=init_topk,
            idx_offset=idx_offset)
    tail = () if step is None else (out[-1],)
    if step is not None:
        out = out[:-1]
    if emit_match:
        top_s, top_i, total, pruned, match = out
        return (top_s, top_i, total, pruned.astype(jnp.float32),
                match) + tail
    top_s, top_i, total, pruned = out
    return (top_s, top_i, total, pruned.astype(jnp.float32)) + tail


def eval_fused_match(seg: dict, desc: tuple, params: tuple,
                     live: jax.Array, bundle: tuple, backend: str = "xla",
                     emit_match: bool = True, step=None):
    """Fused match-mask-only entry for k == 0 plans (size-0 counts /
    filtered aggs): the tile loop computes the exact match mask and
    total with block-max can_match hard-skips, never touching scores or
    top-k — on the XLA engine or the mask-only Pallas grid, per the
    autotuned choice. Returns (total [B], prune_stats [3] f32) plus the
    match mask [B, cap] when emit_match (an aggregation pass follows),
    plus the timed_out scalar when a stepped `step` is given."""
    cl_inputs, msm, boost = _bundle_inputs(desc, params, bundle)
    text_cols = {f: seg["text"][f] for f in bundle_text_fields(bundle)}
    num_cols = {f: seg["num"][f] for _r, kd, f, _w in bundle
                if kd in _FUSED_RANGE_KINDS}
    if any(kd in _FUSED_VEC_KINDS for _r, kd, _f, _w in bundle):
        n_tiles = text_cols[bundle_primary_field(bundle)][
            "tile_max"].n_tiles
        cl_inputs = _vec_clause_inputs(seg, bundle, cl_inputs, n_tiles)
    if _fused_engine(bundle, backend) == "pallas":
        out = match_mask_bundle_pallas(
            text_cols, num_cols, bundle, cl_inputs, msm, boost, live,
            emit_match=emit_match, step=step, interpret=interpret_mode())
    else:
        out = match_mask_bundle_fused(
            text_cols, num_cols, bundle, cl_inputs, msm, boost, live,
            emit_match=emit_match, step=step)
    tail = () if step is None else (out[-1],)
    if step is not None:
        out = out[:-1]
    if emit_match:
        total, pruned, match = out
        return (total, pruned.astype(jnp.float32), match) + tail
    total, pruned = out
    return (total, pruned.astype(jnp.float32)) + tail


# ---------------------------------------------------------------------------
# The jitted per-segment program: query eval + top-k + aggregations
# ---------------------------------------------------------------------------

# per-chunk transient budget in elements: a batch whose [B, cap] dense
# accumulators would exceed this executes as sequential lax.map chunks
# inside ONE program — one device dispatch (each one pays a flat round
# trip), bounded HBM transients
_CHUNK_ELEMS = 1 << 27


def _chunk_b(B: int, cap: int) -> int:
    bc = B
    while bc > 1 and bc * cap > _CHUNK_ELEMS:
        bc //= 2
    return bc


def _segment_body(seg: dict, params: tuple, live: jax.Array,
                  live_views: dict, agg_params: tuple, sort_params: tuple,
                  *, desc: tuple, agg_desc: tuple, cap: int, k: int,
                  sort_spec: tuple, fused: tuple | None = None,
                  step=None):
    B = _batch_size(params)
    if fused is not None:
        # fused transient per row — NOT the dense [*, cap]
        f0 = bundle_primary_field(fused[0])
        n_tiles = seg["text"][f0]["tile_max"].n_tiles
        row_elems = _fused_row_elems(
            cap, n_tiles, k, emit_match=bool(agg_desc),
            vec_clauses=sum(kd in _FUSED_VEC_KINDS
                            for _r, kd, _f, _w in fused[0]),
            pos_width=_bundle_pos_width(fused[0], seg["text"]),
            chunk_tiles=None if step is None else step[0])
    else:
        row_elems = cap
    # a resident stepped body never B-chunks: the step state (deadline
    # verdict + remaining injected-delay budget) is carried through ONE
    # tile loop — lax.map chunks would each re-meter the full budget
    bc = B if step is not None else _chunk_b(B, row_elems)
    if bc >= B:
        return _segment_body_one(
            seg, params, live, live_views, agg_params, sort_params,
            desc=desc, agg_desc=agg_desc, cap=cap, k=k,
            sort_spec=sort_spec, fused=fused, step=step)
    nc = B // bc
    chunked = jax.tree_util.tree_map(
        lambda a: a.reshape((nc, bc) + a.shape[1:]), params)
    out = jax.lax.map(
        lambda p: _segment_body_one(
            seg, p, live, live_views, agg_params, sort_params,
            desc=desc, agg_desc=agg_desc, cap=cap, k=k,
            sort_spec=sort_spec, fused=fused),
        chunked)
    return jax.tree_util.tree_map(
        lambda a: a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:]), out)


def _segment_body_one(seg: dict, params: tuple, live: jax.Array,
                      live_views: dict, agg_params: tuple,
                      sort_params: tuple, *, desc: tuple, agg_desc: tuple,
                      cap: int, k: int, sort_spec: tuple,
                      fused: tuple | None = None, step=None):
    B = _batch_size(params)
    if fused is not None:
        # fused block-max score + top-k: never materializes the [B, cap]
        # SCORE matrix. Plan admission (score sort, k>0, boost>0, tile
        # summaries present) happened host-side in execute_segment_async.
        # Plans that also carry aggregations run the XLA engine in
        # emit-match mode: the tile loop writes the exact bool match
        # mask (hard-pruned tiles keep their zeros) and the ordinary
        # aggregation pass consumes it. A resident `step` threads the
        # per-chunk deadline check through the tile loop and appends
        # the device-side timed_out verdict to the return.
        bundle, backend = fused
        step_tail = (jnp.bool_(False),) if step is not None else ()
        if k == 0:
            # match-mask-only engine: size-0 counts / filtered aggs skip
            # the score matrix AND top-k selection (the k_zero gap)
            if agg_desc:
                out = eval_fused_match(
                    seg, desc, params, live, bundle, backend,
                    emit_match=True, step=step)
                if step is not None:
                    total, pruned, match, timed = out
                    step_tail = (timed,)
                else:
                    total, pruned, match = out
                plan = _agg_view_plan(desc, agg_desc, agg_params, seg,
                                      live_views)
                views = _ViewMasks(desc, params, seg, live_views, cap, B)
                agg_out = eval_aggs(agg_desc, agg_params, seg, match,
                                    views=views, plan=plan)
            else:
                out = eval_fused_match(
                    seg, desc, params, live, bundle, backend,
                    emit_match=False, step=step)
                if step is not None:
                    total, pruned, timed = out
                    step_tail = (timed,)
                else:
                    total, pruned = out
                agg_out = {}
            empty_f = jnp.zeros((B, 0), jnp.float32)
            return ((empty_f, empty_f, jnp.zeros((B, 0), jnp.int32),
                     total, jnp.zeros((B, 0), bool)), agg_out,
                    jnp.broadcast_to(pruned[None, :] / B, (B, 3))
                    ) + step_tail
        if agg_desc:
            out = eval_fused_topk(
                seg, desc, params, live, k, bundle, backend,
                emit_match=True, step=step)
            if step is not None:
                top_score, top_idx, total, pruned, match, timed = out
                step_tail = (timed,)
            else:
                top_score, top_idx, total, pruned, match = out
            plan = _agg_view_plan(desc, agg_desc, agg_params, seg,
                                  live_views)
            views = _ViewMasks(desc, params, seg, live_views, cap, B)
            agg_out = eval_aggs(agg_desc, agg_params, seg, match,
                                views=views, plan=plan)
        else:
            out = eval_fused_topk(
                seg, desc, params, live, k, bundle, backend, step=step)
            if step is not None:
                top_score, top_idx, total, pruned, timed = out
                step_tail = (timed,)
            else:
                top_score, top_idx, total, pruned = out
            agg_out = {}
        # each row carries its chunk's prune stats / chunk size, so a
        # row-sum at collect time reconstructs (approximately, when the
        # real batch undershoots the padded one) the dispatch totals
        prune_rows = jnp.broadcast_to(pruned[None, :] / B, (B, 3))
        top_missing = jnp.zeros_like(top_idx, dtype=bool)
        return ((top_score, top_score, top_idx, total, top_missing),
                agg_out, prune_rows) + step_tail
    plan = _agg_view_plan(desc, agg_desc, agg_params, seg, live_views)
    views = _ViewMasks(desc, params, seg, live_views, cap, B)
    # aggs-only requests whose every agg node rides a sorted view skip
    # the doc-space query eval entirely (total comes from a view mask)
    skip_doc = bool(k == 0 and sort_spec == ("_score",) and agg_desc
                    and plan and all(plan))
    if skip_doc:
        valid = None
        node0 = agg_desc[0][1]
        key0 = (("kw", node0[1]) if node0[0] == "terms_kw"
                else ("num", node0[1]))
        total = views.mask(key0).sum(axis=-1, dtype=jnp.int32)
    else:
        score, match = eval_node(desc, params, seg, cap, B)
        valid = match & live[None, :]
        score = jnp.where(valid, score, 0.0)

    if k == 0:
        top_score = jnp.zeros((B, 0), jnp.float32)
        top_key = top_score
        top_idx = jnp.zeros((B, 0), jnp.int32)
        top_missing = jnp.zeros((B, 0), bool)
        if not skip_doc:
            total = valid.sum(axis=-1, dtype=jnp.int32)
        agg_out = eval_aggs(agg_desc, agg_params, seg, valid,
                            views=views, plan=plan)
        return (top_score, top_key, top_idx, total, top_missing), \
            agg_out, jnp.zeros((B, 3), jnp.float32)

    if sort_spec[0] == "_score":
        top_key, top_idx, total = top_k_hits(score, valid, k)
        top_score = top_key
        top_missing = jnp.zeros_like(top_idx, dtype=bool)
    else:
        _, field, descending, kindtag = sort_spec
        if kindtag == "kw" and field in seg["kw"]:
            # segment-local ordinals -> shard-global ords so the key is
            # comparable across segments (review: local ords mis-merge)
            (s2g,) = sort_params
            local = seg["kw"][field]
            keys = s2g[jnp.clip(local, 0, None)]
            missing = local < 0
        elif kindtag == "geo":
            # geo_distance sort: key = meters/unit from a dynamic origin
            # (sort_params, no recompile per origin)
            from ..ops.geo import haversine_m
            if field in seg["geo"]:
                lat_q, lon_q, unit_m = sort_params
                g = seg["geo"][field]
                keys = haversine_m(g["lat"], g["lon"], lat_q, lon_q) / unit_m
                missing = ~g["exists"]
            else:
                keys = jnp.zeros((cap,), jnp.float32)
                missing = jnp.ones((cap,), bool)
        elif kindtag == "script":
            from ..script import compile_script, ColumnDocAccessor
            src, ptag = field.split("\x00", 1)
            sparams = {kv.split("=", 1)[0]: float(kv.split("=", 1)[1])
                       for kv in ptag.split(",") if kv}
            cs = compile_script(src)
            val = cs.run(doc=ColumnDocAccessor(seg, jnp), params=sparams,
                         xp=jnp)
            keys = jnp.broadcast_to(jnp.asarray(val, jnp.float32), (cap,))
            missing = jnp.zeros((cap,), bool)
        elif kindtag == "num" and field in seg["num"]:
            keys = seg["num"][field]["values"]
            missing = ~seg["num"][field]["exists"]
        else:  # field absent from this whole segment
            keys = jnp.zeros((cap,), jnp.int32)
            missing = jnp.ones((cap,), bool)
        top_key, top_idx, total, top_missing = top_k_by_field(
            keys, valid, missing, k, descending)
        top_score = jnp.take_along_axis(score, top_idx, axis=1)

    agg_out = eval_aggs(agg_desc, agg_params, seg, valid,
                        views=views, plan=plan)
    return (top_score, top_key, top_idx, total, top_missing), \
        agg_out, jnp.zeros((B, 3), jnp.float32)


def _batch_size(params) -> int:
    leaves = jax.tree_util.tree_leaves(params)
    if not leaves:
        return 1
    return leaves[0].shape[0]


# ---------------------------------------------------------------------------
# Aggregations: desc interpreter (device part)
# ---------------------------------------------------------------------------
# agg desc nodes (see search/aggregations.py for parse/reduce):
#   ("terms_kw", field, n_global, sub_metrics)     params: (seg2global, g2seg)
#   ("hist_fixed", field, n_buckets, sub_metrics)  params: (origin, interval)
#   ("hist_edges", field, n_buckets, sub_metrics)  params: (edges,)
#   ("stats", field)                               params: ()
#   ("value_count_kw"|"value_count_num"|..., field) params: ()
#   ("global",) / ("filter", child_desc)           -- round 2
# sub_metrics: tuple of ("avg"|"sum"|"min"|"max"|"stats"|"value_count", field)


def _merge_metric_dicts(acc: dict, st: dict) -> dict:
    """Merge per-value-slot metric partials: min/max fold, others sum."""
    for k, v in st.items():
        if k == "min":
            acc[k] = jnp.minimum(acc[k], v)
        elif k == "max":
            acc[k] = jnp.maximum(acc[k], v)
        else:
            acc[k] = acc[k] + v
    return acc


def _empty_bucket_metric(mkind: str, B: int, n_buckets: int) -> dict:
    entry = {}
    zero = jnp.zeros((B, n_buckets), jnp.float32)
    if mkind in ("avg", "sum", "stats", "extended_stats"):
        entry["sum"] = zero
    if mkind in ("avg", "stats", "extended_stats", "value_count"):
        entry["count"] = zero
    if mkind in ("min", "stats", "extended_stats"):
        entry["min"] = jnp.full((B, n_buckets), jnp.inf, jnp.float32)
    if mkind in ("max", "stats", "extended_stats"):
        entry["max"] = jnp.full((B, n_buckets), -jnp.inf, jnp.float32)
    if mkind == "extended_stats":
        entry["sum_sq"] = zero
    return entry


def _hist_edges_for(kind, params, n_buckets, dtype):
    if kind == "hist_fixed":
        origin, interval = params
        if dtype == jnp.int32:
            # int32 columns (epoch seconds) need EXACT edges — f32 would
            # smear boundaries past 2^24. The pow2-padded tail may
            # overflow int32; clamp it to INT32_MAX (monotonicity is all
            # searchsorted needs past the data max).
            rng = jnp.arange(n_buckets + 1, dtype=jnp.int32)
            o = origin.astype(jnp.int32)
            off = interval.astype(jnp.int32) * rng
            s = o + off
            # the pow2-padded tail may overflow int32 in `off` OR in
            # `o + off`; clamp every edge whose true value could exceed
            # INT32_MAX (f32 magnitude guard catches double-wraps the
            # sign tests can't see). Monotonicity is all searchsorted
            # needs past the data max.
            lim = jnp.int32(2**31 - 1)
            approx = o.astype(jnp.float32) \
                + interval.astype(jnp.float32) * rng.astype(jnp.float32)
            bad = (off < 0) | (s < o) \
                | (approx >= jnp.float32(2**31 - 256))
            return jnp.where(bad, lim, s)
        rng = jnp.arange(n_buckets + 1, dtype=jnp.float32)
        edges = origin.astype(jnp.float32) \
            + interval.astype(jnp.float32) * rng
    else:
        (edges,) = params
    return edges.astype(dtype)


def _sorted_hist_counts(srtn, exists, valid, edges,
                        weights=None) -> jax.Array:
    """Shared sorted-histogram reduce: exists-masked (optionally value-
    weighted) counts per edge bucket — the single calling convention the
    histogram, percentile, and sub-metric paths all go through."""
    w = jnp.where(exists[None, :], valid.astype(jnp.float32), 0.0)
    if weights is not None:
        w = w * weights
    return agg_ops.sorted_hist_reduce(srtn["vals"].astype(edges.dtype)
                                      if srtn["vals"].dtype != edges.dtype
                                      else srtn["vals"],
                                      srtn["perm"], w, edges)


def _hist_sorted(seg, col, srtn, valid, subs, kind, params, n_buckets):
    """Scatter-free histogram: docs are value-sorted (static perm), so
    bucket sums are cumsum differences at searchsorted edge positions
    (ops/aggs.sorted_hist_reduce)."""
    perm, sorted_vals = srtn["perm"], srtn["vals"]
    edges = _hist_edges_for(kind, params, n_buckets, sorted_vals.dtype)
    exists = col["exists"]
    w = jnp.where(exists[None, :], valid.astype(jnp.float32), 0.0)
    entry = {"counts": _sorted_hist_counts(srtn, exists, valid, edges)}
    for mname, mfield, mkind in subs:
        mcol = seg["num"].get(mfield)
        B = valid.shape[0]
        if mcol is None:
            entry[mname] = _empty_bucket_metric(mkind, B, n_buckets)
            continue
        if "mv_values" in mcol or mkind not in ("avg", "sum",
                                                "value_count"):
            # multi-valued sources and min/max-bearing metrics keep the
            # per-doc scatter path
            if kind == "hist_fixed":
                origin, interval = params
                bids = agg_ops.fixed_histogram_bucket_ids(
                    col["values"], exists, origin, interval, n_buckets)
            else:
                bids = agg_ops.edges_bucket_ids(col["values"], exists,
                                                params[0], n_buckets)
            entry[mname] = _bucket_metrics(
                bids, valid, [(mname, mfield, mkind)], seg,
                n_buckets)[mname]
            continue
        mvals, mex = mcol["values"], mcol["exists"]
        wm = jnp.where(mex[None, :], w, 0.0)
        st: dict = {}
        if mkind == "sum":
            st["sum"] = agg_ops.sorted_hist_reduce(
                sorted_vals, perm,
                wm * mvals.astype(jnp.float32)[None, :], edges)
        if mkind == "avg":
            st["sum"] = agg_ops.sorted_hist_reduce(
                sorted_vals, perm,
                wm * mvals.astype(jnp.float32)[None, :], edges)
            st["count"] = agg_ops.sorted_hist_reduce(sorted_vals, perm,
                                                     wm, edges)
        if mkind == "value_count":
            st["count"] = agg_ops.sorted_hist_reduce(sorted_vals, perm,
                                                     wm, edges)
        entry[mname] = st
    return entry


def _to_global(seg_arr, g2seg):
    """Per-segment-group array [B, G] -> shard-global bucket space via
    the INVERSE ordinal map (a gather — global ords map injectively from
    segment ords, so no scatter is ever needed; TPU scatter costs ~65ms
    regardless of size while this gather is microseconds)."""
    safe = jnp.clip(g2seg, 0, None)
    out = jnp.take(seg_arr, safe, axis=-1)
    return jnp.where((g2seg >= 0)[None, :], out, 0.0)


def _terms_sorted(seg, field, srt, valid, subs, seg2global, g2seg,
                  n_global):
    """Scatter-free terms aggregation over the static ordinal-sort
    layout (ops/aggs.sorted_group_reduce): per-doc scatters become
    permute+cumsum+boundary-gather, and the local->global remap rides
    the inverse ordinal map (another gather)."""
    perm, starts = srt["perm"], srt["starts"]
    w = valid.astype(jnp.float32)
    entry = {"counts": _to_global(
        agg_ops.sorted_group_reduce(perm, starts, w), g2seg)}
    for mname, mfield, mkind in subs:
        col = seg["num"].get(mfield)
        B = valid.shape[0]
        if col is None:
            entry[mname] = _empty_bucket_metric(mkind, B, n_global)
            continue
        if "mv_values" in col or mkind not in ("avg", "sum",
                                               "value_count"):
            # multi-valued sources and min/max-bearing metrics keep the
            # per-doc scatter; the layout's presence on a segment does
            # not restrict which descs may run against it
            bids = agg_ops.keyword_bucket_ids(seg["kw"][field],
                                              seg2global, n_global)
            entry[mname] = _bucket_metrics(
                bids, valid, [(mname, mfield, mkind)], seg,
                n_global)[mname]
            continue
        vals, exists = col["values"], col["exists"]
        wm = jnp.where(exists[None, :], w, 0.0)
        st: dict = {}
        if mkind in ("avg", "sum"):
            st["sum"] = _to_global(
                agg_ops.sorted_group_reduce(
                    perm, starts, wm * vals.astype(jnp.float32)[None, :]),
                g2seg)
        if mkind in ("avg", "value_count"):
            st["count"] = _to_global(
                agg_ops.sorted_group_reduce(perm, starts, wm), g2seg)
        entry[mname] = st
    return entry


def _view_bucket_entry(store: dict, vm: jax.Array, subs, bounds,
                       n_out: int, post=None) -> dict:
    """Shared view-space bucket reduce: counts + avg/sum/value_count
    sub-metrics as block reduces of sorted-space weights at `bounds`.
    Repeated (weight, field) reduces are memoized (avg shares sum's
    reduce and value_count's count); counts accumulate in int32.
    `post` maps each per-layout array to the output bucket space
    (terms: segment-ordinal -> shard-global gather)."""
    if post is None:
        post = lambda a: a  # noqa: E731
    B = vm.shape[0]
    memo: dict = {}

    def counts_of(mask, key):
        if key not in memo:
            memo[key] = agg_ops.view_group_reduce(
                mask, bounds, int_weights=True).astype(jnp.float32)
        return memo[key]

    entry = {"counts": post(counts_of(vm, ("count", None)))}
    for mname, mfield, mkind in subs:
        pcol = store.get("vw_num", {}).get(mfield)
        if pcol is None:
            entry[mname] = _empty_bucket_metric(mkind, B, n_out)
            continue
        st: dict = {}
        if mkind in ("avg", "sum"):
            key = ("sum", mfield)
            if key not in memo:
                wv = jnp.where(vm & pcol["exists"][None, :],
                               pcol["values"].astype(jnp.float32)[None, :],
                               0.0)
                memo[key] = agg_ops.view_group_reduce(wv, bounds)
            st["sum"] = post(memo[key])
        if mkind in ("avg", "value_count"):
            st["count"] = post(counts_of(vm & pcol["exists"][None, :],
                                         ("count", mfield)))
        entry[mname] = st
    return entry


def _terms_view(store: dict, vm: jax.Array, subs, g2seg, n_global: int
                ) -> dict:
    """Terms aggregation fully in sorted view space: group sums are
    block reduces of the sorted-space valid mask at the static group
    boundaries — no per-query gather, int32-exact counts."""
    return _view_bucket_entry(store, vm, subs, store["starts"], n_global,
                              post=lambda a: _to_global(a, g2seg))


def _hist_view(store: dict, vm: jax.Array, subs, kind, params,
               n_buckets: int) -> dict:
    """(date_)histogram in sorted view space: bucket boundaries come
    from a log-depth searchsorted of the static sorted values; sums are
    block reduces of sorted-space weights."""
    sv = store["vals"]
    edges = _hist_edges_for(kind, params, n_buckets, sv.dtype)
    pos = jnp.searchsorted(sv, edges, side="left").astype(jnp.int32)
    return _view_bucket_entry(store, vm & store["sexists"][None, :],
                              subs, pos, n_buckets)


def _pctl_view(store: dict, vm: jax.Array, lo, width, n_bins: int) -> dict:
    inner = lo.astype(jnp.float32) + width.astype(jnp.float32) \
        * jnp.arange(1, n_bins, dtype=jnp.float32)
    edges = jnp.concatenate([
        jnp.asarray([-jnp.inf], jnp.float32), inner,
        jnp.asarray([jnp.inf], jnp.float32)])
    pos = jnp.searchsorted(store["vals"].astype(jnp.float32), edges,
                           side="left").astype(jnp.int32)
    w = vm & store["sexists"][None, :]
    return {"counts": agg_ops.view_group_reduce(
        w, pos, int_weights=True).astype(jnp.float32)}


def _compress_topk(entry: dict, top_s: int) -> dict:
    """Shrink a terms partial to its per-segment top buckets by count
    (device-side shard_size, ref: InternalTerms shard-level truncation):
    the wire ships 2*top_s+1 floats per query instead of n_global —
    the download otherwise dominates the agg. Indices ride as f32
    (exact below 2^24)."""
    counts = entry["counts"]
    tv, ti = jax.lax.top_k(counts, top_s)
    out = {"top_counts": tv, "top_idx": ti.astype(jnp.float32),
           "total": counts.sum(axis=-1, keepdims=True)}
    for mname, st in entry.items():
        if mname == "counts" or not isinstance(st, dict):
            continue
        for key, arr in st.items():
            out[f"sub\x00{mname}\x00{key}"] = jnp.take_along_axis(
                arr, ti, axis=-1)
    return out


def _bucket_metrics(bucket_ids, mask, sub_metrics, seg, n_buckets):
    B = mask.shape[0]
    out = {}
    for mname, mfield, mkind in sub_metrics:
        col = seg["num"].get(mfield)
        if col is None:
            out[mname] = _empty_bucket_metric(mkind, B, n_buckets)
            continue
        # multi-valued metric source: every value of the doc lands in the
        # bucket (SortedNumeric values iteration)
        val_cols = ([(col["mv_values"][:, m], col["mv_exists"][:, m])
                     for m in range(col["mv_values"].shape[1])]
                    if "mv_values" in col
                    else [(col["values"], col["exists"])])
        entry = _empty_bucket_metric(mkind, B, n_buckets)
        for vals, exists in val_cols:
            m = mask & exists[None, :]
            if mkind in ("avg", "sum", "stats", "extended_stats"):
                entry["sum"] = entry["sum"] + agg_ops.bucket_sums(
                    bucket_ids, m, vals, n_buckets)
            if mkind in ("avg", "stats", "extended_stats", "value_count"):
                entry["count"] = entry["count"] + agg_ops.bucket_counts(
                    bucket_ids, m, n_buckets)
            if mkind in ("min", "stats", "extended_stats"):
                entry["min"] = jnp.minimum(entry["min"], agg_ops.bucket_min(
                    bucket_ids, m, vals, n_buckets))
            if mkind in ("max", "stats", "extended_stats"):
                entry["max"] = jnp.maximum(entry["max"], agg_ops.bucket_max(
                    bucket_ids, m, vals, n_buckets))
            if mkind == "extended_stats":
                entry["sum_sq"] = entry["sum_sq"] + agg_ops.bucket_sum_sq(
                    bucket_ids, m, vals, n_buckets)
        out[mname] = entry
    return out


def _empty_buckets(subs, B: int, n_buckets: int) -> dict:
    entry = {"counts": jnp.zeros((B, n_buckets), jnp.float32)}
    for mname, _f, mkind in subs:
        entry[mname] = _empty_bucket_metric(mkind, B, n_buckets)
    return entry


def eval_aggs(agg_desc: tuple, agg_params: tuple, seg: dict,
              valid: jax.Array | None, views: "_ViewMasks | None" = None,
              plan: tuple = ()) -> dict:
    """Per-segment device aggregation. A segment lacking the aggregated
    column (field introduced later / sparse mapping) contributes zero
    partials instead of crashing. `plan[i]` (static) routes node i
    through its sorted-view path; `valid` may be None when every node
    does (the doc-space mask was never materialized)."""
    out: dict[str, Any] = {}
    B = views.B if views is not None else valid.shape[0]
    for ni, ((name, node), params) in enumerate(zip(agg_desc, agg_params)):
        kind = node[0]
        use_view = bool(plan) and plan[ni]
        if kind == "terms_kw":
            _, field, n_global, subs, top_s = node
            if use_view:
                seg2global, g2seg = params
                vm = views.mask(("kw", field))
                entry = _terms_view(seg["kw_sorted"][field], vm, subs,
                                    g2seg, n_global)
                out[name] = _compress_topk(entry, top_s) if top_s \
                    else entry
                continue
            if field not in seg["kw"]:
                # every branch must agree on compressed-vs-full: the
                # shard merge reads whichever form the FIRST segment
                # produced for all of them
                entry = _empty_buckets(subs, B, n_global)
                out[name] = _compress_topk(entry, top_s) if top_s \
                    else entry
                continue
            seg2global, g2seg = params
            if field in seg.get("kw_mv", {}):
                # multi-valued: one collect per ordinal SLOT (ref:
                # GlobalOrdinalsStringTermsAggregator over SortedSet —
                # each distinct ord of a doc lands in its bucket once)
                mv = seg["kw_mv"][field]
                entry = _empty_buckets(subs, B, n_global)
                counts = entry["counts"]
                for m in range(mv.shape[1]):
                    bids = agg_ops.keyword_bucket_ids(mv[:, m], seg2global,
                                                      n_global)
                    counts = counts + agg_ops.bucket_counts(bids, valid,
                                                            n_global)
                    sub = _bucket_metrics(bids, valid, subs, seg, n_global)
                    for mname, st in sub.items():
                        _merge_metric_dicts(entry[mname], st)
                entry["counts"] = counts
                out[name] = _compress_topk(entry, top_s) if top_s \
                    else entry
                continue
            srt = seg.get("kw_sorted", {}).get(field)
            if srt is not None and srt["starts"].shape[0] - 1 \
                    == seg2global.shape[0]:
                entry = _terms_sorted(seg, field, srt, valid, subs,
                                      seg2global, g2seg, n_global)
            else:
                bids = agg_ops.keyword_bucket_ids(seg["kw"][field],
                                                  seg2global, n_global)
                entry = {"counts": agg_ops.bucket_counts(bids, valid,
                                                         n_global)}
                entry.update(_bucket_metrics(bids, valid, subs, seg,
                                             n_global))
            if top_s:
                entry = _compress_topk(entry, top_s)
            out[name] = entry
        elif kind in ("hist_fixed", "hist_edges"):
            _, field, n_buckets, subs = node
            if use_view:
                vm = views.mask(("num", field))
                out[name] = _hist_view(seg["num_sorted"][field], vm, subs,
                                       kind, params, n_buckets)
                continue
            if field not in seg["num"]:
                out[name] = _empty_buckets(subs, B, n_buckets)
                continue
            col = seg["num"][field]
            srtn = seg.get("num_sorted", {}).get(field)
            if srtn is not None and "mv_values" not in col:
                out[name] = _hist_sorted(seg, col, srtn, valid, subs,
                                         kind, params, n_buckets)
                continue
            val_cols = ([(col["mv_values"][:, m], col["mv_exists"][:, m])
                         for m in range(col["mv_values"].shape[1])]
                        if "mv_values" in col
                        else [(col["values"], col["exists"])])
            entry = _empty_buckets(subs, B, n_buckets)
            counts = entry["counts"]
            prev_bids: list = []
            for vcol, ecol in val_cols:
                if kind == "hist_fixed":
                    origin, interval = params
                    bids = agg_ops.fixed_histogram_bucket_ids(
                        vcol, ecol, origin, interval, n_buckets)
                else:
                    (edges,) = params
                    bids = agg_ops.edges_bucket_ids(vcol, ecol, edges,
                                                    n_buckets)
                # a doc lands in each DISTINCT bucket once (ref:
                # HistogramAggregator previousKey dedup for multi-values)
                v_ok = valid
                for pb in prev_bids:
                    v_ok = v_ok & (bids != pb)[None, :]
                prev_bids.append(bids)
                counts = counts + agg_ops.bucket_counts(bids, v_ok,
                                                        n_buckets)
                sub = _bucket_metrics(bids, v_ok, subs, seg, n_buckets)
                for mname, st in sub.items():
                    _merge_metric_dicts(entry[mname], st)
            entry["counts"] = counts
            out[name] = entry
        elif kind == "stats_script":
            # metric over a device-evaluated expression (script metric
            # aggs + the restricted scripted_metric; params are baked
            # into the tag as static constants)
            _, tag = node
            vals = _eval_agg_script(tag, seg, valid.shape[-1],
                                    valid.shape[0])
            m = valid
            cnt = m.sum(axis=-1, dtype=jnp.float32)
            out[name] = {
                "count": cnt,
                "sum": jnp.where(m, vals, 0.0).sum(axis=-1),
                "sum_sq": jnp.where(m, vals * vals, 0.0).sum(axis=-1),
                "min": jnp.where(m, vals, jnp.inf).min(axis=-1),
                "max": jnp.where(m, vals, -jnp.inf).max(axis=-1),
            }
        elif kind == "stats":
            _, field = node
            col = seg["num"].get(field)
            if col is not None and "mv_values" in col:
                # every value participates (SortedNumeric stats)
                mv, me = col["mv_values"], col["mv_exists"]
                acc = None
                for m in range(mv.shape[1]):
                    st = agg_ops.masked_stats(mv[:, m], me[:, m], valid)
                    if acc is None:
                        acc = dict(st)
                    else:
                        _merge_metric_dicts(acc, st)
                out[name] = acc
                continue
            if col is None:
                out[name] = {"count": jnp.zeros((B,), jnp.float32),
                             "sum": jnp.zeros((B,), jnp.float32),
                             "sum_sq": jnp.zeros((B,), jnp.float32),
                             "min": jnp.full((B,), jnp.inf, jnp.float32),
                             "max": jnp.full((B,), -jnp.inf, jnp.float32)}
                continue
            out[name] = agg_ops.masked_stats(col["values"], col["exists"], valid)
        elif kind == "value_count_num":
            _, field = node
            col = seg["num"].get(field)
            if col is None:
                out[name] = {"count": jnp.zeros((B,), jnp.float32)}
                continue
            if "mv_values" in col:
                m = valid[:, :, None] & col["mv_exists"][None]
                out[name] = {"count": m.sum(axis=(-1, -2),
                                            dtype=jnp.float32)}
            else:
                m = valid & col["exists"][None, :]
                out[name] = {"count": m.sum(axis=-1, dtype=jnp.float32)}
        elif kind == "value_count_kw":
            _, field = node
            if field not in seg["kw"]:
                out[name] = {"count": jnp.zeros((B,), jnp.float32)}
                continue
            if field in seg.get("kw_mv", {}):
                m = valid[:, :, None] & (seg["kw_mv"][field] >= 0)[None]
                out[name] = {"count": m.sum(axis=(-1, -2),
                                            dtype=jnp.float32)}
            else:
                m = valid & (seg["kw"][field] >= 0)[None, :]
                out[name] = {"count": m.sum(axis=-1, dtype=jnp.float32)}
        elif kind == "pctl":
            # fixed-resolution histogram for percentile interpolation
            # (device-side t-digest analog; host merges weighted bins)
            _, field, n_bins = node
            if use_view:
                lo, width = params
                out[name] = _pctl_view(seg["num_sorted"][field],
                                       views.mask(("num", field)),
                                       lo, width, n_bins)
                continue
            col = seg["num"].get(field)
            if col is None:
                out[name] = {"counts": jnp.zeros((B, n_bins), jnp.float32)}
                continue
            lo, width = params
            if "mv_values" in col:
                counts = jnp.zeros((B, n_bins), jnp.float32)
                mv, me = col["mv_values"], col["mv_exists"]
                for m in range(mv.shape[1]):
                    v = mv[:, m].astype(jnp.float32)
                    bids = jnp.clip((v - lo) / width, 0,
                                    n_bins - 1).astype(jnp.int32)
                    bids = jnp.where(me[:, m], bids, n_bins)
                    counts = counts + agg_ops.bucket_counts(bids, valid,
                                                            n_bins)
                out[name] = {"counts": counts}
                continue
            srtn = seg.get("num_sorted", {}).get(field)
            if srtn is not None:
                # scatter-free: value-sorted cumsum at bin edges; the
                # outer edges are +-inf to reproduce the clip-into-
                # first/last-bin semantics of the bucket-id path
                inner = lo.astype(jnp.float32) \
                    + width.astype(jnp.float32) \
                    * jnp.arange(1, n_bins, dtype=jnp.float32)
                edges = jnp.concatenate([
                    jnp.asarray([-jnp.inf], jnp.float32), inner,
                    jnp.asarray([jnp.inf], jnp.float32)])
                out[name] = {"counts": _sorted_hist_counts(
                    srtn, col["exists"], valid, edges)}
                continue
            v = col["values"].astype(jnp.float32)
            bids = jnp.clip((v - lo) / width, 0, n_bins - 1).astype(jnp.int32)
            bids = jnp.where(col["exists"], bids, n_bins)
            out[name] = {"counts": agg_ops.bucket_counts(bids, valid, n_bins)}
        elif kind == "geo_bounds":
            # masked lat/lon extrema (ref: metrics/geobounds/
            # GeoBoundsAggregator — running min/max per bucket)
            _, field = node
            g = seg.get("geo", {}).get(field)
            if g is None:
                out[name] = {"stats": {
                    "count": jnp.zeros((B,), jnp.float32),
                    "min_lat": jnp.full((B,), jnp.inf, jnp.float32),
                    "max_lat": jnp.full((B,), -jnp.inf, jnp.float32),
                    "min_lon": jnp.full((B,), jnp.inf, jnp.float32),
                    "max_lon": jnp.full((B,), -jnp.inf, jnp.float32)}}
                continue
            m = valid & g["exists"][None, :]
            lat = g["lat"][None, :]
            lon = g["lon"][None, :]
            out[name] = {"stats": {
                "count": m.sum(axis=-1, dtype=jnp.float32),
                "min_lat": jnp.where(m, lat, jnp.inf).min(axis=-1),
                "max_lat": jnp.where(m, lat, -jnp.inf).max(axis=-1),
                "min_lon": jnp.where(m, lon, jnp.inf).min(axis=-1),
                "max_lon": jnp.where(m, lon, -jnp.inf).max(axis=-1)}}
        elif kind == "geo_centroid":
            _, field = node
            g = seg.get("geo", {}).get(field)
            if g is None:
                out[name] = {"stats": {
                    "count": jnp.zeros((B,), jnp.float32),
                    "sum_lat": jnp.zeros((B,), jnp.float32),
                    "sum_lon": jnp.zeros((B,), jnp.float32)}}
                continue
            m = valid & g["exists"][None, :]
            out[name] = {"stats": {
                "count": m.sum(axis=-1, dtype=jnp.float32),
                "sum_lat": jnp.where(m, g["lat"][None, :], 0.0).sum(axis=-1),
                "sum_lon": jnp.where(m, g["lon"][None, :], 0.0).sum(axis=-1)}}
        elif kind == "matchmask":
            # packed per-doc match bitmask -> host (the escape hatch for
            # host-reduced aggs: geohash_grid, scripted_metric). 1 bit
            # per doc = cap/8 bytes per query; little-endian bit order
            # to pair with np.unpackbits(bitorder="little").
            bits = valid.reshape(B, valid.shape[1] // 8, 8).astype(jnp.float32)
            weights = jnp.asarray([1, 2, 4, 8, 16, 32, 64, 128],
                                  jnp.float32)
            out[name] = {"mask": (bits * weights).sum(axis=-1)}
        elif kind == "cardinality_kw":
            _, field, n_global = node
            if field not in seg["kw"]:
                out[name] = {"counts": jnp.zeros((B, n_global), jnp.float32)}
                continue
            (seg2global,) = params
            if field in seg.get("kw_mv", {}):
                mv = seg["kw_mv"][field]
                counts = jnp.zeros((B, n_global), jnp.float32)
                for m in range(mv.shape[1]):
                    bids = agg_ops.keyword_bucket_ids(mv[:, m], seg2global,
                                                      n_global)
                    counts = counts + agg_ops.bucket_counts(bids, valid,
                                                            n_global)
            else:
                bids = agg_ops.keyword_bucket_ids(seg["kw"][field],
                                                  seg2global, n_global)
                counts = agg_ops.bucket_counts(bids, valid, n_global)
            out[name] = {"counts": counts}  # host reduces then counts nonzero
        elif kind == "cardinality_hll":
            # HLL++ sketch: scatter-MAX of per-ordinal ranks into 2^p
            # registers (ref: HyperLogLogPlusPlus.collect); the "max"
            # key makes segment/shard/mesh reduction an elementwise max
            _, field, m = node
            reg_l, rank_l = params
            if field not in seg["kw"] or reg_l.shape[0] == 0:
                out[name] = {"max": jnp.zeros((B, m), jnp.float32)}
                continue

            def hll_update(ords, regs):
                safe = jnp.clip(ords, 0, None)
                r = reg_l[safe]                       # [cap]
                rk = rank_l[safe].astype(jnp.float32)
                ok = valid & (ords >= 0)[None, :]
                vals = jnp.where(ok, rk[None, :], 0.0)

                def one(v):
                    return jnp.zeros((m,), jnp.float32).at[r].max(
                        v, mode="drop")
                return jnp.maximum(regs, jax.vmap(one)(vals))

            regs = jnp.zeros((B, m), jnp.float32)
            if field in seg.get("kw_mv", {}):
                mv = seg["kw_mv"][field]
                for j in range(mv.shape[1]):
                    regs = hll_update(mv[:, j], regs)
            else:
                regs = hll_update(seg["kw"][field], regs)
            out[name] = {"max": regs}
        else:
            raise SearchParseError(f"unknown agg node [{kind}]")
    return out


# ---------------------------------------------------------------------------
# Public per-segment entry
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# Packed wire format for the device call
#
# Every host<->device transfer op costs a round trip of its own, so the
# per-call dynamic data is packed into
# at most THREE upload buffers (int32 / float32 / bool) and ONE download
# buffer (float32). The pack layout is static per plan, so unpacking
# compiles away. (This is the moral analog of the reference's Streamable
# wire protocol — common/io/stream/ — applied to the host<->device hop.)
# ---------------------------------------------------------------------------

_DTYPE_TAGS = {"i": np.int32, "f": np.float32, "b": np.bool_}


def _pack_trees(*trees):
    """Flatten trees into 3 dtype-segregated buffers + a static spec."""
    leaves, treedef = jax.tree_util.tree_flatten(tuple(trees))
    bufs = {"i": [], "f": [], "b": []}
    spec = []
    for leaf in leaves:
        a = np.asarray(leaf)
        if a.dtype == np.bool_:
            tag = "b"
        elif np.issubdtype(a.dtype, np.floating):
            tag = "f"
            a = a.astype(np.float32, copy=False)
        else:
            tag = "i"
            a = a.astype(np.int32, copy=False)
        offset = sum(x.size for x in bufs[tag])
        bufs[tag].append(a.ravel())
        spec.append((tag, a.shape, offset, a.size))
    packed = {tag: (np.concatenate(parts) if parts
                    else np.zeros(0, _DTYPE_TAGS[tag]))
              for tag, parts in bufs.items()}
    # ONE wire buffer: [i32 | f32-bits | bool-as-i32] — every transfer
    # op pays its own round trip, so dtype segments are
    # bit-cast in and out of a single int32 array
    wire = np.concatenate([
        packed["i"],
        packed["f"].view(np.int32),
        packed["b"].astype(np.int32),
    ])
    sizes = (packed["i"].size, packed["f"].size, packed["b"].size)
    return wire, (treedef, tuple(spec), sizes)


def _unpack_trees(wire: jax.Array, static) -> tuple:
    treedef, spec, (ni, nf, nb) = static
    packed = {
        "i": wire[:ni],
        "f": jax.lax.bitcast_convert_type(wire[ni: ni + nf], jnp.float32),
        "b": wire[ni + nf: ni + nf + nb] != 0,
    }
    leaves = []
    for tag, shape, offset, size in spec:
        leaves.append(packed[tag][offset: offset + size].reshape(shape))
    return jax.tree_util.tree_unflatten(treedef, leaves)


@partial(jax.jit, static_argnames=("pack_static", "desc", "agg_desc", "cap",
                                   "k", "sort_spec", "fused"))
def _segment_program_packed(seg: dict, wire, live: jax.Array,
                            live_views: dict,
                            *, pack_static, desc: tuple, agg_desc: tuple,
                            cap: int, k: int, sort_spec: tuple,
                            fused: tuple | None = None):
    params, agg_params, sort_params = _unpack_trees(wire, pack_static)
    (top_score, top_key, top_idx, total, top_missing), agg_out, prune = \
        _segment_body(seg, params, live, live_views, agg_params,
                      sort_params, desc=desc,
                      agg_desc=agg_desc, cap=cap, k=k, sort_spec=sort_spec,
                      fused=fused)
    B = top_score.shape[0]
    # two download buffers: f32 (scores + prune + aggs) and i32 (exact
    # keys/ids) — int sort keys (epoch seconds) must NOT round-trip
    # through f32
    f_parts = [top_score]
    i_parts = [top_idx, total[:, None], top_missing.astype(jnp.int32)]
    if top_key.dtype == jnp.float32:
        f_parts.append(top_key)
    else:
        i_parts.append(top_key.astype(jnp.int32))
    f_parts.append(prune)
    for leaf in jax.tree_util.tree_leaves(agg_out):
        f_parts.append(leaf.reshape(B, -1).astype(jnp.float32))
    fbuf = jnp.concatenate(f_parts, axis=1)
    ibuf = jnp.concatenate(i_parts, axis=1)
    # single download op: f32 section bit-cast into the int32 buffer
    return jnp.concatenate(
        [ibuf, jax.lax.bitcast_convert_type(fbuf, jnp.int32)], axis=1)


# ---------------------------------------------------------------------------
# Base+delta pack dispatch (streaming write path, ROADMAP item 1)
#
# In delta mode the reader holds ONE immutable base segment and ONE
# small delta segment. A fused-admitted plan searches BOTH in a single
# device dispatch: the base tile walk runs first, its running top-k
# state (threshold included) carries into the delta walk via the ops
# layer's init_topk/idx_offset chaining, both walks' candidates merge
# through one selection, and the aggregation passes run per sub-segment
# inside the same program (ordinal spaces stay segment-local, so the
# partials meet in the EXACT same host reduce two dispatches would
# feed). Results are byte-identical to the per-segment path — the
# collect splits the merged top-k back into per-segment candidate
# lists — while the dispatch pays ONE round trip and the delta tiles
# prune against the base's threshold.
# ---------------------------------------------------------------------------


def _pack_body(seg_b: dict, seg_d: dict, params_b: tuple, params_d: tuple,
               live_b: jax.Array, live_d: jax.Array, live_views_b: dict,
               live_views_d: dict, agg_params_b: tuple, agg_params_d: tuple,
               *, desc: tuple, agg_desc: tuple, cap_b: int, cap_d: int,
               k: int, fused: tuple, step=None):
    """Fused base+delta evaluation — ONE selection over both packs plus
    per-sub-segment aggregation passes. Returns the _segment_body shape
    with `totals` widened to [B, 2] (per-sub-segment exact hit counts:
    the host split needs them to rebuild per-segment candidate lists)
    and the agg tree replaced by the (base, delta) PAIR of trees. With
    a `step`, the per-chunk deadline check rides the BASE walk (the
    dominant cost; the delta walk is bounded by the compaction
    threshold) and its verdict covers through the base's final check."""
    B = _batch_size(params_b)
    bundle, backend = fused
    emit = bool(agg_desc)
    step_tail = ()

    def aggs_for(seg, params, live_views, agg_params, match, cap):
        plan = _agg_view_plan(desc, agg_desc, agg_params, seg, live_views)
        views = _ViewMasks(desc, params, seg, live_views, cap, B)
        return eval_aggs(agg_desc, agg_params, seg, match,
                         views=views, plan=plan)

    if k == 0:
        out_b = eval_fused_match(seg_b, desc, params_b, live_b, bundle,
                                 backend, emit_match=emit, step=step)
        if step is not None:
            step_tail = (out_b[-1],)
            out_b = out_b[:-1]
        out_d = eval_fused_match(seg_d, desc, params_d, live_d, bundle,
                                 backend, emit_match=emit)
        if emit:
            total_b, prune_b, match_b = out_b
            total_d, prune_d, match_d = out_d
            agg_pair = (aggs_for(seg_b, params_b, live_views_b,
                                 agg_params_b, match_b, cap_b),
                        aggs_for(seg_d, params_d, live_views_d,
                                 agg_params_d, match_d, cap_d))
        else:
            total_b, prune_b = out_b
            total_d, prune_d = out_d
            agg_pair = ({}, {})
        totals = jnp.stack([total_b, total_d], axis=1)
        empty_f = jnp.zeros((B, 0), jnp.float32)
        prune = (prune_b + prune_d).astype(jnp.float32)
        return ((empty_f, empty_f, jnp.zeros((B, 0), jnp.int32), totals,
                 jnp.zeros((B, 0), bool)), agg_pair,
                jnp.broadcast_to(prune[None, :] / B, (B, 3))) + step_tail

    # the base walk opens at the PACK's k width (running_topk_init —
    # NOT min'd against the base capacity alone, so a delta bigger than
    # the base's tail still fills the window) and the delta walk chains
    # onto its state with indices offset past the base capacity
    from ..ops.topk import running_topk_init
    k_pack = min(k, cap_b + cap_d)
    out_b = eval_fused_topk(seg_b, desc, params_b, live_b, k_pack, bundle,
                            backend, emit_match=emit, step=step,
                            init_topk=running_topk_init(B, k_pack))
    if step is not None:
        step_tail = (out_b[-1],)
        out_b = out_b[:-1]
    if emit:
        top_s, top_i, total_b, prune_b, match_b = out_b
    else:
        top_s, top_i, total_b, prune_b = out_b
    out_d = eval_fused_topk(seg_d, desc, params_d, live_d, k_pack, bundle,
                            backend, emit_match=emit,
                            init_topk=(top_s, top_i), idx_offset=cap_b)
    if emit:
        top_s, top_i, total_d, prune_d, match_d = out_d
        agg_pair = (aggs_for(seg_b, params_b, live_views_b, agg_params_b,
                             match_b, cap_b),
                    aggs_for(seg_d, params_d, live_views_d, agg_params_d,
                             match_d, cap_d))
    else:
        top_s, top_i, total_d, prune_d = out_d
        agg_pair = ({}, {})
    totals = jnp.stack([total_b, total_d], axis=1)
    prune = (prune_b + prune_d).astype(jnp.float32)
    top_missing = jnp.zeros_like(top_i, dtype=bool)
    return ((top_s, top_s, top_i, totals, top_missing), agg_pair,
            jnp.broadcast_to(prune[None, :] / B, (B, 3))) + step_tail


@partial(jax.jit, static_argnames=("pack_static", "desc", "agg_desc",
                                   "cap_b", "cap_d", "k", "fused"))
def _pack_program_packed(seg_b: dict, seg_d: dict, wire,
                         live_b: jax.Array, live_d: jax.Array,
                         live_views_b: dict, live_views_d: dict,
                         *, pack_static, desc: tuple, agg_desc: tuple,
                         cap_b: int, cap_d: int, k: int, fused: tuple):
    """_segment_program_packed's base+delta twin: same one-buffer wire
    in/out discipline, totals carried as TWO i32 columns (base, delta)
    and the agg section holding both sub-segments' trees."""
    params_b, params_d, agg_params_b, agg_params_d = _unpack_trees(
        wire, pack_static)
    (top_score, _tk, top_idx, totals, top_missing), agg_pair, prune = \
        _pack_body(seg_b, seg_d, params_b, params_d, live_b, live_d,
                   live_views_b, live_views_d, agg_params_b, agg_params_d,
                   desc=desc, agg_desc=agg_desc, cap_b=cap_b, cap_d=cap_d,
                   k=k, fused=fused)
    B = top_score.shape[0]
    f_parts = [top_score, prune]
    i_parts = [top_idx, totals, top_missing.astype(jnp.int32)]
    for leaf in jax.tree_util.tree_leaves(agg_pair):
        f_parts.append(leaf.reshape(B, -1).astype(jnp.float32))
    fbuf = jnp.concatenate(f_parts, axis=1)
    ibuf = jnp.concatenate(i_parts, axis=1)
    return jnp.concatenate(
        [ibuf, jax.lax.bitcast_convert_type(fbuf, jnp.int32)], axis=1)


# ---------------------------------------------------------------------------
# Resident query loop (search/resident.py): AOT-pinned stepped programs
# ---------------------------------------------------------------------------

# tile-loop chunks per stepped program: each chunk boundary polls the
# host clock (deadline) and meters any injected straggler delay, so a
# laggard step can exit within one chunk of the cutoff instead of
# finishing its whole tile walk
_RESIDENT_CHUNKS = 8


def _resident_chunk_tiles(n_tiles: int) -> int:
    """Tiles a chunk of a stepped walk over n_tiles."""
    return max(1, -(-n_tiles // _RESIDENT_CHUNKS))


def _step_poll(hi, lo, delay_left, per_chunk, timed):
    """Host half of the device-side deadline check, invoked once per
    tile-loop chunk via io_callback. `hi + lo` reconstructs the f64
    absolute monotonic deadline from two f32 halves (one f32 loses ms
    precision at realistic uptimes); `delay_left`/`per_chunk` meter an
    injected shard_delay fault ACROSS chunks, so the delay burns inside
    device execution — where a real slow step would — and the first
    chunk past the cutoff flips timed_out, skipping the rest."""
    if bool(timed):
        return np.bool_(True), np.float32(delay_left)
    d = float(delay_left)
    if d > 0.0:
        s = min(d, float(per_chunk))
        _time.sleep(s / 1000.0)
        d -= s
    deadline = float(hi) + float(lo)
    late = math.isfinite(deadline) and _time.monotonic() > deadline
    return np.bool_(late), np.float32(d)


def _resident_step(step_arr, chunk_tiles: int):
    """Build the ops-layer step tuple (chunk_tiles, init_state, check)
    from the dynamic step scalars [dead_hi, dead_lo, per_chunk_ms,
    delay_total_ms]. The check chains (timed, delay_left) through the
    loop carry, which also serializes the callbacks."""
    from jax.experimental import io_callback

    def check(_c, st):
        timed, delay_left = st
        timed, delay_left = io_callback(
            _step_poll,
            (jax.ShapeDtypeStruct((), jnp.bool_),
             jax.ShapeDtypeStruct((), jnp.float32)),
            step_arr[0], step_arr[1], delay_left, step_arr[2], timed)
        return timed, (timed, delay_left)

    return (chunk_tiles, (jnp.bool_(False), step_arr[3]), check)


@partial(jax.jit, static_argnames=("pack_static", "desc", "agg_desc",
                                   "cap", "k", "sort_spec", "fused",
                                   "chunk_tiles"),
         donate_argnums=(1,))
def _resident_step_program(seg: dict, wire, live: jax.Array,
                           live_views: dict, step_arr,
                           *, pack_static, desc: tuple, agg_desc: tuple,
                           cap: int, k: int, sort_spec: tuple,
                           fused: tuple, chunk_tiles: int):
    """The stepped twin of _segment_program_packed: same wire format in,
    same wire format out PLUS one trailing i32 column carrying the
    device-side timed_out verdict. The query-param wire buffer is
    DONATED — the pinned executable reuses its memory, so a staged feed
    never allocates twice. AOT-compiled once per resident entry and
    invoked through the pinned executable (search/resident.py)."""
    params, agg_params, sort_params = _unpack_trees(wire, pack_static)
    (top_score, top_key, top_idx, total, top_missing), agg_out, prune, \
        timed = _segment_body(
            seg, params, live, live_views, agg_params, sort_params,
            desc=desc, agg_desc=agg_desc, cap=cap, k=k,
            sort_spec=sort_spec, fused=fused,
            step=_resident_step(step_arr, chunk_tiles))
    B = top_score.shape[0]
    f_parts = [top_score]
    i_parts = [top_idx, total[:, None], top_missing.astype(jnp.int32)]
    if top_key.dtype == jnp.float32:
        f_parts.append(top_key)
    else:
        i_parts.append(top_key.astype(jnp.int32))
    # timed_out rides LAST in the i32 section so collect can strip it
    # without disturbing the shared slice arithmetic
    i_parts.append(jnp.broadcast_to(timed.astype(jnp.int32)[None, None],
                                    (B, 1)))
    f_parts.append(prune)
    for leaf in jax.tree_util.tree_leaves(agg_out):
        f_parts.append(leaf.reshape(B, -1).astype(jnp.float32))
    fbuf = jnp.concatenate(f_parts, axis=1)
    ibuf = jnp.concatenate(i_parts, axis=1)
    return jnp.concatenate(
        [ibuf, jax.lax.bitcast_convert_type(fbuf, jnp.int32)], axis=1)


def _split_deadline(deadline: float | None) -> tuple[float, float]:
    """f64 monotonic deadline -> two f32 halves (hi + lo reconstructs it
    to sub-ms precision); +inf disables."""
    if deadline is None:
        return float("inf"), 0.0
    hi = float(np.float32(deadline))
    return hi, deadline - hi


@partial(jax.jit, static_argnames=("pack_static", "desc", "agg_desc",
                                   "cap_b", "cap_d", "k", "fused",
                                   "chunk_tiles"),
         donate_argnums=(2,))
def _resident_pack_program(seg_b: dict, seg_d: dict, wire,
                           live_b: jax.Array, live_d: jax.Array,
                           live_views_b: dict, live_views_d: dict,
                           step_arr, *, pack_static, desc: tuple,
                           agg_desc: tuple, cap_b: int, cap_d: int,
                           k: int, fused: tuple, chunk_tiles: int):
    """The stepped base+delta twin of _resident_step_program: the
    per-chunk deadline check rides the BASE tile walk (the delta walk
    is bounded by the compaction threshold, at most one chunk's worth
    of work past the base's final check), totals ride as two columns,
    and the timed_out verdict rides last in the i32 section. The wire
    is DONATED exactly like the single-segment entry."""
    params_b, params_d, agg_params_b, agg_params_d = _unpack_trees(
        wire, pack_static)
    (top_score, _tk, top_idx, totals, top_missing), agg_pair, prune, \
        timed = _pack_body(
            seg_b, seg_d, params_b, params_d, live_b, live_d,
            live_views_b, live_views_d, agg_params_b, agg_params_d,
            desc=desc, agg_desc=agg_desc, cap_b=cap_b, cap_d=cap_d,
            k=k, fused=fused,
            step=_resident_step(step_arr, chunk_tiles))
    B = top_score.shape[0]
    f_parts = [top_score, prune]
    i_parts = [top_idx, totals, top_missing.astype(jnp.int32),
               jnp.broadcast_to(timed.astype(jnp.int32)[None, None],
                                (B, 1))]
    for leaf in jax.tree_util.tree_leaves(agg_pair):
        f_parts.append(leaf.reshape(B, -1).astype(jnp.float32))
    fbuf = jnp.concatenate(f_parts, axis=1)
    ibuf = jnp.concatenate(i_parts, axis=1)
    return jnp.concatenate(
        [ibuf, jax.lax.bitcast_convert_type(fbuf, jnp.int32)], axis=1)


def _resident_backend(segment: Segment, bundle: tuple, desc, agg_desc,
                      k_eff: int, b_pad: int, ck: int) -> str | None:
    """Backend a resident stepped entry would pin, resolvable WITHOUT
    timing (the resident path cannot wall-clock a tune — its dispatch
    is pipelined): forced env, the tuner's cached choice, or a
    persisted store hit. None means the shape has no decision yet — the
    caller keeps the cold autotuned dispatch, whose first execution
    tunes the shape and unblocks residency on the NEXT dispatch.

    Pallas-tuned shapes pin Pallas stepped executables now
    (resident_step_ok — the chunked kernel hosts the per-chunk deadline
    check between pallas_call invocations); only when stepping is
    unavailable (kernels disabled) does a pallas-tuned shape stay on
    the cold dispatch rather than silently losing its kernel."""
    forced = _os.environ.get("ES_TPU_FUSED_BACKEND", "").lower()
    if forced in ("pallas", "xla"):
        # forced outranks candidacy AND any cached tuned choice, the
        # same precedence resolve_fused_backend applies — and it
        # reaches the stepped path unconditionally: the chunked walk
        # runs in interpret mode off-TPU exactly like the forced cold
        # path does, so the validation tool sees the real resident
        # pipeline (no resident_step_ok gate here; that gate protects
        # TUNED choices from silently losing their kernel)
        return forced
    if not _bundle_pallas_ok(bundle, agg_desc, ck,
                             _bundle_pos_width(bundle, segment.text),
                             _bundle_fwd_width(bundle, segment.text)):
        return "xla"                     # XLA engine either way
    tune_key = (seg_cache_key(segment), segment.capacity, desc, k_eff,
                b_pad, bool(agg_desc))
    choice = _autotune_choices.get(tune_key)
    if choice is None:
        entry = _autotune_persisted.get(autotune_persist_key(
            seg_cache_key(segment), segment.capacity, desc, k_eff,
            bool(agg_desc)))
        choice = entry["choice"] if entry is not None else None
    if choice is None:
        return None                      # untuned: cold dispatch tunes
    if choice == "pallas" and not resident_step_ok():
        return None                      # keep the kernel, stay cold
    return choice


def _resident_admit(segment: Segment, bundle: tuple, desc, agg_desc,
                    k_eff: int, b_pad: int, ck: int) -> bool:
    """Residency admission on top of fused admission: a plan goes
    resident once its engine backend is decidable without timing
    (_resident_backend) — XLA-only shapes immediately, tuned shapes on
    their winner (either engine), untuned Pallas candidates after one
    cold autotuned dispatch."""
    return _resident_backend(segment, bundle, desc, agg_desc, k_eff,
                             b_pad, ck) is not None


def _dev_shape_sig(dev) -> tuple:
    """Shape/dtype signature of an uploaded pack tree. Part of the
    resident entry key: a delta segment keys by GENERATION (not
    content), so the key itself must pin the exact avals the AOT
    executable was compiled for — within a pow2 bucket the signature
    is constant across epoch bumps (that is what pad_delta_shapes
    buys); when a bucket grows the signature changes and the entry
    recompiles once, log-many times over a delta's life."""
    return tuple((tuple(leaf.shape), str(leaf.dtype))
                 for leaf in jax.tree_util.tree_leaves(dev))


def _resident_entry_key(segment: Segment, desc, agg_desc, sort_spec,
                        k_res: int, b_pad: int, pack_sig, dev_struct,
                        view_keys, bundle, backend: str,
                        shape_sig: tuple = ()):
    return (seg_cache_key(segment), segment.capacity, desc, agg_desc,
            sort_spec, k_res, b_pad, pack_sig, dev_struct, view_keys,
            bundle, backend, shape_sig)


def _gc_backstop(obj, hold):
    """Attach a GC backstop to a utils/breaker.Hold: the bytes release
    when the hold is released OR when `obj` is garbage collected,
    whichever first — GC alone is too lazy for tight query loops, which
    would accumulate estimates to a spurious trip; an un-weakref-able
    object (or None) releases immediately. Hold.release is idempotent,
    so the deterministic path and the finalizer cannot double-release."""
    if obj is None:
        hold.release()
        return hold
    import weakref
    try:
        weakref.finalize(obj, hold.release)
    except TypeError:
        hold.release()
    return hold


_out_layout_cache: dict = {}
# guards the cache STORES only (reads are racy-but-safe dict gets; the
# eval_shape compute runs outside so a slow abstract eval never convoys
# concurrent dispatches) — racing writers compute identical layouts
# and the setdefault keeps the first
_out_layout_lock = _threading.Lock()


def _output_layout(cache_key, seg, params, live, live_views, agg_params,
                   sort_params, desc, agg_desc, cap, k, sort_spec,
                   fused=None):
    """Host-side output layout (shapes + agg treedef) via eval_shape."""
    hit = _out_layout_cache.get(cache_key)
    if hit is not None:
        return hit
    shapes = jax.eval_shape(
        partial(_segment_body, desc=desc, agg_desc=agg_desc, cap=cap, k=k,
                sort_spec=sort_spec, fused=fused),
        seg, params, live, live_views, agg_params, sort_params)
    (ts, tk, ti, tt, tm), agg_shapes, _prune = shapes
    agg_leaves, agg_treedef = jax.tree_util.tree_flatten(agg_shapes)
    layout = {
        "k": k,
        "key_dtype": tk.dtype,
        "agg_treedef": agg_treedef,
        "agg_shapes": [tuple(s.shape) for s in agg_leaves],
        "fused": fused is not None,
        "fused_positional": (fused is not None
                             and _bundle_positional(fused[0])),
    }
    with _out_layout_lock:
        layout = _out_layout_cache.setdefault(cache_key, layout)
    return layout


def _sort_key_dtype(segment: Segment, sort_spec: tuple):
    if sort_spec[0] == "_score":
        return np.dtype(np.float32)
    _, field, _desc, kindtag = sort_spec[:4]
    if kindtag in ("script", "geo"):
        return np.dtype(np.float32)
    if kindtag == "num" and field in segment.numerics:
        return np.dtype(segment.numerics[field].values.dtype)
    return np.dtype(np.int32)  # kw ords / absent field path


def _device_live(segment: Segment, live: np.ndarray) -> jax.Array:
    """Cache the live-mask upload per (segment, mask identity): every
    host->device hop is a transfer op with its own round trip, and
    the mask only changes on delete/refresh."""
    if isinstance(live, jax.Array):
        return live
    cached = getattr(segment, "_live_dev", None)
    if cached is not None and cached[0] is live:
        return cached[1]
    dev = jnp.asarray(live)
    segment._live_dev = (live, dev)  # type: ignore[attr-defined]
    return dev


def _live_views_for(segment: Segment, live_dev: jax.Array,
                    agg_desc: tuple) -> dict:
    """Layout-permuted live masks for every agg layout that carries
    sorted-view projections. One device gather per (live epoch, layout),
    cached — the per-dispatch cost is a dict of cached arrays."""
    if not agg_desc:
        return {}
    dev = device_arrays(segment)
    cache = getattr(segment, "_live_view_cache", None)
    if cache is None or cache[0] is not live_dev:
        cache = (live_dev, {})
        segment._live_view_cache = cache  # type: ignore[attr-defined]
    out = {}
    for lkind, store_name in (("kw", "kw_sorted"), ("num", "num_sorted")):
        for f, store in dev.get(store_name, {}).items():
            if "vw_num" not in store and "vw_kw" not in store:
                continue
            key = (lkind, f)
            if key not in cache[1]:
                cache[1][key] = jnp.take(live_dev, store["perm"])
            out[key] = cache[1][key]
    return out


def _execute_resident(segment: Segment, live, desc: tuple, params: tuple,
                      agg_desc: tuple, agg_params: tuple,
                      sort_spec: tuple, sort_params: tuple,
                      bundle: tuple, backend: str, k_eff: int,
                      b_pad: int, deadline: float | None, step_budget,
                      shard_key: tuple | None, n_real: int, bind=None):
    """Serve one dispatch through a pinned resident entry: stage the
    donated param feed asynchronously, invoke the AOT-compiled stepped
    executable, start the async result fetch — the split
    feed/execute/fetch pipeline that replaces the cold path's
    monolithic dispatch. k is bucketed to its next power of two so
    nearby request sizes share one executable; the response window is a
    prefix of the (larger) top-k, so responses stay byte-identical.
    `backend` is the engine _resident_backend resolved — "xla" runs the
    stepped fori tile loop, "pallas" the chunked pallas_call grid; both
    host the identical per-chunk deadline check."""
    cap = segment.capacity
    k_res = min(next_pow2(max(k_eff, 1), floor=1), cap) if k_eff > 0 else 0
    fused = (bundle, backend)
    f0 = bundle_primary_field(bundle)
    n_tiles = segment.text[f0].tile_max.n_tiles
    chunk_tiles = _resident_chunk_tiles(n_tiles)
    n_chunks = -(-n_tiles // chunk_tiles)
    row_elems = _fused_row_elems(
        cap, n_tiles, k_res, emit_match=bool(agg_desc),
        vec_clauses=sum(kd in _FUSED_VEC_KINDS
                        for _r, kd, _f, _w in bundle),
        pos_width=_bundle_pos_width(bundle, segment.text),
        chunk_tiles=chunk_tiles)
    from ..utils.breaker import breaker_service
    req_breaker = breaker_service().breaker("request")
    # the stepped body never B-chunks (the step state rides ONE loop),
    # so the transient estimate covers the whole padded batch
    est = b_pad * row_elems * 8
    req_hold = req_breaker.hold(est)
    try:
        dev = device_arrays(segment)
        live_dev = _device_live(segment, live)
        live_views = _live_views_for(segment, live_dev, agg_desc)
        wire, pack_static = _pack_trees(params, agg_params, sort_params)
        # -- feed stage: async device_put; the transfer lands while the
        # host resolves the entry / earlier enqueued programs execute
        t_stage = _time.perf_counter()
        wire_dev = jax.device_put(wire)
        hi, lo = _split_deadline(deadline)
        delay_ms = float(step_budget.take()) if step_budget is not None \
            else 0.0
        step_arr = jax.device_put(np.asarray(
            [hi, lo, delay_ms / n_chunks, delay_ms], np.float32))
        key_dtype = _sort_key_dtype(segment, sort_spec)
        dev_struct = jax.tree_util.tree_structure(dev)
        view_keys = tuple(sorted(live_views))
        is_delta = getattr(segment, "delta_parent", None) is not None
        key = _resident_entry_key(segment, desc, agg_desc, sort_spec,
                                  k_res, b_pad, pack_static[1],
                                  dev_struct, view_keys, bundle, backend,
                                  shape_sig=(_dev_shape_sig(dev)
                                             if is_delta else ()))
        entry = _resident.cache.get(
            key, delta_epoch=(getattr(segment, "delta_epoch", 0)
                              if is_delta else None))
        if entry is None:
            # cold: AOT-compile and pin. The jit wrapper's cache would
            # re-hash the statics per call; the pinned executable skips
            # straight to the runtime.
            _resident.stats.cold_dispatches.inc()
            import warnings
            with warnings.catch_warnings():
                # the donated wire is only reusable when an output
                # happens to match its shape; "not usable" is the
                # expected steady state for small feeds, not a problem
                warnings.filterwarnings(
                    "ignore", message="Some donated buffers were not")
                compiled = _resident_step_program.lower(
                    dev, wire_dev, live_dev, live_views, step_arr,
                    pack_static=pack_static, desc=desc, agg_desc=agg_desc,
                    cap=cap, k=k_res, sort_spec=sort_spec, fused=fused,
                    chunk_tiles=chunk_tiles).compile()
            entry = _resident.ResidentEntry(
                key, label=repr((desc, k_res, b_pad, bool(agg_desc),
                                 backend)),
                compiled=compiled, seg_id=segment.seg_id,
                fingerprint=segment.fingerprint(),
                # delta entries hold NO segment weakref: the epoch's
                # segment dies at every refresh while the executable
                # (which takes the pack as a runtime argument) must
                # survive it — compaction evicts via evict_generation
                seg_ref=(None if is_delta
                         else _resident.make_ref(segment)),
                backend=backend,
                generation=seg_cache_key(segment),
                delta_epoch=getattr(segment, "delta_epoch", 0))
            _resident.cache.put(entry)
        layout = _output_layout(
            (cap, key_dtype, desc, agg_desc, k_res, sort_spec,
             pack_static[1], dev_struct, view_keys, fused),
            dev, params, live_dev, live_views, agg_params, sort_params,
            desc, agg_desc, cap, k_res, sort_spec, fused=fused)
        # -- execute stage: invoke the pinned executable (donates wire)
        with _trace_guard.trap(), \
                _launch(bind, "resident", "resident_dispatch"):
            buf = entry.compiled(dev, wire_dev, live_dev, live_views,
                                 step_arr)
        _resident.stats.staged_feed_overlap_ms.record(
            (_time.perf_counter() - t_stage) * 1000.0)
        # -- fetch stage: start the device->host copy now so it overlaps
        # with whatever executes next; collect's device_get then finds
        # the bytes already in flight
        fetch = _start_fetch(buf)
    except BaseException:
        req_hold.release()
        raise
    out_bytes = min(est, int(getattr(buf, "nbytes", 0)) or est)
    req_hold.shrink(out_bytes)
    # the request-breaker hold is attached (with its GC backstop)
    # BEFORE any further accounting can raise — no exit may leak the
    # out_bytes reservation (PR 4's invariant)
    layout = {**layout, **fetch, "resident": True, "shard_key": shard_key,
              "walks": (_fused_walk(bundle, backend, n_tiles, k_res,
                                    chunk_tiles),),
              "_breaker_hold": _gc_backstop(buf, req_hold),
              "_span_args": _span_args(bind)}
    # residency-bytes accounting (fielddata breaker, held until the
    # entry is evicted): staged feed + queued output + generated code.
    # A fielddata trip here means the entry cannot afford residency —
    # evict it (releasing any partial hold) and serve this result; the
    # NEXT dispatch goes cold until pressure clears.
    code_bytes = 0
    try:
        ma = entry.compiled.memory_analysis()
        code_bytes = int(getattr(ma, "generated_code_size_in_bytes", 0)
                         or 0)
    except Exception:  # noqa: BLE001 — backend-optional introspection
        pass
    try:
        entry.account(code_bytes + int(wire.nbytes) + out_bytes)
    except Exception:  # noqa: BLE001 — breaker trip on accounting
        _resident.cache.evict(entry.key)
    return buf, layout, n_real


@dataclass(slots=True)
class SegmentPlan:
    """What `execute_segment_async` resolves before the launch of
    `_segment_program_packed` from the bound queries, the group's
    descriptors and the segment alone, kept by the reader
    (search/bound_plans.py) so that the next search with the same
    bodies launches from it: the descriptors, the packed wire
    parameters as the device array the upload returned, the fused
    decision as it was resolved (bundle and backend, or the reject
    reason), the breaker estimate and the output layout. `epoch` is
    the segment's `device_epoch` when the layout was made: the layout
    and the program's branches follow the column tree's shape
    (`segment_plan_valid`)."""

    desc: tuple
    agg_desc: tuple
    sort_spec: tuple
    pack_static: tuple
    wire_dev: jax.Array
    k_eff: int
    fused: tuple | None
    reject: str | None
    pallas_reason: str | None
    tune_key: tuple | None
    switches: tuple | None
    est: int
    layout: dict
    epoch: tuple | None


def plan_switches() -> tuple:
    """The process-wide gates every kept bind and plan was resolved
    under (fused admission, positional clauses): part of the reader's
    stamp, so that nothing resolved under other values is served. (The
    resident loop, paged packs and the pack dispatch leave the path
    that keeps plans and are checked where they branch; what only a
    fused plan reads is `_fused_switches`.)"""
    return fused_enabled(), _positional_enabled()


def _fused_switches() -> tuple:
    """What a fused plan's backend and kernel verdict were resolved
    under: the kernel coverage and the forced backend."""
    return (_pallas_coverage(),
            _os.environ.get("ES_TPU_FUSED_BACKEND", "").lower())


def segment_plan_valid(segment: Segment, plan: SegmentPlan) -> bool:
    """May `plan` launch against the segment as it stands? Not once the
    column tree has changed since the layout was made (an ensure_*
    upload added leaves and switched the program's branches, or
    drop_device forgot the tree), not on a pack that pages, and not
    where a fused plan's switches have moved or the autotuner no longer
    holds the backend the plan runs."""
    if plan.epoch != segment.device_epoch() \
            or _tiering.paged_fields(segment):
        return False
    if plan.fused is None:
        return True
    switches = _fused_switches()
    # a forced backend outranks the tuner's choice
    return plan.switches == switches and plan.fused[1] in (
        switches[1], _autotune_choices.get(plan.tune_key))


def _launch_plan(segment: Segment, live, plan: SegmentPlan, req_hold,
                 bind, inputs: tuple | None = None):
    """The launch of `_segment_program_packed` from a plan, under the
    breaker hold `req_hold` (released here on any raise): everything
    that is done per launch whatever the reader kept. The column tree,
    the live mask and its views (`inputs`, where the caller has just
    made them) are this launch's; the program call and the request for
    the result's copy to the host are the `dispatch` phase."""
    fused = plan.fused
    try:
        if inputs is None:
            live_dev = _device_live(segment, live)
            inputs = (device_arrays(segment), live_dev,
                      _live_views_for(segment, live_dev, plan.agg_desc))
        dev, live_dev, live_views = inputs
        with _trace_guard.trap(), _launch(
                bind, "unfused" if fused is None else "fused_" + fused[1]):
            buf = _segment_program_packed(
                dev, plan.wire_dev, live_dev, live_views,
                pack_static=plan.pack_static,
                desc=plan.desc, agg_desc=plan.agg_desc,
                cap=segment.capacity, k=plan.k_eff,
                sort_spec=plan.sort_spec, fused=fused)
            fetch = _start_fetch(buf)
    except BaseException:
        req_hold.release()
        raise
    # program enqueued: downgrade the transient estimate to the queued
    # OUTPUT buffer's footprint (held until collection or GC)
    est = plan.est
    req_hold.shrink(min(est, int(getattr(buf, "nbytes", 0)) or est))
    # layout dicts are shared across calls — attach the per-call hold
    # and the launch's own fields to a shallow copy
    layout = {**plan.layout, **fetch,
              "_breaker_hold": _gc_backstop(buf, req_hold),
              "_span_args": _span_args(bind)}
    return buf, layout


def execute_segment_async(segment: Segment, live: np.ndarray,
                          bounds: Sequence[Bound], k: int,
                          agg_desc: tuple = (), agg_params: tuple = (),
                          sort_spec: tuple = ("_score",),
                          sort_params: tuple = (),
                          deadline: float | None = None,
                          step_budget=None,
                          shard_key: tuple | None = None,
                          bind=None, plan: SegmentPlan | None = None,
                          keep: list | None = None):
    """Dispatch one batched query against one segment WITHOUT syncing.

    Uses the packed wire format: 3 upload buffers, 1 download buffer —
    each transfer op pays its own round trip. Returns
    (device_buffer, layout, n_real); pass to collect_segment_result.
    The batch is padded to a power of two (repeating the last bound) so
    the compiled-program cache is keyed on log-many batch sizes.

    With ES_TPU_RESIDENT_LOOP set, fused-admitted plans route through a
    pinned AOT-compiled stepped entry (search/resident.py) with a
    donated, asynchronously staged param feed; `deadline` (absolute
    monotonic seconds) then arms the per-chunk DEVICE-side deadline
    check (collect raises SearchTimeoutError when the device reports
    timed_out), `step_budget` carries an injected straggler budget
    (utils/faults.StepBudget), and `shard_key` = (index, shard) labels
    the timeout. All three are ignored on the cold path, whose deadline
    stays cooperative at the caller's collect boundary.

    `bind` is the caller's open `bind` phase (utils/profiler.phase),
    paused here around the launch; its arguments name the requests on
    this dispatch's spans.

    The result's device-to-host copy is asked for at the launch, inside
    the `dispatch` phase (`_start_fetch`), so a caller that launches
    several programs before it collects the first (a fan-out round, an
    `_msearch` batch, a reader of several segments) finds the bytes on
    the host; `collect` then times what is left of the copy.

    `plan` is what an earlier call with these bounds on this segment
    put into `keep` (a `SegmentPlan`, which the caller has checked with
    `segment_plan_valid`). With it the call skips `finalize`, the fused
    admission, the packing and the upload of the wire parameters, the
    autotuner's lookup and the output layout, and goes to what is done
    per launch whatever was kept: the admission counters, the breaker
    hold (taken for the kept estimate, shrunk to the output and given
    its GC backstop), the live mask and its views, the launch with its
    `launches` count and its `dispatch` phase, the copy request.
    Without it, a call that ends in the cold launch of
    `_segment_program_packed` appends its plan to `keep` (the paged
    walk and the resident loop keep none)."""
    n_real = len(bounds)
    if n_real == 0:
        raise ValueError("execute_segment requires at least one bound query")
    from ..utils.breaker import breaker_service
    if plan is not None:
        if plan.fused is not None:
            _fused_stats.record_admit(
                positional=_bundle_positional(plan.fused[0]))
            if plan.pallas_reason is not None:
                _fused_stats.record_pallas_reject(plan.pallas_reason)
        else:
            _fused_stats.record_reject(plan.reject)
        buf, layout = _launch_plan(
            segment, live, plan,
            breaker_service().breaker("request").hold(plan.est), bind)
        return buf, layout, n_real
    b_pad = next_pow2(n_real, floor=1)
    if b_pad != n_real:
        bounds = list(bounds) + [bounds[-1]] * (b_pad - n_real)
    desc, params = finalize(bounds)
    k_eff = min(k, segment.capacity)
    # fused block-max score+top-k admission: the plan classifier
    # accepts (bool clause bundle over dense text + range masks), the
    # pack carries the tile summaries, and every bool boost is positive
    fused = None
    ck = 0
    fused_width = 0
    bundle, reject = _fused_plan_bundle(desc, k_eff, agg_desc, sort_spec,
                                        allow_k0=True)
    if bundle is not None:
        reject = _fused_pack_ok(segment, bundle)
        if reject is None and not _fused_params_ok(desc, params, bundle):
            reject = "nonpositive_boost"
        if reject is not None:
            bundle = None
    if bundle is not None:
        f0 = bundle_primary_field(bundle)
        n_tiles = segment.text[f0].tile_max.n_tiles
        ck = min(k_eff, segment.capacity // n_tiles)
        fused_width = _fused_row_elems(
            segment.capacity, n_tiles, k_eff,
            emit_match=bool(agg_desc),
            vec_clauses=sum(kd in _FUSED_VEC_KINDS
                            for _r, kd, _f, _w in bundle),
            pos_width=_bundle_pos_width(bundle, segment.text))
        fused = (bundle,)
        _fused_stats.record_admit(positional=_bundle_positional(bundle))
    else:
        _fused_stats.record_reject(reject)
    # tiered tile residency (index/tiering.py): a PAGED pack serves
    # fused-admitted plans through the chunked paged walk — the bound
    # computation over the resident summaries picks the survivor tiles,
    # only those stream host->device. Paged packs never pin resident
    # executables (the walk is host-driven); plans outside the fused
    # matrix fall back to a counted, breaker-accounted full upload.
    paged = _tiering.activate(segment)
    if paged:
        if bundle is not None \
                and not any(kd in _FUSED_VEC_KINDS
                            for _r, kd, _f, _w in bundle):
            return _execute_tiered(
                segment, live, desc, params, agg_desc, agg_params,
                sort_spec, sort_params, bundle, k_eff, b_pad, deadline,
                shard_key, n_real, bind)
        # knn bundles on a paged pack take the full-upload fallback:
        # the knn tile bound is a device product (the similarity
        # column), so the HOST survivor oracle
        # (ops/scoring.bundle_tile_bounds_np) cannot mirror it — the
        # tiered walk would have to fetch every vector tile anyway
        ensure_fwd_cols(segment)
    if _resident.enabled():
        res_backend = None if bundle is None else _resident_backend(
            segment, bundle, desc, agg_desc, k_eff, b_pad, ck)
        if res_backend is not None:
            return _execute_resident(
                segment, live, desc, params, agg_desc, agg_params,
                sort_spec, sort_params, bundle, res_backend, k_eff,
                b_pad, deadline, step_budget, shard_key, n_real, bind)
        # resident mode on, but the plan fell outside residency
        # admission (unfused, or an untuned Pallas candidate whose
        # first cold dispatch tunes it): cold dispatch
        _resident.stats.cold_dispatches.inc()
    # request breaker (ref: the request breaker of
    # HierarchyCircuitBreakerService): the dominant transient is the
    # dense [B, cap] score + match accumulators — or, on the fused
    # path, one [B, tile] scoring slab plus the [B, n_tiles*ck]
    # candidate strip. The device executes programs serially, so
    # transients of PIPELINED dispatches never coexist — the transient
    # estimate is checked here and swapped for an output-buffer-sized
    # hold once the program is enqueued; holding full transients per
    # queued dispatch would spuriously trip on any async batch loop.
    req_breaker = breaker_service().breaker("request")
    # chunked bodies bound the transient to one chunk's worth
    row_elems = fused_width if fused is not None else segment.capacity
    est = _chunk_b(b_pad, row_elems) * row_elems * 8
    req_hold = req_breaker.hold(est)
    tune_key = pallas_reason = None
    try:
        dev = device_arrays(segment)
        # before the tree's shape is read: a leaf added meanwhile makes
        # the kept plan stale, not wrong
        epoch = segment.device_epoch()
        live_dev = _device_live(segment, live)
        live_views = _live_views_for(segment, live_dev, agg_desc)
        wire, pack_static = _pack_trees(params, agg_params, sort_params)
        wire_dev = jnp.asarray(wire)
        if fused is not None:
            # per-(pack fingerprint, shape-bucket) autotune: the first
            # execution warms then best-of-N-times pallas vs xla on the
            # real inputs and caches (+ persists) the winner — k == 0
            # plans now tune too (the mask-only Pallas grid vs the XLA
            # mask engine). The fingerprint (not seg_id) keys the
            # persisted store so the choice survives restarts and a
            # refreshed pack re-tunes. bool(agg_desc) is part of the
            # shape bucket: the agg (emit-match) and agg-less variants
            # of the same desc must tune independently, or whichever
            # runs first would pin — and persist — the other's backend
            # choice
            tune_key = (seg_cache_key(segment), segment.capacity, desc,
                        k_eff, b_pad, bool(agg_desc))
            pallas_reason = _bundle_pallas_reason(
                fused[0], agg_desc, ck,
                _bundle_pos_width(fused[0], segment.text),
                _bundle_fwd_width(fused[0], segment.text))
            if pallas_reason is not None:
                _fused_stats.record_pallas_reject(pallas_reason)

            def _run(backend_name, _f=fused[0]):
                # audited (graftlint PR): this block_until_ready is the
                # autotuner's stopwatch — the sync IS the measurement.
                # It runs only on a key's first execution (choice then
                # cached + persisted), serialized by _autotune_lock, so
                # the steady-state query path never passes through it.
                jax.block_until_ready(_segment_program_packed(
                    dev, wire_dev, live_dev, live_views,
                    pack_static=pack_static, desc=desc,
                    agg_desc=agg_desc, cap=segment.capacity, k=k_eff,
                    sort_spec=sort_spec, fused=(_f, backend_name)))

            fused = (fused[0],
                     resolve_fused_backend(
                         tune_key, ck, _run,
                         pallas_candidate=pallas_reason is None,
                         persist_keys=(autotune_persist_key(
                             seg_cache_key(segment), segment.capacity,
                             desc, k_eff, bool(agg_desc)),)))
        # value-based cache key (id(segment) could be reused after GC
        # and serve a stale key_dtype): the only segment-dependent
        # layout input is the sort-key dtype, so resolve it here
        key_dtype = _sort_key_dtype(segment, sort_spec)
        layout = _output_layout(
            (segment.capacity, key_dtype, desc, agg_desc, k_eff,
             sort_spec, pack_static[1],
             # the dev tree STRUCTURE keys the eval path too: lazy
             # uploads (kw_sorted/num_sorted/script_vals/view
             # projections) switch interpreter branches, so a layout
             # cached before an ensure_* mutation must not serve the
             # program after it
             jax.tree_util.tree_structure(dev),
             tuple(sorted(live_views)), fused),
            dev, params, live_dev, live_views, agg_params, sort_params,
            desc, agg_desc, segment.capacity, k_eff, sort_spec,
            fused=fused)
        if fused is not None:
            layout = {**layout, "walks": (
                _fused_walk(fused[0], fused[1], n_tiles, k_eff),)}
    except BaseException:
        req_hold.release()
        raise
    plan = SegmentPlan(desc, agg_desc, sort_spec, pack_static, wire_dev,
                       k_eff, fused, reject, pallas_reason, tune_key,
                       _fused_switches() if fused is not None else None,
                       est, layout, epoch)
    buf, layout = _launch_plan(segment, live, plan, req_hold, bind,
                               (dev, live_dev, live_views))
    if keep is not None:
        keep.append(plan)
    return buf, layout, n_real


def _collect(out, layout, leaf):
    """The `collect` phase, after which `leaf` is the `unpack` phase.
    The launch asked for the device-to-host copy (`_start_fetch`), so
    the phase times what is left of device time, copy and the runtime's
    wake-up when the launching thread gets here: the whole round trip
    only where the collect follows its launch at once. Counted in
    `collects` (`prefetched` where the layout says the launch started
    the copy) and timed in two parts that sum to the phase, each
    weighted like it (`collect_parts`): `compute`, up to the return of
    `block_until_ready` (what is left of the program, and the runtime
    waking the thread), and `copy`, the rest (what is left of the copy,
    and the conversion to host arrays); spans `collect:compute` and
    `collect:copy` while a trace runs. A device error raised at either
    leaves through the same `finally`, its time all `compute` where the
    program failed. `unpack` begins by releasing the breaker hold: the
    transient device accumulators are dead once the result is on host —
    release NOW instead of waiting for GC. Released on the error exit
    too (a failed collect must not pin breaker bytes until collection
    of the GC backstop)."""
    _collects.counter("total").inc()
    if layout.get("_prefetched"):
        _collects.counter("prefetched").inc()
    compute = None
    try:
        with _trace_guard.trap():
            with _enclosing("collect:compute", **leaf.args):
                jax.block_until_ready(out)
            compute = leaf.lap()
            with _enclosing("collect:copy", **leaf.args):
                return jax.device_get(out)
    finally:
        whole = leaf.switch("unpack")
        if compute is None:
            compute = whole
        for _ in range(leaf.weight):
            _collect_compute.inc(compute)
            _collect_copy.inc(whole - compute)
        hold = layout.get("_breaker_hold")
        if hold is not None:
            hold.release()


def collect_segment_result(out, layout, n_real: int):
    """Sync + unpack + slice an async result back to the true B."""
    with _phase("collect", **layout.get("_span_args") or {}) as leaf:
        host = _collect(out, layout, leaf)
        if layout.get("tiered"):
            return _unpack_tiered(host, layout, n_real)
        return _unpack_wire(host[:n_real], layout, n_real)


def _unpack_tiered(host, layout, n_real: int):
    """Tiered chunked walk (see _execute_tiered): `host` is the final
    state pytree, not a packed wire buffer — slice the padding, and
    fold the never-fetched (I/O-filtered) tiles into the prune counters
    as the hard skips they are."""
    k = layout["k"]
    if k > 0:
        top_s, top_i, totals, prune, agg_tree = host
        top_score = np.asarray(top_s)[:n_real]
        top_idx = np.asarray(top_i)[:n_real].astype(np.int32)
    else:
        totals, prune, agg_tree = host
        top_score = np.zeros((n_real, 0), np.float32)
        top_idx = np.zeros((n_real, 0), np.int32)
    total = np.asarray(totals)[:n_real].astype(np.int32)
    top_missing = np.zeros_like(top_idx, dtype=bool)
    hard, thr, examined = (float(x) for x in np.asarray(prune))
    sk = float(layout.get("skipped_tiles", 0))
    _fused_stats.record_prune(
        hard + sk, thr, examined + sk,
        positional=bool(layout.get("fused_positional")),
        steps=_walk_steps(examined, layout.get("walks", ())))
    # agg leaves round-trip through f32 on the packed-wire path;
    # mirror that here so reduce-side inputs are byte-identical
    agg_leaves = [np.asarray(leaf)[:n_real].astype(np.float32)
                  for leaf in jax.tree_util.tree_leaves(agg_tree)]
    agg_out = jax.tree_util.tree_unflatten(layout["agg_treedef"],
                                           agg_leaves)
    return (top_score, top_score, top_idx, total, top_missing), agg_out


def _unpack_wire(wire, layout, n_real: int):
    k = layout["k"]
    key_is_float = layout["key_dtype"] == np.float32
    n_i = 2 * k + 1 + (0 if key_is_float else k)
    ibuf = wire[:, :n_i]
    n_i_total = n_i
    if layout.get("resident"):
        # resident stepped programs append the device-side timed_out
        # verdict as one trailing i32 column: a laggard step that the
        # per-chunk deadline check preempted surfaces HERE as the same
        # SearchTimeoutError the cooperative path raises — after the
        # breaker hold above is already released
        n_i_total += 1
        if bool(wire[:, n_i].any()):
            _resident.stats.preempted_by_deadline.inc()
            sk = layout.get("shard_key") or (None, None)
            raise SearchTimeoutError(sk[0], sk[1])
    fbuf = np.ascontiguousarray(wire[:, n_i_total:]).view(np.float32)
    top_score = fbuf[:, 0:k]
    top_idx = ibuf[:, 0:k]
    total = ibuf[:, k]
    top_missing = ibuf[:, k + 1: 2 * k + 1].astype(bool)
    if key_is_float:
        top_key = fbuf[:, k: 2 * k]
        f_off = 2 * k
    else:
        top_key = ibuf[:, 2 * k + 1: 3 * k + 1]
        f_off = k
    prune = fbuf[:, f_off: f_off + 3]
    f_off += 3
    if layout.get("fused"):
        hard, thr, examined = prune.sum(axis=0)
        _fused_stats.record_prune(
            hard, thr, examined,
            positional=bool(layout.get("fused_positional")),
            steps=_walk_steps(examined, layout.get("walks", ())))
    agg_leaves = []
    for shape in layout["agg_shapes"]:
        size = int(np.prod(shape[1:])) if len(shape) > 1 else 1
        leaf = fbuf[:, f_off: f_off + size]
        agg_leaves.append(leaf.reshape(n_real, *shape[1:]))
        f_off += size
    agg_out = jax.tree_util.tree_unflatten(layout["agg_treedef"], agg_leaves)
    return (top_score, top_key, top_idx, total, top_missing), agg_out


# ---------------------------------------------------------------------------
# Tiered tile residency (index/tiering.py): the chunked paged walk
#
# A pack over the HBM budget keeps its forward-index columns in host
# RAM, partitioned into the SAME SCORE_TILE doc tiles the block-max
# walk prunes on. A fused-admitted dispatch then runs:
#
#   1. the bound computation over the PERMANENTLY-RESIDENT summaries,
#      on host (ops/scoring.bundle_tile_bounds_np) — tiles no query in
#      the batch can match are never fetched: pruning as an I/O filter;
#   2. a chunked walk over the survivor tiles in ASCENDING tile order:
#      each chunk's cold tiles stream host->device through the LRU
#      tile pager while the PREVIOUS chunk's program executes (async
#      dispatch = upload/compute overlap), and one jitted chunk program
#      evaluates the ordinary fused engine (XLA or Pallas — the same
#      eval_fused_topk/eval_fused_match entries) over the compacted
#      chunk columns, carrying the running top-k state across chunks
#      exactly like the base->delta pack chaining;
#   3. when the plan has aggregations, the exact per-chunk match masks
#      scatter into a full [B, cap] mask and ONE aggregation program
#      runs over the resident doc-value columns.
#
# Byte-identity argument: survivor tiles ascend, so the compacted walk
# visits the same matchable tiles in the same order as the full walk
# (skipped tiles are exactly the can_match-false tiles, which the full
# walk hard-skips without touching results); doc ids translate through
# a monotone slot->tile map, so lax.top_k tie order is preserved; and
# the running threshold state at every survivor tile equals the full
# walk's state at that tile. Totals and match masks are exact because
# only provably-matchless tiles are skipped. Chunk shapes are static
# (pow2-bucketed chunk_tiles), so page events never recompile, and no
# fingerprint/cache_key input changes with residency state.
# ---------------------------------------------------------------------------


def _bundle_inputs_np(desc: tuple, params: tuple, bundle: tuple):
    """HOST mirror of _bundle_inputs over the not-yet-uploaded numpy
    params — feeds the tiered pager's survivor computation
    (bundle_tile_bounds_np). Walks desc/params in the exact group order
    the classifier emitted the bundle in; keep in lockstep with
    _bundle_inputs above."""
    B = _batch_size(params)
    ones_i = np.ones((B,), np.int32)
    ones_f = np.ones((B,), np.float32)

    def leaf_inputs(d, p):
        if d[0] == "terms_dense":
            qt, wq = p
            return np.asarray(qt), np.asarray(wq)
        tid, weight = p                  # term_text: single-term Q=1
        return np.asarray(tid)[:, None], np.asarray(weight)[:, None]

    if desc[0] != "bool":
        if isinstance(desc[0], str) and positional_prefix(desc[0]):
            return (tuple(np.asarray(x) for x in params)
                    + (ones_i, ones_f),), ones_i, None
        qt, wq = leaf_inputs(desc, params)
        return ((qt, wq, ones_i, ones_f),), ones_i, None
    _, d_must, d_should, d_not, d_filter = desc
    p_must, p_should, p_not, p_filter, msm, boost = params
    groups = {"must": (d_must, p_must), "should": (d_should, p_should),
              "must_not": (d_not, p_not), "filter": (d_filter, p_filter)}
    nxt = {r: 0 for r in groups}
    out = []
    for role, kind, _field, wrapped in bundle:
        dg, pg = groups[role]
        d, p = dg[nxt[role]], pg[nxt[role]]
        nxt[role] += 1
        if kind in _FUSED_RANGE_KINDS:
            lo, hi, _boost_r = p
            out.append((np.asarray(lo), np.asarray(hi)))
        elif wrapped:
            _, _cm, c_should, _cn, _cf = d
            _pm, pc_should, _pn, _pf, msm_c, boost_c = p
            if positional_prefix(kind):
                out.append(tuple(np.asarray(x) for x in pc_should[0])
                           + (np.asarray(msm_c), np.asarray(boost_c)))
            else:
                qt, wq = leaf_inputs(c_should[0], pc_should[0])
                out.append((qt, wq, np.asarray(msm_c),
                            np.asarray(boost_c)))
        elif isinstance(kind, str) and positional_prefix(kind):
            out.append(tuple(np.asarray(x) for x in p)
                       + (ones_i, ones_f))
        else:
            qt, wq = leaf_inputs(d, p)
            out.append((qt, wq, ones_i, ones_f))
    return tuple(out), np.asarray(msm), np.asarray(boost)


def ensure_fwd_cols(segment: Segment) -> None:
    """Full-residency fallback for a PAGED pack serving a plan outside
    the fused tiered path (field sort, unfused clause kinds, rescore):
    upload the forward-index columns after all — breaker-accounted with
    the segment-GC backstop — drop the pack's paged tiles, and record
    the segment un-paged so later dispatches take the ordinary path.
    May trip the fielddata breaker when the pack genuinely cannot fit;
    that surfaces as the same CircuitBreakingError an oversized
    ordinary upload raises. Concurrent callers race benignly: the
    membership check keeps the dev tree single-valued, and a doubled
    hold releases at segment GC via the backstop."""
    paged = _tiering.paged_fields(segment)
    if not paged:
        return
    dev = device_arrays(segment)
    from ..utils.breaker import breaker_service
    fielddata = breaker_service().breaker("fielddata")
    for f in sorted(paged):
        tf = dev["text"].get(f)
        if tf is None or "fwd_tids" in tf:
            continue
        pf = segment.text[f]
        pos = getattr(pf, "fwd_pos", None)
        hold = fielddata.hold(pf.fwd_tids.nbytes + pf.fwd_imps.nbytes
                              + (pos.nbytes if pos is not None else 0))
        try:
            tf["fwd_tids"] = jnp.asarray(pf.fwd_tids)
            tf["fwd_imps"] = jnp.asarray(pf.fwd_imps)
            if pos is not None:
                tf["fwd_pos"] = jnp.asarray(pos)
        except BaseException:
            hold.release()
            raise
        _gc_backstop(segment, hold)
        segment.device_changed()
    _tiering.clear_paged(segment)
    _tiering.stats.unfused_full_uploads.inc()


def _tiered_backend(segment: Segment, bundle: tuple, desc, agg_desc,
                    k_eff: int, b_pad: int, ck: int) -> str:
    """Engine for the tiered chunk walk, resolved WITHOUT timing (the
    host-driven chunk loop cannot wall-clock a tune): the resident
    resolution ladder verbatim — forced env > cached/persisted tuned
    choice, same Pallas-candidacy gates (a compacted chunk is just a
    smaller pack on the same SCORE_TILE grid, so kernel availability
    is identical) — except that an UNDECIDED shape runs XLA instead of
    staying cold: both engines are byte-identical, so an untuned pack
    walking chunks on the slower engine is a perf note, not a
    correctness event."""
    return _resident_backend(segment, bundle, desc, agg_desc, k_eff,
                             b_pad, ck) or "xla"


def _tiered_chunk_cols(seg_res: dict, live: jax.Array, tiles_dev,
                       tile_bufs: dict, bundle: tuple, tile: int,
                       chunk_tiles: int):
    """Compacted chunk columns (traced): paged forward tiles
    concatenate into [chunk_cap, L] arrays, everything else — tile_max
    summaries, numeric filter columns + extrema, live mask — gathers
    on-device from the resident arrays. Pad slots (tiles_dev < 0) map
    to out-of-bounds gathers whose fills make them unmatchable: live
    False, tile_max 0, empty numeric extrema intervals."""
    cap = live.shape[0]
    n_full = cap // tile
    sane = jnp.where(tiles_dev < 0, n_full, tiles_dev)
    docs = (sane[:, None] * tile
            + jnp.arange(tile, dtype=jnp.int32)[None, :]).reshape(-1)
    live_c = jnp.take(live, docs, mode="fill", fill_value=False)
    text_fields = bundle_text_fields(bundle)
    num_fields = tuple(dict.fromkeys(
        f for _r, kd, f, _w in bundle if kd in _FUSED_RANGE_KINDS))
    pos_fields = bundle_pos_fields(bundle)
    text_cols = {}
    for f in text_fields:
        parts = tile_bufs[f]
        tids_parts, imps_parts = parts[0], parts[1]
        text_cols[f] = {
            "fwd_tids": jnp.concatenate(tids_parts, axis=0),
            "fwd_imps": jnp.concatenate(imps_parts, axis=0),
            "tile_max": seg_res["text"][f]["tile_max"].take(sane),
        }
        if f in pos_fields:
            # paged position tiles concatenate like the forward pair;
            # the per-doc length norms are permanently resident and
            # gather through the same slot->tile map (pad fill 1.0 —
            # harmless: pad docs decode to zero phrase freq anyway)
            text_cols[f]["fwd_pos"] = jnp.concatenate(parts[2], axis=0)
            text_cols[f]["k1ln"] = jnp.take(
                seg_res["text"][f]["k1ln"], docs, mode="fill",
                fill_value=1.0)
            text_cols[f]["lnorm"] = jnp.take(
                seg_res["text"][f]["lnorm"], docs, mode="fill",
                fill_value=1.0)
    num_cols = {}
    for f in num_fields:
        e = seg_res["num"][f]
        if e["values"].dtype == jnp.int32:
            lo_fill = int(np.iinfo(np.int32).max)
            hi_fill = int(np.iinfo(np.int32).min)
        else:
            lo_fill, hi_fill = float("inf"), float("-inf")
        num_cols[f] = {
            "values": jnp.take(e["values"], docs, mode="fill",
                               fill_value=0),
            "exists": jnp.take(e["exists"], docs, mode="fill",
                               fill_value=False),
            "tile_lo": jnp.take(e["tile_lo"], sane, mode="fill",
                                fill_value=lo_fill),
            "tile_hi": jnp.take(e["tile_hi"], sane, mode="fill",
                                fill_value=hi_fill),
        }
    return {"text": text_cols, "num": num_cols}, live_c, docs


@partial(jax.jit, static_argnames=("pack_static", "desc", "cap", "k",
                                   "tile", "chunk_tiles", "fused",
                                   "emit_match"))
def _tiered_chunk_program(seg_res: dict, wire, live: jax.Array,
                          tiles_dev, tile_bufs: dict, state, *,
                          pack_static, desc: tuple, cap: int, k: int,
                          tile: int, chunk_tiles: int, fused: tuple,
                          emit_match: bool):
    """One k>0 chunk of the tiered walk. The running top-k state enters
    with GLOBAL doc ids; they are encoded out of the chunk-local id
    range (+chunk_cap — locals are < chunk_cap by construction) so the
    engine's in-walk merge stays positional (existing-first, the tie
    rule), then every id decodes back to global through the monotone
    slot->tile map. Carried state: (top_s, top_i, totals, prune
    [, match_acc])."""
    params, _agg_params, _sort_params = _unpack_trees(wire, pack_static)
    bundle, _backend = fused
    chunk_cap = chunk_tiles * tile
    seg_c, live_c, docs = _tiered_chunk_cols(seg_res, live, tiles_dev,
                                             tile_bufs, bundle, tile,
                                             chunk_tiles)
    run_s, run_i, totals, prune = state[:4]
    out = eval_fused_topk(seg_c, desc, params, live_c, k, bundle,
                          fused[1], emit_match=emit_match,
                          init_topk=(run_s, run_i + chunk_cap))
    if emit_match:
        top_s, top_i, total_c, pruned, match = out
    else:
        top_s, top_i, total_c, pruned = out
    slot = jnp.clip(top_i // tile, 0, chunk_tiles - 1)
    base = jnp.take(tiles_dev, slot) * tile
    glob = jnp.where(top_i >= chunk_cap, top_i - chunk_cap,
                     base + top_i % tile)
    new = (top_s, glob, totals + total_c, prune + pruned)
    if emit_match:
        new = new + (state[4].at[:, docs].set(match, mode="drop"),)
    return new


@partial(jax.jit, static_argnames=("pack_static", "desc", "cap", "tile",
                                   "chunk_tiles", "fused", "emit_match"))
def _tiered_chunk_match_program(seg_res: dict, wire, live: jax.Array,
                                tiles_dev, tile_bufs: dict, state, *,
                                pack_static, desc: tuple, cap: int,
                                tile: int, chunk_tiles: int,
                                fused: tuple, emit_match: bool):
    """The k == 0 (match-mask-only) chunk twin: exact totals and, when
    an aggregation pass follows, the exact match mask scattered into
    the global [B, cap] accumulator. Carried state: (totals, prune
    [, match_acc])."""
    params, _agg_params, _sort_params = _unpack_trees(wire, pack_static)
    bundle, _backend = fused
    seg_c, live_c, docs = _tiered_chunk_cols(seg_res, live, tiles_dev,
                                             tile_bufs, bundle, tile,
                                             chunk_tiles)
    out = eval_fused_match(seg_c, desc, params, live_c, bundle,
                           fused[1], emit_match=emit_match)
    if emit_match:
        total_c, pruned, match = out
        return (state[0] + total_c, state[1] + pruned,
                state[2].at[:, docs].set(match, mode="drop"))
    total_c, pruned = out
    return (state[0] + total_c, state[1] + pruned)


@partial(jax.jit, static_argnames=("pack_static", "desc", "agg_desc",
                                   "cap"))
def _tiered_agg_program(seg: dict, wire, live_views: dict,
                        match: jax.Array, *, pack_static, desc: tuple,
                        agg_desc: tuple, cap: int):
    """ONE aggregation pass over the assembled exact match mask and the
    RESIDENT doc-value columns — the same eval_aggs + sorted-view
    machinery the fully-resident program runs, fed the same mask, so
    agg trees are identical."""
    params, agg_params, _sort_params = _unpack_trees(wire, pack_static)
    B = _batch_size(params)
    plan = _agg_view_plan(desc, agg_desc, agg_params, seg, live_views)
    views = _ViewMasks(desc, params, seg, live_views, cap, B)
    return eval_aggs(agg_desc, agg_params, seg, match, views=views,
                     plan=plan)


def _execute_tiered(segment: Segment, live, desc: tuple, params: tuple,
                    agg_desc: tuple, agg_params: tuple,
                    sort_spec: tuple, sort_params: tuple, bundle: tuple,
                    k_eff: int, b_pad: int, deadline: float | None,
                    shard_key: tuple | None, n_real: int, bind=None):
    """Serve one fused-admitted dispatch from a PAGED pack via the
    chunked tiered walk (see the section comment above). Returns
    (state_tuple, layout, n_real) for collect_segment_result — the
    layout carries "tiered": True and collect fetches the state pytree
    instead of a packed wire buffer. The deadline is checked
    cooperatively at every chunk boundary (finer than the cold path's
    collect-only check); residency stays with the tile pager, so no
    resident executable is pinned for paged packs."""
    store = _tiering.store_for(segment)
    cap = segment.capacity
    tile = store.tile
    ct = min(_tiering.chunk_tiles(), next_pow2(store.n_tiles))
    emit = bool(agg_desc)
    ck = min(max(k_eff, 0), tile)
    backend = _tiered_backend(segment, bundle, desc, agg_desc, k_eff,
                              b_pad, ck)
    fused = (bundle, backend)
    _tiering.stats.tiered_dispatches.inc()
    text_fields = bundle_text_fields(bundle)
    pos_fields = bundle_pos_fields(bundle)
    num_fields = tuple(dict.fromkeys(
        f for _r, kd, f, _w in bundle if kd in _FUSED_RANGE_KINDS))
    # -- survivor tiles from the resident summaries (host oracle) ------
    cl_np, msm_np, boost_np = _bundle_inputs_np(desc, params, bundle)
    from ..ops.scoring import bundle_tile_bounds_np
    can, _bound = bundle_tile_bounds_np(
        bundle, cl_np, {f: segment.text[f].tile_max for f in text_fields},
        {f: store.extrema(segment, f) for f in num_fields},
        msm_np, boost_np)
    surv = np.nonzero(can.any(axis=0))[0]
    skipped = int(store.n_tiles - surv.size)
    _tiering.note_prune_skipped(skipped)
    k_run = min(k_eff, cap)
    row_elems = (ct * tile + ct * max(min(k_run, tile), 1)
                 + (cap if emit else 0)
                 + _bundle_pos_width(bundle, segment.text) * tile)
    from ..utils.breaker import breaker_service
    req_hold = breaker_service().breaker("request").hold(
        b_pad * row_elems * 8)
    try:
        dev = device_arrays(segment)
        live_dev = _device_live(segment, live)
        live_views = _live_views_for(segment, live_dev, agg_desc)
        wire, pack_static = _pack_trees(params, agg_params, sort_params)
        wire_dev = jax.device_put(wire)
        seg_res = {
            "text": {f: {"tile_max": dev["text"][f]["tile_max"],
                         **({"k1ln": dev["text"][f]["k1ln"],
                             "lnorm": dev["text"][f]["lnorm"]}
                            if f in pos_fields else {})}
                     for f in text_fields},
            "num": {f: {kk: dev["num"][f][kk]
                        for kk in ("values", "exists", "tile_lo",
                                   "tile_hi")}
                    for f in num_fields},
        }
        # initial walk state staged via EXPLICIT device_put: the tiered
        # driver runs outside jit, where an eager jnp.zeros would be an
        # implicit host->device transfer (disallowed under the armed
        # trace guard — page events must stay transfer-clean except for
        # their explicit tile stages)
        if k_run > 0:
            state = (jax.device_put(np.full((b_pad, k_run), -np.inf,
                                            np.float32)),
                     jax.device_put(np.zeros((b_pad, k_run), np.int32)),
                     jax.device_put(np.zeros((b_pad,), np.int32)),
                     jax.device_put(np.zeros((3,), np.float32)))
        else:
            state = (jax.device_put(np.zeros((b_pad,), np.int32)),
                     jax.device_put(np.zeros((3,), np.float32)))
        if emit:
            state = state + (jax.device_put(np.zeros((b_pad, cap),
                                                     bool)),)
        chunks = [surv[i: i + ct] for i in range(0, len(surv), ct)]

        def stage(tiles: np.ndarray):
            """Fetch one chunk's tiles through the LRU pager (misses
            device_put asynchronously — issued while the previous
            chunk's program is still executing, which IS the
            upload/compute overlap)."""
            padded = np.full(ct, -1, np.int64)
            padded[: len(tiles)] = tiles
            t0 = _time.perf_counter()
            bufs = _tiering.pager.fetch(store, text_fields, padded)
            ms = (_time.perf_counter() - t0) * 1000.0
            return jax.device_put(padded.astype(np.int32)), bufs, ms

        pending = stage(chunks[0]) if chunks else None
        for i, _tiles in enumerate(chunks):
            if deadline is not None and _time.monotonic() > deadline:
                sk = shard_key or (None, None)
                raise SearchTimeoutError(sk[0], sk[1])
            tiles_dev, bufs, _ms = pending
            with _trace_guard.trap(), \
                    _launch(bind, "tiered", "tiered_dispatch"):
                if k_run > 0:
                    state = _tiered_chunk_program(
                        seg_res, wire_dev, live_dev, tiles_dev, bufs,
                        state, pack_static=pack_static, desc=desc,
                        cap=cap, k=k_run, tile=tile, chunk_tiles=ct,
                        fused=fused, emit_match=emit)
                else:
                    state = _tiered_chunk_match_program(
                        seg_res, wire_dev, live_dev, tiles_dev, bufs,
                        state, pack_static=pack_static, desc=desc,
                        cap=cap, tile=tile, chunk_tiles=ct, fused=fused,
                        emit_match=emit)
            if i + 1 < len(chunks):
                # prefetch the NEXT chunk while this one executes
                pending = stage(chunks[i + 1])
                _tiering.record_overlap_ms(pending[2])
        agg_tree = {}
        if emit:
            with _trace_guard.trap(), \
                    _launch(bind, "tiered", "tiered_aggs"):
                agg_tree = _tiered_agg_program(
                    dev, wire_dev, live_views, state[-1],
                    pack_static=pack_static, desc=desc,
                    agg_desc=agg_desc, cap=cap)
        out = (state[:4] if k_run > 0 else state[:2]) + (agg_tree,)
    except BaseException:
        req_hold.release()
        raise
    out_leaves = jax.tree_util.tree_leaves(out)
    out_bytes = sum(int(getattr(leaf, "nbytes", 0)) for leaf in out_leaves)
    req_hold.shrink(max(out_bytes, 1))
    agg_leaves, agg_treedef = jax.tree_util.tree_flatten(agg_tree)
    layout = {
        "k": k_run,
        "key_dtype": np.dtype(np.float32),
        "agg_treedef": agg_treedef,
        "agg_shapes": [tuple(s.shape) for s in agg_leaves],
        "fused": True,
        "fused_positional": _bundle_positional(bundle),
        "tiered": True,
        "skipped_tiles": skipped,
        "walks": (_fused_walk(bundle, backend, ct, k_run),),
        "_breaker_hold": _gc_backstop(out_leaves[0] if out_leaves
                                      else None, req_hold),
        "_span_args": _span_args(bind),
    }
    return out, layout, n_real


def _pack_tune_key(base: Segment, delta: Segment, desc: tuple, k_eff: int,
                   b_pad: int, agg: bool) -> tuple:
    return ("pack", seg_cache_key(base), seg_cache_key(delta),
            base.capacity, delta.capacity, desc, k_eff, b_pad, agg)


def _pack_resident_backend(base: Segment, delta: Segment, bundle: tuple,
                           desc, agg_desc, k_eff: int, b_pad: int,
                           ck: int) -> str | None:
    """_resident_backend's base+delta twin: resolve the pack's engine
    without timing (forced env / cached / persisted), None = untuned
    (the cold dispatch tunes it and unblocks residency next time)."""
    forced = _os.environ.get("ES_TPU_FUSED_BACKEND", "").lower()
    if forced in ("pallas", "xla"):
        return forced
    if not _bundle_pallas_ok(bundle, agg_desc, ck,
                             max(_bundle_pos_width(bundle, base.text),
                                 _bundle_pos_width(bundle, delta.text)),
                             max(_bundle_fwd_width(bundle, base.text),
                                 _bundle_fwd_width(bundle, delta.text))):
        return "xla"
    choice = _autotune_choices.get(
        _pack_tune_key(base, delta, desc, k_eff, b_pad, bool(agg_desc)))
    if choice is None:
        entry = _autotune_persisted.get(autotune_persist_key(
            f"{seg_cache_key(base)}+{seg_cache_key(delta)}",
            base.capacity + delta.capacity, desc, k_eff, bool(agg_desc)))
        choice = entry["choice"] if entry is not None else None
    if choice is None:
        return None
    if choice == "pallas" and not resident_step_ok():
        return None
    return choice


def execute_pack_async(base: Segment, delta: Segment, live_b: np.ndarray,
                       live_d: np.ndarray, bounds_b: Sequence[Bound],
                       bounds_d: Sequence[Bound], k: int,
                       agg_desc: tuple = (), agg_params_b: tuple = (),
                       agg_params_d: tuple = (),
                       sort_spec: tuple = ("_score",),
                       deadline: float | None = None,
                       step_budget=None, shard_key: tuple | None = None,
                       bind=None):
    """Dispatch one batched query against a (base, delta) generation
    pair as ONE device program (see _pack_body), without syncing.

    Returns (buf, layout, n_real) for collect_pack_result — or None
    when the plan/pack pair is not pack-admissible (caller falls back
    to the ordinary per-segment dispatches; responses are identical
    either way, this is purely the one-round-trip fast path). Autotune
    and resident keys embed BOTH generations' cache keys, so a
    refresh's delta epoch bump re-keys NOTHING; only compaction (a new
    base fingerprint) does."""
    n_real = len(bounds_b)
    if n_real == 0 or len(bounds_d) != n_real:
        return None
    if tuple(sort_spec) != ("_score",):
        return None
    b_pad = next_pow2(n_real, floor=1)
    if b_pad != n_real:
        bounds_b = list(bounds_b) + [bounds_b[-1]] * (b_pad - n_real)
        bounds_d = list(bounds_d) + [bounds_d[-1]] * (b_pad - n_real)
    desc, params_b = finalize(bounds_b)
    desc_d, params_d = finalize(bounds_d)
    if desc != desc_d:
        return None  # segment-local binds diverged structurally
    cap_b, cap_d = base.capacity, delta.capacity
    k_eff = min(k, cap_b + cap_d)
    # tiered residency: a paged generation (usually the base — deltas
    # are compaction-bounded) dispatches per-segment, where the tiered
    # chunked walk serves it; the one-round-trip pack program assumes a
    # fully-resident pair. Responses are identical either way.
    if _tiering.activate(base) or _tiering.activate(delta):
        return None
    bundle, _reject = _fused_plan_bundle(desc, k_eff, agg_desc, sort_spec,
                                         allow_k0=True)
    if bundle is None:
        return None
    if _fused_pack_ok(base, bundle) is not None \
            or _fused_pack_ok(delta, bundle) is not None:
        return None
    if not _fused_params_ok(desc, params_b, bundle) \
            or not _fused_params_ok(desc, params_d, bundle):
        return None
    f0 = bundle_primary_field(bundle)
    n_tiles_b = base.text[f0].tile_max.n_tiles
    n_tiles_d = delta.text[f0].tile_max.n_tiles
    ck = max(min(k_eff, cap_b // n_tiles_b),
             min(k_eff, cap_d // n_tiles_d))
    n_vec = sum(kd in _FUSED_VEC_KINDS for _r, kd, _f, _w in bundle)
    row_elems = (_fused_row_elems(cap_b, n_tiles_b, k_eff,
                                  emit_match=bool(agg_desc),
                                  vec_clauses=n_vec,
                                  pos_width=_bundle_pos_width(
                                      bundle, base.text))
                 + _fused_row_elems(cap_d, n_tiles_d, k_eff,
                                    emit_match=bool(agg_desc),
                                    vec_clauses=n_vec,
                                    pos_width=_bundle_pos_width(
                                        bundle, delta.text)))
    if _chunk_b(b_pad, row_elems) < b_pad:
        # a batch this wide needs the per-segment path's B-chunked
        # body (the pack body runs one un-chunked walk so its carried
        # top-k state spans the whole batch); fall back rather than
        # hold a chunk-budget-busting transient
        return None
    _fused_stats.record_admit(positional=_bundle_positional(bundle))
    if _resident.enabled():
        res_backend = _pack_resident_backend(base, delta, bundle, desc,
                                             agg_desc, k_eff, b_pad, ck)
        if res_backend is not None:
            return _execute_pack_resident(
                base, delta, live_b, live_d, desc, params_b, params_d,
                agg_desc, agg_params_b, agg_params_d, bundle,
                res_backend, k_eff, b_pad, deadline, step_budget,
                shard_key, n_real, bind)
        _resident.stats.cold_dispatches.inc()
    from ..utils.breaker import breaker_service
    req_hold = breaker_service().breaker("request").hold(
        b_pad * row_elems * 8)
    try:
        dev_b, dev_d = device_arrays(base), device_arrays(delta)
        live_dev_b = _device_live(base, live_b)
        live_dev_d = _device_live(delta, live_d)
        views_b = _live_views_for(base, live_dev_b, agg_desc)
        views_d = _live_views_for(delta, live_dev_d, agg_desc)
        wire, pack_static = _pack_trees(params_b, params_d,
                                        agg_params_b, agg_params_d)
        wire_dev = jnp.asarray(wire)
        tune_key = _pack_tune_key(base, delta, desc, k_eff, b_pad,
                                  bool(agg_desc))
        pallas_reason = _bundle_pallas_reason(
            bundle, agg_desc, ck,
            max(_bundle_pos_width(bundle, base.text),
                _bundle_pos_width(bundle, delta.text)),
            max(_bundle_fwd_width(bundle, base.text),
                _bundle_fwd_width(bundle, delta.text)))
        if pallas_reason is not None:
            _fused_stats.record_pallas_reject(pallas_reason)

        def _run(backend_name):
            # the autotuner's stopwatch (first execution per key only,
            # serialized by _autotune_lock — same discipline as the
            # single-segment tuner)
            jax.block_until_ready(_pack_program_packed(
                dev_b, dev_d, wire_dev, live_dev_b, live_dev_d,
                views_b, views_d, pack_static=pack_static, desc=desc,
                agg_desc=agg_desc, cap_b=cap_b, cap_d=cap_d, k=k_eff,
                fused=(bundle, backend_name)))

        fused = (bundle,
                 resolve_fused_backend(
                     tune_key, ck, _run,
                     pallas_candidate=pallas_reason is None,
                     persist_keys=(autotune_persist_key(
                         f"{seg_cache_key(base)}+{seg_cache_key(delta)}",
                         cap_b + cap_d, desc, k_eff, bool(agg_desc)),)))
        layout = _pack_output_layout(
            (cap_b, cap_d, desc, agg_desc, k_eff, pack_static[1],
             jax.tree_util.tree_structure(dev_b),
             jax.tree_util.tree_structure(dev_d),
             tuple(sorted(views_b)), tuple(sorted(views_d)), fused),
            dev_b, dev_d, params_b, params_d, live_dev_b, live_dev_d,
            views_b, views_d, agg_params_b, agg_params_d, desc, agg_desc,
            cap_b, cap_d, k_eff, fused)
        with _trace_guard.trap(), _launch(bind, "fused_" + fused[1]):
            buf = _pack_program_packed(
                dev_b, dev_d, wire_dev, live_dev_b, live_dev_d,
                views_b, views_d, pack_static=pack_static, desc=desc,
                agg_desc=agg_desc, cap_b=cap_b, cap_d=cap_d, k=k_eff,
                fused=fused)
            fetch = _start_fetch(buf)
    except BaseException:
        req_hold.release()
        raise
    est = b_pad * row_elems * 8
    out_bytes = min(est, int(getattr(buf, "nbytes", 0)) or est)
    req_hold.shrink(out_bytes)
    layout = {**layout, **fetch,
              "walks": (_fused_walk(bundle, fused[1], n_tiles_b, k_eff),
                        _fused_walk(bundle, fused[1], n_tiles_d, k_eff)),
              "_breaker_hold": _gc_backstop(buf, req_hold),
              "_span_args": _span_args(bind)}
    return buf, layout, n_real


def _pack_output_layout(cache_key, dev_b, dev_d, params_b, params_d,
                        live_b, live_d, views_b, views_d, agg_params_b,
                        agg_params_d, desc, agg_desc, cap_b, cap_d, k,
                        fused):
    hit = _out_layout_cache.get(cache_key)
    if hit is not None:
        return hit
    shapes = jax.eval_shape(
        partial(_pack_body, desc=desc, agg_desc=agg_desc, cap_b=cap_b,
                cap_d=cap_d, k=k, fused=fused),
        dev_b, dev_d, params_b, params_d, live_b, live_d, views_b,
        views_d, agg_params_b, agg_params_d)
    (ts, _tk, _ti, _tt, _tm), agg_shapes, _prune = shapes
    agg_leaves, agg_treedef = jax.tree_util.tree_flatten(agg_shapes)
    layout = {
        "k": int(ts.shape[1]),
        "key_dtype": np.dtype(np.float32),
        "agg_treedef": agg_treedef,
        "agg_shapes": [tuple(s.shape) for s in agg_leaves],
        "fused": True,
        "fused_positional": _bundle_positional(fused[0]),
        "pack": True,
        "cap_b": cap_b,
    }
    with _out_layout_lock:
        layout = _out_layout_cache.setdefault(cache_key, layout)
    return layout


def _execute_pack_resident(base: Segment, delta: Segment, live_b, live_d,
                           desc: tuple, params_b: tuple, params_d: tuple,
                           agg_desc: tuple, agg_params_b: tuple,
                           agg_params_d: tuple, bundle: tuple,
                           backend: str, k_eff: int, b_pad: int,
                           deadline: float | None, step_budget,
                           shard_key: tuple | None, n_real: int,
                           bind=None):
    """Serve a base+delta dispatch through a pinned resident entry.
    The entry key embeds BOTH generations' cache keys and the exact
    pack shape signatures — a refresh's delta rebuild (same pow2
    buckets) lands on the SAME pinned executable and just feeds it the
    new epoch's arrays; the delta extent only re-keys when its pow2
    bucket grows. This is the zero-recompile refresh the counters
    (refresh_reuses) prove."""
    cap_b, cap_d = base.capacity, delta.capacity
    k_res = (min(next_pow2(max(k_eff, 1), floor=1), cap_b + cap_d)
             if k_eff > 0 else 0)
    fused = (bundle, backend)
    f0 = bundle_primary_field(bundle)
    n_tiles_b = base.text[f0].tile_max.n_tiles
    n_tiles_d = delta.text[f0].tile_max.n_tiles
    chunk_tiles = _resident_chunk_tiles(n_tiles_b)
    n_chunks = -(-n_tiles_b // chunk_tiles)
    n_vec = sum(kd in _FUSED_VEC_KINDS for _r, kd, _f, _w in bundle)
    # the step's chunks ride the base walk only (_pack_body)
    row_elems = (_fused_row_elems(cap_b, n_tiles_b, k_res,
                                  emit_match=bool(agg_desc),
                                  vec_clauses=n_vec,
                                  pos_width=_bundle_pos_width(
                                      bundle, base.text),
                                  chunk_tiles=chunk_tiles)
                 + _fused_row_elems(cap_d, n_tiles_d, k_res,
                                    emit_match=bool(agg_desc),
                                    vec_clauses=n_vec,
                                    pos_width=_bundle_pos_width(
                                        bundle, delta.text)))
    from ..utils.breaker import breaker_service
    est = b_pad * row_elems * 8
    req_hold = breaker_service().breaker("request").hold(est)
    try:
        dev_b, dev_d = device_arrays(base), device_arrays(delta)
        live_dev_b = _device_live(base, live_b)
        live_dev_d = _device_live(delta, live_d)
        views_b = _live_views_for(base, live_dev_b, agg_desc)
        views_d = _live_views_for(delta, live_dev_d, agg_desc)
        wire, pack_static = _pack_trees(params_b, params_d,
                                        agg_params_b, agg_params_d)
        t_stage = _time.perf_counter()
        wire_dev = jax.device_put(wire)
        hi, lo = _split_deadline(deadline)
        delay_ms = float(step_budget.take()) if step_budget is not None \
            else 0.0
        step_arr = jax.device_put(np.asarray(
            [hi, lo, delay_ms / n_chunks, delay_ms], np.float32))
        key = ("pack", seg_cache_key(base), seg_cache_key(delta),
               cap_b, cap_d, desc, agg_desc, k_res, b_pad,
               pack_static[1], jax.tree_util.tree_structure(dev_b),
               jax.tree_util.tree_structure(dev_d),
               tuple(sorted(views_b)), tuple(sorted(views_d)), bundle,
               backend, _dev_shape_sig(dev_b), _dev_shape_sig(dev_d))
        entry = _resident.cache.get(
            key, delta_epoch=getattr(delta, "delta_epoch", 0))
        if entry is None:
            _resident.stats.cold_dispatches.inc()
            import warnings
            with warnings.catch_warnings():
                warnings.filterwarnings(
                    "ignore", message="Some donated buffers were not")
                compiled = _resident_pack_program.lower(
                    dev_b, dev_d, wire_dev, live_dev_b, live_dev_d,
                    views_b, views_d, step_arr,
                    pack_static=pack_static, desc=desc,
                    agg_desc=agg_desc, cap_b=cap_b, cap_d=cap_d,
                    k=k_res, fused=fused,
                    chunk_tiles=chunk_tiles).compile()
            entry = _resident.ResidentEntry(
                key, label=repr((desc, k_res, b_pad, bool(agg_desc),
                                 backend, "pack")),
                compiled=compiled, seg_id=base.seg_id,
                fingerprint=base.fingerprint(),
                seg_ref=None,  # epoch segments die; the entry must not
                backend=backend,
                generation=seg_cache_key(delta),
                delta_epoch=getattr(delta, "delta_epoch", 0))
            _resident.cache.put(entry)
        layout = _pack_output_layout(
            (cap_b, cap_d, desc, agg_desc, k_res, pack_static[1],
             jax.tree_util.tree_structure(dev_b),
             jax.tree_util.tree_structure(dev_d),
             tuple(sorted(views_b)), tuple(sorted(views_d)), fused),
            dev_b, dev_d, params_b, params_d, live_dev_b, live_dev_d,
            views_b, views_d, agg_params_b, agg_params_d, desc, agg_desc,
            cap_b, cap_d, k_res, fused)
        with _trace_guard.trap(), \
                _launch(bind, "resident", "resident_dispatch"):
            buf = entry.compiled(dev_b, dev_d, wire_dev, live_dev_b,
                                 live_dev_d, views_b, views_d, step_arr)
        _resident.stats.staged_feed_overlap_ms.record(
            (_time.perf_counter() - t_stage) * 1000.0)
        fetch = _start_fetch(buf)
    except BaseException:
        req_hold.release()
        raise
    out_bytes = min(est, int(getattr(buf, "nbytes", 0)) or est)
    req_hold.shrink(out_bytes)
    layout = {**layout, **fetch, "resident": True, "shard_key": shard_key,
              "walks": (_fused_walk(bundle, backend, n_tiles_b, k_res,
                                    chunk_tiles),
                        _fused_walk(bundle, backend, n_tiles_d, k_res)),
              "_breaker_hold": _gc_backstop(buf, req_hold),
              "_span_args": _span_args(bind)}
    code_bytes = 0
    try:
        ma = entry.compiled.memory_analysis()
        code_bytes = int(getattr(ma, "generated_code_size_in_bytes", 0)
                         or 0)
    except Exception:  # noqa: BLE001 — backend-optional introspection
        pass
    try:
        entry.account(code_bytes + int(wire.nbytes) + out_bytes)
    except Exception:  # noqa: BLE001 — breaker trip on accounting
        _resident.cache.evict(entry.key)
    return buf, layout, n_real


def collect_pack_result(out, layout, n_real: int):
    """Collect a pack dispatch and split the merged selection back into
    PER-SEGMENT candidate lists (scores stay globally sorted; indices
    below cap_b are base rows, the rest delta rows offset by cap_b), so
    the ordinary cross-segment response merge consumes them unchanged —
    responses are byte-identical to two per-segment dispatches. Returns
    ([base_top, delta_top], [base_aggs, delta_aggs]); the top tuples
    carry a 6th element, the per-row VALID count (a split list can hold
    fewer than min(total, k) entries when the other side won the
    window)."""
    with _phase("collect", **layout.get("_span_args") or {}) as leaf:
        host = _collect(out, layout, leaf)
        return _unpack_pack(host[:n_real], layout, n_real)


def _unpack_pack(wire, layout, n_real: int):
    k = layout["k"]
    n_i = 2 * k + 2
    n_i_total = n_i
    if layout.get("resident"):
        n_i_total += 1
        if bool(wire[:, n_i].any()):
            _resident.stats.preempted_by_deadline.inc()
            sk = layout.get("shard_key") or (None, None)
            raise SearchTimeoutError(sk[0], sk[1])
    ibuf = wire[:, :n_i_total]
    fbuf = np.ascontiguousarray(wire[:, n_i_total:]).view(np.float32)
    top_idx = ibuf[:, :k]
    totals = ibuf[:, k: k + 2]
    top_score = fbuf[:, :k]
    prune = fbuf[:, k: k + 3]
    hard, thr, examined = prune.sum(axis=0)
    _fused_stats.record_prune(
        hard, thr, examined,
        positional=bool(layout.get("fused_positional")),
        steps=_walk_steps(examined, layout.get("walks", ())))
    f_off = k + 3
    agg_leaves = []
    for shape in layout["agg_shapes"]:
        size = int(np.prod(shape[1:])) if len(shape) > 1 else 1
        leaf = fbuf[:, f_off: f_off + size]
        agg_leaves.append(leaf.reshape(n_real, *shape[1:]))
        f_off += size
    agg_b, agg_d = jax.tree_util.tree_unflatten(layout["agg_treedef"],
                                                agg_leaves)
    cap_b = layout["cap_b"]
    B = n_real
    sb = np.full((B, k), -np.inf, np.float32)
    sd = np.full((B, k), -np.inf, np.float32)
    ib = np.zeros((B, k), np.int32)
    idd = np.zeros((B, k), np.int32)
    vb = np.zeros(B, np.int32)
    vd = np.zeros(B, np.int32)
    for r in range(B):
        valid = top_score[r] > -np.inf
        idxs = top_idx[r][valid]
        scs = top_score[r][valid]
        mb = idxs < cap_b
        nb = int(mb.sum())
        nd = int(valid.sum()) - nb
        sb[r, :nb] = scs[mb]
        ib[r, :nb] = idxs[mb]
        vb[r] = nb
        sd[r, :nd] = scs[~mb]
        idd[r, :nd] = idxs[~mb] - cap_b
        vd[r] = nd
    miss = np.zeros((B, k), bool)
    top_b = (sb, sb, ib, totals[:, 0], miss, vb)
    top_d = (sd, sd, idd, totals[:, 1], miss, vd)
    return [top_b, top_d], [agg_b, agg_d]


def execute_segment(segment: Segment, live: np.ndarray,
                    bounds: Sequence[Bound], k: int,
                    agg_desc: tuple = (), agg_params: tuple = (),
                    sort_spec: tuple = ("_score",), sort_params: tuple = ()):
    """Synchronous wrapper: dispatch + collect. Returns host numpy:
    (top_score [B,k], top_key, top_idx, total [B], top_missing), aggs."""
    out, layout, n_real = execute_segment_async(
        segment, live, bounds, k, agg_desc, agg_params, sort_spec, sort_params)
    return collect_segment_result(out, layout, n_real)
