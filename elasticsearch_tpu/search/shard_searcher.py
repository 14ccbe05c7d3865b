"""Shard-level search: segments -> merged hits + reduced aggs + fetch.

Reference analog: search/SearchService.java executeQueryPhase/
executeFetchPhase over an acquired searcher, plus the per-shard part of
SearchPhaseController. A ShardReader is the immutable
`Engine.acquireSearcher` analog: a point-in-time view over segments +
live masks. Cross-SEGMENT merging here mirrors Lucene's cross-leaf
collection; cross-SHARD merging lives in search/controller.py.
"""

from __future__ import annotations

import json
import pickle
import time
from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from ..index.mapping import MapperService
from ..index.segment import Segment
from ..utils import faults
from ..utils.errors import SearchParseError, SearchTimeoutError
from ..utils.profiler import phase, request_args
from .query_dsl import QueryParser, Query
from . import bound_plans
from .executor import (QueryBinder, execute_segment, execute_segment_async,
                       execute_pack_async, collect_segment_result,
                       collect_pack_result, plan_switches,
                       segment_plan_valid)
from .aggregations import (parse_aggs, ShardAggContext, reduce_aggs,
                           shard_partials, AggSpec)
from .highlight import parse_highlight, highlight_hit
from .suggest import parse_suggest, execute_suggest


def rewrite_knn_body(body: dict) -> dict:
    """Top-level HYBRID `knn` section -> plain query-DSL form: the knn
    spec becomes a `knn` SCORING CLAUSE in a bool should beside the
    query section (minimum_should_match 1 — a hit matches either
    side), combined by ES's hybrid score-sum rule. As a plain query it
    rides the whole fused substrate: bundle admission
    (executor._fused_plan_bundle), ONE device dispatch for BM25+vector
    top-k, pack (base+delta) dispatch, coalescing and pipelining on
    the DispatchScheduler, and the mesh shard_map program. Shared with
    parallel/distributed.py so single-chip and mesh rewrite
    identically."""
    spec = body["knn"]
    knn_node = {"knn": {"field": spec["field"],
                        "query_vector": spec["query_vector"],
                        "boost": float(spec.get("boost", 1.0))}}
    q = body.get("query")
    if q:
        new_q = {"bool": {"should": [q, knn_node],
                          "minimum_should_match": 1}}
    else:
        new_q = knn_node
    out = {k: v for k, v in body.items() if k not in ("knn", "query")}
    out["query"] = new_q
    return out


def knn_body_mode(body: dict, mappers: MapperService) -> tuple[str, str]:
    """(mode, admission reason) for a top-level `knn` search section:

      "rewrite"    — hybrid (a `query` section rides along): rewrite
                     onto the bundle substrate (rewrite_knn_body);
      "candidates" — pure knn: per-segment candidate top-k dispatched
                     ASYNC at submit (IVF probe where the segment
                     carries an index, exact scan otherwise) so vector
                     searches pipeline through the dispatch scheduler
                     like everything else; counted as "ivf" / "exact"
                     by what the submit actually used;
      "host"       — shapes the device paths cannot take (unmapped
                     field, unsupported similarity, nonpositive
                     boost): the legacy host-driven combine, counted
                     under admission.knn as host_fallback:<why>.
    """
    spec = body.get("knn") or {}
    field = spec.get("field")
    fm = mappers.field(field) if field else None
    if fm is None or fm.type != "dense_vector":
        return "host", "host_fallback:unmapped_field"
    sim = fm.similarity if fm.similarity else "cosine"
    from ..ops.knn import SIMILARITIES
    if sim not in SIMILARITIES:
        return "host", f"host_fallback:similarity:{sim}"
    try:
        if float(spec.get("boost", 1.0)) <= 0.0:
            return "host", "host_fallback:nonpositive_boost"
    except (TypeError, ValueError):
        return "host", "host_fallback:bad_boost"
    if body.get("query"):
        return "rewrite", "query_rewrite"
    return "candidates", "candidates"


def _pack_dispatch_enabled() -> bool:
    """Base+delta one-dispatch kill switch (`ES_TPU_PACK_DISPATCH=0`):
    with it off, delta-mode readers fall back to per-segment dispatches
    — an A/B and bisection tool; responses are identical either way."""
    import os
    return os.environ.get("ES_TPU_PACK_DISPATCH", "1").lower() not in (
        "0", "false", "off")


class _PendingMsearch:
    """In-flight half of a split msearch (see ShardReader.msearch_submit):
    device programs are already enqueued; finish() collects in
    submission order and builds responses. `group_sizes` (queries per
    coalesced signature group) and `dispatch_count` (device programs
    enqueued) feed the dispatch scheduler's stats."""

    __slots__ = ("reader", "bodies", "with_partials", "started",
                 "knn_idx", "knn_sub", "parsed", "multi", "main",
                 "groups", "no_segments", "group_sizes",
                 "dispatch_count", "deadline", "step_budget", "fetch_s")

    def __init__(self, reader: "ShardReader", bodies: list[dict],
                 with_partials: bool, started: float,
                 knn_idx: list[int], parsed: dict[int, dict]):
        self.reader = reader
        self.bodies = bodies
        self.with_partials = with_partials
        self.started = started
        self.knn_idx = knn_idx
        # per-knn-item ASYNC candidate dispatches (device programs
        # already enqueued at submit; None = legacy host path)
        self.knn_sub: dict[int, dict | None] = {}
        self.parsed = parsed
        self.multi: set[int] = set()
        self.main: list[int] = []
        self.groups: list[dict] = []
        self.no_segments = False
        self.group_sizes: list[int] = []
        self.dispatch_count = 0
        self.deadline: float | None = None
        # the `fetch` seconds each body's response took to build, which
        # the node's search stats read
        self.fetch_s: list[float] = [0.0] * len(bodies)
        # straggler budget for resident (device-stepped) dispatches —
        # None on the cold path (utils/faults.StepBudget)
        self.step_budget = None

    def finish(self) -> list[dict]:
        return self.reader._msearch_finish(self)


@dataclass
class ShardHit:
    doc_id: str
    score: float | None
    sort_key: float | None
    seg_ord: int
    local_doc: int
    source: bytes


class ShardReader:
    """Point-in-time searcher over a shard's segments (+ deletions)."""

    def __init__(self, index_name: str, segments: list[Segment],
                 live_masks: dict[str, np.ndarray], mapper: MapperService,
                 shard_id: int = 0):
        self.index_name = index_name
        self.segments = [s for s in segments if s.num_docs > 0]
        # live_all: engine deletions + parent-liveness propagated onto
        # nested child rows; live: additionally restricted to primary rows
        # (hidden block-join children never surface as hits — ref: Lucene
        # NonNestedDocsFilter)
        self.live_all = {}
        self.live = {}
        for s in self.segments:
            la = np.array(live_masks.get(s.seg_id, _default_live(s)),
                          dtype=bool, copy=True)
            if s.parent_of is not None:
                ch = s.parent_of >= 0
                la[ch] &= la[s.parent_of[ch]]
            self.live_all[s.seg_id] = la
            self.live[s.seg_id] = la & s.primary_mask()
        self.mappers = mapper
        self.shard_id = shard_id
        self._global_ords: dict[str, tuple[list[str], list[np.ndarray]]] = {}
        self._generation_key: tuple | None = None
        # what the submit made of the bodies this view has served
        # (search/bound_plans.py); like the two above it lives and dies
        # with the view
        self._bound_plans = bound_plans.BoundPlans()

    def generation_key(self) -> tuple:
        """Content-exact generation of this point-in-time view — the
        shard-request cache's invalidation signal (index/cache.py).
        Per segment: `Segment.cache_key()` (base content fingerprint /
        delta `(base generation, pow2 extent)` key), the delta epoch
        (bumped every delta rebuild, so a refresh that added docs
        re-keys even though the delta cache_key is epoch-stable), and
        a digest of the live mask (deletes flip bits without touching
        the segment). Memoized: the reader is immutable, one digest
        pass per refresh."""
        if self._generation_key is None:
            import hashlib
            parts = []
            for seg in self.segments:
                h = hashlib.blake2b(digest_size=8)
                h.update(self.live_all[seg.seg_id].tobytes())
                parts.append((seg.cache_key(),
                              int(getattr(seg, "delta_epoch", 0) or 0),
                              h.hexdigest()))
            self._generation_key = (self.index_name, self.shard_id,
                                    tuple(parts))
        return self._generation_key

    # -- global ordinals (ref: fielddata/ordinals/GlobalOrdinalsBuilder) ---
    def global_ords(self, field: str) -> tuple[list[str], list[np.ndarray]]:
        cached = self._global_ords.get(field)
        if cached is not None:
            return cached
        all_terms: set[str] = set()
        for seg in self.segments:
            kc = seg.keywords.get(field)
            if kc is not None:
                all_terms.update(kc.terms)
        terms = sorted(all_terms)
        lookup = {t: i for i, t in enumerate(terms)}
        seg_maps = []
        for seg in self.segments:
            kc = seg.keywords.get(field)
            if kc is None:
                seg_maps.append(np.zeros(1, dtype=np.int32))
            else:
                seg_maps.append(np.asarray([lookup[t] for t in kc.terms],
                                           dtype=np.int32))
        result = (terms, seg_maps)
        self._global_ords[field] = result
        return result

    # -- search ------------------------------------------------------------
    def search(self, body: dict) -> dict:
        return self.msearch([body])[0]

    def count(self, body: dict | None = None) -> int:
        res = self.search({"query": (body or {}).get("query"), "size": 0})
        return res["hits"]["total"]

    def msearch(self, bodies: list[dict], with_partials: bool = False,
                deadline: float | None = None,
                requests: list | None = None,
                keys: list | None = None) -> list[dict]:
        """Execute a batch of requests; structurally-identical requests are
        batched into one device program (leading dim B).

        with_partials=True attaches "_agg_partials" (keyed shard partials
        for the coordinator's cross-shard reduce) instead of finalized
        "aggregations" — the QUERY phase of a distributed search."""
        return self.msearch_submit(bodies, with_partials, deadline=deadline,
                                   requests=requests, keys=keys).finish()

    def msearch_submit(self, bodies: list[dict],
                       with_partials: bool = False,
                       deadline: float | None = None,
                       requests: list | None = None,
                       keys: list | None = None) -> "_PendingMsearch":
        """Dispatch half of msearch: parse, group structurally-identical
        requests, and enqueue EVERY group's device programs through the
        non-syncing executor entry WITHOUT collecting — so a scheduler
        (search/dispatch.py) can pipeline several readers' round trips
        before any collection. `.finish()` collects in submission order
        and builds the responses. knn / multi-sort / empty-reader items
        are deferred to finish (they are host-driven, nothing to
        pipeline).

        `deadline` (absolute monotonic seconds) is the cooperative
        search deadline: finish() raises SearchTimeoutError instead of
        collecting once it has passed, releasing any still-queued
        breaker holds — the whole shard counts as failed-by-timeout.

        This is also the reader dispatch boundary the fault-injection
        registry (utils/faults.py) hooks: an injected shard_error /
        breaker_trip raises here exactly where a real device error
        would, and an injected shard_delay makes this shard a
        straggler.

        `requests` names the request behind each body (ids from
        utils/profiler.next_request_id) on the phase spans. All of
        this is the `bind` phase, but for the launches: the executor
        pauses it around each, so that the spans stay leaves. Every
        phase of the call weighs its number of bodies: each of those
        searches waits through all of it.

        What the call makes of a body and the reader alone is made once
        and kept on the reader (search/bound_plans.py). A body served
        before skips the parse and the per-segment bind; a group of
        bodies served before skips the aggregation context, the sort's
        maps, the view uploads and, in `execute_segment_async`, the
        finalize, the fused admission, the packing and the upload of
        the wire parameters and the output layout: it goes from its key
        to the launches. Done per call whatever is kept: the faults
        hook, the deadline and the step budget, the grouping; per
        launch: the breaker hold, the live mask and its views, the
        launch and its counters, the request for the result's copy, the
        spans with this call's request ids. Nothing of a result is
        kept. `keys` brings the bodies' `bound_plans.body_key`s where
        the caller has them (a fan-out hands one body to every shard);
        without them they are made here. Not kept, and counted as
        `bypassed`: `knn` items, multi-key sorts, a reader without
        segments, a (base, delta) pair, the resident loop when it is
        on, a paged pack, a body whose parse or bind read the clock or
        the script registry or uploaded a column."""
        ids = list(requests) if requests else [None] * len(bodies)
        with phase("bind", len(bodies), **request_args(ids)) as bind:
            return self._msearch_submit(bodies, with_partials, deadline,
                                        ids, bind, keys)

    def _pack_pair(self) -> bool:
        """A (base, delta) generation pair, which the pack dispatch
        serves in one program (streaming write path)."""
        return len(self.segments) == 2 \
            and getattr(self.segments[1], "delta_parent", None) is not None \
            and _pack_dispatch_enabled()

    def _bind_body(self, body: dict) -> "bound_plans.BoundBody":
        """Parse one body and bind it against every segment."""
        p = self._parse_request(body)
        if not self.segments or p["sort_spec"][0] == "multi":
            return bound_plans.BoundBody(p, [], None)
        bounds = [QueryBinder(seg, self.mappers,
                              live=self.live[seg.seg_id],
                              dfs=p["dfs_stats"]).bind(p["query"])
                  for seg in self.segments]
        # groups by (plan signature per segment, agg/sort/k sig)
        sig = (tuple(b.signature() for b in bounds), p["static_sig"])
        return bound_plans.BoundBody(p, bounds, sig)

    def _msearch_submit(self, bodies: list[dict], with_partials: bool,
                        deadline: float | None, ids: list,
                        bind: phase, keys: list | None
                        ) -> "_PendingMsearch":
        faults.on_dispatch("reader", index=self.index_name,
                           shard=self.shard_id)
        started = time.monotonic()
        # resident mode: device-stepped dispatches meter any injected
        # straggler delay INSIDE device execution (per tile chunk, where
        # the preemptive deadline check can cut it short); the budget
        # object is shared across this pend's segment dispatches so the
        # shard sleeps its delay once, like the collect boundary would
        step_budget = None
        from .resident import enabled as _resident_enabled
        if _resident_enabled() and faults.enabled():
            step_budget = faults.StepBudget("reader",
                                            index=self.index_name,
                                            shard=self.shard_id)
        n = len(bodies)
        from .executor import _fused_stats
        bodies = list(bodies)
        knn_idx = []
        knn_modes: dict[int, str] = {}
        knn_any = set()  # the rewritten ones too
        for i, b in enumerate(bodies):
            if not (b or {}).get("knn"):
                continue
            knn_any.add(i)
            mode, reason = knn_body_mode(b, self.mappers)
            if mode != "candidates":
                # candidates items record "ivf" / "exact" from the
                # submit helper instead, so IVF-served and exact-
                # degraded segments are distinguishable in the stats
                _fused_stats.record_knn(reason)
            if mode == "rewrite" and self.segments:
                # hybrid BM25+knn: the knn spec becomes a scoring
                # clause in a plain bool query and the item joins the
                # ordinary grouped path — fused bundle admission, pack
                # dispatch, scheduler coalescing all apply
                bodies[i] = rewrite_knn_body(b)
            else:
                knn_idx.append(i)
                knn_modes[i] = mode
        knn_set = set(knn_idx)
        # the kept plans serve the grouped cold path of a reader with
        # segments; a (base, delta) pair goes through the pack dispatch
        # and the resident loop through its pinned entries
        plans = self._bound_plans
        kept = bool(self.segments) and not _resident_enabled() \
            and not self._pack_pair()
        if kept:
            plans.open((self.mappers.version, plan_switches()))
        bound: dict[int, bound_plans.BoundBody] = {}
        body_keys: dict[int, str | None] = {}
        for i in range(n):
            if i in knn_set:
                bound_plans.bypassed.inc()
                continue
            key = None
            if kept and i not in knn_any:
                key = keys[i] if keys and keys[i] is not None \
                    else bound_plans.body_key(bodies[i])
            bb = plans.get(key) if key is not None else None
            if bb is None:
                with bound_plans.keep_scope() as scope:
                    # a kept parse outlives the caller's dict: it is
                    # made from a copy (the key has shown the body to
                    # be plain JSON values)
                    bb = self._bind_body(
                        pickle.loads(pickle.dumps(bodies[i], -1))
                        if key is not None else bodies[i])
                if bb.sig is None or not scope.keep:
                    key = None
                if key is not None:
                    plans.put(key, bb)
            bound[i] = bb
            body_keys[i] = key
        parsed = {i: bb.parsed for i, bb in bound.items()}
        pend = _PendingMsearch(self, bodies, with_partials, started,
                               knn_idx, parsed)
        pend.deadline = deadline
        pend.step_budget = step_budget
        if not self.segments:
            pend.no_segments = True
            bound_plans.bypassed.inc(len(parsed))
            return pend
        for i in knn_idx:
            # pure-knn items dispatch their per-segment candidate
            # top-k HERE (async, nothing collected) so they pipeline
            # with every other enqueued program; finish() combines
            if knn_modes[i] != "candidates":
                pend.knn_sub[i] = None
                continue
            sub = self._knn_candidates_submit(bodies[i])
            _fused_stats.record_knn(
                "ivf" if any(kind == "ivf" for _o, kind, _p
                             in sub["pending"]) else "exact")
            pend.knn_sub[i] = sub
        pend.multi = {i for i, p in parsed.items()
                      if p["sort_spec"][0] == "multi"}
        bound_plans.bypassed.inc(len(pend.multi))
        pend.main = [i for i in range(n)
                     if i not in knn_set and i not in pend.multi]

        groups: dict[tuple, list[int]] = {}
        for i in pend.main:
            groups.setdefault(bound[i].sig, []).append(i)

        for idxs in groups.values():
            # from here on the spans name this group's requests
            group_args = bind.args = request_args(ids[i] for i in idxs)
            p0 = parsed[idxs[0]]
            # a group of one is kept with its body, a larger one under
            # its bodies' keys in order
            gkey = tuple(body_keys[i] for i in idxs)
            if None in gkey:
                gkey = None
            first = bound[idxs[0]]
            if gkey is None:
                group = None
            elif len(idxs) == 1:
                group = first.alone
            else:
                group = plans.get(gkey)
            if group is not None and not all(
                    segment_plan_valid(seg, sp)
                    for seg, sp in zip(self.segments, group.plans)):
                group = None
            hit = group is not None
            if not hit:
                group = self._bind_group(p0, first.bounds)
            try:
                pending = self._launch_group(
                    group, p0, [bound[i].bounds for i in idxs], hit,
                    deadline, step_budget, bind)
            except BaseException:
                if hit:
                    # kept values that do not launch are built anew by
                    # the next search
                    plans.drop({gkey, *gkey})
                raise
            if hit:
                bound_plans.hits.inc()
            elif gkey is not None \
                    and len(group.plans) == len(self.segments):
                bound_plans.misses.inc()
                if len(idxs) == 1:
                    first.alone = group
                else:
                    plans.put(gkey, group)
            else:
                bound_plans.bypassed.inc()
            pend.groups.append({"idxs": idxs, "p0": p0,
                                "agg_ctx": group.agg_ctx,
                                "pending": pending,
                                "sort_terms": group.sort_terms,
                                "span_args": group_args})
        pend.group_sizes = [len(g["idxs"]) for g in pend.groups]
        pend.dispatch_count = sum(len(g["pending"]) for g in pend.groups)
        return pend

    def _bind_group(self, p0: dict, bounds0: list
                    ) -> "bound_plans.BoundGroup":
        """What one group of structurally identical bodies needs before
        its launches, from its first body: the aggregation context and
        descriptors, k, the sort's spec, terms and maps, and the view
        and script-value uploads. The executor fills in `plans`."""
        agg_ctx = ShardAggContext(self.segments,
                                  self._ords_for(p0["agg_specs"]))
        agg_desc, agg_params = agg_ctx.build(p0["agg_specs"])
        k = p0["from"] + p0["size"]
        if k == 0 and (p0["sort_spec"][0] != "_score"
                       or p0["rescore"] is not None):
            # size-0 requests skip top-k entirely only on the plain
            # score-sort path; sorted/rescored requests keep k>=1
            k = 1
        sort_spec = p0["sort_spec"]
        if p0["agg_specs"]:
            # sorted-space query views: project the filter columns
            # onto each agg layout so the agg mask never rides a
            # per-query permutation gather (see executor.py)
            from .executor import ensure_agg_views
            for seg, b in zip(self.segments, bounds0):
                ensure_agg_views(seg, b, agg_desc)
        sort_terms = None
        sort_maps = [() for _ in self.segments]
        if sort_spec[0] == "field" and sort_spec[3] == "kw":
            sort_terms, seg_maps = self.global_ords(sort_spec[1])
            sort_maps = [(m,) for m in seg_maps]
        elif sort_spec[0] == "field" and sort_spec[3] == "script":
            from ..script import compile_script
            from .executor import ensure_script_vals
            cs = compile_script(sort_spec[1].split("\x00", 1)[0])
            for seg in self.segments:
                ensure_script_vals(seg, cs.fields)
        elif sort_spec[0] == "field" and len(sort_spec) > 4:
            # extended spec (geo origin etc.): extras become dynamic
            # sort_params; the static jit key keeps only the 4-tuple
            extras = tuple(np.float32(e) for e in sort_spec[4:])
            sort_maps = [extras for _ in self.segments]
            sort_spec = sort_spec[:4]
        return bound_plans.BoundGroup(agg_ctx, agg_desc, agg_params, k,
                                      sort_spec, sort_terms, sort_maps, [])

    def _launch_group(self, group: "bound_plans.BoundGroup", p0: dict,
                      bounds: list, hit: bool, deadline: float | None,
                      step_budget, bind: phase) -> list:
        """Dispatch all segments async; collection happens in finish(),
        so round trips overlap across segments AND across
        groups/readers. `bounds` holds each body's per-segment bound
        trees. On a `hit` every segment launches from its kept plan;
        otherwise the executor resolves the plans and leaves them in
        `group.plans`."""
        # nested-scope requests (aggregations over hidden child rows)
        # lift the primary-row restriction
        live_sel = self.live_all if p0["nested_scope"] else self.live
        agg_params = group.agg_params
        pending = []
        # streaming write path: a (base, delta) generation pair
        # serves fused-admitted plans in ONE device dispatch (the
        # delta walk chains onto the base's running top-k;
        # executor.execute_pack_async) — one dispatch round trip per
        # refresh-heavy reader instead of one per segment, with
        # byte-identical responses. Inadmissible plans fall back
        # to the per-segment dispatches below.
        if self._pack_pair():
            b_seg, d_seg = self.segments
            pack = execute_pack_async(
                b_seg, d_seg, live_sel[b_seg.seg_id],
                live_sel[d_seg.seg_id],
                [b[0] for b in bounds], [b[1] for b in bounds], group.k,
                agg_desc=group.agg_desc,
                agg_params_b=agg_params[0] if agg_params else (),
                agg_params_d=agg_params[1] if agg_params else (),
                sort_spec=group.sort_spec, deadline=deadline,
                step_budget=step_budget,
                shard_key=(self.index_name, self.shard_id), bind=bind)
            if pack is not None:
                pending.append(pack)
        if not pending:
            for si, seg in enumerate(self.segments):
                pending.append(execute_segment_async(
                    seg, live_sel[seg.seg_id], [b[si] for b in bounds],
                    group.k, agg_desc=group.agg_desc,
                    agg_params=agg_params[si],
                    sort_spec=group.sort_spec,
                    sort_params=group.sort_maps[si],
                    deadline=deadline, step_budget=step_budget,
                    shard_key=(self.index_name, self.shard_id),
                    bind=bind, plan=group.plans[si] if hit else None,
                    keep=None if hit else group.plans))
        return pending

    @staticmethod
    def _release_pending_holds(pend: "_PendingMsearch") -> None:
        """Release every breaker hold still queued on the pend. Holds
        release at most once (utils/breaker.Hold), so sweeping ALL
        groups is safe after any number of them already collected."""
        for g in pend.groups:
            for _out, layout, _n in g["pending"]:
                hold = layout.get("_breaker_hold")
                if hold is not None:
                    hold.release()

    def _deadline_check(self, pend: "_PendingMsearch") -> None:
        if pend.deadline is not None \
                and time.monotonic() > pend.deadline:
            raise SearchTimeoutError(self.index_name, self.shard_id)

    def _msearch_finish(self, pend: "_PendingMsearch") -> list[dict]:
        try:
            return self._msearch_finish_inner(pend)
        except BaseException:
            # NO exit may leak breaker reservations: deadline raises,
            # collect-phase injected faults, and real device errors
            # mid-collect all sweep the still-queued holds before
            # propagating (the GC backstop alone accumulates estimates
            # into spurious trips under tight chaos/error loops)
            self._release_pending_holds(pend)
            raise

    def _msearch_finish_inner(self, pend: "_PendingMsearch") -> list[dict]:
        # collect-phase fault boundary: a straggler shard (injected
        # shard_delay) burns wall-clock HERE, where the caller waits on
        # device results — so only this shard (and shards collected
        # after it) can miss the deadline, never already-collected ones.
        # When a resident stepped dispatch already took the straggler
        # budget (metered inside device execution), delay rules are
        # skipped so the shard is not slowed twice.
        faults.on_dispatch("reader", index=self.index_name,
                           shard=self.shard_id, phase="collect",
                           skip_delay=bool(pend.step_budget is not None
                                           and pend.step_budget.taken))
        bodies = pend.bodies
        parsed = pend.parsed
        started = pend.started
        with_partials = pend.with_partials
        responses: list[dict | None] = [None] * len(bodies)
        for i in pend.knn_idx:
            # host-driven paths honor the deadline too: without this, a
            # knn/multi-sort-only pend would never consult it at all
            self._deadline_check(pend)
            sub = pend.knn_sub.get(i)
            if sub is None:
                responses[i] = self._knn_search(bodies[i], started,
                                                with_partials)
            else:
                responses[i] = self._knn_collect(bodies[i], sub, started,
                                                 with_partials)
        if pend.no_segments:
            for i, p in parsed.items():
                responses[i] = self._empty_response(p, started,
                                                    with_partials)
            return responses  # type: ignore[return-value]
        for i in sorted(pend.multi):
            self._deadline_check(pend)
            p = parsed[i]
            responses[i] = self._multi_sort_search(bodies[i], p,
                                                   started, with_partials)
            if p["highlight"] is not None:
                self._apply_highlight(responses[i], p)
            if p["suggest_specs"]:
                responses[i]["suggest"] = execute_suggest(
                    p["suggest_specs"], self.segments,
                    self.mappers.search_analyzer_for, self.mappers)
        for g in pend.groups:
            # deadline passed before this group's collect: the shard is
            # a laggard and fails whole by timeout (holds released by
            # the _msearch_finish wrapper). Fully-resident groups skip
            # the cooperative pre-check: EVERY dispatch carries the
            # device-side per-chunk deadline verdict (incl. a final
            # post-loop check), and collect_segment_result raises the
            # same SearchTimeoutError when one reports timed_out — a
            # step that beat the cutoff on-device is collected rather
            # than discarded on host lag. A group with ANY cold
            # dispatch keeps the cooperative check: that dispatch has
            # no device verdict to fall back on.
            if not all(l.get("resident") for _o, l, _n in g["pending"]):
                self._deadline_check(pend)
            idxs = g["idxs"]
            p0 = g["p0"]
            agg_ctx = g["agg_ctx"]
            partials = []
            seg_tops = []
            # `collect` and `unpack` are the executor's spans; `fetch`
            # below is everything that builds this group's responses
            for out, layout, n_real in g["pending"]:
                if layout.get("pack"):
                    # one pack dispatch covered (base, delta): the
                    # collect splits back into per-segment candidate
                    # lists + per-segment agg partials, so everything
                    # downstream is unchanged
                    tops2, aggs2 = collect_pack_result(out, layout,
                                                       n_real)
                    seg_tops.extend(tops2)
                    partials.extend(aggs2)
                    continue
                top, aggs = collect_segment_result(out, layout, n_real)
                seg_tops.append(top)
                partials.append(aggs)
            with phase("fetch", len(bodies), **g["span_args"]) as fetch:
                if p0["agg_specs"] and with_partials:
                    part_json = shard_partials(p0["agg_specs"], agg_ctx,
                                               partials, len(idxs))
                    agg_json = [{} for _ in idxs]
                elif p0["agg_specs"]:
                    part_json = None
                    agg_json = reduce_aggs(p0["agg_specs"], agg_ctx,
                                           partials, len(idxs))
                else:
                    part_json = None
                    agg_json = [{} for _ in idxs]
                for bi, i in enumerate(idxs):
                    responses[i] = self._build_response(
                        parsed[i], seg_tops, bi, agg_json[bi], started,
                        sort_terms=g["sort_terms"])
                    if part_json is not None:
                        responses[i]["_agg_partials"] = part_json[bi]
                # the results are on the host: let go of the device
                # buffers here, inside the span, not where this frame
                # and the pend die
                g["pending"] = ()
                out = None
            for i in idxs:
                pend.fetch_s[i] = fetch.seconds / len(idxs)
        for i in pend.main:
            # post-processing (rescore windows, derived aggs, sig_terms
            # fan back into msearch) is host-driven and unbounded — a
            # shard that finishes it past the cutoff is a laggard too
            self._deadline_check(pend)
            p = parsed[i]
            if p["rescore"] is not None:
                self._apply_rescore(responses[i], p)
            if p["highlight"] is not None:
                self._apply_highlight(responses[i], p)
            if p["suggest_specs"]:
                responses[i]["suggest"] = execute_suggest(
                    p["suggest_specs"], self.segments,
                    self.mappers.search_analyzer_for, self.mappers)
            if p["derived_specs"]:
                self._apply_derived(responses[i], p, with_partials)
            self._apply_sig_subs(responses[i], p, with_partials)
        return responses  # type: ignore[return-value]

    def sig_term_counts(self, field: str, flt_field: str | None = None,
                        flt_value=None,
                        allowed_ids=None) -> tuple[int, dict]:
        """(n_docs, {token: doc_count}) over live docs, optionally
        restricted to docs whose `flt_field` equals `flt_value`. Counts
        TOKENS of analyzed text via the postings CSR (the fielddata view
        significant_terms works on in the reference — ref:
        SignificantTermsAggregatorFactory bg/fg frequency lookup);
        keyword fields count whole values."""
        total = 0
        counts: dict[str, int] = {}
        for seg in self.segments:
            mask = self.live[seg.seg_id].copy()
            if allowed_ids is not None:
                # enclosing-query scope: only docs the query matched
                in_q = np.zeros(seg.capacity, dtype=bool)
                for d, did in enumerate(seg.ids):
                    if did in allowed_ids:
                        in_q[d] = True
                mask &= in_q
            if flt_field is not None:
                kc = (seg.keywords.get(flt_field)
                      or seg.keywords.get(f"{flt_field}.keyword"))
                if kc is None:
                    continue
                t = kc.term_index.get(str(flt_value), -1)
                if t < 0:
                    continue
                m = kc.ords == t
                if kc.mv_ords is not None:
                    m |= (kc.mv_ords == t).any(axis=1)
                mask &= m
            total += int(mask.sum())
            pf = seg.text.get(field)
            if pf is not None:
                tids = np.repeat(
                    np.arange(len(pf.terms), dtype=np.int64),
                    np.diff(pf.indptr))
                sel = mask[pf.doc_ids]
                bc = np.bincount(tids[sel], minlength=len(pf.terms))
                for t_idx in np.nonzero(bc)[0]:
                    term = pf.terms[int(t_idx)]
                    counts[term] = counts.get(term, 0) + int(bc[t_idx])
            else:
                kc = (seg.keywords.get(field)
                      or seg.keywords.get(f"{field}.keyword"))
                if kc is None:
                    continue
                live_ords = kc.ords[mask]
                bc = np.bincount(live_ords[live_ords >= 0],
                                 minlength=len(kc.terms))
                for t_idx in np.nonzero(bc)[0]:
                    term = kc.terms[int(t_idx)]
                    counts[term] = counts.get(term, 0) + int(bc[t_idx])
        return total, counts

    def _apply_sig_subs(self, resp: dict, p: dict,
                        with_partials: bool) -> None:
        """significant_terms nested under a terms agg (see
        aggregations.apply_sig_subs). Single-host path only; the mesh
        path reduces its own partials and does not carry sig sub-aggs."""
        if with_partials:
            return
        if not any(getattr(spec, "sig_subs", None)
                   for spec in p["agg_specs"]):
            return
        from .aggregations import apply_sig_subs

        def search_ids(query: dict) -> set:
            r = self.search({"query": query, "size": 10_000,
                             "_source": False})
            return {h["_id"] for h in r["hits"]["hits"]}

        apply_sig_subs(p["agg_specs"], resp.get("aggregations", {}),
                       [self], raw_query=p["raw_query"],
                       search_ids=search_ids)

    def _apply_derived(self, resp: dict, p: dict,
                       with_partials: bool) -> None:
        """Derived bucket aggs (filter/filters/range/date_range/missing/
        global/top_hits): each bucket is an auxiliary filtered request
        through the same batched executor; nested sub-aggregations of any
        kind recurse naturally. Ref: the wrapped-collector designs in
        search/aggregations/bucket/{filter,filters,range,missing,global}.
        """
        for spec in p["derived_specs"]:
            if spec.kind in ("nested", "reverse_nested", "children"):
                aux_bodies = [self._scope_shift_body(spec, p)]
            elif spec.kind == "significant_terms":
                # foreground (query scope) vs background (whole index)
                # term counts; scored host-side with JLH
                base = {"size": 0, "aggs": spec.sub_raw}
                aux_bodies = [
                    {"query": p["raw_query"] or {"match_all": {}}, **base},
                    {"query": {"match_all": {}}, **base}]
                for b2 in aux_bodies:
                    if p["nested_scope"]:
                        b2["_nested_scope"] = p["nested_scope"]
            else:
                aux_bodies = []
                for key, flt, _extra in spec.buckets:
                    if spec.mode == "ignore_query":
                        q = flt or {"match_all": {}}
                    else:
                        clauses = {"filter": [flt] if flt else []}
                        if p["raw_query"] is not None:
                            clauses["must"] = [p["raw_query"]]
                        q = {"bool": clauses}
                    size = spec.top_hits_size if spec.kind == "top_hits" \
                        else 0
                    body = {"query": q, "size": size,
                            "_source": spec.top_hits_source}
                    if spec.sub_raw:
                        body["aggs"] = spec.sub_raw
                    # derived aggs nested inside a scope-shifted context
                    # (e.g. filter under nested) stay in that scope
                    if p["nested_scope"]:
                        body["_nested_scope"] = p["nested_scope"]
                    if p["reverse_ctx"]:
                        body["_reverse_ctx"] = p["reverse_ctx"]
                    aux_bodies.append(body)
            aux = self.msearch(aux_bodies, with_partials)
            if with_partials:
                derived = {}
                for (key, _f, _x), ar in zip(spec.buckets, aux):
                    bucket = {"count": ar["hits"]["total"],
                              "sub": ar.get("_agg_partials", {})}
                    if spec.kind == "top_hits":
                        bucket["hits"] = ar["hits"]["hits"]
                    derived[key] = bucket
                resp.setdefault("_agg_partials", {})[spec.name] = \
                    {"derived": derived}
            else:
                resp.setdefault("aggregations", {})[spec.name] = \
                    self._stitch_derived(spec, aux)

    def _scope_shift_body(self, spec, p: dict) -> dict:
        """Aux request for scope-shifting bucket aggs: nested (to child
        rows), reverse_nested (back to parents), children (to join-child
        docs). The aux request's own derived/sub aggs recurse naturally."""
        outer = p["raw_query"]
        if spec.kind == "nested":
            path = spec.mode.split(":", 1)[1]
            q = {"bool": {"filter": [
                {"term": {"_nested_path": path}},
                {"_parents_match": {"query": outer or {"match_all": {}}}}]}}
            body = {"query": q, "size": 0, "_nested_scope": path,
                    "_reverse_ctx": {"path": path, "outer": outer}}
        elif spec.kind == "reverse_nested":
            ctx = p.get("reverse_ctx")
            if not ctx:
                raise SearchParseError(
                    "[reverse_nested] must be nested inside a [nested] "
                    "aggregation")
            clauses: dict = {"filter": [{"nested": {
                "path": ctx["path"], "query": {"match_all": {}}}}]}
            if ctx.get("outer"):
                clauses["must"] = [ctx["outer"]]
            body = {"query": {"bool": clauses}, "size": 0}
        else:  # children
            ctype = spec.mode.split(":", 1)[1]
            fm = self._join_field("children")
            parent_rel = None
            for parent, kids in (fm.relations or {}).items():
                kids = kids if isinstance(kids, list) else [kids]
                if ctype in kids:
                    parent_rel = parent
            if parent_rel is None:
                raise SearchParseError(
                    f"[children] no relation to type [{ctype}]")
            q = {"bool": {
                "must": [{"has_parent": {"parent_type": parent_rel,
                                         "query": outer or
                                         {"match_all": {}}}}],
                "filter": [{"term": {fm.name: ctype}}]}}
            body = {"query": q, "size": 0}
        if spec.sub_raw:
            body["aggs"] = spec.sub_raw
        return body

    def _stitch_derived(self, spec, aux: list[dict]) -> dict:
        def bucket_json(ar: dict) -> dict:
            out = {"doc_count": ar["hits"]["total"]}
            out.update(ar.get("aggregations", {}))
            return out

        if spec.kind == "top_hits":
            ar = aux[0]
            return {"hits": {"total": ar["hits"]["total"],
                             "max_score": ar["hits"]["max_score"],
                             "hits": ar["hits"]["hits"]}}
        if spec.kind == "significant_terms":
            from .aggregations import significant_buckets
            fg, bg = aux[0], aux[1]
            return significant_buckets(
                spec, fg["hits"]["total"],
                fg["aggregations"]["__sig_terms"]["buckets"],
                bg["hits"]["total"],
                bg["aggregations"]["__sig_terms"]["buckets"])
        if spec.kind in ("filter", "missing", "global", "nested",
                         "reverse_nested", "children"):
            return bucket_json(aux[0])
        if spec.kind == "filters":
            return {"buckets": {key: bucket_json(ar)
                                for (key, _f, _x), ar in
                                zip(spec.buckets, aux)}}
        buckets = []
        for (key, _f, extra), ar in zip(spec.buckets, aux):
            buckets.append({"key": key,
                            **{k: v for k, v in extra.items()
                               if v is not None},
                            **bucket_json(ar)})
        return {"buckets": buckets}

    def _knn_spec(self, body: dict) -> tuple:
        spec = body["knn"]
        field = spec["field"]
        qv = np.asarray(spec["query_vector"], dtype=np.float32)
        k = int(spec.get("k", spec.get("num_candidates", 10)))
        boost = float(spec.get("boost", 1.0))
        fm = self.mappers.field(field)
        similarity = (fm.similarity if fm is not None and fm.similarity
                      else "cosine")
        return field, qv, k, boost, similarity

    def _knn_exact_dispatch(self, seg, field: str, qv: np.ndarray,
                            k: int, similarity: str):
        """Exact-scan candidate dispatch for one segment (async).
        Large segments select candidates approximately like the
        reference's HNSW stage (exact top_k over a 1M-doc score row
        costs ~80x more), but with a 4x overscan window whose exact
        re-sort at combine keeps the FINAL k effectively exact."""
        from ..ops.knn import knn_topk
        from .executor import device_arrays, _device_live

        dev = device_arrays(seg)["vec"][field]
        live = _device_live(seg, self.live[seg.seg_id])
        approx = seg.capacity >= (1 << 18)
        window = min(max(4 * k, 100), seg.capacity) if approx \
            else min(k, seg.capacity)
        return knn_topk(
            dev["values"], dev["norms"], dev["exists"], live,
            qv[None, :], similarity=similarity, k=window,
            approx_recall=0.99 if approx else None)

    def _knn_candidates_submit(self, body: dict) -> dict:
        """Dispatch half of a pure-knn search: per-segment candidate
        top-k ENQUEUED here (jax dispatch is async), collected in
        finish — vector searches overlap round trips with every other
        submitted program instead of serializing host-side. Segments
        carrying (or lazily building — index/ann.ensure_ann) an IVF
        index serve the coarse-quantized probe (ops/ann.ivf_topk);
        the rest take the exact scan. `site=ann:phase=probe` is the
        fault boundary: an injected error here surfaces exactly like a
        real device error — a structured `_shards.failures` partial."""
        from ..index import ann as ann_idx
        from ..index import tiering as _tiering
        from ..ops import ann as ann_ops
        from .executor import device_arrays, _device_live

        field, qv, k, _boost, similarity = self._knn_spec(body)
        pending = []
        for seg_ord, seg in enumerate(self.segments):
            vc = seg.vectors.get(field)
            if vc is None:
                continue
            ann = ann_idx.ensure_ann_device(
                seg, field, similarity, index=self.index_name,
                shard=self.shard_id)
            if ann is None:
                pending.append((seg_ord, "exact",
                                self._knn_exact_dispatch(
                                    seg, field, qv, k, similarity)))
                continue
            faults.on_dispatch("ann", index=self.index_name,
                               shard=self.shard_id, phase="probe")
            ai, adev = ann
            nprobe = ann_idx.default_nprobe(ai.n_clusters)
            probe = None
            if _tiering.enabled() and _tiering.paged_fields(seg):
                # oversubscribed pack: rank + pick the probe set with
                # the HOST bound mirror (ops/ann.cluster_bounds_np) so
                # the device program never touches clusters the bound
                # already ruled out — the PR 11 I/O-filter idea at
                # cluster granularity
                nb = ann_ops.cluster_bounds_np(
                    ai.centroids, ai.radii, qv[None, :],
                    similarity=similarity)
                rank = ann_ops.cluster_bounds_np(
                    ai.centroids, np.zeros_like(ai.radii),
                    qv[None, :], similarity=similarity)
                order = np.argsort(-rank, axis=1,
                                   kind="stable")[:, :nprobe]
                probe = (jnp.asarray(np.take_along_axis(nb, order,
                                                        axis=1)),
                         jnp.asarray(order.astype(np.int32)))
            dev = device_arrays(seg)["vec"][field]
            live = _device_live(seg, self.live[seg.seg_id])
            out = ann_ops.ivf_topk(
                dev["values"], dev["norms"], dev["exists"], live,
                adev["members"], adev["centroids"],
                adev["radii"], jnp.asarray(qv[None, :]),
                similarity=similarity, k=min(k, seg.capacity),
                nprobe=nprobe, probe=probe)
            pending.append((seg_ord, "ivf", out))
        return {"pending": pending, "k": k}

    def _knn_collect(self, body: dict, sub: dict, started: float,
                     with_partials: bool) -> dict:
        """Collect half: sync the candidate buffers, merge across
        segments (score desc, (segment, doc) tie order — the exact
        host rule the legacy path used), build the response."""
        from .executor import _fused_stats

        cands: list[tuple[float, int, int]] = []
        for seg_ord, kind, out in sub["pending"]:
            if kind == "ivf":
                scores, idx, stats = out
                st = np.asarray(stats)
                _fused_stats.record_ann_prune(int(st[0]), int(st[1]),
                                              int(st[2]))
            else:
                scores, idx = out
            s = np.asarray(scores[0])
            ix = np.asarray(idx[0])
            for j in range(s.shape[0]):
                if np.isfinite(s[j]):
                    cands.append((float(s[j]), seg_ord, int(ix[j])))
        cands.sort(key=lambda c: (-c[0], c[1], c[2]))
        return self._knn_build_response(body, cands[: sub["k"]],
                                        started, with_partials)

    def _knn_search(self, body: dict, started: float,
                    with_partials: bool = False) -> dict:
        """Host-fallback kNN (optionally hybrid with a query section)
        — the legacy synchronous path, kept for shapes the device
        paths decline (knn_body_mode "host") and for empty readers.

        Ref: BASELINE.json config[4] (dense_vector kNN + BM25 rescore);
        API shape follows modern ES `knn` search. Scoring = one MXU
        matmul per segment (ops/knn.py); hybrid combine = score sum with
        boosts, the ES hybrid-retrieval rule. Aggregations over kNN hits
        run host-side (candidate sets are k-sized, not corpus-sized).
        """
        field, qv, k, _boost, similarity = self._knn_spec(body)
        cands: list[tuple[float, int, int]] = []
        for seg_ord, seg in enumerate(self.segments):
            vc = seg.vectors.get(field)
            if vc is None:
                continue
            scores, idx = self._knn_exact_dispatch(seg, field, qv, k,
                                                   similarity)
            s = np.asarray(scores[0])
            ix = np.asarray(idx[0])
            for j in range(s.shape[0]):
                if np.isfinite(s[j]):
                    cands.append((float(s[j]), seg_ord, int(ix[j])))
        cands.sort(key=lambda c: (-c[0], c[1], c[2]))
        return self._knn_build_response(body, cands[:k], started,
                                        with_partials)

    def _knn_build_response(self, body: dict,
                            cands: list[tuple[float, int, int]],
                            started: float, with_partials: bool) -> dict:
        spec = body["knn"]
        k = int(spec.get("k", spec.get("num_candidates", 10)))
        knn_boost = float(spec.get("boost", 1.0))
        # fetch options / highlight reuse the standard request parsing
        p = self._parse_request({kk: vv for kk, vv in body.items()
                                 if kk != "knn"})
        combined: dict[str, float] = {}
        locate: dict[str, tuple[int, int]] = {}
        for score, seg_ord, local in cands:
            did = self.segments[seg_ord].ids[local]
            combined[did] = score * knn_boost
            locate[did] = (seg_ord, local)
        if body.get("query"):
            qboost = 1.0
            sub = self.msearch([{"query": body["query"],
                                 "size": max(k, p["from"] + p["size"]),
                                 "_source": False}])[0]
            for h in sub["hits"]["hits"]:
                did = h["_id"]
                combined[did] = combined.get(did, 0.0) + \
                    (h["_score"] or 0.0) * qboost
                if did not in locate:
                    seg, local = self._locate(did)
                    if seg is not None:
                        locate[did] = (self.segments.index(seg), local)

        ranked = sorted(combined.items(), key=lambda kv: (-kv[1], kv[0]))
        window = ranked[p["from"]: p["from"] + p["size"]]
        hits = []
        for did, score in window:
            seg_ord, local = locate[did]
            seg = self.segments[seg_ord]
            hit = {"_index": self.index_name, "_type": "_doc",
                   "_id": did, "_score": float(score)}
            if p["want_version"]:
                hit["_version"] = int(seg.versions[local])
            if p["source_filter"] is not False:
                src = filter_source(_load_source(seg.sources[local]),
                                    p["source_filter"])
                if src is not None:
                    hit["_source"] = src
            hits.append(hit)
        resp = {
            "took": int((time.monotonic() - started) * 1000),
            "timed_out": False,
            "_shards": {"total": 1, "successful": 1, "failed": 0},
            "hits": {"total": len(ranked),
                     "max_score": ranked[0][1] if ranked else None,
                     "hits": hits},
        }
        if p["highlight"] is not None:
            self._apply_highlight(resp, p)
        if p["agg_specs"] and with_partials:
            resp["_agg_partials"] = {}
        return resp

    def _multi_sort_search(self, body: dict, p: dict, started: float,
                           with_partials: bool = False) -> dict:
        """Multi-key field sort: the device returns the packed match
        bitmask; the host gathers the sort-key columns for matching rows
        and lexsorts (exact Lucene FieldComparator-chain semantics,
        missing-last per key). Exactness over the full match set — no
        top-k truncation risk on tie-heavy primaries."""
        keys = p["sort_spec"][1]
        agg_desc = (("__match", ("matchmask",)),)
        pending = []
        for seg in self.segments:
            bound = QueryBinder(seg, self.mappers,
                                live=self.live[seg.seg_id],
                                dfs=p["dfs_stats"]).bind(p["query"])
            pending.append(execute_segment_async(
                seg, self.live[seg.seg_id], [bound], 1,
                agg_desc=agg_desc, agg_params=((),),
                sort_spec=("_score",), sort_params=()))
        rows_per_seg: list[np.ndarray] = []
        for si, (out, layout, n_real) in enumerate(pending):
            _top, aggs = collect_segment_result(out, layout, n_real)
            seg = self.segments[si]
            mask = np.unpackbits(
                np.asarray(aggs["__match"]["mask"][0]).astype(np.uint8),
                bitorder="little")[: seg.capacity].astype(bool)
            mask &= self.live[seg.seg_id]
            rows_per_seg.append(np.nonzero(mask)[0])

        # per-key global ordinal spaces for keyword keys
        gords = {fld: self.global_ords(fld)
                 for fld, _d, kind in keys if kind == "kw"}
        seg_ids = np.concatenate(
            [np.full(r.size, si, dtype=np.int64)
             for si, r in enumerate(rows_per_seg)]) \
            if rows_per_seg else np.empty(0, np.int64)
        locals_ = np.concatenate(rows_per_seg) \
            if rows_per_seg else np.empty(0, np.int64)
        lex_arrays: list[np.ndarray] = []
        display: list[tuple] = []   # (kind, per-seg accessor) for hit sort
        for fld, desc, kind in keys:
            # keep each key column in its raw dtype: int64 sort values
            # beyond 2^53 would lose precision (and so order) as float64
            if kind == "kw":
                key_dtype = np.int64
            else:
                raw_dtypes = {self.segments[si].numerics[fld].raw.dtype
                              for si in range(len(self.segments))
                              if fld in self.segments[si].numerics}
                key_dtype = (np.int64 if raw_dtypes == {np.dtype(np.int64)}
                             else np.float64)
            vals = np.zeros(locals_.size, dtype=key_dtype)
            miss = np.ones(locals_.size, dtype=bool)
            off = 0
            for si, rows in enumerate(rows_per_seg):
                seg = self.segments[si]
                nrow = rows.size
                if kind == "kw":
                    kc = seg.keywords.get(fld)
                    if kc is not None and nrow:
                        terms, seg_maps = gords[fld]
                        ords = kc.ords[rows]
                        has = ords >= 0
                        vals[off:off + nrow][has] = \
                            seg_maps[si][ords[has]].astype(key_dtype)
                        miss[off:off + nrow] = ~has
                else:
                    nc = seg.numerics.get(fld)
                    if nc is not None and nrow:
                        has = nc.exists[rows]
                        vals[off:off + nrow][has] = \
                            nc.raw[rows][has].astype(key_dtype)
                        miss[off:off + nrow] = ~has
                off += nrow
            lex_arrays.append((miss, np.where(miss, vals.dtype.type(0),
                                              -vals if desc else vals)))
            display.append((fld, kind))
        # np.lexsort: LAST array is the primary key -> build least-
        # significant-first: (doc, seg) tie-breaks, then key_n..key_1,
        # each key's missing flag outranking its value (missing last)
        lsb_first: list[np.ndarray] = [locals_, seg_ids]
        for miss, vals in reversed(lex_arrays):
            lsb_first.append(vals)
            lsb_first.append(miss)
        order = np.lexsort(tuple(lsb_first))
        total = int(locals_.size)
        window = order[p["from"]: p["from"] + p["size"]]

        hits = []
        for j in window:
            si = int(seg_ids[j])
            d = int(locals_[j])
            seg = self.segments[si]
            hit = {"_index": self.index_name, "_type": "_doc",
                   "_id": seg.ids[d], "_score": None}
            sort_vals = []
            for fld, kind in display:
                if kind == "kw":
                    kc = seg.keywords.get(fld)
                    sort_vals.append(
                        kc.terms[kc.ords[d]]
                        if kc is not None and kc.ords[d] >= 0 else None)
                else:
                    nc = seg.numerics.get(fld)
                    if nc is None or not nc.exists[d]:
                        sort_vals.append(None)
                    else:
                        v = nc.raw[d]
                        sort_vals.append(int(v) if nc.raw.dtype == np.int64
                                         else float(v))
            hit["sort"] = sort_vals
            if p["want_version"]:
                hit["_version"] = int(seg.versions[d])
            if p["source_filter"] is not False:
                src = filter_source(_load_source(seg.sources[d]),
                                    p["source_filter"])
                if src is not None:
                    hit["_source"] = src
            hits.append(hit)
        resp = {
            "took": int((time.monotonic() - started) * 1000),
            "timed_out": False,
            "_shards": {"total": 1, "successful": 1, "failed": 0},
            "hits": {"total": total, "max_score": None, "hits": hits},
        }
        if p["agg_specs"] or p["derived_specs"]:
            aux_body = {"query": p["raw_query"], "size": 0,
                        "aggs": body.get("aggs") or body.get("aggregations")}
            aux = self.msearch([aux_body], with_partials)[0]
            if with_partials:
                resp["_agg_partials"] = aux.get("_agg_partials", {})
            elif "aggregations" in aux:
                resp["aggregations"] = aux["aggregations"]
        return resp

    def _apply_rescore(self, resp: dict, p: dict) -> None:
        """Query rescorer over the top window (ref:
        search/rescore/QueryRescorer.java — combine original and rescore
        scores for the window docs, re-sort)."""
        spec = p["rescore"]
        window = max(spec["window_size"], p["from"] + p["size"])
        sub = self.msearch([{"query": spec["query"], "size": window,
                             "_source": False}])[0]
        re_scores = {h["_id"]: h["_score"] for h in sub["hits"]["hits"]}
        w1, w2, mode = (spec["query_weight"], spec["rescore_query_weight"],
                        spec["score_mode"])
        for h in resp["hits"]["hits"]:
            orig = h.get("_score") or 0.0
            rs = re_scores.get(h["_id"])
            if rs is None:
                h["_score"] = orig * w1
            elif mode == "multiply":
                h["_score"] = (orig * w1) * (rs * w2)
            elif mode == "avg":
                h["_score"] = (orig * w1 + rs * w2) / 2.0
            elif mode == "max":
                h["_score"] = max(orig * w1, rs * w2)
            elif mode == "min":
                h["_score"] = min(orig * w1, rs * w2)
            else:  # total
                h["_score"] = orig * w1 + rs * w2
        resp["hits"]["hits"].sort(key=lambda h: -(h["_score"] or 0.0))
        if resp["hits"]["hits"]:
            resp["hits"]["max_score"] = resp["hits"]["hits"][0]["_score"]

    def _apply_highlight(self, resp: dict, p: dict) -> None:
        for h in resp["hits"]["hits"]:
            source = h.get("_source")
            if source is None:
                seg, local = self._locate(h["_id"])
                if seg is None:
                    continue
                source = _load_source(seg.sources[local])
            hl = highlight_hit(source, p["query"], p["highlight"],
                               self.mappers)
            if hl:
                h["highlight"] = hl

    def term_stats(self, pairs: list[tuple[str, str]]
                   ) -> dict[str, tuple[int, int]]:
        """(field, term) -> (df, doc_count) summed over this shard's
        segments — the per-shard half of the DFS phase (ref:
        search/dfs/DfsPhase.java termStatistics)."""
        out: dict[str, tuple[int, int]] = {}
        for f, t in pairs:
            df = 0
            n = 0
            for seg in self.segments:
                pf = seg.text.get(f)
                if pf is not None:
                    tid = pf.lookup(str(t))
                    if tid >= 0:
                        df += int(pf.df[tid])
                    n += pf.doc_count
                    continue
                kc = seg.keywords.get(f)
                if kc is not None:
                    o = kc.lookup(str(t))
                    if o >= 0:
                        df += int(kc.df[o])
                    n += seg.num_docs
            out[f"{f}\x00{t}"] = (df, n)
        return out

    # -- parent/child joins (host-side two-pass resolution) ----------------
    # The reference resolves has_child/has_parent with per-shard parent-id
    # collectors (index/search/child/ChildrenQuery.java: collect matching
    # child docs' parent ids into a set, then filter parents). Same shape
    # here: an auxiliary device query collects one side, the ids become a
    # host-computed filter for the other side. Parent/child requires
    # children routed to the parent's shard (routing=parent), as in ES.

    JOIN_RESOLVE_WINDOW = 10_000

    def _collect_all_hits(self, query: dict) -> list[dict]:
        """All hits of an auxiliary join-resolution query. Two passes at
        most: the first learns the total, an optional second fetches
        everything in one top-k (no silent truncation, no quadratic
        re-paging)."""
        res = self.msearch([{"query": query,
                             "size": self.JOIN_RESOLVE_WINDOW,
                             "_source": False}])[0]
        total = res["hits"]["total"]
        if total <= self.JOIN_RESOLVE_WINDOW:
            return res["hits"]["hits"]
        res = self.msearch([{"query": query, "size": total,
                             "_source": False}])[0]
        return res["hits"]["hits"]

    def _join_field(self, ctx: str):
        fm = self.mappers.join_field()
        if fm is None:
            raise SearchParseError(
                f"[{ctx}] no join field is mapped on [{self.index_name}]")
        return fm

    # compound query shapes whose bodies contain QUERY nodes — join
    # resolution only recurses here, so field names like "parent_id"
    # inside term/match leaves are never misread as join queries
    _QUERY_LIST_KEYS = ("must", "should", "must_not", "filter", "queries",
                        "filters")
    _QUERY_CHILD_KEYS = ("query", "filter", "positive", "negative",
                         "no_match_query", "include", "exclude")
    _COMPOUND_NODES = ("bool", "constant_score", "filtered", "not", "and",
                       "or", "nested", "function_score", "boosting",
                       "dis_max", "indices", "_parents_match",
                       "span_multi")

    def _resolve_joins(self, q):
        """Replace has_child/has_parent/parent_id QUERY NODES (by position
        in the query tree, not by key name) with resolved id filters."""
        if not isinstance(q, dict):
            return q
        out = {}
        for name, body in q.items():
            if name == "has_child":
                out.update(self._resolve_has_child(body))
            elif name == "has_parent":
                out.update(self._resolve_has_parent(body))
            elif name == "parent_id":
                out.update(self._resolve_parent_id(body))
            elif name in self._COMPOUND_NODES and isinstance(body, dict):
                nb = dict(body)
                for k, v in body.items():
                    if k in self._QUERY_LIST_KEYS and isinstance(v, list):
                        nb[k] = [self._resolve_joins(x) for x in v]
                    elif k in self._QUERY_LIST_KEYS + self._QUERY_CHILD_KEYS \
                            and isinstance(v, dict):
                        nb[k] = self._resolve_joins(v)
                    elif k == "functions" and isinstance(v, list):
                        # function_score function entries carry a filter
                        # query each
                        nb[k] = [
                            ({**fn, "filter": self._resolve_joins(
                                fn["filter"])}
                             if isinstance(fn, dict) and
                             isinstance(fn.get("filter"), dict) else fn)
                            for fn in v]
                out[name] = nb
            elif name in ("and", "or", "dis_max") and isinstance(body, list):
                out[name] = [self._resolve_joins(x) for x in body]
            else:
                out[name] = body  # leaf query — never recurse into values
        return out

    def _join_parent_of_hit(self, doc_id: str, pcol: str) -> str | None:
        seg, local = self._locate(doc_id)
        if seg is None:
            return None
        kc = seg.keywords.get(pcol)
        if kc is None or kc.ords[local] < 0:
            return None
        return kc.terms[kc.ords[local]]

    def _resolve_has_child(self, spec: dict) -> dict:
        from collections import Counter
        fm = self._join_field("has_child")
        ctype = spec.get("type") or spec.get("child_type")
        inner = self._resolve_joins(spec.get("query") or {"match_all": {}})
        hits = self._collect_all_hits(
            {"bool": {"must": [inner],
                      "filter": [{"term": {fm.name: ctype}}]}})
        pcol = f"{fm.name}#parent"
        counts: Counter = Counter()
        for h in hits:
            pid = self._join_parent_of_hit(h["_id"], pcol)
            if pid is not None:
                counts[pid] += 1
        mn = int(spec.get("min_children", 1) or 1)
        mx = spec.get("max_children")
        ids = [p for p, c in counts.items()
               if c >= mn and (mx is None or c <= int(mx))]
        if not ids:
            return {"match_none": {}}
        return {"ids": {"values": sorted(ids)}}

    def _resolve_has_parent(self, spec: dict) -> dict:
        fm = self._join_field("has_parent")
        ptype = spec.get("parent_type") or spec.get("type")
        inner = self._resolve_joins(spec.get("query") or {"match_all": {}})
        hits = self._collect_all_hits(
            {"bool": {"must": [inner],
                      "filter": [{"term": {fm.name: ptype}}]}})
        pids = {h["_id"] for h in hits}
        if not pids:
            return {"match_none": {}}
        # children of the matched parents: vectorized membership test on
        # the parent-id ordinal column
        pcol = f"{fm.name}#parent"
        child_ids: list[str] = []
        for seg in self.segments:
            kc = seg.keywords.get(pcol)
            if kc is None:
                continue
            want = np.asarray([i for i, t in enumerate(kc.terms)
                               if t in pids], dtype=np.int32)
            if want.size == 0:
                continue
            n = seg.num_docs
            mask = (self.live[seg.seg_id][:n]
                    & np.isin(kc.ords[:n], want))
            child_ids.extend(seg.ids[d] for d in np.nonzero(mask)[0])
        if not child_ids:
            return {"match_none": {}}
        return {"ids": {"values": sorted(child_ids)}}

    def _resolve_parent_id(self, spec: dict) -> dict:
        fm = self._join_field("parent_id")
        ctype = spec.get("type")
        pid = spec.get("id")
        clauses = [{"term": {f"{fm.name}#parent": str(pid)}}]
        if ctype:
            clauses.append({"term": {fm.name: ctype}})
        return {"bool": {"filter": clauses}}

    def _locate(self, doc_id: str) -> tuple[Segment | None, int]:
        for seg in self.segments:
            d = seg.id_map.get(doc_id)
            if d is not None and self.live[seg.seg_id][d]:
                return seg, d
        return None, -1

    # -- internals ---------------------------------------------------------
    def _ords_for(self, specs: list[AggSpec]) -> dict:
        out = {}
        for s in specs:
            if s.kind in ("terms", "cardinality"):
                out[s.field] = self.global_ords(s.field)
        return out

    def _parse_request(self, body: dict) -> dict:
        body = body or {}

        def doc_lookup(doc_id: str):
            seg, local = self._locate(doc_id)
            return _load_source(seg.sources[local]) if seg is not None else None

        raw_query = body.get("query")
        if raw_query is not None and _has_join_nodes(raw_query):
            raw_query = self._resolve_joins(raw_query)
        query: Query = QueryParser(self.mappers, index_name=self.index_name,
                                   doc_lookup=doc_lookup).parse(raw_query)
        all_specs = parse_aggs(body.get("aggs") or body.get("aggregations"))
        from .aggregations import DERIVED_KINDS
        derived_specs = [s for s in all_specs if s.kind in DERIVED_KINDS]
        agg_specs = [s for s in all_specs if s.kind not in DERIVED_KINDS]
        for spec in agg_specs:
            if spec.kind in ("terms", "cardinality", "value_count"):
                spec.field = self._keyword_fallback(spec.field)
        size = int(body.get("size", 10))
        frm = int(body.get("from", 0))
        if size < 0 or frm < 0:
            raise SearchParseError("[from] and [size] must be >= 0")
        sort_spec = self._parse_sort(body.get("sort"))
        src = body.get("_source", True)
        stored_fields = body.get("fields")
        if isinstance(stored_fields, str):
            stored_fields = [stored_fields]
        if stored_fields is not None:
            # a fields list suppresses _source unless "_source" is listed
            # (ref: search/fetch/FieldsParseElement)
            if "_source" in stored_fields:
                stored_fields = [f for f in stored_fields if f != "_source"]
            elif "_source" not in body:
                src = False
        rescore = body.get("rescore")
        if rescore is not None:
            if isinstance(rescore, list):
                rescore = rescore[0] if rescore else None
        if rescore is not None:
            q = rescore.get("query") or {}
            rescore = {
                "window_size": int(rescore.get("window_size", 10)),
                "query": q.get("rescore_query"),
                "query_weight": float(q.get("query_weight", 1.0)),
                "rescore_query_weight": float(q.get("rescore_query_weight", 1.0)),
                "score_mode": str(q.get("score_mode", "total")),
            }
            if rescore["query"] is None:
                raise SearchParseError("[rescore] requires [rescore_query]")
        nested_scope = body.get("_nested_scope")
        static_sig = (
            tuple((s.name, s.kind, s.field, s.interval, s.size,
                   s.min_doc_count, s.order, s.precision,
                   tuple((m.name, m.kind, m.field) for m in s.sub_metrics))
                  for s in agg_specs),
            sort_spec, frm + size, bool(nested_scope),
        )
        return {"query": query, "agg_specs": agg_specs, "size": size,
                "from": frm, "sort_spec": sort_spec, "source_filter": src,
                "static_sig": static_sig,
                "want_version": bool(body.get("version", False)),
                "stored_fields": stored_fields,
                "rescore": rescore,
                "script_fields": self._parse_script_fields(
                    body.get("script_fields")),
                "derived_specs": derived_specs,
                "raw_query": raw_query,
                "nested_scope": nested_scope,
                "dfs_stats": body.get("_dfs_stats"),
                "reverse_ctx": body.get("_reverse_ctx"),
                "highlight": parse_highlight(body.get("highlight")),
                "suggest_specs": parse_suggest(body.get("suggest"))}

    def _parse_script_fields(self, spec) -> list:
        """script_fields (ref: search/fetch/script/ScriptFieldsParseElement)
        -> [(name, CompiledScript, params)], evaluated host-side per hit."""
        if not spec:
            return []
        from ..script import parse_script_spec, compile_script
        out = []
        for name, conf in spec.items():
            src, params = parse_script_spec(conf)
            out.append((name, compile_script(src), params))
        return out

    def _keyword_fallback(self, field: str) -> str:
        """Aggregating/sorting on a text field falls back to its .keyword
        multi-field twin when one exists (modern-ES UX; the ES 2.0
        equivalent was analyzed-string fielddata)."""
        fm = self.mappers.field(field)
        if fm is not None and fm.type == "text":
            twin = self.mappers.field(f"{field}.keyword")
            if twin is not None and twin.type == "keyword":
                return f"{field}.keyword"
        return field

    def _parse_sort(self, sort) -> tuple:
        """-> ("_score",) | ("field", name, descending, kindtag)
        | ("multi", ((name, descending, kindtag), ...)).

        Multi-key sorts take a dedicated host-lexsort path over the
        device match mask (ref: SortParseElement multi-field sort +
        Lucene FieldComparator chaining)."""
        if sort is None:
            return ("_score",)
        entries = sort if isinstance(sort, list) else [sort]
        if not entries:
            return ("_score",)
        if len(entries) > 1:
            keys = []
            for e in entries:
                if isinstance(e, str):
                    fld, order = e, "asc"
                else:
                    fld, spec = next(iter(e.items()))
                    order = (spec.get("order", "asc")
                             if isinstance(spec, dict) else str(spec))
                if fld in ("_geo_distance", "_geoDistance", "_script"):
                    raise SearchParseError(
                        f"[{fld}] is not supported in multi-key sort")
                if fld == "_score":
                    raise SearchParseError(
                        "[_score] in a multi-key sort is not supported "
                        "yet (field keys only)")
                fld = self._keyword_fallback(fld)
                kindtag = "num"
                for seg in self.segments:
                    k = seg.field_kind(fld)
                    if k == "keyword":
                        kindtag = "kw"
                    elif k == "text":
                        if seg.ensure_text_sort_column(fld):
                            self._global_ords.pop(fld, None)
                        kindtag = "kw"
                fm = self.mappers.field(fld)
                if fm is not None and fm.type == "keyword":
                    kindtag = "kw"
                keys.append((fld, str(order).lower() == "desc", kindtag))
            return ("multi", tuple(keys))
        entry = entries[0]
        if isinstance(entry, str):
            fld, order = entry, "asc"
            if fld == "_score":
                return ("_score",)
        else:
            fld, spec = next(iter(entry.items()))
            if fld == "_score":
                return ("_score",)
            if fld in ("_geo_distance", "_geoDistance"):
                # ref: search/sort/GeoDistanceSortParser.java
                from ..ops.geo import parse_geo_point, distance_unit_meters
                if not isinstance(spec, dict):
                    raise SearchParseError(
                        "[_geo_distance] sort requires an object")
                geo_field = None
                point = None
                for k, v in spec.items():
                    if k not in ("order", "unit", "mode", "distance_type",
                                 "ignore_unmapped", "nested_path"):
                        geo_field, point = k, v
                if geo_field is None:
                    raise SearchParseError(
                        "[_geo_distance] sort requires a geo_point field")
                lat, lon = parse_geo_point(point)
                unit_m = distance_unit_meters(spec.get("unit", "m"))
                order = str(spec.get("order", "asc")).lower()
                return ("field", geo_field, order == "desc", "geo",
                        lat, lon, unit_m)
            if fld == "_script":
                # script sort (ref: search/sort/ScriptSortParser.java) —
                # keys computed on-device from doc-value columns; params
                # baked into the static tag (part of the jit cache key)
                from ..script import parse_script_spec, compile_script
                from ..script.service import numeric_param
                src, sparams = parse_script_spec(spec)
                compile_script(src)
                ptag = ",".join(f"{k}={numeric_param(k, v)}"
                                for k, v in sorted(sparams.items()))
                order = str(spec.get("order", "asc")).lower() \
                    if isinstance(spec, dict) else "asc"
                return ("field", f"{src}\x00{ptag}", order == "desc",
                        "script")
            order = (spec.get("order", "asc") if isinstance(spec, dict)
                     else str(spec)).lower()
        fld = self._keyword_fallback(fld)
        kindtag = None
        for seg in self.segments:
            k = seg.field_kind(fld)
            if k == "keyword":
                kindtag = "kw"
            elif k == "numeric":
                kindtag = kindtag or "num"
            elif k == "text":
                # analyzed-string sort: min-term ordinal view (ES 2.0
                # string fielddata semantics)
                if seg.ensure_text_sort_column(fld):
                    self._global_ords.pop(fld, None)
                kindtag = "kw"
        if kindtag is None:
            fm = self.mappers.field(fld)
            if fm is None:
                # ref: SortParseElement "No mapping found for [f] in order to sort on"
                raise SearchParseError(
                    f"No mapping found for [{fld}] in order to sort on")
            kindtag = "kw" if fm.type == "keyword" else "num"
        return ("field", fld, order == "desc", kindtag)

    def _build_response(self, p: dict, seg_tops: list, b: int, aggs: dict,
                        started: float, sort_terms: list[str] | None = None) -> dict:
        is_score_sort = p["sort_spec"][0] == "_score"
        descending = True if is_score_sort else p["sort_spec"][2]
        cands = []
        total = 0
        for seg_ord, entry in enumerate(seg_tops):
            top_score, top_key, top_idx, tot, top_miss = entry[:5]
            total += int(tot[b])
            # pack-split entries (streaming delta path) carry a 6th
            # element: the per-row count of candidates that actually
            # landed in this segment's split of the merged top-k (its
            # total alone would over-read into the pad)
            n_valid = (int(entry[5][b]) if len(entry) > 5
                       else min(int(tot[b]), top_score.shape[1]))
            for j in range(n_valid):
                missing = bool(top_miss[b, j])
                cands.append((missing, float(top_key[b, j]), seg_ord,
                              int(top_idx[b, j]), float(top_score[b, j])))
        sign = -1.0 if descending else 1.0
        # missing-field docs sort last regardless of direction (ES _last)
        cands.sort(key=lambda c: (c[0], sign * c[1], c[2], c[3]))
        window = cands[p["from"]: p["from"] + p["size"]]

        hits = []
        max_score = None
        if is_score_sort and cands:
            max_score = cands[0][4] if cands[0][4] > -np.inf else None
        for missing, key, seg_ord, local_doc, score in window:
            seg = self.segments[seg_ord]
            hit = {
                "_index": self.index_name,
                "_type": "_doc",
                "_id": seg.ids[local_doc],
                "_score": score if is_score_sort else (score or None),
            }
            if not is_score_sort:
                if missing:
                    hit["sort"] = [None]
                elif sort_terms is not None:
                    hit["sort"] = [sort_terms[int(key)]]  # global ord -> term
                else:
                    hit["sort"] = [int(key) if float(key).is_integer() else key]
            if p["want_version"]:
                hit["_version"] = int(seg.versions[local_doc])
            src = p["source_filter"]
            if src is not False:
                source = _load_source(seg.sources[local_doc])
                filtered = filter_source(source, src)
                if filtered is not None:
                    hit["_source"] = filtered
            if p["stored_fields"]:
                # stored fields load from _source (all fields are
                # source-backed here; ref: FetchPhase fieldsVisitor)
                source = _load_source(seg.sources[local_doc])
                flds = {}
                for f in p["stored_fields"]:
                    v = source.get(f)
                    if v is None and "." in f:
                        # dotted path into nested objects
                        cur = source
                        for part in f.split("."):
                            cur = (cur.get(part)
                                   if isinstance(cur, dict) else None)
                            if cur is None:
                                break
                        v = cur
                    if v is not None:
                        flds[f] = v if isinstance(v, list) else [v]
                if flds:
                    hit["fields"] = flds
            if p["script_fields"]:
                from ..script import run_field_script
                sf = hit.setdefault("fields", {})
                for name, cs, sparams in p["script_fields"]:
                    val = run_field_script(cs, seg, local_doc, sparams,
                                           score=score)
                    sf[name] = [val]
            hits.append(hit)

        took = int((time.monotonic() - started) * 1000)
        resp = {
            "took": took,
            "timed_out": False,
            "_shards": {"total": 1, "successful": 1, "failed": 0},
            "hits": {"total": total, "max_score": max_score, "hits": hits},
        }
        if aggs:
            resp["aggregations"] = aggs
        return resp

    def _empty_response(self, p: dict, started: float,
                        with_partials: bool = False) -> dict:
        resp = {
            "took": int((time.monotonic() - started) * 1000),
            "timed_out": False,
            "_shards": {"total": 1, "successful": 1, "failed": 0},
            "hits": {"total": 0, "max_score": None, "hits": []},
        }
        if p["agg_specs"]:
            from .aggregations import finalize_partials
            if with_partials:
                resp["_agg_partials"] = {}
            else:
                resp["aggregations"] = finalize_partials(p["agg_specs"], {})
        return resp


def filter_source(source: dict, spec) -> dict | None:
    """_source filtering: True/False, "field", [fields], or
    {"includes": [...], "excludes": [...]} with * wildcards
    (ref: search/fetch/source/FetchSourceContext.java). The _ttl_expiry
    metadata column never surfaces (the reference keeps _ttl out of
    _source too)."""
    if isinstance(source, dict) and "_ttl_expiry" in source:
        source = {k: v for k, v in source.items() if k != "_ttl_expiry"}
    if spec is True:
        return source
    if spec is False:
        return None
    if isinstance(spec, (str, list)):
        includes = [spec] if isinstance(spec, str) else list(spec)
        excludes = []
    else:
        includes = spec.get("includes") or spec.get("include") or []
        excludes = spec.get("excludes") or spec.get("exclude") or []
        if isinstance(includes, str):
            includes = [includes]
        if isinstance(excludes, str):
            excludes = [excludes]

    import fnmatch

    def keep(path: str) -> bool:
        # an include pattern keeps the node itself, any ancestor (so the
        # walk can descend), and any descendant of a matched subtree
        if includes and not any(fnmatch.fnmatch(path, p)
                                or p.startswith(path + ".")
                                or path.startswith(p + ".")
                                for p in includes):
            return False
        if any(fnmatch.fnmatch(path, p)
               or path.startswith(p + ".") for p in excludes):
            return False
        return True

    def walk(obj: dict, prefix: str) -> dict:
        out = {}
        for k, v in obj.items():
            path = f"{prefix}{k}"
            if isinstance(v, dict):
                sub = walk(v, f"{path}.")
                if sub or keep(path):
                    out[k] = sub
            elif keep(path):
                out[k] = v
        return out

    return walk(source, "")


_JOIN_NODE_KEYS = ("has_child", "has_parent", "parent_id")


def _has_join_nodes(q) -> bool:
    if isinstance(q, dict):
        return any(k in _JOIN_NODE_KEYS or _has_join_nodes(v)
                   for k, v in q.items())
    if isinstance(q, list):
        return any(_has_join_nodes(x) for x in q)
    return False


def _load_source(raw: bytes) -> dict:
    """Parse stored _source bytes; rows without source (legacy hidden
    child rows) read as an empty object."""
    if not raw:
        return {}
    return json.loads(raw)


def _default_live(seg: Segment) -> np.ndarray:
    live = np.zeros(seg.capacity, dtype=bool)
    live[: seg.num_docs] = True
    return live
