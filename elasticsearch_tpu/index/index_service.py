"""Per-index service: settings + mapper + shard engines.

Reference analog: the per-index injector the reference builds
(index/IndexService via indices/IndicesService.java) holding
MapperService, AnalysisService and the index's IndexShards.
"""

from __future__ import annotations

import contextlib
import os
import threading

from ..utils import profiler
from ..utils.settings import Settings
from ..utils.errors import (DocumentMissingError, ElasticsearchTpuError,
                            PowerLossError, ShardNotFoundError)
from ..cluster.routing import shard_id as route_shard
from .mapping import MapperService
from .engine import Engine, IndexOp
from .stats import IndexOpStats


class IndexService:
    def __init__(self, name: str, settings: Settings = Settings.EMPTY,
                 mapping: dict | None = None, data_path: str | None = None,
                 type_mappings: dict | None = None):
        self.name = name
        self.settings = settings
        self.num_shards = settings.get_int("index.number_of_shards", 1)
        self.num_replicas = settings.get_int("index.number_of_replicas", 0)
        self.mappers = MapperService(settings, mapping,
                                     type_mappings=type_mappings)
        self.data_path = data_path
        self.shards: dict[int, Engine] = {}
        for s in range(self.num_shards):
            path = None
            if data_path:
                path = os.path.join(data_path, name, str(s))
                os.makedirs(path, exist_ok=True)
            self.shards[s] = Engine(name, s, self.mappers, path=path,
                                    settings=settings)
        from ..percolator import PercolatorRegistry
        self.percolator = PercolatorRegistry(
            os.path.join(data_path, name) if data_path else None)
        # per-doc mapping type (ref: the _uid = type#id identity of
        # index/mapper/internal/UidFieldMapper.java; we keep a single
        # type per id — last write wins — which covers the REST
        # contract: typed get/delete must match, _all returns it)
        self.doc_types: dict[str, str] = {}
        # per-doc routing value when one was supplied at index time
        # (ref: index/mapper/internal/RoutingFieldMapper.java)
        self.doc_routing: dict[str, str] = {}
        # per-doc parent id (ref: ParentFieldMapper; parent routes the doc)
        self.doc_parent: dict[str, str] = {}
        # per-doc index timestamp millis (ref: TimestampFieldMapper)
        self.doc_ts: dict[str, int] = {}
        # mapping type names declared via create-index/put-mapping
        # (rendered in GET _mapping; distinct from per-doc types above)
        self.mapping_types: set[str] = set()
        # operation counters feeding the _stats API
        # (ref: action/admin/indices/stats/CommonStats.java)
        self.op_stats = IndexOpStats()
        # engines record pack-build wall-time/docs here (the
        # indices_stats indexing block's build_* fields)
        for eng in self.shards.values():
            eng.op_stats = self.op_stats
        # shard request cache (ref: indices/cache/query/
        # IndicesQueryCache.java) — generation-keyed (index/cache.py):
        # entries are invalidated exactly by compaction / delta-epoch
        # re-keys, never flushed by refresh; stats live here
        from .cache import ShardRequestCache
        self.request_cache = ShardRequestCache(
            max_entries=self.settings.get_int(
                "index.cache.query.max_entries", 1024),
            max_bytes=self.settings.get_int(
                "index.cache.query.max_bytes", 64 * 1024 * 1024))
        # engine-write + metadata updates for ONE doc id must be atomic
        # (a concurrent delete interleaving between them could pop
        # metadata a write just recorded), but writes to DIFFERENT ids
        # must stay parallel across shards — so stripe locks by id and
        # keep a single lock only for the shared _types.json tmp file
        self._id_locks = [threading.Lock() for _ in range(16)]
        self._meta_lock = threading.Lock()
        # per-thread write-through batching (batched_meta_saves)
        self._meta_batch = threading.local()
        self._types_path = (os.path.join(data_path, name, "_types.json")
                            if data_path else None)
        if self._types_path and os.path.exists(self._types_path):
            import json
            with open(self._types_path) as f:
                meta = json.load(f)
            if "types" in meta or "routing" in meta or "parent" in meta:
                self.doc_types = meta.get("types", {})
                self.doc_routing = meta.get("routing", {})
                self.doc_parent = meta.get("parent", {})
                self.doc_ts = meta.get("ts", {})
            else:   # legacy flat {id: type} layout
                self.doc_types = meta

    def _id_lock(self, doc_id: str) -> threading.Lock:
        return self._id_locks[hash(doc_id) % len(self._id_locks)]

    def percolate(self, doc: dict, percolate_filter: dict | None = None,
                  size: int | None = None) -> dict:
        from ..percolator import percolate as run
        return run(self.percolator, self.mappers, self.name, doc,
                   percolate_filter, size, index_settings=self.settings)

    def shard(self, sid: int) -> Engine:
        eng = self.shards.get(sid)
        if eng is None:
            raise ShardNotFoundError(self.name, sid)
        return eng

    def shard_for(self, doc_id: str, routing: str | None = None) -> Engine:
        return self.shard(route_shard(doc_id, self.num_shards, routing))

    # -- write path --------------------------------------------------------
    def index_doc(self, doc_id: str, source, version: int | None = None,
                  routing: str | None = None,
                  doc_type: str | None = None,
                  version_type: str = "internal",
                  parent: str | None = None,
                  timestamp_ms: int | None = None) -> dict:
        """One document: a batch of one."""
        r = self.index_many([IndexOp(doc_id, source, version, version_type,
                                     False, routing, parent, doc_type,
                                     timestamp_ms)])[0]
        if isinstance(r, ElasticsearchTpuError):
            raise r
        return r

    def index_many(self, ops: list[IndexOp]
                   ) -> list[dict | ElasticsearchTpuError]:
        """A run of index operations as one batch per shard (ref:
        TransportBulkAction groups a request's items by shard): route
        every id, hand each shard its ops in request order, then record
        the metadata of those that went in. One result an op, in the
        order given: the write's response, or the error that op alone
        failed with (a failed or closed shard fails its own ops)."""
        with profiler.phase("bulk_route"):
            if self.num_shards == 1:
                by_shard = {0: list(range(len(ops)))}
            else:
                by_shard: dict[int, list[int]] = {}
                for i, op in enumerate(ops):
                    sid = route_shard(
                        op.doc_id, self.num_shards,
                        op.routing if op.routing is not None else op.parent)
                    by_shard.setdefault(sid, []).append(i)
            stripes = sorted({hash(op.doc_id) % len(self._id_locks)
                              for op in ops})
        results: list = [None] * len(ops)
        shards = {"total": 1 + self.num_replicas, "successful": 1,
                  "failed": 0}
        indexed: list[str | None] = []
        batches = batch_docs = 0
        with contextlib.ExitStack() as locks:
            # every stripe the batch's ids fall in, in one order (a
            # single write holds one stripe, so no two holders cross)
            for stripe in stripes:
                locks.enter_context(self._id_locks[stripe])
            # one metadata write-through a batch, at this block's exit:
            # before the caller can acknowledge any of it
            locks.enter_context(self.batched_meta_saves())
            for sid, slots in by_shard.items():
                try:
                    written = self.shard(sid).index_many(
                        [ops[i] for i in slots])
                except PowerLossError:
                    raise       # the process died: nothing answers
                except ElasticsearchTpuError as e:
                    written = [e] * len(slots)
                done = len(indexed)
                for i, r in zip(slots, written):
                    if not isinstance(r, ElasticsearchTpuError):
                        op = ops[i]
                        r["_index"] = self.name
                        r["_type"] = self._record_meta(op)
                        r["_shards"] = dict(shards)
                        indexed.append(op.doc_type)
                    results[i] = r
                if len(slots) > 1:
                    batches += 1
                    batch_docs += len(indexed) - done
        self.op_stats.on_index_many(indexed, batches, batch_docs)
        return results

    def _record_meta(self, op: IndexOp) -> str:
        """Record a written doc's timestamp, parent, type and routing
        (caller holds the id's stripe lock, so the persisted snapshot
        always includes the triggering write's); returns the type the
        response names, read under the same lock, or a concurrent
        delete could make a typed write report _doc."""
        doc_id = op.doc_id
        routing = op.routing if op.routing is not None else op.parent
        meta_dirty = False
        if op.timestamp_ms is not None:
            meta_dirty |= self.doc_ts.get(doc_id) != op.timestamp_ms
            self.doc_ts[doc_id] = op.timestamp_ms
        if op.parent is not None:
            meta_dirty |= self.doc_parent.get(doc_id) != str(op.parent)
            self.doc_parent[doc_id] = str(op.parent)
        else:
            meta_dirty |= self.doc_parent.pop(doc_id, None) is not None
        if op.doc_type and op.doc_type != "_doc":
            meta_dirty |= self.doc_types.get(doc_id) != op.doc_type
            self.doc_types[doc_id] = op.doc_type
        else:
            meta_dirty |= self.doc_types.pop(doc_id, None) is not None
        if routing is not None:
            meta_dirty |= self.doc_routing.get(doc_id) != str(routing)
            self.doc_routing[doc_id] = str(routing)
        else:
            meta_dirty |= self.doc_routing.pop(doc_id, None) is not None
        resp_type = self.doc_types.get(doc_id, "_doc")
        if meta_dirty:
            # write-through: the engine's translog made the DOC durable
            # at this point, so its type/routing metadata must be
            # durable too (crash between here and flush must not turn
            # a typed get into a 404 after replay)
            self._save_types()
        return resp_type

    def _check_type(self, doc_id: str, doc_type: str | None) -> str:
        stored = self.doc_types.get(doc_id, "_doc")
        if doc_type not in (None, "_all", stored):
            raise DocumentMissingError(self.name, doc_id)
        return stored

    def delete_doc(self, doc_id: str, version: int | None = None,
                   routing: str | None = None,
                   doc_type: str | None = None,
                   version_type: str = "internal") -> dict:
        with self._id_lock(doc_id):
            # type check + stored-type read belong under the same lock as
            # the engine op (symmetric with index_doc's resp_type read)
            stored = self._check_type(doc_id, doc_type)
            r = self.shard_for(doc_id, routing).delete(
                doc_id, version, version_type=version_type)
            # only clear metadata when the engine actually removed the doc:
            # a routed doc deleted without routing hits the wrong shard and
            # returns found:false — its type/routing must survive
            if r.get("found"):
                dirty = self.doc_types.pop(doc_id, None) is not None
                dirty |= self.doc_routing.pop(doc_id, None) is not None
                dirty |= self.doc_parent.pop(doc_id, None) is not None
                self.doc_ts.pop(doc_id, None)
                if dirty:
                    self._save_types()
        r["_index"] = self.name
        r["_type"] = stored
        r["_shards"] = {"total": 1 + self.num_replicas,
                        "successful": 1, "failed": 0}
        self.op_stats.on_delete()
        return r

    def get_doc(self, doc_id: str, routing: str | None = None,
                doc_type: str | None = None, realtime: bool = True) -> dict:
        try:
            stored = self._check_type(doc_id, doc_type)
            r = self.shard_for(doc_id, routing).get(doc_id,
                                                    realtime=realtime)
        except DocumentMissingError:
            self.op_stats.on_get(found=False)
            raise
        self.op_stats.on_get(found=bool(r.get("found", True)))
        r["_index"] = self.name
        r["_type"] = stored
        if doc_id in self.doc_routing:
            r["_routing"] = self.doc_routing[doc_id]
        if doc_id in self.doc_parent:
            r["_parent"] = self.doc_parent[doc_id]
        return r

    def doc_type_of(self, doc_id: str) -> str:
        return self.doc_types.get(doc_id, "_doc")

    @contextlib.contextmanager
    def batched_meta_saves(self):
        """Coalesce this thread's per-op metadata write-through into ONE
        save at exit — for a bulk request, still BEFORE its response
        acknowledges any item, so the durability contract of
        _save_types is unchanged. Without it every op of a bulk rewrites
        the whole snapshot: O(docs^2) bytes, minutes per 5k-doc chunk
        once an index holds a few hundred thousand docs."""
        if getattr(self._meta_batch, "on", False):
            yield                      # nested: the outer exit saves
            return
        self._meta_batch.on, self._meta_batch.dirty = True, False
        try:
            yield
        finally:
            self._meta_batch.on = False
            if self._meta_batch.dirty:
                self._save_types()

    def _save_types(self) -> None:
        if self._types_path is None:
            return
        if getattr(self._meta_batch, "on", False):
            self._meta_batch.dirty = True
            return
        import json
        with self._meta_lock:
            # snapshot INSIDE the file lock so the last write always
            # reflects every previously completed mutation (a snapshot
            # taken before the lock could overwrite a newer file with
            # older state); dict() of a str-keyed dict is GIL-atomic, so
            # concurrent id-stripe holders can't corrupt the copy
            snap = {"types": dict(self.doc_types),
                    "routing": dict(self.doc_routing),
                    "parent": dict(self.doc_parent),
                    "ts": dict(self.doc_ts)}
            tmp = self._types_path + ".tmp"
            with open(tmp, "w") as f:
                # dumps, not dump: the C encoder (dump streams through
                # the pure-Python one, ~10x slower on a 1M-entry map)
                f.write(json.dumps(snap))
            os.replace(tmp, self._types_path)

    # -- maintenance -------------------------------------------------------
    def refresh(self) -> None:
        from .stats import timed
        with timed() as t:
            for eng in self.shards.values():
                eng.refresh()
        self.op_stats.on_refresh(t.ms)

    def flush(self) -> None:
        from .stats import timed
        with timed() as t:
            for eng in self.shards.values():
                eng.flush()
            self._save_types()
        self.op_stats.on_flush(t.ms)

    def force_merge(self, max_num_segments: int = 1) -> None:
        from .stats import timed
        with timed() as t:
            for eng in self.shards.values():
                eng.force_merge(max_num_segments)
        self.op_stats.on_merge(t.ms)

    def doc_count(self) -> int:
        return sum(e.doc_count() for e in self.shards.values())

    def stats(self) -> dict:
        seg = [e.segment_stats() for e in self.shards.values()]
        return {
            "docs": {"count": self.doc_count()},
            "segments": {"count": sum(s["count"] for s in seg),
                         "memory_in_bytes": sum(s["memory_in_bytes"] for s in seg)},
            "shards": {str(i): s for i, s in enumerate(seg)},
        }

    def close(self) -> None:
        for eng in self.shards.values():
            eng.close()
        self._save_types()
