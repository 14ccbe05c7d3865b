"""Per-shard engine: indexing buffer, versioning, refresh, flush, recovery.

Reference analog: index/engine/InternalEngine.java — one writer + NRT
searcher + LiveVersionMap per shard: create/index (:234/:340 with per-uid
version checks :253-274), delete (:439), refresh (:549-555), flush =
commit + translog rotation (:574+), forceMerge (:715), plus
index/gateway/ local recovery (translog replay on restart).

TPU-first reinterpretation:
  * Lucene IndexWriter buffer -> host-side SegmentBuilder of parsed docs
  * NRT reader -> immutable list of device-resident Segments + live masks;
    refresh() builds a new segment, uploads its columns, publishes a new
    ShardReader (searches never block writes)
  * liveDocs -> numpy live masks (device copy refreshed on publish)
  * versioned optimistic concurrency preserved exactly (VersionConflict)
  * merge -> host-side columnar repack of the smallest segments
    (TieredMergePolicy-lite) to bound per-query segment count
"""

from __future__ import annotations

import itertools
import os
import threading
# module-scope clock (was an inline `import time` per delete/refresh):
# tombstone retention (index.gc_deletes) measures a RETENTION WINDOW,
# so it reads time.monotonic() — a wall-clock jump (NTP step, DST) must
# not prematurely GC a tombstone (late replicated deletes would
# resurrect docs) or immortalize one (the map would grow unbounded)
import time
from typing import NamedTuple

import numpy as np

from ..utils import profiler
from ..utils.errors import (DocumentMissingError, ElasticsearchTpuError,
                            IllegalArgumentError, ShardFailedError,
                            ShardNotFoundError, VersionConflictError)
from ..utils.settings import Settings
from ..index.mapping import MapperService, ParsedDocument
from . import devbuild, durability
from .segment import (Segment, SegmentBuilder, concat_segments,
                      merge_segments, pad_delta_shapes)
from .store import CorruptIndexError, Store
from .translog import (Translog, TranslogCorruptedError, TranslogOp,
                       OP_INDEX, OP_DELETE)
from ..search.shard_searcher import ShardReader

_TRUE = ("1", "true", "on", "yes")


def delta_pack_default() -> bool:
    """Streaming delta-pack mode default (`ES_TPU_DELTA_PACK`); the
    per-index setting `index.streaming.delta` overrides. Opt-in, the
    resident-loop convention: unset keeps the legacy
    append-a-segment-per-refresh engine byte-for-byte."""
    return os.environ.get("ES_TPU_DELTA_PACK", "").lower() in _TRUE

_seg_counter = itertools.count(1)
_seg_counter_mx = threading.Lock()


def _ensure_seg_counter_above(n: int) -> None:
    """Advance the process-wide segment-id counter past `n`. Recovery
    calls this with the highest recovered sid ordinal: a restarted
    process otherwise counts from 1 again and a NEW segment eventually
    collides with a COMMITTED one's seg_id — the live-mask dict and
    the commit's file map are sid-keyed, so the collision silently
    drops committed docs (found by the kill -9 soak)."""
    global _seg_counter
    with _seg_counter_mx:
        cur = next(_seg_counter)
        _seg_counter = itertools.count(max(cur, n + 1))

_MERGE_POOL = None


def _merge_pool(settings: Settings):
    """Process-wide merge executor (ref: the merge thread pool behind
    ConcurrentMergeScheduler); first engine's
    index.merge.scheduler.max_thread_count wins."""
    global _MERGE_POOL
    if _MERGE_POOL is None:
        from concurrent.futures import ThreadPoolExecutor
        _MERGE_POOL = ThreadPoolExecutor(
            max_workers=settings.get_int(
                "index.merge.scheduler.max_thread_count", 2),
            thread_name_prefix="merge")
    return _MERGE_POOL

_VERSION_TYPES = ("internal", "external", "external_gte", "external_gt",
                  "force")


def _validate_version_type(version: int | None, version_type: str) -> None:
    """Reject malformed version args up front (HTTP 400), regardless of
    whether the target doc exists (ref: VersionType.fromString +
    validateVersionForWrites)."""
    if version_type not in _VERSION_TYPES:
        raise IllegalArgumentError(
            f"version type [{version_type}] is not supported")
    if version is None and version_type != "internal":
        raise IllegalArgumentError(
            f"version type [{version_type}] requires an explicit version")


class IndexOp(NamedTuple):
    """One index operation of a batch, as Node hands it to
    IndexService.index_many and that to Engine.index_many; the engine
    reads the first five fields, the rest is the index's metadata."""

    doc_id: str
    source: dict | bytes | str
    version: int | None = None
    version_type: str = "internal"
    create: bool = False          # op_type=create: fail on a live id
    routing: str | None = None
    parent: str | None = None
    doc_type: str | None = None
    timestamp_ms: int | None = None


class Engine:
    """One shard's write path + searcher publication."""

    def __init__(self, index_name: str, shard_id: int, mapper: MapperService,
                 path: str | None = None, settings: Settings = Settings.EMPTY):
        self.index_name = index_name
        self.shard_id = shard_id
        self.mappers = mapper
        self.settings = settings
        self._lock = threading.RLock()
        self.max_segments = settings.get_int("index.merge.max_segment_count", 8)

        # device-parallel build (index/devbuild.py): route this shard's
        # pack builds (refresh + compaction) through the device builder;
        # the per-index `index.build.device` setting overrides the
        # process default (ES_TPU_DEVICE_BUILD / devbuild.configure)
        self._device_build = settings.get_bool(
            "index.build.device", devbuild.device_build_default())
        # IndexService points this at its IndexOpStats so refresh and
        # compaction surface build wall-time + docs/sec in the
        # indices_stats() indexing block
        self.op_stats = None

        # per-field similarity resolver, re-resolved at every segment
        # build so put-mapping'd fields take effect at next refresh
        # (ref: index/similarity/SimilarityService.java)
        self._sim_for = mapper.similarity_for

        self.segments: list[Segment] = []
        self.live: dict[str, np.ndarray] = {}
        self.buffer = SegmentBuilder(similarity=self._sim_for)
        self._buffer_docs: dict[str, tuple[int, bytes]] = {}  # id -> (version, src)
        # live version map (ref: LiveVersionMap.java): holds ONLY ids
        # written since the last refresh plus recent tombstones —
        # versions of refreshed docs load from the segments on demand,
        # and tombstones GC after index.gc_deletes (so the map stays
        # bounded under index/delete churn instead of growing forever)
        self.versions: dict[str, tuple[int, bool]] = {}
        self._tombstone_ts: dict[str, float] = {}
        self._gc_deletes_s = settings.get_time("index.gc_deletes", 60.0)
        self._commit_gen = 0

        # streaming write path (ROADMAP item 1, opt-in): ONE immutable
        # base generation + ONE small delta segment rebuilt per refresh
        # from the parsed docs written since the last compaction, so a
        # refresh is an epoch bump (every (base_generation, delta)
        # keyed cache survives) instead of an eviction. Background
        # compaction folds the delta into a new base via the
        # impact-preserving concat (segment.concat_segments) — the only
        # event that re-keys.
        self._delta_enabled = settings.get_bool("index.streaming.delta",
                                                delta_pack_default())
        self._delta_docs: dict[str, tuple] = {}   # id -> (parsed, version)
        self._delta_seg: Segment | None = None
        self._delta_epoch = 0
        self._base_gen: str | None = None
        self._compactions = 0
        self._compact_inflight = False
        self._compact_min = settings.get_int(
            "index.delta.min_compact_docs", 4096)
        self._compact_ratio = settings.get_float(
            "index.delta.compact_ratio", 0.5)

        # contained-shard state (ISSUE 15): a corruption that salvage
        # cannot prove lossless FAILS the shard — `failed` carries the
        # structured reason, the corruption marker stands in the store
        # dir, and every write/search answers ShardFailedError(503)
        # while the node keeps serving its healthy shards. `on_failed`
        # is the cluster path's containment callback
        # (cluster/distributed_node.py reports the failure to the
        # master so allocation promotes a surviving copy).
        self.failed: dict | None = None
        self.on_failed = None
        self._durability = settings.get_str("index.translog.durability",
                                            "request")
        # the index.shard.check_on_startup analog: verify the store
        # (commit + per-segment checksums) BEFORE serving it
        self._check_on_startup = settings.get_bool(
            "index.shard.check_on_startup", False)
        self.store = Store(path, index=index_name, shard=shard_id) \
            if path else None
        self.translog = None
        # seg_ids referenced by the last durable commit point: their
        # store files must survive until the NEXT commit is written
        # (cleanup_uncommitted reclaims them then) — deleting them at
        # refresh/compaction time would make the commit unrecoverable
        # after a crash, and the rotated translog no longer holds the
        # docs
        self._committed_seg_ids: set[str] = set()
        # sid -> (write-once file stem, live-mask hash) as of the last
        # commit: a flush re-saves a segment ONLY when its live mask
        # changed (segment content is immutable per sid), so committed
        # file pairs are never rewritten in place — the crash window
        # between npz replace and meta write can only ever hit a stem
        # no commit references
        self._committed_files: dict[str, tuple[str, str]] = {}
        self._reader: ShardReader | None = None
        # point-in-time view frozen at the last refresh: searches and
        # non-realtime gets read THIS, not the live bitmaps, so deletes/
        # updates after a refresh stay invisible until the next refresh
        # (ref: InternalEngine.get falls back to getFromSearcher)
        self._view_segments: list[Segment] = []
        self._view_live: dict[str, np.ndarray] = {}
        self._dirty = True
        if self.store is not None:
            # recovery errors must NEVER escape __init__ and poison
            # node startup: one flipped bit wedging shard creation is
            # exactly the failure mode this path contains. Salvage
            # first (_recover falls back per commit generation); what
            # salvage cannot prove lossless becomes a structured
            # contained shard failure. PowerLossError (an injected
            # crash) is deliberately NOT caught — a crashed process
            # runs no handlers.
            try:
                marker = self.store.corruption_marker()
                if marker is not None:
                    raise CorruptIndexError(
                        f"corruption marker present: {marker}")
                if self._check_on_startup:
                    report = self.store.verify_integrity()
                    if not report["clean"]:
                        raise CorruptIndexError(
                            "check_on_startup failed: "
                            f"{report['failures']}")
                self.translog = Translog(
                    f"{path}/translog", durability=self._durability,
                    index=index_name, shard=shard_id)
                self._recover()
            except (CorruptIndexError, TranslogCorruptedError,
                    OSError) as e:
                self._contain(e, during="recovery")

    # -- version map helpers ----------------------------------------------
    def _segment_version(self, doc_id: str) -> int | None:
        """Version of a refresh-published live copy (the LiveVersionMap
        loadFromIndex analog)."""
        for seg in reversed(self.segments):
            d = seg.id_map.get(doc_id)
            if d is not None and self.live[seg.seg_id][d]:
                return int(seg.versions[d])
        return None

    def _current_version(self, doc_id: str) -> int | None:
        v = self.versions.get(doc_id)
        if v is not None:
            return None if v[1] else v[0]
        return self._segment_version(doc_id)

    def _check_open(self) -> None:
        """Writes racing an engine swap (close) surface as
        shard-not-found, which every caller treats as retriable /
        covered-by-recovery rather than an internal error. A FAILED
        (contained) shard answers 503 instead: the data exists but
        this copy refuses to serve it — clients retry against a
        promoted copy (ref: writes to a corruption-failed shard)."""
        if getattr(self, "_engine_closed", False):
            raise ShardNotFoundError(self.index_name, self.shard_id)
        self._check_failed()

    # -- write path (ref: InternalEngine.index :340) -----------------------
    def index(self, doc_id: str, source: dict | bytes | str,
              version: int | None = None, _replay: bool = False,
              version_type: str = "internal") -> dict:
        """One document: a batch of one."""
        r = self.index_many([IndexOp(doc_id, source, version, version_type)],
                            _replay=_replay)[0]
        if isinstance(r, ElasticsearchTpuError):
            raise r
        return r

    def index_many(self, ops: list[IndexOp], _replay: bool = False
                   ) -> list[dict | ElasticsearchTpuError]:
        """Apply a batch in order under ONE take of the lock (ref:
        TransportShardBulkAction.shardOperationOnPrimary: a bulk
        request's items for this shard in one pass, one translog sync).
        The mapper parses the batch, then each op resolves its version
        against what the batch has written so far (the same id twice
        gives versions n and n+1), then the translog takes every applied
        op in one append. An op that fails (version conflict, a `create`
        of a live id, a document the mapper refuses) is its own error in
        the result, and the rest go on. Returns only after the translog
        has the batch, fsynced under `request` durability: nothing of it
        may be acknowledged before.

        Unlike one document at a time, an op refused for its version has
        been through the mapper, so the fields it introduced stay mapped."""
        with self._lock:
            self._check_open()
            with profiler.phase("bulk_parse"):
                parsed = self.mappers.parse_many(
                    [(op.doc_id, op.source) for op in ops])
            results: list[dict | ElasticsearchTpuError] = []
            logged: list[TranslogOp] = []
            with profiler.phase("bulk_apply"):
                for op, doc in zip(ops, parsed):
                    try:
                        results.append(self._apply_index(op, doc, logged))
                    except ElasticsearchTpuError as e:
                        results.append(e)
            if logged and self.translog is not None and not _replay:
                with profiler.phase("bulk_translog"):
                    self.translog.add_many(logged)
            return results

    def _apply_index(self, op: IndexOp,
                     parsed: ParsedDocument | ElasticsearchTpuError,
                     logged: list[TranslogOp]) -> dict:
        doc_id = op.doc_id
        current = self._current_version(doc_id)
        if op.create and current is not None:
            # op_type=create fails on ANY live doc, whatever the version
            # type (ref: DocumentAlreadyExistsException)
            raise VersionConflictError(self.index_name, doc_id, -1, -1)
        new_version = self._resolve_write_version(
            doc_id, current, op.version, op.version_type)
        if isinstance(parsed, ElasticsearchTpuError):
            raise parsed
        self._delete_everywhere(doc_id)
        self.buffer.add(parsed, version=new_version)
        self._buffer_docs[doc_id] = (new_version, parsed.source)
        if self._delta_enabled:
            # the delta rebuild's doc set; re-inserts land at the
            # END (dict order), matching where a fresh segment
            # would have put the updated doc
            self._delta_docs[doc_id] = (parsed, new_version)
        self.versions[doc_id] = (new_version, False)
        self._tombstone_ts.pop(doc_id, None)  # re-index revives
        logged.append(TranslogOp(OP_INDEX, doc_id, new_version,
                                 parsed.source))
        self._dirty = True
        return {"_id": doc_id, "_version": new_version,
                "created": current is None}

    def _resolve_write_version(self, doc_id: str, current: int | None,
                               version: int | None,
                               version_type: str) -> int:
        """Version check + next version (ref: common/lucene/uid/Versions
        + VersionType.{internal,external,external_gte,force}). External
        types take the PROVIDED version as the new version."""
        _validate_version_type(version, version_type)
        if version is None or version_type == "internal":
            if version is not None and current is not None \
                    and current != version:
                raise VersionConflictError(self.index_name, doc_id,
                                           current, version)
            return (current or 0) + 1
        if version_type in ("external", "external_gt"):
            # external_gt is an alias for EXTERNAL (strictly greater),
            # ref: index/VersionType.fromString
            if current is not None and version <= current:
                raise VersionConflictError(self.index_name, doc_id,
                                           current, version)
        elif version_type == "external_gte":
            if current is not None and version < current:
                raise VersionConflictError(self.index_name, doc_id,
                                           current, version)
        return version

    def delete(self, doc_id: str, version: int | None = None,
               _replay: bool = False,
               version_type: str = "internal") -> dict:
        with self._lock:
            self._check_open()
            _validate_version_type(version, version_type)
            current = self._current_version(doc_id)
            if current is None:
                if version is not None and version_type == "internal":
                    raise VersionConflictError(self.index_name, doc_id, -1, version)
                return {"_id": doc_id, "found": False}
            new_version = self._resolve_write_version(
                doc_id, current, version, version_type)
            self._delete_everywhere(doc_id)
            self.versions[doc_id] = (new_version, True)
            self._tombstone_ts[doc_id] = time.monotonic()
            if self.translog is not None and not _replay:
                self.translog.add(TranslogOp(OP_DELETE, doc_id, new_version))
            self._dirty = True
            return {"_id": doc_id, "found": True, "_version": new_version}

    def _delete_everywhere(self, doc_id: str) -> None:
        """Mark any prior copy of doc_id dead (buffer or any segment)."""
        self._delta_docs.pop(doc_id, None)
        if doc_id in self._buffer_docs:
            # rebuild buffer without the doc (rare within one refresh window)
            old = self.buffer
            self.buffer = SegmentBuilder(similarity=self._sim_for)
            for doc, ver in zip(old.docs, old.versions):
                if doc.doc_id != doc_id:
                    self.buffer.add(doc, ver)
            del self._buffer_docs[doc_id]
        for seg in self.segments:
            d = seg.id_map.get(doc_id)
            if d is not None:
                self.live[seg.seg_id][d] = False

    def apply_replicated(self, doc_id: str, source: bytes | None,
                         version: int, delete: bool = False) -> None:
        """Replica-side op application: the primary already resolved the
        version, so apply it verbatim; drop out-of-order older ops.
        Ref: TransportShardBulkAction.shardOperationOnReplica:551."""
        with self._lock:
            self._check_open()
            cur = self.versions.get(doc_id)
            cur_v = cur[0] if cur is not None \
                else self._segment_version(doc_id)
            if cur_v is not None and cur_v >= version:
                return
            self._delete_everywhere(doc_id)
            if delete:
                self.versions[doc_id] = (version, True)
                self._tombstone_ts[doc_id] = time.monotonic()
                if self.translog is not None:
                    self.translog.add(TranslogOp(OP_DELETE, doc_id, version))
            else:
                parsed = self.mappers.parse(doc_id, source)
                self.buffer.add(parsed, version=version)
                self._buffer_docs[doc_id] = (version, parsed.source)
                if self._delta_enabled:
                    self._delta_docs[doc_id] = (parsed, version)
                self.versions[doc_id] = (version, False)
                self._tombstone_ts.pop(doc_id, None)
                if self.translog is not None:
                    self.translog.add(TranslogOp(OP_INDEX, doc_id, version,
                                                 parsed.source))
            self._dirty = True

    def snapshot_docs(self) -> list[tuple[str, int, bytes]]:
        """All live (id, version, source) — the peer-recovery doc stream
        (ref: RecoverySourceHandler phase2 translog snapshot; we stream
        the live-doc set, which subsumes phases 1-2 for a columnar store
        whose segments are rebuilt device-side anyway)."""
        with self._lock:
            self._check_failed()  # a contained copy must never source
            #                       a recovery (its doc set is suspect)
            out: list[tuple[str, int, bytes]] = []
            for seg in self.segments:
                live = self.live[seg.seg_id]
                for d, did in enumerate(seg.ids):
                    if live[d]:
                        out.append((did, int(seg.versions[d]), seg.sources[d]))
            for did, (ver, src) in self._buffer_docs.items():
                out.append((did, ver, src))
            return out

    # -- realtime get (ref: index/get/ShardGetService.java) ----------------
    def get(self, doc_id: str, realtime: bool = True) -> dict:
        with self._lock:
            self._check_failed()
            if realtime:
                v = self.versions.get(doc_id)
                if v is not None and v[1]:
                    # recent tombstone: dead even if a stale segment
                    # copy is still live-masked pre-refresh
                    raise DocumentMissingError(self.index_name, doc_id)
                buffered = self._buffer_docs.get(doc_id)
                if buffered is not None:
                    return {"_id": doc_id, "_version": buffered[0],
                            "found": True, "_source": buffered[1]}
            # realtime reads see current bitmaps; non-realtime reads the
            # last-refresh snapshot (an unrefreshed delete/update must not
            # hide the previously refreshed copy)
            segs = self.segments if realtime else self._view_segments
            live = self.live if realtime else self._view_live
            for seg in segs:
                d = seg.id_map.get(doc_id)
                if d is not None and live[seg.seg_id][d]:
                    return {"_id": doc_id, "_version": int(seg.versions[d]),
                            "found": True, "_source": seg.sources[d]}
            raise DocumentMissingError(self.index_name, doc_id)

    # -- refresh (ref: InternalEngine.refresh :549) ------------------------
    def refresh(self) -> None:
        with self._lock:
            if self.failed is not None:
                return  # a contained shard has nothing to publish
            if not self._dirty:
                return  # nothing indexed/deleted since the last refresh
            if self._delta_enabled:
                self._refresh_delta()
            elif len(self.buffer):
                seg = self._build_segment(self.buffer)
                self.segments.append(seg)
                live = np.zeros(seg.capacity, dtype=bool)
                live[: seg.num_docs] = True
                self.live[seg.seg_id] = live
                self.buffer = SegmentBuilder(similarity=self._sim_for)
                self._buffer_docs = {}
                self._maybe_merge()
            self._prune_version_map()
            self._capture_view()
            self._reader = None  # next acquire builds a fresh point-in-time view
            self._dirty = False

    def _build_segment(self, builder: SegmentBuilder) -> Segment:
        """Build a refresh's pack — through the device-parallel builder
        when enabled (automatic host fallback inside) — and record
        build wall-time + docs for the indices_stats indexing block."""
        seg_id = f"{self.shard_id}_{next(_seg_counter)}"
        t0 = time.monotonic()
        if self._device_build:
            seg = devbuild.build_segment(builder, seg_id,
                                         index=self.index_name,
                                         shard=self.shard_id)
        else:
            seg = builder.build(seg_id)
        if self.op_stats is not None:
            self.op_stats.on_build((time.monotonic() - t0) * 1000.0,
                                   seg.num_docs,
                                   device=self._device_build)
        return seg

    # -- streaming delta pack (ROADMAP item 1) -----------------------------
    def base_generation(self) -> str:
        """Generation key of the immutable base segment set — what delta
        cache keys (Segment.cache_key) ride on. Changes only at
        compaction / force-merge / recovery, never at refresh."""
        if self._base_gen is None:
            import hashlib
            h = hashlib.blake2b(digest_size=8)
            for s in self.segments:
                if s is not self._delta_seg:
                    h.update(s.fingerprint().encode())
            self._base_gen = h.hexdigest()
        return self._base_gen

    def _refresh_delta(self) -> None:
        """Delta-mode refresh: rebuild the ONE delta segment from every
        doc written since the last compaction (caller holds the lock).
        The epoch bump — not an eviction: the new delta carries the
        same (base generation, pow2 capacity bucket) cache key, so
        autotune choices, pinned resident executables, and mesh
        programs all keep serving; deletions of base docs stay live-
        mask flips on the untouched base."""
        if len(self.buffer):
            builder = SegmentBuilder(similarity=self._sim_for)
            for did, (doc, ver) in self._delta_docs.items():
                builder.add(doc, ver)
            seg = self._build_segment(builder)
            seg.delta_parent = self.base_generation()
            seg.delta_epoch = self._delta_epoch + 1
            pad_delta_shapes(seg)
            self._drop_delta_segment()
            if seg.num_docs:
                live = np.zeros(seg.capacity, dtype=bool)
                live[: seg.num_docs] = True
                self.segments.append(seg)
                self.live[seg.seg_id] = live
                self._delta_seg = seg
            self._delta_epoch += 1
            self.buffer = SegmentBuilder(similarity=self._sim_for)
            self._buffer_docs = {}
            self._maybe_compact()

    def _drop_delta_segment(self) -> None:
        old = self._delta_seg
        if old is None:
            return
        if old in self.segments:
            self.segments.remove(old)
        self.live.pop(old.seg_id, None)
        if self.store is not None and old.seg_id not in self._committed_seg_ids:
            # a COMMITTED delta's file must outlive it: the last commit
            # point still lists it and the translog rotated at that
            # commit, so deleting here would lose its docs on a crash
            # before the next flush (cleanup_uncommitted reclaims it
            # once the next commit lands)
            self.store.delete_segment(old.seg_id)
        self._delta_seg = None

    def _maybe_compact(self) -> None:
        """Schedule (or, with the sync merge scheduler, run) background
        compaction once the delta outgrows
        max(index.delta.min_compact_docs,
            index.delta.compact_ratio * base docs)."""
        d = self._delta_seg
        if d is None or self._compact_inflight:
            return
        base_docs = sum(s.num_docs for s in self.segments if s is not d)
        threshold = max(self._compact_min,
                        int(base_docs * self._compact_ratio))
        if d.num_docs <= threshold:
            return
        if self.settings.get_bool("index.merge.scheduler.async", False):
            self._compact_inflight = True
            _merge_pool(self.settings).submit(self._compact_guarded)
        else:
            self._compact_now()

    def _compact_guarded(self) -> None:
        try:
            self._compact_now()
        except Exception:
            import logging
            logging.getLogger(__name__).exception(
                "[%s][%d] background compaction failed",
                self.index_name, self.shard_id)
        finally:
            self._compact_inflight = False

    def compact(self) -> bool:
        """Explicit synchronous compaction (test/bench hook)."""
        with self._lock:
            if self._delta_seg is None:
                if self._delta_enabled and self.segments:
                    # deletes-only window since the last fold: live-mask
                    # flips don't change the source column set, so a
                    # fold would rebuild a byte-equivalent base — skip
                    # the copy and count it (the build_skipped stat)
                    devbuild.count_skipped("compact")
                return False
        return self._compact_now()

    def _compact_now(self) -> bool:
        """Build-aside / keep-serving / atomic-swap compaction (the
        PR 7 repack substrate, parallel/repack.run_build_aside): the
        impact-preserving concat runs OFF the engine lock while the old
        generation serves every in-flight and new search; the swap
        re-validates under the lock (a refresh that replaced the delta
        mid-build aborts the fold — the next refresh retries), replays
        deletes that landed mid-build, and publishes the new base.
        Byte-identity: concat_segments preserves every surviving
        posting's impact, so responses before and after the swap are
        identical — only the fingerprint-keyed caches re-key, which is
        the ONE event that is allowed to."""
        from ..parallel.repack import run_build_aside
        with self._lock:
            snapshot = list(self.segments)
            snap_live = {s.seg_id: self.live[s.seg_id].copy()
                         for s in snapshot}
            # exactly the delta entries this build folds (by tuple
            # IDENTITY): only docs actually IN the snapshotted delta
            # segment (a still-buffered doc is not), and a doc indexed
            # or updated during the off-lock build replaces its entry —
            # the swap must keep both kinds for the next delta rebuild;
            # clearing the map wholesale would silently lose writes
            # that raced the build
            d = self._delta_seg
            folded = {did: e for did, e in self._delta_docs.items()
                      if d is not None and did in d.id_map}
        if not snapshot:
            return False
        seg_id = f"{self.shard_id}_{next(_seg_counter)}"

        def build():
            t0 = time.monotonic()
            if self._device_build:
                # the per-index setting rides to the _pack_layout seam
                # (and the k-means gate) on a thread-scoped override
                with devbuild.enable_scope():
                    merged = concat_segments(snapshot, seg_id, snap_live)
            else:
                merged = concat_segments(snapshot, seg_id, snap_live)
            if self.op_stats is not None:
                self.op_stats.on_build((time.monotonic() - t0) * 1000.0,
                                       merged.num_docs,
                                       device=self._device_build)
            return merged

        def swap(merged: Segment) -> bool:
            from ..search import resident
            with self._lock:
                if getattr(self, "_engine_closed", False):
                    return False
                if len(self.segments) != len(snapshot) or any(
                        a is not b for a, b in zip(self.segments,
                                                   snapshot)):
                    return False  # a refresh won the race; retry later
                m_live = np.zeros(merged.capacity, dtype=bool)
                m_live[: merged.num_docs] = True
                for s in snapshot:
                    flipped = snap_live[s.seg_id] & ~self.live[s.seg_id]
                    for d in np.nonzero(flipped)[0]:
                        row = merged.id_map.get(s.ids[int(d)])
                        if row is not None:
                            m_live[row] = False
                old_gen = self.base_generation()
                for old in snapshot:
                    self.live.pop(old.seg_id, None)
                    if (self.store is not None
                            and old.seg_id not in self._committed_seg_ids):
                        # committed files stay until the next commit's
                        # cleanup_uncommitted (crash-recovery safety,
                        # same rule as _drop_delta_segment)
                        self.store.delete_segment(old.seg_id)
                self.segments = [merged]
                self.live[merged.seg_id] = m_live
                self._delta_seg = None
                for did, entry in folded.items():
                    if self._delta_docs.get(did) is entry:
                        del self._delta_docs[did]
                self._delta_epoch = 0
                self._base_gen = None
                self._compactions += 1
                # compaction does not change visibility (same docs) but
                # NEW searches must read the compacted pack; in-flight
                # readers keep their refs to the retired generation
                self._capture_view()
                self._reader = None
            # the retired generation's fingerprint/generation-keyed
            # residue is reclaimed now — the ONLY re-key event
            resident.evict_generation(f"delta({old_gen})")
            resident.evict_segments(s.seg_id for s in snapshot)
            return True

        return run_build_aside(f"compact-{self.index_name}", build, swap)

    def _prune_version_map(self) -> None:
        """Refresh-time map pruning (ref: LiveVersionMap pruning at
        refresh + index.gc_deletes tombstone GC): every non-tombstone
        entry is now covered by a segment; tombstones survive one
        retention window (measured on the monotonic clock — wall-clock
        jumps must neither prematurely GC nor immortalize a tombstone)
        so late replicated ops still see the delete."""
        now = time.monotonic()
        keep: dict[str, tuple[int, bool]] = {}
        for did, v in self.versions.items():
            if not v[1]:
                continue   # live entry: the segment row covers it now
            ts = self._tombstone_ts.get(did, now)
            if now - ts <= self._gc_deletes_s:
                keep[did] = v
            else:
                self._tombstone_ts.pop(did, None)
        self.versions = keep

    def _capture_view(self) -> None:
        """Freeze the refresh-point snapshot searches/gets read from."""
        self._view_segments = list(self.segments)
        self._view_live = {s.seg_id: self.live[s.seg_id].copy()
                           for s in self.segments}

    def invalidate_reader(self) -> None:
        """Drop the cached point-in-time reader WITHOUT changing
        visibility (the next acquire rebuilds over the SAME refreshed
        view) — request-scoped state tied to the reader (request-cache
        entries, micro-batchers) dies with it. Ref: cache clear must
        never act like a refresh."""
        with self._lock:
            self._reader = None

    def acquire_searcher(self) -> ShardReader:
        """NRT searcher over the last refresh (ref: acquireSearcher).
        A FAILED shard raises ShardFailedError — the search path turns
        it into a structured `_shards.failures` entry and reduces over
        the survivors instead of 500ing the whole request."""
        with self._lock:
            self._check_failed()
            if self._reader is None:
                self._reader = ShardReader(
                    self.index_name, list(self._view_segments),
                    dict(self._view_live),
                    self.mappers, shard_id=self.shard_id)
            return self._reader

    # -- merge (ref: merge/policy/TieredMergePolicyProvider.java +
    # merge/scheduler/ConcurrentMergeSchedulerProvider.java) ---------------
    def _maybe_merge(self) -> None:
        if self.settings.get_bool("index.merge.scheduler.async", False):
            self._schedule_background_merge()
            return
        while len(self.segments) > self.max_segments:
            i = self._pick_merge_pair()
            self._apply_merge(self.segments[i: i + 2],
                              self._merge_pair(self.segments[i: i + 2]))

    def _pick_merge_pair(self) -> int:
        """Index of the smallest adjacent pair (keeps doc order stable)."""
        sizes = [s.num_docs for s in self.segments]
        return int(np.argmin([sizes[j] + sizes[j + 1]
                              for j in range(len(sizes) - 1)]))

    def _merge_pair(self, pair: list[Segment]) -> Segment:
        return merge_segments(
            pair, seg_id=f"{self.shard_id}_{next(_seg_counter)}",
            live_masks=self.live, similarity=self._sim_for)

    def _apply_merge(self, pair: list[Segment], merged: Segment) -> None:
        """Swap `pair` -> `merged` in the segment list (caller holds the
        lock on the sync path; the async path re-validates)."""
        i = self.segments.index(pair[0])
        for old in pair:
            self.live.pop(old.seg_id, None)
            if (self.store is not None
                    and old.seg_id not in self._committed_seg_ids):
                # committed files stay until the next commit's
                # cleanup_uncommitted (crash-recovery safety, same
                # rule as _drop_delta_segment)
                self.store.delete_segment(old.seg_id)
        live = np.zeros(merged.capacity, dtype=bool)
        live[: merged.num_docs] = True
        self.segments[i: i + 2] = [merged]
        self.live[merged.seg_id] = live

    def _schedule_background_merge(self) -> None:
        """Concurrent merge scheduling: the merge itself (a columnar
        rebuild) runs OFF the engine lock on the shared merge pool, so
        writes and refreshes proceed while it works; the swap
        re-validates under the lock and replays deletes that landed
        mid-merge (the liveDocs carry-over ConcurrentMergeScheduler
        relies on IndexWriter for). One merge in flight per engine;
        pool width = index.merge.scheduler.max_thread_count."""
        if len(self.segments) <= self.max_segments \
                or getattr(self, "_merge_inflight", False):
            return
        i = self._pick_merge_pair()
        pair = self.segments[i: i + 2]
        snapshot_live = {s.seg_id: self.live[s.seg_id].copy()
                         for s in pair}
        self._merge_inflight = True

        def run():
            ok = False
            try:
                merged = merge_segments(
                    pair, seg_id=f"{self.shard_id}_{next(_seg_counter)}",
                    live_masks=snapshot_live, similarity=self._sim_for)
                with self._lock:
                    if getattr(self, "_engine_closed", False):
                        return
                    if not all(s in self.segments for s in pair):
                        return  # sources vanished (force_merge/close won)
                    # deletes that raced the merge: any id whose live bit
                    # flipped since the snapshot dies in `merged` too
                    m_live = np.zeros(merged.capacity, dtype=bool)
                    m_live[: merged.num_docs] = True
                    for s in pair:
                        flipped = snapshot_live[s.seg_id] \
                            & ~self.live[s.seg_id]
                        for d in np.nonzero(flipped)[0]:
                            row = merged.id_map.get(s.ids[int(d)])
                            if row is not None:
                                m_live[row] = False
                    self._apply_merge(pair, merged)
                    self.live[merged.seg_id] = m_live
                    self._dirty = True
                    ok = True
            except Exception:
                # a persistently failing merge must not spin the pool:
                # log and stop; the next refresh retries at most once
                # per flush of new writes (ref: MergeScheduler handling
                # of merge exceptions)
                import logging
                logging.getLogger(__name__).exception(
                    "[%s][%d] background merge failed",
                    self.index_name, self.shard_id)
            finally:
                self._merge_inflight = False
                if ok:
                    with self._lock:
                        if not getattr(self, "_engine_closed", False) \
                                and len(self.segments) > self.max_segments:
                            self._schedule_background_merge()

        _merge_pool(self.settings).submit(run)

    def force_merge(self, max_num_segments: int = 1) -> None:
        """Ref: InternalEngine.forceMerge :715 / _optimize API."""
        with self._lock:
            self.refresh()
            if len(self.segments) > max_num_segments:
                merged = merge_segments(
                    self.segments, seg_id=f"{self.shard_id}_{next(_seg_counter)}",
                    live_masks=self.live, similarity=self._sim_for)
                from ..search import resident
                old_gen = self.base_generation()
                old_segs = list(self.segments)
                for old in old_segs:
                    self.live.pop(old.seg_id, None)
                    if (self.store is not None
                            and old.seg_id not in self._committed_seg_ids):
                        # committed files stay until the next commit's
                        # cleanup_uncommitted (crash-recovery safety,
                        # same rule as _drop_delta_segment)
                        self.store.delete_segment(old.seg_id)
                live = np.zeros(merged.capacity, dtype=bool)
                live[: merged.num_docs] = True
                self.segments = [merged]
                self.live = {merged.seg_id: live}
                # the merged segment IS the new base generation
                self._delta_seg = None
                self._delta_docs = {}
                self._delta_epoch = 0
                self._base_gen = None
                self._capture_view()
                self._reader = None
                # a force_merge is a re-key event exactly like
                # compaction: the retired generation's delta resident
                # entries carry no seg weakref (only evict_generation
                # reclaims them) and its per-segment entries would
                # otherwise wait on LRU pressure
                resident.evict_generation(f"delta({old_gen})")
                resident.evict_segments(s.seg_id for s in old_segs)

    # -- flush = commit + translog rotation (ref: :574+) -------------------
    def flush(self) -> None:
        with self._lock:
            if self.failed is not None:
                return  # a contained shard has nothing durable to add
            self.refresh()
            if self.store is None:
                return
            try:
                import hashlib
                stems: dict[str, str] = {}
                hashes: dict[str, str] = {}
                for seg in self.segments:
                    live = self.live[seg.seg_id]
                    h = hashlib.blake2b(live.tobytes(),
                                        digest_size=8).hexdigest()
                    hashes[seg.seg_id] = h
                    prev = self._committed_files.get(seg.seg_id)
                    if prev is not None and prev[1] == h:
                        # unchanged since the last commit: the
                        # write-once pair on disk stays authoritative
                        stems[seg.seg_id] = prev[0]
                    else:
                        stems[seg.seg_id] = self.store.save_segment(
                            seg, live, suffix=self._commit_gen + 1)
                self._commit_gen += 1
                # the commit records the exact write-once file stems
                # plus the translog generation ACTIVE at commit time:
                # every op acked after this commit lands in
                # generations >= it, so recovery can PROVE whether a
                # fallback to this commit is lossless (the salvage
                # walk's coverage check) instead of guessing
                self.store.write_commit(
                    self._commit_gen, [s.seg_id for s in self.segments],
                    extra={"files": stems,
                           "translog_gen": (self.translog.generation
                                            if self.translog is not None
                                            else 0)})
                self._committed_seg_ids = {s.seg_id
                                           for s in self.segments}
                self._committed_files = {
                    sid: (stems[sid], hashes[sid]) for sid in stems}
                self.store.cleanup_uncommitted(set(stems.values()))
                if self.translog is not None:
                    self.translog.sync()
                    self.translog.rotate()
            except OSError as e:
                # a flush that cannot make writes durable fails the
                # SHARD (ref: IndexShard failing on translog/store IO
                # errors): acked-but-uncommittable state must not keep
                # serving as if durable. PowerLossError (injected
                # crash) is not OSError and propagates — a crashed
                # process runs no handlers.
                self._contain(e, during="flush")
                raise ShardFailedError(self.index_name, self.shard_id,
                                       self.failed["reason"]) from e

    # -- recovery (ref: IndexShardGateway translog replay) -----------------
    def _salvage_commit(self) -> tuple[dict | None, list[tuple]]:
        """Pick the commit point recovery serves: walk generations
        newest→oldest, skipping torn/corrupt commit FILES and commits
        whose segments fail their checksums — each skip counted under
        `commits_fell_back`. A FALLBACK candidate (anything but the
        newest on-disk generation) is accepted only when the translog
        still covers every op acked since it: flush writes the commit
        STRICTLY before rotating the translog, and each commit records
        the translog generation active at commit time, so coverage
        holds iff the oldest on-disk translog generation <= recorded
        gen + 1. A fallback that cannot prove coverage — or a corrupt
        segment in a commit whose translog rotated — raises
        CorruptIndexError and the shard is CONTAINED: a structured
        failure beats silently serving with acked writes missing.
        Returns (commit, [(sid, segment, live), ...])."""
        gens = self.store.commit_generations()
        fell_back = False
        last_err: Exception | None = None
        for gen in gens:
            try:
                commit = self.store.read_commit(gen)
            except CorruptIndexError as e:
                durability.on_commit_fell_back()
                fell_back = True
                last_err = e
                continue
            if fell_back:
                tl_gen = commit.get("translog_gen")
                min_gen = (self.translog.min_generation()
                           if self.translog is not None else None)
                if tl_gen is None or min_gen is None \
                        or min_gen > int(tl_gen) + 1:
                    raise CorruptIndexError(
                        f"newest commit unusable ({last_err}) and the "
                        f"translog no longer covers commit [{gen}] "
                        "(rotated since) — refusing a fallback that "
                        "would silently lose acked writes")
            files = commit.get("files") or {}
            try:
                loaded = [(sid, *self.store.load_segment(
                              sid, stem=files.get(sid)))
                          for sid in commit["segments"]]
            except CorruptIndexError as e:
                durability.on_commit_fell_back()
                fell_back = True
                last_err = e
                continue
            # segment files NO readable commit references are crash
            # residue (saves of a commit that never landed, torn
            # half-pairs, retired files a crashed cleanup missed):
            # their docs re-enter via translog replay — drop the files
            # and count the salvage. Stems the RETAINED older commit
            # references stay: they are the fallback's data until the
            # next flush supersedes it
            orphans = (self.store.seg_stems_on_disk()
                       - self.store.referenced_stems())
            durability.on_segments_salvaged(len(orphans))
            for stem in orphans:
                for path in self.store._stem_paths(stem):
                    try:
                        os.remove(path)
                    except OSError:
                        pass
            return commit, loaded
        if gens:
            raise CorruptIndexError(
                f"no usable commit point among generations {gens}: "
                f"{last_err}")
        return None, []

    def _recover(self) -> None:
        commit, loaded = self._salvage_commit()
        if commit:
            import hashlib
            self._commit_gen = int(commit["generation"])
            self._committed_seg_ids = set(commit["segments"])
            tails = [sid.rsplit("_", 1)[-1]
                     for sid in commit["segments"]]
            ordinals = [int(t) for t in tails if t.isdigit()]
            if ordinals:
                _ensure_seg_counter_above(max(ordinals))
            files = commit.get("files") or {}
            self._committed_files = {
                sid: (files.get(sid, f"seg_{sid}"),
                      hashlib.blake2b(live.tobytes(),
                                      digest_size=8).hexdigest())
                for sid, seg, live in loaded}
            for sid, seg, live in loaded:
                self.segments.append(seg)
                self.live[sid] = live
                for d in range(seg.num_docs):
                    if live[d]:
                        self.versions[seg.ids[d]] = (int(seg.versions[d]), False)
                if self._delta_enabled and seg.delta_parent is not None:
                    # a recovered delta stays THE delta: future epoch
                    # bumps must keep rebuilding over its docs, so they
                    # re-enter the rebuild set (re-parsed from source —
                    # the same per-delta cost MeshIndex.refresh pays)
                    self._delta_seg = seg
                    self._delta_epoch = int(seg.delta_epoch)
                    for d in range(seg.num_docs):
                        if live[d] and (seg.parent_of is None
                                        or seg.parent_of[d] < 0):
                            self._delta_docs[seg.ids[d]] = (
                                self.mappers.parse(seg.ids[d],
                                                   seg.sources[d]),
                                int(seg.versions[d]))
        if self.translog is not None:
            for op in self.translog.snapshot():
                if op.op == OP_INDEX:
                    self.index(op.doc_id, op.source, _replay=True)
                    self.versions[op.doc_id] = (op.version, False)
                    self._buffer_docs[op.doc_id] = (op.version, op.source)
                    self.buffer.versions[-1] = op.version
                    if op.doc_id in self._delta_docs:
                        # replays carry the PERSISTED version, which
                        # must survive the next delta rebuild too
                        self._delta_docs[op.doc_id] = (
                            self._delta_docs[op.doc_id][0], op.version)
                elif op.op == OP_DELETE:
                    if self._current_version(op.doc_id) is not None:
                        self.delete(op.doc_id, _replay=True)
                    self.versions[op.doc_id] = (op.version, True)
        # recovery ends with a refresh so replayed ops are searchable
        # (ref: InternalEngine opens its searcher manager post-recovery)
        self.refresh()

    # -- shard-level containment (ref: Store.markStoreCorrupted +
    # IndexShard.failShard: corruption fails the SHARD, never the node) ----
    def _contain(self, exc: BaseException, during: str) -> None:
        """Fail this shard into a structured contained state: drop
        every in-memory structure (the data on disk stays put for
        forensics / peer re-source) and answer everything with
        ShardFailedError(503) from here on. The on-disk corruption
        marker is persisted ONLY for VERIFIED corruption (checksum /
        crc failures) — a transient OSError (EIO, disk full) fails the
        shard for this process but must not permanently brand an
        intact store corrupt: the next open retries cleanly once the
        condition clears (ref: the reference marks stores corrupted
        only on CorruptIndexException, never on plain IOExceptions)."""
        reason = f"{type(exc).__name__}: {exc}"
        marker = None
        if self.store is not None and isinstance(
                exc, (CorruptIndexError, TranslogCorruptedError)):
            try:
                marker = self.store.write_corruption_marker(reason)
            except OSError:
                pass   # a disk too broken to mark still fails in-memory
        self.failed = {"reason": reason, "during": during,
                       "marker": marker}
        self.segments = []
        self.live = {}
        self.buffer = SegmentBuilder(similarity=self._sim_for)
        self._buffer_docs = {}
        self.versions = {}
        self._tombstone_ts = {}
        self._delta_seg = None
        self._delta_docs = {}
        self._view_segments = []
        self._view_live = {}
        self._reader = None
        if self.translog is not None:
            self.translog.close()
            self.translog = None
        durability.on_shard_failed_corrupt()
        cb = self.on_failed
        if cb is not None:
            cb(self)

    def fail_shard(self, reason: str, exc: BaseException | None = None,
                   during: str = "runtime") -> None:
        """Public containment entry (corruption detected outside
        recovery — a failed flush, an external verify pass). Idempotent."""
        with self._lock:
            if self.failed is not None:
                return
            self._contain(exc or CorruptIndexError(reason), during)

    def _check_failed(self) -> None:
        if self.failed is not None:
            raise ShardFailedError(self.index_name, self.shard_id,
                                   self.failed["reason"])

    # -- stats / lifecycle -------------------------------------------------
    def doc_count(self) -> int:
        with self._lock:
            n = len(self.buffer)
            for seg in self.segments:
                n += int(self.live[seg.seg_id][: seg.num_docs].sum())
            return n

    def segment_stats(self) -> dict:
        with self._lock:
            out = {
                "count": len(self.segments),
                "docs": self.doc_count(),
                "memory_in_bytes": sum(s.nbytes() for s in self.segments),
                "buffered_docs": len(self.buffer),
            }
            if self.failed is not None:
                out["failed"] = dict(self.failed)
            if self._delta_enabled:
                d = self._delta_seg
                out["streaming"] = {
                    "base_generation": self.base_generation(),
                    "delta_epoch": self._delta_epoch,
                    "delta_docs": (d.num_docs if d is not None else 0),
                    "compactions": self._compactions,
                }
            return out

    def close(self) -> None:
        with self._lock:
            self._engine_closed = True
            if self.translog is not None:
                self.translog.close()
            gen = self.base_generation() if self.segments else None
            seg_ids = [s.seg_id for s in self.segments]
        if self._delta_enabled and gen is not None:
            # delta/pack resident entries carry NO seg weakref (the
            # epoch's segments are meant to die under them) — only an
            # explicit generation eviction reclaims their pinned
            # executables + breaker-accounted bytes; without this an
            # index close/delete strands them until LRU cap pressure
            from ..search import resident
            resident.evict_generation(f"delta({gen})")
            resident.evict_segments(seg_ids)
