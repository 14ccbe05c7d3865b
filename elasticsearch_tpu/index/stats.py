"""Per-index operation counters backing the `_stats` API.

Reference analog: action/admin/indices/stats/CommonStats.java — the
per-shard stats sections (docs, store, indexing, get, search, merges,
refresh, flush, ...) aggregated per index and across indices, with
per-type indexing counters (index/indexing/ShardIndexingService.java)
and per-group search counters (index/search/stats/ShardSearchService
`groupStats`).

TPU-first deviation: counters live at the index-service level, not per
shard — the engine's shards share one write path here, and the `_stats`
`level=shards` view derives per-shard rows from the segment state. All
counters are monotonically increasing ints guarded by the GIL (single
increments), matching the reference's CounterMetric semantics.
"""

from __future__ import annotations

import threading
import time


class _Counter:
    __slots__ = ("total", "time_ms")

    def __init__(self) -> None:
        self.total = 0
        self.time_ms = 0

    def inc(self, took_ms: float = 0.0) -> None:
        self.total += 1
        self.time_ms += int(took_ms)


class IndexOpStats:
    """Operation counters for one index."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # indexing (ref: ShardIndexingService.StatsHolder)
        self.index_total = 0
        self.index_time_ms = 0
        self.delete_total = 0
        self.delete_time_ms = 0
        self.noop_update_total = 0
        # index ops that reached their shard in a batch of two or more
        # (IndexService.index_many), and those batches: against
        # index_total, the share of writes that batching engaged on
        self.bulk_batches = 0
        self.bulk_batch_docs = 0
        self.types: dict[str, _Counter] = {}       # per-type index counters
        # get (ref: index/get/ShardGetService stats)
        self.get_total = 0
        self.get_time_ms = 0
        self.get_exists = 0
        self.get_missing = 0
        # search (ref: index/search/stats/ShardSearchService)
        self.query_total = 0
        self.query_time_ms = 0
        self.fetch_total = 0
        self.fetch_time_ms = 0
        self.groups: dict[str, _Counter] = {}      # per-stats-group counters
        # pack build (refresh rebuilds + compaction folds): wall-time
        # and docs so operators and the ingest bench can see where
        # indexing time goes (today only merge counters existed);
        # build_device_total counts builds routed through the
        # device-parallel builder (index/devbuild.py)
        self.build_total = 0
        self.build_time_ms = 0
        self.build_docs = 0
        self.build_device_total = 0
        # maintenance
        self.refresh_total = 0
        self.refresh_time_ms = 0
        self.flush_total = 0
        self.flush_time_ms = 0
        self.merge_total = 0
        self.merge_time_ms = 0
        self.warmer_total = 0
        self.warmer_time_ms = 0
        # suggest / percolate
        self.suggest_total = 0
        self.suggest_time_ms = 0
        self.percolate_total = 0
        self.percolate_time_ms = 0

    # -- record sites ------------------------------------------------------
    def on_index_many(self, doc_types: list[str | None], batches: int,
                      batch_docs: int) -> None:
        """One take of the lock for a run of written docs."""
        with self._lock:
            self.index_total += len(doc_types)
            self.bulk_batches += batches
            self.bulk_batch_docs += batch_docs
            for doc_type in doc_types:
                self.types.setdefault(doc_type or "_doc", _Counter()).inc()

    def on_delete(self, took_ms: float = 0.0) -> None:
        with self._lock:
            self.delete_total += 1
            self.delete_time_ms += int(took_ms)

    def on_noop_update(self) -> None:
        with self._lock:
            self.noop_update_total += 1

    def on_get(self, found: bool, took_ms: float = 0.0) -> None:
        with self._lock:
            self.get_total += 1
            self.get_time_ms += int(took_ms)
            if found:
                self.get_exists += 1
            else:
                self.get_missing += 1

    def on_search(self, groups: list[str] | None = None,
                  took_ms: float = 0.0) -> None:
        with self._lock:
            self.query_total += 1
            self.query_time_ms += int(took_ms)
            for g in groups or ():
                self.groups.setdefault(str(g), _Counter()).inc(took_ms)

    def on_fetch(self, took_ms: float = 0.0) -> None:
        with self._lock:
            self.fetch_total += 1
            # kept as a float: one search's fetch is a fraction of a
            # millisecond, and whole ones would all count 0
            self.fetch_time_ms += took_ms

    def on_build(self, took_ms: float = 0.0, docs: int = 0,
                 device: bool = False) -> None:
        with self._lock:
            self.build_total += 1
            self.build_time_ms += int(took_ms)
            self.build_docs += int(docs)
            if device:
                self.build_device_total += 1

    def on_refresh(self, took_ms: float = 0.0) -> None:
        with self._lock:
            self.refresh_total += 1
            self.refresh_time_ms += int(took_ms)

    def on_flush(self, took_ms: float = 0.0) -> None:
        with self._lock:
            self.flush_total += 1
            self.flush_time_ms += int(took_ms)

    def on_merge(self, took_ms: float = 0.0) -> None:
        with self._lock:
            self.merge_total += 1
            self.merge_time_ms += int(took_ms)

    def on_warmer(self, took_ms: float = 0.0) -> None:
        with self._lock:
            self.warmer_total += 1
            self.warmer_time_ms += int(took_ms)

    def on_suggest(self, took_ms: float = 0.0) -> None:
        with self._lock:
            self.suggest_total += 1
            self.suggest_time_ms += int(took_ms)

    def on_percolate(self, took_ms: float = 0.0) -> None:
        with self._lock:
            self.percolate_total += 1
            self.percolate_time_ms += int(took_ms)


class timed:
    """`with timed() as t: ...; stats.on_x(t.ms)` helper."""

    def __enter__(self) -> "timed":
        self._t0 = time.monotonic()
        self.ms = 0.0
        return self

    def __exit__(self, *exc) -> None:
        self.ms = (time.monotonic() - self._t0) * 1000.0


def merge_type_counters(parts: list[dict[str, _Counter]]) -> dict[str, dict]:
    """Sum per-key counters across indices -> plain dict rows."""
    out: dict[str, dict] = {}
    for part in parts:
        for k, c in part.items():
            row = out.setdefault(k, {"index_total": 0,
                                     "index_time_in_millis": 0,
                                     "index_current": 0})
            row["index_total"] += c.total
            row["index_time_in_millis"] += c.time_ms
    return out


def merge_group_counters(parts: list[dict[str, _Counter]]) -> dict[str, dict]:
    out: dict[str, dict] = {}
    for part in parts:
        for k, c in part.items():
            row = out.setdefault(k, {
                "query_total": 0, "query_time_in_millis": 0,
                "query_current": 0,
                "fetch_total": 0, "fetch_time_in_millis": 0,
                "fetch_current": 0})
            row["query_total"] += c.total
            row["query_time_in_millis"] += c.time_ms
    return out
