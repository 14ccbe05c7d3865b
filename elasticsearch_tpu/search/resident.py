"""Resident query loop: pinned on-device executables + staged feeds.

The dispatch scheduler (PR 3) amortized the flat per-dispatch
round trip across CONCURRENT traffic; a truly lone query still paid one
full synchronous dispatch — jit-dispatch overhead, param upload, program
launch, result fetch, all serialized. This module keeps the read path's
hot programs RESIDENT instead: per `(pack fingerprint, plan signature,
pow2 k-bucket, batch bucket)` the executor AOT-compiles the fused
stepped program once (``jax.jit(...).lower().compile()``), pins the
executable here, and serves every later call through it with

  * an asynchronously ``jax.device_put``-staged query-param wire buffer
    (DONATED to the executable, so XLA reuses its memory) that lands
    while earlier enqueued work executes — the feed stage;
  * the pinned executable invocation — the execute stage;
  * an async copy-to-host started at enqueue — the fetch stage;

so a lone query pays a one-way param feed + result fetch instead of a
monolithic round trip. The stepped program additionally carries a
device-side deadline check per tile-loop chunk (see ops/scoring.py
``step``), which turns PR 4's cooperative collect-boundary timeout into
a preemptive one: a laggard step exits early and reports ``timed_out``
from the device. BOTH fused engines step: an XLA-tuned shape pins the
chunked fori tile loop, a pallas-tuned shape pins the chunked
``pallas_call`` grid (ops/pallas_scoring — threshold and prune state
carried across kernel-chunk boundaries, the deadline callback hosted
between chunks), so pallas-tuned packs no longer fall back to cold
dispatch; the entry key carries the engine.

Residency is opt-in via ``ES_TPU_RESIDENT_LOOP`` (unset => every
response stays byte-identical to the cold path and all counters here
read zero). ``search.resident.max_entries`` caps the pinned-entry LRU.
Stats surface under ``nodes_stats()["dispatch"]["resident"]``.
"""

from __future__ import annotations

import os
import threading
import weakref

from ..utils.metrics import CounterMetric, HighWaterMetric

_TRUE = ("1", "true", "on", "yes")

DEFAULT_MAX_ENTRIES = 32


def enabled() -> bool:
    """Residency is an explicit opt-in: with the env unset the read
    path never touches this module's caches or counters."""
    return os.environ.get("ES_TPU_RESIDENT_LOOP", "").lower() in _TRUE


class ResidentStats:
    """Process-wide resident-loop counters (the executor serves every
    node in the process, like the fused-scoring stats)."""

    def __init__(self):
        self.resident_hits = CounterMetric()
        self.cold_dispatches = CounterMetric()
        self.evictions = CounterMetric()
        self.preempted_by_deadline = CounterMetric()
        # streaming write path (index/engine.py delta mode): entries a
        # refresh REUSED across a delta-epoch bump (the refresh-storm
        # fix made provable from stats — each count is one avoided
        # recompile+retune), and entries evicted because a background
        # compaction re-keyed their generation (the only event allowed
        # to evict on the write path)
        self.refresh_reuses = CounterMetric()
        self.compaction_evictions = CounterMetric()
        # how long a staged param feed had to land on-device before its
        # step was invoked (ms, high-water) — the overlap the split
        # feed/execute/fetch pipeline buys over a monolithic dispatch
        self.staged_feed_overlap_ms = HighWaterMetric()

    def snapshot(self, cache: "ResidentCache") -> dict:
        return {
            "resident_hits": self.resident_hits.count,
            "cold_dispatches": self.cold_dispatches.count,
            "evictions": self.evictions.count,
            "preempted_by_deadline": self.preempted_by_deadline.count,
            "refresh_reuses": self.refresh_reuses.count,
            "compaction_evictions": self.compaction_evictions.count,
            "staged_feed_overlap_ms": {
                "high_water": round(
                    float(self.staged_feed_overlap_ms.max), 3),
                "last": round(float(self.staged_feed_overlap_ms.last), 3),
            },
            **cache.snapshot(),
        }


class ResidentEntry:
    """One pinned executable + its feed slot.

    ``nbytes`` is the entry's residency footprint (staged wire + queued
    output buffers + generated code where the backend reports it); the
    cache accounts it against the fielddata breaker for the life of the
    entry — pinned executables are long-lived HBM tenants exactly like
    uploaded columns, and must be visible to the same parent budget."""

    __slots__ = ("key", "label", "compiled", "seg_id", "fingerprint",
                 "seg_ref", "backend", "generation", "delta_epoch",
                 "nbytes", "hits", "_hold", "__weakref__")

    def __init__(self, key, label: str, compiled, seg_id, fingerprint,
                 seg_ref, backend: str = "xla",
                 generation: str | None = None, delta_epoch: int = 0):
        self.key = key
        self.label = label
        self.compiled = compiled
        self.seg_id = seg_id
        self.fingerprint = fingerprint
        self.seg_ref = seg_ref
        self.backend = backend
        # streaming write path: `generation` is the Segment.cache_key
        # the entry is pinned under ("delta(<base>):c<cap>" for delta
        # entries — no seg_ref, survives epoch bumps, evicted only by
        # compaction); `delta_epoch` is the LAST epoch served, advanced
        # by ResidentCache.get so refresh reuse is countable
        self.generation = generation if generation is not None \
            else fingerprint
        self.delta_epoch = delta_epoch
        self.nbytes = 0
        self.hits = 0
        self._hold = 0

    def account(self, nbytes: int) -> None:
        """Record the entry's residency bytes (known after the first
        execution) against the fielddata breaker."""
        if nbytes <= self._hold:
            return
        from ..utils.breaker import breaker_service
        add = nbytes - self._hold
        breaker_service().breaker("fielddata").add_estimate(add)
        self._hold = nbytes
        self.nbytes = nbytes

    def release(self) -> None:
        if self._hold:
            from ..utils.breaker import breaker_service
            breaker_service().breaker("fielddata").release(self._hold)
            self._hold = 0


class ResidentCache:
    """LRU of pinned entries. Keys embed the pack FINGERPRINT, so a
    refresh/merge (which mints a new fingerprint) can never serve a
    stale executable; the stale entry itself is evicted by the dead-
    segment sweep (entries hold only a weakref to their segment) or by
    the LRU cap, releasing its breaker hold."""

    def __init__(self, max_entries: int | None = None):
        from ..utils import race_guard
        self._mx = threading.Lock()
        # key -> ResidentEntry (LRU order)
        self._entries: dict = race_guard.guarded_dict(
            self._mx, "resident.ResidentCache._entries")
        self.max_entries = max_entries or DEFAULT_MAX_ENTRIES

    def configure(self, max_entries: int) -> None:
        with self._mx:
            self.max_entries = max(1, int(max_entries))
            self._trim_locked()

    def get(self, key, delta_epoch: int | None = None
            ) -> ResidentEntry | None:
        with self._mx:
            e = self._entries.pop(key, None)
            if e is None:
                return None
            self._entries[key] = e            # LRU touch
            e.hits += 1
            stats.resident_hits.inc()
            if delta_epoch is not None and delta_epoch != e.delta_epoch:
                # the pinned executable survived a refresh's epoch bump
                # and now serves the NEW delta contents — the zero-
                # eviction refresh, made countable
                stats.refresh_reuses.inc()
                e.delta_epoch = delta_epoch
            return e

    def put(self, entry: ResidentEntry) -> None:
        with self._mx:
            self._sweep_locked()
            # two threads racing the same cold compile: the displaced
            # duplicate must drop its breaker hold (not an eviction —
            # the plan stays resident under the winner)
            old = self._entries.pop(entry.key, None)
            if old is not None and old is not entry:
                old.release()
            self._entries[entry.key] = entry
            self._trim_locked()

    def evict(self, key) -> None:
        """Evict one entry (e.g. its residency bytes tripped the
        fielddata breaker at accounting time)."""
        with self._mx:
            self._evict_locked(key)

    def _evict_locked(self, key) -> None:
        # drop the cache's reference only — a thread that looked the
        # entry up just before the eviction may still be mid-invoke, so
        # the executable itself dies with its last reference
        e = self._entries.pop(key, None)
        if e is not None:
            e.release()
            stats.evictions.inc()

    def _trim_locked(self) -> None:
        while len(self._entries) > self.max_entries:
            self._evict_locked(next(iter(self._entries)))

    def _sweep_locked(self) -> None:
        """Drop entries whose segment died (pack refresh/merge replaced
        it): a dead segment's executable pins unreachable device columns
        and can never be keyed again (the fingerprint changed)."""
        dead = [k for k, e in self._entries.items()
                if e.seg_ref is not None and e.seg_ref() is None]
        for k in dead:
            self._evict_locked(k)

    def evict_segment(self, seg_id) -> None:
        """Explicit invalidation (Segment.drop_device / cache clear):
        the pinned executables reference the dropped device columns and
        must not outlive them."""
        with self._mx:
            for k in [k for k, e in self._entries.items()
                      if e.seg_id == seg_id]:
                self._evict_locked(k)

    def evict_generation(self, gen_prefix: str) -> int:
        """Compaction re-key (index/engine.Engine._compact_now): drop
        every entry pinned under a generation key starting with
        `gen_prefix` (a compaction retires EVERY capacity bucket of the
        folded delta, so this matches on the "delta(<base>)" prefix).
        Returns how many entries were evicted; they also count in the
        compaction_evictions stat — rare and background by design."""
        with self._mx:
            dead = [k for k, e in self._entries.items()
                    if isinstance(e.generation, str)
                    and e.generation.startswith(gen_prefix)]
            for k in dead:
                self._evict_locked(k)
        if dead:
            stats.compaction_evictions.inc(len(dead))
        return len(dead)

    def clear(self) -> None:
        with self._mx:
            for k in list(self._entries):
                self._evict_locked(k)

    def snapshot(self) -> dict:
        with self._mx:
            entries = [{"plan": e.label, "fingerprint": e.fingerprint,
                        "backend": e.backend, "bytes": e.nbytes,
                        "hits": e.hits, "generation": e.generation,
                        "delta_epoch": e.delta_epoch}
                       for e in self._entries.values()]
            max_entries = self.max_entries
        return {"entries": entries,
                "entry_count": len(entries),
                "max_entries": max_entries,
                "residency_bytes": sum(e["bytes"] for e in entries)}


stats = ResidentStats()
cache = ResidentCache()


def configure(max_entries: int | None = None) -> None:
    """Node startup hook (`search.resident.max_entries`). The cache is
    process-global, so with several in-process nodes the last
    configuration wins — same convention as the breaker service."""
    if max_entries is not None:
        cache.configure(max_entries)


def evict_segment(seg_id) -> None:
    cache.evict_segment(seg_id)


def evict_generation(gen_prefix: str) -> int:
    """Compaction hook (index/engine.py): retire every pinned entry of
    a folded delta generation. The ONLY write-path event that evicts."""
    return cache.evict_generation(gen_prefix)


def evict_segments(seg_ids) -> None:
    """Batch invalidation for a retired pack's segments (the elastic
    repack swap, parallel/repack.py): the old pack's pinned executables
    reference device columns the swap just retired — reclaim them NOW
    instead of waiting for the weakref sweep."""
    for sid in seg_ids:
        cache.evict_segment(sid)


def note_mesh_programs_dropped(n: int) -> None:
    """A retired DistributedSearcher's pinned shard_map programs died
    with the instance (its `_compiled` cache IS the mesh's resident
    entry table). Counted as evictions through the same counters the
    mesh reports reuse through — and, like them, only while residency
    is enabled (counters read zero otherwise)."""
    if n > 0 and enabled():
        stats.evictions.inc(n)


def reset() -> None:
    """Test hook: drop every pinned entry, zero the counters, restore
    the default entry cap."""
    global stats
    cache.clear()
    cache.configure(DEFAULT_MAX_ENTRIES)
    # graftlint: ok(shared-state-race): test-only hook, called between
    # requests with no dispatch in flight; the rebind itself is atomic
    stats = ResidentStats()


def resident_stats() -> dict:
    """Snapshot for nodes_stats()["dispatch"]["resident"]."""
    return stats.snapshot(cache)


def make_ref(segment) -> weakref.ref | None:
    try:
        return weakref.ref(segment)
    except TypeError:
        return None
