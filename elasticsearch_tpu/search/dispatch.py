"""Search dispatch scheduler: cross-request coalescing + pipelining.

Two things, one layer ABOVE the per-reader signature batching the
executor already does:

* **coalescing** — concurrent searches whose plans finalize to the same
  (desc, agg_desc, sort_spec, k, segment) group into ONE batched device
  dispatch (leading dim B; the executor's pow2 batch padding means no
  new compile keys), and the batched wire result is scattered back into
  per-request responses;
* **pipelining** — requests that cannot coalesce (different plan
  shapes, different readers/shards) are dispatched back-to-back through
  the executor's non-syncing entry so their dispatch round trips OVERLAP
  instead of serializing; collection happens in submission order.

Callers build a `DispatchBatch`, submit (reader, body) jobs, and call
`dispatch()`. Batches arriving while another batch executes queue up
and are drained by the next leader (the adaptive zero-latency
coalescing the per-reader MicroBatcher pioneered, now cross-reader).

**What a reader is.** A round is served one way whatever its number of
reader groups (`_serve_groups`), through one call:
`reader.msearch_submit(bodies, with_partials, deadline=, requests=,
keys=)` (each keyword left out when the group has none) enqueues the
group's
device programs without collecting and returns an object with
`finish()` (collects, returns one response a body), `group_sizes`
(bodies per coalesced signature group), `dispatch_count` (device
programs enqueued) and, where it has them, `fetch_s` (seconds spent
building each body's response). `ShardReader`, `MeshReader` (one for
every shard of an index placed one shard a chip, parallel/mesh_reader.py;
its job counts `shards_served` shard jobs) and `DistributedSearcher` are
the readers. The isolated retry of a failed group calls
`reader.msearch`, which is `msearch_submit(...).finish()`.

**Priority lanes** (traffic control plane, search/traffic.py): every
batch carries a lane (`interactive` / `msearch` / `scroll` / `bulk`)
and each drain round takes ALL pending interactive batches plus at
most a per-lane quota of batches from the other lanes — a bulk flood
is split into bounded rounds instead of one monolithic backlog, so an
interactive batch pending at round start always rides the very next
round and can never starve behind a full bulk lane. Leftover batches
stay queued; the leader's drain loop continues until nothing is
pending, so nothing is ever dropped, only re-ordered.

**Coalescing window**: `ES_TPU_COALESCE_WINDOW_MS` (or the
`search.dispatch.coalesce_window_ms` setting) > 0 forces a STATIC
window — the leader sleeps that long before draining so concurrent
REST traffic can coalesce even when requests do not overlap an
in-flight dispatch. With no static window configured, the traffic
controller's AdaptiveWindow decides per drain from observed arrival
rate and per-round merge depth: 0 for sequential traffic (a lone
query never sleeps), up to a few ms under real concurrency.

**What a search waits for here.** `scheduler_wait` is recorded once a
SEARCH, by `DispatchBatch.dispatch()`: the time between its call and
its return in which the leading thread served none of that batch's
jobs (the coalescing window's sleep, the round in flight, other
batches' reader groups, the round's end, the caller's wake-up). The
time the leader did serve one of them is in the leaf phases `bind` /
`dispatch` / `collect` / `unpack` / `fetch`, which weigh the searches a
reader call serves, so phases and wait tile the search whether it is
one shard job or a fan-out of five. A batch that holds several
searches (`_msearch`) records the same wait for each of them: all of
them waited from the one `dispatch()` call to its return. Searches of
two batches coalesced into one reader call are each served by it:
both batches' clocks stop.

The wait is recorded in three parts that tile it, from the same clock
readings, beside `phases` (`scheduler_wait: {parked, queued, woken}`):
`parked`, the batch pending while no thread holds the leader (from its
call, or the last leader's release if later, to the next acquisition:
what a caller sleeping out the poll pays); `woken`, from its round's
`_done.set()` to `dispatch()` resuming (the caller's wake-up); and
`queued`, the rest (the round in flight ahead of it, the coalescing
window, other reader groups of its own round). While a trace runs,
each sleep of a caller on its batch is a `wait:scheduler` span.

Stats surface under `nodes_stats()["dispatch"]` (lanes/window/tenant
counters under `["dispatch"]["traffic"]`): `searches` coordinated and
the shard jobs run for them (`queries`), the `leader` timer (rounds led,
the seconds some thread spent leading one, the reader groups served)
and the node's `merge` timer stand beside `phases`, not inside it:
they are no tiles of a search.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time

from ..utils import profiler
from ..utils.errors import SearchTimeoutError
from ..utils.metrics import CounterMetric, HighWaterMetric, MeanMetric


class FailoverStats:
    """Replica-failover counters (process-wide: mesh searchers are
    constructed outside any Node, so the counters live here and every
    node's `nodes_stats()["dispatch"]["failover"]` reports them; a Node
    installs a FRESH instance at init and resets on close like the
    fault registry, so two nodes in one process no longer share and
    double-count — see install_failover_stats/reset_failover_stats).

    `retries` counts dispatch attempts moved to another replica row
    after a shard row's dispatch failed; `succeeded`/`failed` count how
    those retries resolved. `per_row` breaks the same counts down by
    PHYSICAL replica row (the full-mesh row id, stable across degraded
    repacks): failures attribute to the row whose attempt failed,
    retries/successes to the row retried onto."""

    def __init__(self):
        self.retries = CounterMetric()
        self.succeeded = CounterMetric()
        self.failed = CounterMetric()
        self._rows_mx = threading.Lock()
        self._rows: dict[int, dict[str, CounterMetric]] = {}

    def _row(self, phys_row: int | None) -> dict | None:
        if phys_row is None:
            return None
        with self._rows_mx:
            row = self._rows.get(phys_row)
            if row is None:
                row = {"retries": CounterMetric(),
                       "succeeded": CounterMetric(),
                       "failed": CounterMetric()}
                self._rows[phys_row] = row
            return row

    def record_retry(self, phys_row: int | None = None) -> None:
        self.retries.inc()
        row = self._row(phys_row)
        if row is not None:
            row["retries"].inc()

    def record_succeeded(self, phys_row: int | None = None) -> None:
        self.succeeded.inc()
        row = self._row(phys_row)
        if row is not None:
            row["succeeded"].inc()

    def record_failed(self, phys_row: int | None = None) -> None:
        self.failed.inc()
        row = self._row(phys_row)
        if row is not None:
            row["failed"].inc()

    def snapshot(self) -> dict:
        with self._rows_mx:
            per_row = {str(r): {k: c.count for k, c in row.items()}
                       for r, row in sorted(self._rows.items())}
        return {"retries": self.retries.count,
                "succeeded": self.succeeded.count,
                "failed": self.failed.count,
                "per_row": per_row}


class EvictionStats:
    """Dead-device eviction lifecycle counters (parallel/repack.py) —
    process-wide like FailoverStats and owned/reset the same way.

    `serving_degraded` is a high-water mark of how many replica rows
    were simultaneously evicted (0 = full replication restored)."""

    def __init__(self):
        self.rows_dead = CounterMetric()
        self.repacks = CounterMetric()
        self.swaps = CounterMetric()
        self.re_expansions = CounterMetric()
        self.serving_degraded = HighWaterMetric()

    def snapshot(self) -> dict:
        return {"rows_dead": self.rows_dead.count,
                "repacks": self.repacks.count,
                "swaps": self.swaps.count,
                "re_expansions": self.re_expansions.count,
                "serving_degraded": {
                    "high_water": self.serving_degraded.max,
                    "last": self.serving_degraded.last}}


class MembershipStats:
    """Pod-membership lifecycle counters (parallel/membership.py +
    parallel/multihost.py) — process-wide like FailoverStats and
    owned/reset the same way.

    `joins` counts NEW hosts admitted to the pod; `replacements` the
    subset-like sibling where the joiner takes over a crashed/known
    host id (the kill→replace arc); `drains` graceful decommissions
    (drain_host — planned, distinguished from crash eviction);
    `lease_handoffs` voluntary coordinator-lease transfers (an idle
    holder granting LEASE_RELEASE); `fenced_drivers` exec attempts
    409'd by lease-term fencing (each one is a seq collision the PR 13
    convention would have risked); `partitions_survived` membership
    transitions REFUSED for lack of quorum (a minority half declining
    to fork the pod state — the split-brain that did not happen)."""

    def __init__(self):
        self.joins = CounterMetric()
        self.replacements = CounterMetric()
        self.drains = CounterMetric()
        self.lease_handoffs = CounterMetric()
        self.fenced_drivers = CounterMetric()
        self.partitions_survived = CounterMetric()

    def snapshot(self) -> dict:
        return {"joins": self.joins.count,
                "replacements": self.replacements.count,
                "drains": self.drains.count,
                "lease_handoffs": self.lease_handoffs.count,
                "fenced_drivers": self.fenced_drivers.count,
                "partitions_survived": self.partitions_survived.count}


failover_stats = FailoverStats()
eviction_stats = EvictionStats()
membership_stats = MembershipStats()
# the three parts of `scheduler_wait` (module doc), once a search like
# the wait; process-wide like the phase timer they tile
WAIT_PARTS = ("parked", "queued", "woken")
_wait_parts = {name: MeanMetric() for name in WAIT_PARTS}
# serializes the install/reset pair: two nodes racing init/close could
# otherwise interleave the reads and rebinds and strand one node's
# counters installed under the other's ownership check
_process_stats_mx = threading.Lock()


def install_process_stats() -> tuple[
        FailoverStats, EvictionStats, MembershipStats]:
    """Node-init hook: install FRESH failover/eviction/membership
    counter objects so a new node never inherits (or double-counts
    into) a previous node's counters. Returns the installed triple;
    the node passes it back to reset_process_stats on close."""
    global failover_stats, eviction_stats, membership_stats
    with _process_stats_mx:
        failover_stats = FailoverStats()
        eviction_stats = EvictionStats()
        membership_stats = MembershipStats()
        return failover_stats, eviction_stats, membership_stats


def reset_process_stats(if_owner=None) -> None:
    """Node-close hook, fault-registry convention: reset only while the
    installed objects are still the closing node's (a node must not
    clobber counters someone configured after it)."""
    global failover_stats, eviction_stats, membership_stats
    with _process_stats_mx:
        if if_owner is None or \
                if_owner == (failover_stats, eviction_stats,
                             membership_stats):
            failover_stats = FailoverStats()
            eviction_stats = EvictionStats()
            membership_stats = MembershipStats()


class DispatchStats:
    """Scheduler counters (thread-safe; plumbed into nodes_stats).

    Granularity: `queries` and `coalesced_queries` count PER-SHARD query
    executions (one search against an S-shard index is S entries) —
    the unit the scheduler actually batches and dispatches; `searches`
    counts the searches those jobs were submitted for, so `queries`
    over `searches` is the fan-out.

    `leader` is fed once a round with the seconds its leading thread
    spent in `_execute` (count = rounds led); one thread leads at a
    time, so its sum cannot outrun the wall clock. `merge` is the
    node's coordinator merge of one search's shard results
    (Node._reduce_on_readers), with the shard results and hits that
    went in. Neither is a tile of a search (a round serves many, the
    merge lies inside `reduce`), so they stay out of `phases`."""

    def __init__(self):
        self.searches = CounterMetric()
        self.queries = CounterMetric()
        self.coalesced_queries = CounterMetric()
        self.batches_dispatched = CounterMetric()
        self.pipeline_depth = HighWaterMetric()
        self._window_batches = CounterMetric()
        self._window_coalesced = CounterMetric()
        self._adopted_batches = CounterMetric()
        self.leader = MeanMetric()
        self.leader_groups = CounterMetric()
        self.merge = MeanMetric()
        self.merge_shard_results = CounterMetric()
        self.merge_hits = CounterMetric()
        # traffic control plane (search/traffic.py) — set by the
        # scheduler when a node wires one in; snapshot() then reports
        # per-tenant admission counters, lane depths, the adaptive
        # window, and the query-cache hit rate under "traffic"
        self.traffic = None

    def record_round(self, n_batches: int, windowed: bool) -> None:
        """A drain round merged n_batches callers. `windowed` rounds
        credit the timed window (ES_TPU_COALESCE_WINDOW_MS held the
        leader open); merges in un-windowed rounds are in-flight
        ADOPTION (a batch arrived while a dispatch executed) and are
        counted separately so the window knob's hit rate reflects only
        what the window bought."""
        if windowed:
            self._window_batches.inc(n_batches)
            if n_batches > 1:
                self._window_coalesced.inc(n_batches - 1)
        elif n_batches > 1:
            self._adopted_batches.inc(n_batches - 1)

    def record_merge(self, shard_responses: list[dict]) -> None:
        self.merge_shard_results.inc(len(shard_responses))
        self.merge_hits.inc(sum(len(r["hits"]["hits"])
                                for r in shard_responses))

    def record_groups(self, group_sizes, dispatches: int) -> None:
        self.batches_dispatched.inc(dispatches)
        for sz in group_sizes:
            if sz > 1:
                self.coalesced_queries.inc(sz)

    def snapshot(self) -> dict:
        from ..utils import race_guard, trace_guard
        from . import bound_plans
        from ..parallel import mesh_reader
        from .executor import collect_counts, collect_parts, launch_counts
        from .resident import resident_stats
        wb = self._window_batches.count
        wc = self._window_coalesced.count
        snap = {
            "searches": self.searches.count,
            "queries": self.queries.count,
            "coalesced_queries": self.coalesced_queries.count,
            "batches_dispatched": self.batches_dispatched.count,
            "pipeline_depth": self.pipeline_depth.max,
            "adopted_batches": self._adopted_batches.count,
            "window": {"batches": wb, "coalesced": wc,
                       "hit_rate": (wc / wb if wb else 0.0)},
            "failover": failover_stats.snapshot(),
            # dead-device eviction lifecycle (parallel/repack.py):
            # rows evicted, degraded repacks, searcher swaps,
            # re-expansions, serving-degraded high-water
            "eviction": eviction_stats.snapshot(),
            # pod-membership lifecycle (parallel/membership.py):
            # joins, replacements, drains, lease handoffs, fenced
            # drivers, partitions survived — all zero single-host
            "membership": membership_stats.snapshot(),
            # resident query loop (search/resident.py): pinned-entry
            # hits, evictions, preemptions, residency bytes — all zero
            # with ES_TPU_RESIDENT_LOOP unset
            "resident": resident_stats(),
            # where a served search's time goes (utils/profiler.py):
            # {name: {"count", "sum", "mean"}} in seconds per phase and
            # wait, always on; and device-program launches by backend.
            # Process-wide, so read them as deltas
            "phases": profiler.phase_stats(),
            "launches": launch_counts(),
            # the parts of two of those phases, beside them and never
            # inside (each part is time its phase already counts):
            # `scheduler_wait` as parked, queued and woken (module
            # doc), `collect` as compute and copy (executor._collect)
            "scheduler_wait": {name: m.snapshot()
                               for name, m in _wait_parts.items()},
            "collect_parts": collect_parts(),
            # collects, and those whose launch had started the copy to
            # the host
            "collects": collect_counts(),
            # what the readers kept of the bodies they had served, once
            # a reader call's group: launched from kept plans (hits),
            # built and kept (misses), not kept (bypassed); evictions
            # at a reader's capacity, entries live readers hold
            "bound_plans": bound_plans.counts(),
            # searches answered by one program over an index's chips,
            # and those of such an index that went to its shards' own
            # readers, by reason (parallel/mesh_reader.py)
            "mesh": mesh_reader.counts(),
            # what is no tile of a search: the rounds led (count), the
            # seconds their leaders spent executing them and the reader
            # groups they served; the coordinator's merges, with the
            # shard results and hits that went in
            "leader": {**self.leader.snapshot(),
                       "groups": self.leader_groups.count},
            "merge": {**self.merge.snapshot(),
                      "shard_results": self.merge_shard_results.count,
                      "hits": self.merge_hits.count},
        }
        if self.traffic is not None:
            snap["traffic"] = self.traffic.snapshot()
        # runtime hygiene counters (utils/trace_guard.py): present only
        # while the guard is armed, so bench runs report unexpected
        # transfers/recompiles alongside latency without changing the
        # steady-state stats shape
        tg = trace_guard.snapshot()
        if tg is not None:
            snap.update(tg)
        # race sanitizer trips (utils/race_guard.py): same contract —
        # the key exists only while ES_TPU_RACE_GUARD armed it
        rg = race_guard.snapshot()
        if rg is not None:
            snap.update(rg)
        return snap


class _Job:
    """One shard-level search riding a DispatchBatch. `deadline` is an
    absolute time.monotonic() cutoff (None = no deadline): the reader's
    collect phase raises SearchTimeoutError past it, and the caller
    (node._finish_on_readers) converts that into a failed-by-timeout
    shard on a `timed_out: true` response."""

    __slots__ = ("batch", "reader", "body", "with_partials", "deadline",
                 "request", "key", "fetch_s", "_result", "_error", "_done")

    def __init__(self, batch: "DispatchBatch", reader, body: dict,
                 with_partials: bool, deadline: float | None = None,
                 request: int | None = None, key: str | None = None):
        # whose wait clock stops while the leader serves this job
        self.batch = batch
        self.reader = reader
        self.body = body
        self.with_partials = with_partials
        self.deadline = deadline
        # the request's id (utils/profiler.next_request_id), named on
        # the phase spans of whatever dispatch serves this job
        self.request = request
        # the body's bound_plans.body_key where the caller made it once
        # for all shards of a search (None: the reader makes its own)
        self.key = key
        # seconds the reader spent building this job's response
        self.fetch_s = 0.0
        self._result = None
        self._error = None
        self._done = False

    def result(self) -> dict:
        if not self._done:
            raise RuntimeError(
                "dispatch job collected before batch.dispatch()")
        if self._error is not None:
            raise self._error
        return self._result


class DispatchBatch:
    """One caller's set of shard-level jobs, dispatched as a unit (and
    possibly merged with concurrently-arriving batches). `lane` is the
    priority lane the scheduler drains it from (traffic control plane;
    defaults to interactive — the protected class)."""

    def __init__(self, scheduler: "DispatchScheduler",
                 lane: str = "interactive"):
        self._scheduler = scheduler
        self.lane = lane
        self.jobs: list[_Job] = []
        self._done = threading.Event()
        # `scheduler_wait`: the time inside dispatch() in which the
        # leading thread serves none of this batch's jobs. `t_handed`
        # is the perf_counter reading from which the batch is waiting
        # (None: not inside dispatch()); DispatchScheduler._serving
        # banks the wait so far where the leader takes up one of its
        # reader groups and starts the clock again where it lets go
        self.t_handed: float | None = None
        self.waited_s = 0.0
        # the wait's parts (module doc): `t_entry` is the reading at
        # dispatch()'s call (None: run() was called without it),
        # `parked_s` what DispatchScheduler._lead charged the batch at
        # each acquisition of the leader, `t_done` the reading at its
        # `_done.set()`
        self.t_entry: float | None = None
        self.parked_s = 0.0
        self.t_done: float | None = None
        # the three, in seconds, as dispatch() recorded them
        self.parts: dict[str, float] = {}
        # `request=` / `requests=` of its `wait:scheduler` spans
        self.span_args: dict = {}

    def submit(self, reader, body: dict, with_partials: bool = False,
               deadline: float | None = None,
               request: int | None = None,
               key: str | None = None) -> _Job:
        job = _Job(self, reader, body, with_partials, deadline, request,
                   key)
        self.jobs.append(job)
        return job

    def dispatch(self) -> None:
        """Execute every submitted job; per-job errors are re-raised by
        job.result(), never by dispatch() itself. Records the batch's
        `scheduler_wait` once for each search it holds (the jobs of one
        search carry one request id; in-process callers' jobs, which
        carry none, are one search), and so its parts."""
        if not self.jobs:
            self._done.set()
            return
        requests = list(dict.fromkeys(j.request for j in self.jobs))
        searches = len(requests)
        self._scheduler.stats.searches.inc(searches)
        self.span_args = profiler.request_args(requests)
        self.t_handed = self.t_entry = time.perf_counter()
        self._scheduler.run(self)
        now = time.perf_counter()
        wait = self.waited_s + now - self.t_handed
        woken = now - self.t_done
        self.parts = {"parked": self.parked_s,
                      "queued": wait - self.parked_s - woken,
                      "woken": woken}
        for _ in range(searches):
            profiler.waited("scheduler_wait", wait)
            for name, seconds in self.parts.items():
                _wait_parts[name].inc(seconds)


class DispatchScheduler:
    """Leader-drain scheduler over DispatchBatches (see module doc)."""

    def __init__(self, window_ms: float = 0.0, traffic=None):
        from ..utils import race_guard
        self._mx = threading.Lock()
        # graftlint: ok(lock-discipline): serialization latch, not a data
        # lock — the leader HOLDS it across the coalescing window sleep
        # and the drain's dispatch/collect by design; waiters are exactly
        # the batches the drain is executing, parked on batch._done
        self._leader = threading.Lock()
        # the perf_counter reading at the last release of `_leader`,
        # written by its holder: from it a pending batch is parked
        self._released = time.perf_counter()
        self._pending: list[DispatchBatch] = race_guard.guarded_list(
            self._mx, "dispatch.DispatchScheduler._pending")
        self._window_default = float(window_ms)
        # traffic control plane (search/traffic.py): lane quotas for the
        # weighted drain, the adaptive coalescing window, and the stats
        # surface. None = legacy single-FIFO behavior (static window
        # only), so scheduler unit tests need no controller.
        self._traffic = traffic
        self.stats = DispatchStats()
        self.stats.traffic = traffic

    def batch(self, lane: str = "interactive") -> DispatchBatch:
        return DispatchBatch(self, lane=lane)

    def window_ms(self) -> float:
        """Effective coalescing window for THIS drain. Precedence: the
        env override (explicit operator knob), then a non-zero static
        setting, then the traffic controller's adaptive window (0 when
        traffic is sequential or the controller is absent)."""
        raw = os.environ.get("ES_TPU_COALESCE_WINDOW_MS")
        if raw not in (None, ""):
            try:
                return float(raw)
            except ValueError:
                pass
        if self._window_default > 0:
            return self._window_default
        if self._traffic is not None:
            return self._traffic.window.window_ms()
        return self._window_default

    # -- core --------------------------------------------------------------
    def run(self, batch: DispatchBatch) -> None:
        with self._mx:
            self._pending.append(batch)
            lane_depth = sum(1 for b in self._pending
                             if b.lane == batch.lane)
        if self._traffic is not None:
            self._traffic.note_lane_depth(batch.lane, lane_depth)
            self._traffic.window.observe_arrival()
        self._lead(batch, windowed=True)
        # a leader was mid-flight: it adopts this batch in a coming
        # round. Wait on COMPLETION, not on the leader lock — with
        # priority lanes the leader may keep draining a deep bulk
        # backlog long after this batch's round finished, and an
        # interactive caller must return the moment its own round
        # completes. The timed re-check only closes the rare
        # enqueue/last-take race (a leader exited without seeing this
        # batch): the first retry comes fast, then the poll backs off
        # so a deep backlog of waiting callers is not a wakeup storm.
        poll_s = 0.001
        while not self._sleep(batch, poll_s):
            self._lead(batch, windowed=False)
            poll_s = 0.05

    def _lead(self, until: DispatchBatch, windowed: bool) -> None:
        """Drain as the leader where no thread leads. At the
        acquisition every pending batch is charged the time it was
        `parked`: since its call or the last release, whichever is
        later. `windowed`: the first try of a caller, which may hold
        the door (the coalescing window)."""
        if self._leader.acquire(blocking=False):
            try:
                acquired = time.perf_counter()
                with self._mx:
                    for b in self._pending:
                        if b.t_entry is not None:
                            b.parked_s += max(0.0, acquired - max(
                                b.t_entry, self._released))
                w = self.window_ms() if windowed else 0.0
                if w > 0:
                    # hold the door for concurrent REST traffic that
                    # would otherwise just miss this drain (static: the
                    # operator asked; adaptive: the controller predicts
                    # another arrival inside the window)
                    time.sleep(w / 1000.0)
                self._drain(windowed=w > 0, until=until)
            finally:
                self._released = time.perf_counter()
                self._leader.release()

    @staticmethod
    def _sleep(batch: DispatchBatch, timeout: float) -> bool:
        """One sleep of a caller on its batch's completion, drawn as a
        `wait:scheduler` span while a trace runs (none where the batch
        is already done: its caller led the round)."""
        if batch._done.is_set():
            return True
        with profiler.enclosing("wait:scheduler", **batch.span_args):
            return batch._done.wait(timeout=timeout)

    def _lane_quota(self, lane: str) -> int | None:
        if lane == "interactive":
            return None  # the protected class is never capped
        if self._traffic is not None:
            return self._traffic.lane_quota(lane)
        return None  # no controller: legacy single-FIFO drain

    def _take_round_locked(self) -> list[DispatchBatch]:
        """One drain round: ALL interactive batches plus up to the
        per-lane quota from each other lane, in lane priority order
        (FIFO within a lane — Python's sort is stable). Leftovers stay
        pending for the next round, where freshly-arrived interactive
        batches again outrank them."""
        if not self._pending:
            return []
        from .traffic import lane_priority
        ordered = sorted(self._pending, key=lambda b: lane_priority(b.lane))
        take: list[DispatchBatch] = []
        leftover: list[DispatchBatch] = []
        counts: dict[str, int] = {}
        for b in ordered:
            q = self._lane_quota(b.lane)
            c = counts.get(b.lane, 0)
            if q is not None and c >= q:
                leftover.append(b)
            else:
                counts[b.lane] = c + 1
                take.append(b)
        # leftovers keep within-lane FIFO order (the sort above is
        # stable); new arrivals append after them under the same lock.
        # In-place (not a rebind): the list is a race_guard-declared
        # structure and must keep its guard for the process lifetime
        self._pending[:] = leftover
        return take

    def _drain(self, windowed: bool = False,
               until: "DispatchBatch | None" = None) -> None:
        """Drain rounds until nothing is pending — or, when `until` is
        given, until that batch's round has completed. The early exit
        keeps a drain leader's OWN latency bounded under a sustained
        over-quota flood (leftover rounds would otherwise pin an
        interactive caller's thread for the flood's duration); every
        leftover batch has its own caller parked in run(), whose timed
        leader re-check picks the backlog up within one poll."""
        first = True
        while True:
            if until is not None and until._done.is_set():
                return
            with self._mx:
                round_ = self._take_round_locked()
            if not round_:
                return
            # only the FIRST round's merges were bought by the timed
            # window; later rounds of the same drain are in-flight
            # adoption like any un-windowed leader's
            self.stats.record_round(len(round_), windowed and first)
            if self._traffic is not None:
                self._traffic.window.observe_round(len(round_))
            first = False
            try:
                self._execute([j for b in round_ for j in b.jobs])
            finally:
                for b in round_:
                    b.t_done = time.perf_counter()
                    b._done.set()

    # -- execution ---------------------------------------------------------
    @staticmethod
    def _call_kw(g: list[_Job]) -> dict:
        """Deadline, request-id and body-key kwargs for a coalesced
        group's reader call — each left out when the group has none, so plain
        mock
        readers without the kwarg keep working. Grouping buckets
        deadlines to 10 ms (see _reader_groups), so members differ by less
        than a bucket; the LATEST wins — a cooperative timeout may fire
        a few ms late but must never fail a request before its own
        deadline."""
        kw: dict = {}
        if g[0].deadline is not None:
            kw["deadline"] = max(j.deadline for j in g)
        if any(j.request is not None for j in g):
            kw["requests"] = [j.request for j in g]
        if any(j.key is not None for j in g):
            kw["keys"] = [j.key for j in g]
        return kw

    @staticmethod
    @contextlib.contextmanager
    def _serving(g: list[_Job]):
        """The leading thread works for this group inside the block, so
        for every batch that has a job in it: their wait (the coalescing
        window's sleep and other batches' groups ahead in the round
        included) ends here and begins again after it, for the rest of
        the round and their caller's wake-up. A batch with jobs in
        several groups (a fan-out) stops its clock in each of them and
        waits through none of its own."""
        waiting = [b for b in {j.batch for j in g}
                   if b.t_handed is not None]
        now = time.perf_counter()
        for b in waiting:
            b.waited_s += now - b.t_handed
        try:
            yield
        finally:
            now = time.perf_counter()
            for b in waiting:
                b.t_handed = now

    def _fail_or_isolate(self, g: list[_Job], e: Exception) -> None:
        """A group's shared execution failed: retry singly so
        batch-mates survive one bad body — EXCEPT on deadline exits,
        where re-dispatching cannot succeed (the deadline won't
        un-pass) and only burns device time the laggard already
        wasted."""
        if isinstance(e, SearchTimeoutError):
            for j in g:
                j._error = e
                j._done = True
        else:
            self._run_isolated(g)

    @staticmethod
    def _reader_groups(jobs: list[_Job]) -> list[list[_Job]]:
        """The round's jobs by the reader call that will serve them, in
        order of first arrival."""
        groups: dict[tuple, list[_Job]] = {}
        for j in jobs:
            # deadlines bucket at 10 ms rather than keying raw floats:
            # msearch items sharing one `timeout` compute deadlines
            # microseconds apart, and exact-float keys would put every
            # job in its own group — silently disabling coalescing for
            # any deadline-carrying traffic. Different timeout ORDERS
            # (100ms vs 10s) still split, as they must.
            dkey = (None if j.deadline is None
                    else int(j.deadline * 100))
            groups.setdefault((id(j.reader), j.with_partials, dkey),
                              []).append(j)
        return list(groups.values())

    def _execute(self, jobs: list[_Job]) -> None:
        """One round on the leading thread: the `leader` timer and the
        `request:round` span, around every reader group's service."""
        # shard jobs: a mesh reader's job serves every shard of its index
        self.stats.queries.inc(sum(getattr(j.reader, "shards_served", 1)
                                   for j in jobs))
        groups = self._reader_groups(jobs)
        self.stats.leader_groups.inc(len(groups))
        with profiler.enclosing("request:round", timer=self.stats.leader,
                                batches=len({j.batch for j in jobs}),
                                groups=len(groups)):
            self._serve_groups(jobs, groups)

    def _serve_groups(self, jobs: list[_Job],
                      groups: list[list[_Job]]) -> None:
        # enqueue EVERY group's device programs back-to-back through
        # the reader's non-syncing submit, then collect in submission
        # order — round trips overlap instead of serializing
        pendings = []
        for g in groups:
            with self._serving(g):
                try:
                    pend = g[0].reader.msearch_submit(
                        [j.body for j in g], g[0].with_partials,
                        **self._call_kw(g))
                except Exception as e:  # noqa: BLE001 — submit-time (parse)
                    self._fail_or_isolate(g, e)
                    continue
            pendings.append((g, pend))
        # depth = device programs enqueued before the first collection —
        # the number of dispatch round trips actually overlapped
        self.stats.pipeline_depth.record(
            sum(p.dispatch_count for _g, p in pendings))
        for g, pend in pendings:
            with self._serving(g):
                try:
                    rs = pend.finish()
                except Exception as e:  # noqa: BLE001 — one bad body
                    # fails the shared program (see _fail_or_isolate)
                    self._fail_or_isolate(g, e)
                    continue
                for j, r in zip(g, rs):
                    j._result = r
                    j._done = True
            # a mesh searcher's pend keeps no fetch times
            for j, fetch_s in zip(g, getattr(pend, "fetch_s", ())):
                j.fetch_s = fetch_s
            self.stats.record_groups(pend.group_sizes,
                                     pend.dispatch_count)
        for j in jobs:  # backstop: no job may leave undecided
            if not j._done:
                j._error = RuntimeError("dispatch job was not executed")
                j._done = True

    def _run_isolated(self, g: list[_Job]) -> None:
        """Per-job fallback: each body runs alone so only the bad one
        errors (batch-mates must not inherit a stranger's 400)."""
        for j in g:
            if j._done:
                continue
            try:
                j._result = j.reader.msearch([j.body], j.with_partials,
                                             **self._call_kw([j]))[0]
            except Exception as e:  # noqa: BLE001
                j._error = e
            j._done = True
