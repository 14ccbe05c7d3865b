"""The readers of the program's phase timers and launch counters:
`GET /_nodes/stats/dispatch` -> `phases` ({name: {"count", "sum",
"mean"}}, seconds, always on) and `launches` ({backend: n}), which
`elasticsearch_tpu/utils/profiler.py` and `search/executor.py` keep
process-wide. Every reader takes the window's delta between the run's
two snapshots (`Run.stats_before`, `Run.stats_after`), and returns None
where the section has no such key: a program from before the timers
reports none of these metrics, and the harness leaves them out.

A served search is tiled by twelve of the names: the spans
`rest_parse`, `resolve`, `bind`, `dispatch` (or `resident_dispatch`,
`tiered_dispatch`, `tiered_aggs`), `collect`, `unpack`, `fetch`,
`reduce`, `finish`, `respond`, and the waits `pool_wait` and
`scheduler_wait`, in which no thread works for it. `request` is the
whole of it inside the program, from the REST handler's first line to
its last write. The sums are in search-seconds: a reader call that
serves n searches at once adds its time n times, since each of them
waited through it.
"""

from __future__ import annotations

WHOLE = "request"
LAUNCHES = ("dispatch", "resident_dispatch", "tiered_dispatch",
            "tiered_aggs")


def _delta(run, key: str) -> dict | None:
    """{name: how far it moved over the window} of one of the section's
    maps; a phase's value is its `sum` of seconds."""
    before = run.stats_before.get("dispatch", {}).get(key)
    after = run.stats_after.get("dispatch", {}).get(key)
    if before is None or after is None:
        return None

    def value(entry) -> float:
        return entry["sum"] if isinstance(entry, dict) else entry

    return {name: value(entry) - value(before.get(name, 0))
            for name, entry in after.items()}


def _seconds(run, *names: str) -> float | None:
    moved = _delta(run, "phases")
    if moved is None:
        return None
    return sum(moved.get(name, 0.0) for name in names)


def _ms_per_search(run, *names: str) -> float | None:
    """The window's seconds in these phases over the searches answered
    in it, in ms."""
    seconds = _seconds(run, *names)
    n = len(run.answered())
    if seconds is None or not n:
        return None
    return 1e3 * seconds / n


def rest_parse_ms(run):
    """`rest_parse`: the REST thread's own work on a search but for the
    response — URL, headers, body read, JSON parse, routing, up to the
    route's call into `node.search`, and from its return to `_respond`."""
    return _ms_per_search(run, "rest_parse")


def rest_respond_ms(run):
    """`respond`: `json.dumps` of the response, headers, the socket
    write."""
    return _ms_per_search(run, "respond")


def pool_wait_ms(run):
    """`pool_wait`: the two hand-overs between the REST thread and the
    `search` pool's thread (admission and `pool.submit` to the first
    line on the pool thread; its last line to `.result()` resuming)."""
    return _ms_per_search(run, "pool_wait")


def coordinate_ms(run):
    """`resolve` + `reduce` + `finish`: the node's own work around the
    shard search — index resolution, `acquire_searcher`, the
    request-cache lookup and job creation; the merge of the shard
    results; slowlog, search stats, doc types."""
    return _ms_per_search(run, "resolve", "reduce", "finish")


def searches_in_flight(run):
    """The window's delta of the `request` timer over the window's
    seconds: how many searches are inside the program on average. One
    minus it bounds the share of the device's idle time in which no
    search was there to serve."""
    seconds = _seconds(run, WHOLE)
    if seconds is None or not run.window_s:
        return None
    return seconds / run.window_s


def span_coverage_pct(run):
    """Every phase and wait of the window summed, over the summed client
    time (first byte sent to last received) of the answered requests:
    what the program can account for of what its callers waited. The
    rest is the socket, the kernel, and threads waiting for the
    interpreter lock outside any timed block."""
    moved = _delta(run, "phases")
    client = sum(r["done"] - r["sent"] for r in run.answered())
    if moved is None or not client:
        return None
    return 100.0 * sum(v for k, v in moved.items() if k != WHOLE) / client


def scheduler_wait_ms(run):
    """`scheduler_wait`, counted once a search whatever its fan-out
    (since PR 28): the time between `DispatchBatch.dispatch()` being
    called and returning in which the leading thread served none of the
    batch's shard jobs (the coalescing window's sleep and a search
    parked behind a round in flight included). The batch's one clock
    stands still while the leader serves a reader group that holds any
    of its jobs; that time is in `bind`, the launches, `collect`,
    `unpack` and `fetch`."""
    return _ms_per_search(run, "scheduler_wait")


def fetch_ms(run):
    """`unpack` + `fetch`: slicing the device's result back into
    requests, then building the hits and loading and filtering their
    `_source`."""
    return _ms_per_search(run, "unpack", "fetch")


def bind_ms(run):
    """`bind`: the reader's `msearch_submit` but for the launches —
    parse, grouping, bind, wire params, layout, breaker accounting."""
    return _ms_per_search(run, "bind")


def launch_ms(run):
    """`dispatch` (and `resident_dispatch`, `tiered_dispatch`,
    `tiered_aggs` where those paths run): the host's time in the jitted
    programs' launches and, since PR 29, in asking for each result's
    copy to the host at its launch (`executor._start_fetch`)."""
    return _ms_per_search(run, *LAUNCHES)


def device_wait_ms(run):
    """`collect`: `jax.device_get` of the result. The copy to the host
    was asked for at the launch (since PR 29), so this is what is left
    of device time, copy and the runtime waking the thread when the
    leader gets there."""
    return _ms_per_search(run, "collect")


def device_launches_per_search(run):
    """Device programs launched in the window, all backends summed
    (`unfused`, `fused_xla`, `fused_pallas`, `resident`, `tiered`), over
    the searches answered in it: under 1 where searches share a
    dispatch, over 1 where one fans out to shards or segments."""
    moved = _delta(run, "launches")
    n = len(run.answered())
    if moved is None or not n:
        return None
    return sum(moved.values()) / n
