"""Per-layer metric `leader_busy_pct`: seconds some thread spent
leading a dispatch round (`DispatchScheduler._execute`: every reader
group's bind, launches, collect, unpack and fetch, each
round once however many searches ride it), from the always-on timer
`GET /_nodes/stats/dispatch` -> `leader` (`sum`, seconds): the window's
delta over the window's seconds. One thread leads at a time, so it
cannot pass 100; what is left of 100 is what the leader could still
take on, which is where the knee is. A program without the timer
reports nothing.

Read in every cell that reports `search_p50_ms`."""

NAME = "leader_busy_pct"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "dispatch scheduler and shard searcher"
MOVES = "search_p50_ms"


def read(run):
    before = run.stats_before.get("dispatch", {}).get("leader")
    after = run.stats_after.get("dispatch", {}).get("leader")
    if before is None or after is None or not run.window_s:
        return None
    return 100.0 * (after["sum"] - before["sum"]) / run.window_s
