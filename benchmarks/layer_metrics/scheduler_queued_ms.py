"""Per-layer metric `scheduler_queued_ms`: the part of `scheduler_wait`
that is neither parked nor the wake-up: a leader held the scheduler
while the search waited (the round in flight ahead of it, the
coalescing window's sleep, the other reader groups of its own round).

From the always-on timer `GET /_nodes/stats/dispatch` ->
`scheduler_wait.queued` (`sum`, seconds, in search-seconds like the
phase it is a part of): the window's delta over the searches answered
in it, in ms. A program without the timer reports nothing.

Read in every cell that reports `search_p50_ms`."""

NAME = "scheduler_queued_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "dispatch scheduler and shard searcher"
MOVES = "search_p50_ms"


def read(run):
    before = run.stats_before.get("dispatch", {}).get("scheduler_wait", {})
    after = run.stats_after.get("dispatch", {}).get("scheduler_wait", {})
    n = len(run.answered())
    if "queued" not in before or "queued" not in after or not n:
        return None
    return 1e3 * (after["queued"]["sum"] - before["queued"]["sum"]) / n
