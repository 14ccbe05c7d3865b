"""Per-layer metric `scheduler_parked_ms`: the part of `scheduler_wait` in
which a search's batch was pending while no thread led the dispatch
scheduler: from its call, or the last leader's release if later, to the
next acquisition of the leader (`DispatchScheduler._lead`). What a
search parked behind a round in flight pays until the next arrival
leads or the caller's 50 ms poll ends (ROADMAP S3).

From the always-on timer `GET /_nodes/stats/dispatch` ->
`scheduler_wait.parked` (`sum`, seconds, in search-seconds like the
phase it is a part of): the window's delta over the searches answered
in it, in ms. A program without the timer reports nothing.

Read in every cell that reports `search_p50_ms`."""

NAME = "scheduler_parked_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "dispatch scheduler and shard searcher"
MOVES = "search_p50_ms"


def read(run):
    before = run.stats_before.get("dispatch", {}).get("scheduler_wait", {})
    after = run.stats_after.get("dispatch", {}).get("scheduler_wait", {})
    n = len(run.answered())
    if "parked" not in before or "parked" not in after or not n:
        return None
    return 1e3 * (after["parked"]["sum"] - before["parked"]["sum"]) / n
