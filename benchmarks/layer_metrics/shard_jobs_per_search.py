"""Per-layer metric `shard_jobs_per_search`: shard-level jobs the
dispatch scheduler executed in the window (`GET /_nodes/stats/dispatch`
-> `queries`, one a shard a search) over the searches answered in it:
the fan-out the coordinator pays for. 1.0 on an index of one shard, 5.0
on the track's default five.

Read in every cell that reports `search_p50_ms`."""

NAME = "shard_jobs_per_search"
UNIT = "jobs"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "dispatch scheduler and shard searcher"
MOVES = "search_p50_ms"


def read(run):
    before = run.stats_before.get("dispatch", {}).get("queries")
    after = run.stats_after.get("dispatch", {}).get("queries")
    n = len(run.answered())
    if before is None or after is None or not n:
        return None
    return (after - before) / n
