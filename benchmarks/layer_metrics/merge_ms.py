"""Per-layer metric `merge_ms`: the coordinator's merge of a search's
shard results (`search/controller.py:merge_shard_results` as
`Node._reduce_on_readers` calls it: top-k by score or sort value, then
shard, then document; totals; aggregation partials), from the always-on
timer `GET /_nodes/stats/dispatch` -> `merge` (`sum`, seconds): the
window's delta over the searches answered in it, in ms. A part of the
`reduce` phase, so of `coordinate_ms`; not a tile of its own. A program
without the timer reports nothing.

Read in every cell that reports `search_p50_ms`."""

NAME = "merge_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "REST front end and node"
MOVES = "search_p50_ms"


def read(run):
    before = run.stats_before.get("dispatch", {}).get("merge")
    after = run.stats_after.get("dispatch", {}).get("merge")
    n = len(run.answered())
    if before is None or after is None or not n:
        return None
    return 1e3 * (after["sum"] - before["sum"]) / n
