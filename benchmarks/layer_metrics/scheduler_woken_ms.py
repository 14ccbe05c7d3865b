"""Per-layer metric `scheduler_woken_ms`: the part of `scheduler_wait`
from its round's end (the batch's `_done.set()`) to
`DispatchBatch.dispatch()` resuming: the waiting thread's wake-up and
its wait for the interpreter lock.

From the always-on timer `GET /_nodes/stats/dispatch` ->
`scheduler_wait.woken` (`sum`, seconds, in search-seconds like the
phase it is a part of): the window's delta over the searches answered
in it, in ms. A program without the timer reports nothing.

Read in every cell that reports `search_p50_ms`."""

NAME = "scheduler_woken_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "dispatch scheduler and shard searcher"
MOVES = "search_p50_ms"


def read(run):
    before = run.stats_before.get("dispatch", {}).get("scheduler_wait", {})
    after = run.stats_after.get("dispatch", {}).get("scheduler_wait", {})
    n = len(run.answered())
    if "woken" not in before or "woken" not in after or not n:
        return None
    return 1e3 * (after["woken"]["sum"] - before["woken"]["sum"]) / n
