"""Per-layer metric `collect_compute_ms`: the part of the `collect` phase
(`executor._collect`) up to the return of `jax.block_until_ready`: what
is left of the device program when the leader gets there, and the
runtime waking the thread.

From the always-on timer `GET /_nodes/stats/dispatch` ->
`collect_parts.compute` (`sum`, seconds, in search-seconds like the
phase it is a part of): the window's delta over the searches answered
in it, in ms. A program without the timer reports nothing.

Read in every cell that reports `search_p50_ms`."""

NAME = "collect_compute_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "executor"
MOVES = "search_p50_ms"


def read(run):
    before = run.stats_before.get("dispatch", {}).get("collect_parts", {})
    after = run.stats_after.get("dispatch", {}).get("collect_parts", {})
    n = len(run.answered())
    if "compute" not in before or "compute" not in after or not n:
        return None
    return 1e3 * (after["compute"]["sum"] - before["compute"]["sum"]) / n
