"""Per-layer metric `collect_copy_ms`: the part of the `collect` phase
(`executor._collect`) after the program's end: what is left of the
device-to-host copy the launch asked for (`executor._start_fetch`), and
`jax.device_get`'s conversion to host arrays.

From the always-on timer `GET /_nodes/stats/dispatch` ->
`collect_parts.copy` (`sum`, seconds, in search-seconds like the phase
it is a part of): the window's delta over the searches answered in it,
in ms. A program without the timer reports nothing.

Read in every cell that reports `search_p50_ms`."""

NAME = "collect_copy_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "executor"
MOVES = "search_p50_ms"


def read(run):
    before = run.stats_before.get("dispatch", {}).get("collect_parts", {})
    after = run.stats_after.get("dispatch", {}).get("collect_parts", {})
    n = len(run.answered())
    if "copy" not in before or "copy" not in after or not n:
        return None
    return 1e3 * (after["copy"]["sum"] - before["copy"]["sum"]) / n
