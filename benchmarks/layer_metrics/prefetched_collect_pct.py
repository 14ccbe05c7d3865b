"""Per-layer metric `prefetched_collect_pct`: of the collects in the
window (`GET /_nodes/stats/dispatch` -> `collects.total`, one a
launched program whose result the reader took), the share whose launch
had already asked for the result's device-to-host copy
(`collects.prefetched`), so that `jax.device_get` found the bytes on
the host or in flight instead of starting the transfer and waiting it
out. 100 where every launch starts its copy; a program without the
counter reports nothing.

Read in every cell that reports `search_p50_ms`."""

NAME = "prefetched_collect_pct"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
LAYER = "executor"
MOVES = "search_p50_ms"


def read(run):
    before = run.stats_before.get("dispatch", {}).get("collects")
    after = run.stats_after.get("dispatch", {}).get("collects")
    if before is None or after is None:
        return None
    total = after["total"] - before["total"]
    if not total:
        return None
    return 100.0 * (after["prefetched"] - before["prefetched"]) / total
