"""Per-layer metric `bound_plan_hit_pct`: of the groups of bodies the
shard readers were handed in the window (`GET /_nodes/stats/dispatch`
-> `bound_plans`, counted once a reader call's group), the share that
launched from what the reader had kept of an earlier search with the
same bodies (`hits`: the parse, the bind, the packing and the upload of
the wire parameters and the output layout skipped), over `hits` +
`misses` (built and kept) + `bypassed` (bodies and groups the reader
does not keep). Near 100 where the traffic repeats its bodies, as the
track's eight fixed operations do; a deployment whose bodies carry a
moving bound hits less. A program without the counter reports nothing.

Read in every cell that reports `search_p50_ms`."""

NAME = "bound_plan_hit_pct"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
LAYER = "executor"
MOVES = "search_p50_ms"


def read(run):
    before = run.stats_before.get("dispatch", {}).get("bound_plans")
    after = run.stats_after.get("dispatch", {}).get("bound_plans")
    if before is None or after is None:
        return None
    hits = after["hits"] - before["hits"]
    total = hits + sum(after[k] - before[k] for k in ("misses", "bypassed"))
    if not total:
        return None
    return 100.0 * hits / total
