"""What the per-layer metrics' readers share. Each takes the `Run` that
`run.py` hands it and returns a number, or None where it finds nothing
to read; the harness then leaves the metric out of the line."""

from __future__ import annotations

import statistics

import numpy as np

from .workbytes import search_bytes

FAILED_MS = 60_000.0    # what a failed or refused request counts as


def latencies_ms(requests: list) -> list:
    """Each request of a window from when it was due to the last byte of
    its response; a failed or refused one counts a minute."""
    return [1e3 * (r["done"] - r["due"]) if r["ok"] else FAILED_MS
            for r in requests]


def spec_of(mix: dict):
    """request -> the reference's spec of the query it sent: its
    operation's one, or that of its own planned query."""
    ops = {op["name"]: op for op in mix["operations"]}

    def spec(r: dict) -> dict:
        op = ops[r["op"]]
        return op["specs"][r["query"]] if "query" in r else op["spec"]
    return spec


def _delta(run, *path: str):
    """How far a counter of `_nodes/stats` moved over the window."""
    def dig(stats):
        for key in path:
            stats = stats[key]
        return stats
    before, after = dig(run.stats_before), dig(run.stats_after)
    if isinstance(after, dict):
        return sum(after.values()) - sum(before.values())
    return after - before


def search_tail_p95_ms(run):
    """The 95th percentile over all the requests of the traced run's
    window, each timed from when it was due to the last byte of its
    response, a failed or refused one counting a minute: the tail a
    caller feels, which is where requests wait for one another in front
    of the node. Not end to end, because a machine that stands still
    for a tenth of a second moves it by more than any bound could admit
    (PERF.md, section 2)."""
    lat = latencies_ms(run.requests)
    return float(np.percentile(lat, 95)) if lat else None


def front_end_ms(run):
    """Median over the answered requests of the client's time from the
    first byte sent to the last byte received, less the `took` the
    response states: what REST parsing, the thread hand-over, response
    encoding and the socket cost beyond the node's own clock around the
    search."""
    gaps = [1e3 * (r["done"] - r["sent"]) - r["took"]
            for r in run.answered() if r["took"] is not None]
    return statistics.median(gaps) if gaps else None


def searches_per_dispatch(run):
    """Searches answered in the window over the window's delta of the
    scheduler's `batches_dispatched`: under 1 where a search fans out to
    several shards, higher where the scheduler coalesces."""
    dispatches = _delta(run, "dispatch", "batches_dispatched")
    return len(run.answered()) / dispatches if dispatches else None


def fused_admission_pct(run):
    """Plans the executor admitted to the fused engines, of those it
    considered in the window. The rest run the unfused program."""
    admitted = _delta(run, "fused_scoring", "admission", "admitted")
    considered = admitted + _delta(run, "fused_scoring", "admission",
                                   "rejected")
    return 100.0 * admitted / considered if considered else None


def _traced(run) -> list:
    if not run.trace or not run.traced:
        return []
    return run.answered(*run.traced)


def device_ms_per_search(run):
    """Device time of the scoring programs in the traced window over the
    searches answered in it."""
    n = len(_traced(run))
    if not n or not run.trace["scoring_s"]:
        return None
    return 1e3 * run.trace["scoring_s"] / n


def column_scan_roofline(run):
    """Least time the chip's memory could take for the columns that the
    searches answered in the traced window had to read (`workbytes.py`,
    each search counted by its own operation; HBM peak of `peaks.json`)
    over the device time the scoring programs took. Bounded by HBM
    bandwidth. The compares and the top-k run on the VPU, for which no
    peak is published, so a low share does not by itself mean memory
    stalls."""
    if not run.trace or not run.trace["scoring_s"] or not run.peaks:
        return None
    spec = spec_of(run.mix)
    least = sum(search_bytes(run.config["mappings"], spec(r), run.docs)
                for r in _traced(run))
    if not least:
        return None
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] \
        / run.trace["scoring_s"]


def device_idle_pct(run):
    """1 - the union of the device's operation intervals over the traced
    window."""
    if not run.trace or not run.trace["window_s"]:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])


def setup_load_s(run):
    """Bulk + refresh + flush, or the reopening of the stored commit, up
    to the `_count` that proves every document is there."""
    return run.load_s or None


def setup_compile_s(run):
    """Seconds inside JAX's backend compiles during set-up (cache reads
    included), from its own compile-duration events."""
    return run.compile_s or None
