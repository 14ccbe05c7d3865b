#!/usr/bin/env python3
"""What a builder reads from `run.py --keep-requests DIR` dumps before a
window's length or a bound is set. Not part of a check's runs.

    python3 benchmarks/tools/spread.py DIR [--lengths 15,30,45]

For each dump: the median and the 95th percentile of the whole window
and of each stretch of every length (requests by when they were due:
the first 15 s, the second, ...; a stretch of a longer window stands
for a run of that length, since the schedule is the same process), how
late the generator ran and how many requests took over 50 ms. Then, for
each length, the spread of each percentile over the dumps' first
stretches and over all stretches: first to third quartile over the
median, as `statistics.quantiles(values, n=4)` gives them.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import sys

import numpy as np


def spread(values: list[float]) -> float | None:
    if len(values) < 3:
        return None
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("dir")
    ap.add_argument("--lengths", default="15,30,45")
    args = ap.parse_args(argv)
    lengths = [float(x) for x in args.lengths.split(",")]
    first: dict = {}
    every: dict = {}
    for path in sorted(glob.glob(os.path.join(args.dir, "*.requests.json"))):
        with open(path) as f:
            d = json.load(f)
        t0 = d["t_first_due"]
        due = np.array([r[1] - t0 for r in d["requests"]])
        lat = np.array([1e3 * (r[3] - r[1]) for r in d["requests"]])
        late = np.array([1e3 * (r[2] - r[1]) for r in d["requests"]])
        row = {"file": os.path.basename(path), "n": len(lat),
               "p50": float(np.percentile(lat, 50)),
               "p95": float(np.percentile(lat, 95)),
               "p99": float(np.percentile(lat, 99)),
               "max": float(lat.max()), "over_50ms": int((lat > 50).sum()),
               "late_max_ms": float(late.max()), "stretches": {}}
        for L in lengths:
            k = 0
            while (k + 1) * L <= d["seconds"] + 1e-9:
                m = (due >= k * L) & (due < (k + 1) * L)
                pair = (float(np.percentile(lat[m], 50)),
                        float(np.percentile(lat[m], 95)))
                row["stretches"].setdefault(f"{L:g}", []).append(
                    [round(x, 3) for x in pair])
                every.setdefault(L, []).append(pair)
                if k == 0:
                    first.setdefault(L, []).append(pair)
                k += 1
        print(json.dumps(row))
    for L in lengths:
        for name, pool in (("first stretches", first), ("all stretches", every)):
            pairs = pool.get(L, [])
            print(json.dumps({
                "length_s": L, "of": name, "n": len(pairs),
                "p50_median": statistics.median(p[0] for p in pairs)
                if pairs else None,
                "p50_spread": spread([p[0] for p in pairs]),
                "p95_median": statistics.median(p[1] for p in pairs)
                if pairs else None,
                "p95_spread": spread([p[1] for p in pairs])}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
