#!/usr/bin/env python3
"""The builder's probe: what a benchmark PR runs on the chip before it
sets a rate or a limit. Not part of a check's runs.

    python3 benchmarks/tools/probe.py sweep --workload <cell> --seed <n> \
        --seconds <s> --scales 0.5,1,1.5,2
    python3 benchmarks/tools/probe.py readings --workload <cell> \
        --seeds 1,2,3 --seconds <s>
    python3 benchmarks/tools/probe.py control --workload <cell> --seeds 1,2,3

`sweep` loads one corpus, times each operation of the mix alone (eight
requests one after another) and then offers the mix at each
`rate_scale` in turn: a rate is sustained where nothing failed, the
backlog does not grow (the last quarter's median latency is not above
the first's by more than half, and the generator did not run late) and
the median latency is still under twice the lightest rate's. `readings`
reads, for each seed in one process (a new corpus each), the numbers
`correct` compares from the program's answers at the cell's own load,
and from the control. `control` reads the control alone and needs no
program: the reference put in the program's place and computed in
bfloat16, the nearest precision below the float32 the configurations
state. All write one JSON line per step to standard output.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import run as R  # noqa: E402
from harness import corpus as C  # noqa: E402
from harness.loadgen import plan_queries  # noqa: E402


def control_numbers(corpus: C.Records, mix: dict) -> dict:
    """The numbers `correct` compares, read from the control's answers
    to the mix's queries: each operation's one, or every planned query
    of a drawn operation."""
    bf16 = C.bfloat16()
    ref = C.Reference(corpus)
    return C.fold([ref.compare(spec, C.answer_from(corpus, spec, bf16))
                   for op in mix["operations"]
                   for spec in op.get("specs") or [op["spec"]]])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("sweep", "readings", "control"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--scales", default="")
    ap.add_argument("--docs", type=int, default=None)
    ap.add_argument("--keep", type=int, choices=(0, 1), default=0,
                    help="leave the flushed index for later runs of the seed")
    args = ap.parse_args(argv)

    _bench, _cell, config, mix = R.resolve_cell(args.workload)
    docs = args.docs or config["docs"]
    seeds = [int(s) for s in args.seeds.split(",") if s] or [args.seed]
    if args.mode == "control":
        for seed in seeds:
            corpus = C.corpus_of(config, docs, seed)
            print(json.dumps({"seed": seed, "docs": docs, "control_bf16":
                              control_numbers(corpus, plan_queries(
                                  mix, corpus, seed, args.seconds))}),
                  flush=True)
        return 0
    from harness import served as S
    for seed in seeds:      # each in a process of its own, before JAX here
        rc = S.ensure_stored(config, seed, docs, False)
        if rc:
            return rc
    import jax
    from elasticsearch_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    dev = jax.devices()[0]
    tag = {"platform": dev.platform, "kind": dev.device_kind}
    clock = S.CompileClock()
    for seed in seeds:
        t = time.perf_counter()
        corpus = C.corpus_of(config, docs, seed)
        planned = plan_queries(mix, corpus, seed, args.seconds)
        with S.Served(config, seed, docs, keep_data=bool(args.keep)) as sv:
            sv.open(corpus)
            sv.warm(planned, clock)
            setup = time.perf_counter() - t
            if args.mode == "sweep":
                for op in planned["operations"]:
                    took = []
                    bodies = corpus.warm_bodies(op)
                    for i in range(8):
                        t1 = time.perf_counter()
                        _st, r = sv.http.call(
                            "POST", f"/{sv.index}/_search",
                            bodies[i % len(bodies)])
                        took.append((1e3 * (time.perf_counter() - t1),
                                     r.get("took")))
                    print(json.dumps({
                        "device": tag, "operation": op["name"],
                        "alone_ms": statistics.median(x for x, _ in took),
                        "took_ms": statistics.median(x for _, x in took)}),
                        flush=True)
                for scale in [float(x) for x in args.scales.split(",")]:
                    gen, _, _ = R.drive(sv, plan_queries(
                        dict(mix, rate_scale=scale), corpus, seed,
                        args.seconds), seed, args.seconds, 0.0)
                    reqs = gen["requests"]
                    lat = [1e3 * (r["done"] - r["due"]) for r in reqs]
                    q = max(1, len(lat) // 4)
                    e2e = R.end_to_end(reqs)
                    print(json.dumps({
                        "device": tag, "rate_scale": scale,
                        "requests": len(reqs),
                        "per_s": len(reqs) / args.seconds,
                        "failed": sum(not r["ok"] for r in reqs),
                        "p50_ms": e2e["search_p50_ms"],
                        "p95_ms": e2e["search_p95_ms"],
                        "first_quarter_p50_ms": statistics.median(lat[:q]),
                        "last_quarter_p50_ms": statistics.median(lat[-q:]),
                        "drain_s": gen["t_close"] - gen["t_first_due"]
                        - args.seconds,
                        "late_ms": gen["late_ms"],
                        "compiles": clock.compiles}), flush=True)
                continue
            gen, _, _ = R.drive(sv, planned, seed, args.seconds, 0.0)
        print(json.dumps({
            "device": tag, "seed": seed, "docs": docs, "setup_s": setup,
            "requests": len(gen["requests"]),
            "failed": sum(not r["ok"] for r in gen["requests"]),
            "window": R.end_to_end(gen["requests"]),
            "program": R.compare_all(corpus, planned, gen["requests"]),
            "control_bf16": control_numbers(corpus, planned)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
