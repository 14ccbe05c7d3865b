#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json on the machine it is started on.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration and a traffic mix; this file opens
`benchmarks/configs/<configuration>.json`, `benchmarks/traffic/<mix>.json`
and, where it exists, `benchmarks/cells/<cell>.json` (the cell's own
numbers, such as an open loop's rate, laid over the mix) by those names
and knows nothing else about the cell. The last line of standard output
is the result. See `benchmarks/README.md`.

`--rehearse 1` is the CPU rehearsal: the whole command at the
configuration's `rehearsal_docs`, on whatever device JAX has, with every
metric that is a time, a rate or a share of the device left out of the
last line. Without it, finding no TPU is an error and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from harness import corpus as C  # noqa: E402
from harness.loadgen import plan_queries  # noqa: E402
from harness.readers import latencies_ms, spec_of  # noqa: E402


def read_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload [{name}] in BENCHMARK.json")


def resolve_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """BENCHMARK.json, the cell's entry, its configuration (the `file`
    of its entry under `configs`; `path` is added to it, for the loader)
    and its traffic mix with the cell's own numbers laid over it: each a
    file found by the name in the entry."""
    bench = read_json(ROOT, "BENCHMARK.json")
    cell = find_cell(bench, name)
    path = os.path.join(ROOT, *next(
        c["file"] for c in bench["configs"]
        if c["name"] == cell["config"]).split("/"))
    config = dict(read_json(path), path=path)
    mix = read_json(HERE, "traffic", cell["traffic"] + ".json")
    own = os.path.join(HERE, "cells", cell["name"] + ".json")
    if os.path.exists(own):
        mix.update(read_json(own))
    return bench, cell, config, mix


def cell_metrics(bench: dict, cell: str) -> tuple[list[dict], list[dict]]:
    """The end-to-end and the per-layer metrics this cell reports. A
    metric with a `workloads` key is reported in those cells; a per-layer
    metric without one in every cell that reports what it `moves`."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (cell in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return e2e, layer


def load_reader(name: str):
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def end_to_end(requests: list[dict]) -> dict:
    """Every end-to-end metric the harness knows, over all the requests
    of the window, each timed from when it was due to the last byte of
    its response."""
    lat = latencies_ms(requests)
    return {"search_p50_ms": float(np.percentile(lat, 50)),
            "search_p95_ms": float(np.percentile(lat, 95))}


def compare_all(corpus, mix: dict, requests: list[dict]) -> dict:
    """Every answer of the window against the plain reference, each with
    the spec of its own query, query by query: the reference then works
    each query out once and has to keep few."""
    spec = spec_of(mix)
    ref = C.Reference(corpus)
    return C.fold([ref.compare(spec(r), r["digest"]) for r in sorted(
        (r for r in requests if r["ok"]),
        key=lambda r: (r["op"], r.get("query", 0)))])


@dataclasses.dataclass
class Run:
    """What one run knows, handed to each per-layer metric's reader."""

    requests: list = dataclasses.field(default_factory=list)
    window_s: float = 0.0
    stats_before: dict = dataclasses.field(default_factory=dict)
    stats_after: dict = dataclasses.field(default_factory=dict)
    trace: dict | None = None       # harness.trace.reduce_trace
    traced: tuple | None = None     # the traced interval, monotonic clock
    docs: int = 0
    config: dict = dataclasses.field(default_factory=dict)
    mix: dict = dataclasses.field(default_factory=dict)
    peaks: dict | None = None       # this device's entry of peaks.json
    load_s: float = 0.0
    compile_s: float = 0.0

    def answered(self, lo=None, hi=None) -> list:
        """The requests answered, in the whole window or between two
        readings of the monotonic clock."""
        return [r for r in self.requests
                if r["ok"] and (lo is None or lo <= r["done"] <= hi)]


def drive(sv, mix: dict, seed: int, seconds: float, trace_span: float):
    """One window: the load generator, a process of its own, sends the
    mix to the served node for `seconds`. With `trace_span` > 0 the last
    `trace_span` seconds of the window are traced, stopped as it closes.
    Returns the generator's result, the traced interval on the monotonic
    clock and the trace's directory."""
    import jax
    from harness import trace as T

    os.makedirs(sv.data_dir, exist_ok=True)
    spec_path = os.path.join(sv.data_dir, f"loadgen-{os.getpid()}.json")
    start_at = time.monotonic() + 1.5
    with open(spec_path, "w") as f:
        json.dump({"host": sv.server.host, "port": sv.server.port,
                   "index": sv.index, "mix": mix, "seed": seed,
                   "seconds": seconds, "start_at": start_at}, f)
    child = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "harness", "loadgen.py"),
         spec_path], stdout=subprocess.PIPE)
    traced = trace_dir = None
    try:
        if trace_span > 0:
            time.sleep(max(0.0, start_at + seconds - trace_span
                           - time.monotonic()))
            # the program starts the trace through its own route (its
            # `query_phase:*` spans are live only then); JAX's python
            # tracer stays off, it would slow the host under test
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            plain = jax.profiler.start_trace
            jax.profiler.start_trace = \
                lambda d, **kw: plain(d, profiler_options=opts, **kw)
            try:
                st, r = sv.http.call("POST", "/_nodes/profiler/start",
                                     {"path": "trace"})
            finally:
                jax.profiler.start_trace = plain
            if st != 200:
                raise RuntimeError(f"profiler start: {st} {r}")
            trace_dir = r["path"]
            t_a = time.monotonic()
            with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
                time.sleep(max(0.0, start_at + seconds - time.monotonic()))
            traced = (t_a, time.monotonic())
            sv.http.call("POST", "/_nodes/profiler/stop")
        out, _ = child.communicate(timeout=seconds + 120)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
        os.remove(spec_path)
    if child.returncode != 0:
        raise RuntimeError(f"load generator exited {child.returncode}")
    return json.loads(out), traced, trace_dir


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the traced run's .xplane.pb there")
    ap.add_argument("--keep-requests", default=None, metavar="DIR",
                    help="write every request's operation and times there "
                         "(what tools/spread.py reads)")
    args = ap.parse_args(argv)

    bench, cell, config, mix = resolve_cell(args.workload)
    e2e_defs, layer_defs = cell_metrics(bench, cell["name"])

    docs = config["rehearsal_docs"] if args.rehearse else config["docs"]
    try:
        from harness import served as S
        # a new seed's corpus is loaded by a process of its own, which
        # has ended before this one touches JAX and takes the chip
        t = time.perf_counter()
        rc = S.ensure_stored(config, args.seed, docs, bool(args.rehearse))
        if rc:
            print(f"run.py: the loader exited {rc}", file=sys.stderr)
            return rc
        t_loader = time.perf_counter() - t
        import jax
        from elasticsearch_tpu.utils.compile_cache import \
            configure_compile_cache
    except ImportError as e:
        print(f"run.py: the program is not here: {e}", file=sys.stderr)
        return 3
    from harness import trace as T

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    tag = f"[{device['platform']} x{device['count']}]"

    def log(*parts) -> None:
        print(tag, *parts, flush=True)

    if not args.rehearse and (device["platform"] != "tpu"
                              or device["count"] < cell["chips"]):
        print(f"run.py: cell [{cell['name']}] needs {cell['chips']} TPU "
              f"chip(s); JAX found {json.dumps(device)}. --rehearse 1 runs "
              f"the CPU rehearsal.", file=sys.stderr)
        return 2
    peaks_all = read_json(HERE, "peaks.json")
    if not args.rehearse and device["kind"] not in peaks_all:
        print(f"run.py: no peaks for device kind [{device['kind']}] in "
              f"benchmarks/peaks.json", file=sys.stderr)
        return 2
    cache_dir = configure_compile_cache()
    log(f"cell {cell['name']}: config {config['name']} ({docs} docs, "
        f"{config['number_of_shards']} shard(s)), mix {cell['traffic']} "
        f"({', '.join(op['name'] for op in mix['operations'])}; rate scale "
        f"{mix.get('rate_scale', 1.0)}), "
        f"seed {args.seed}, {args.seconds}s, trace {args.trace}, "
        f"compile cache {cache_dir}")

    clock = S.CompileClock()
    t = time.perf_counter()
    corpus = C.corpus_of(config, docs, args.seed)
    mix = plan_queries(mix, corpus, args.seed, args.seconds)
    run = Run(mix=mix, peaks=peaks_all.get(device["kind"]), docs=docs,
              config=config)
    log(f"corpus: {docs} docs from seed {args.seed} in "
        f"{time.perf_counter() - t:.1f}s")
    with S.Served(config, args.seed, docs, log=log) as sv:
        t = time.perf_counter()
        sv.open(corpus)
        run.load_s = t_loader + time.perf_counter() - t
        sv.warm(mix, clock)
        run.compile_s = clock.seconds
        run.stats_before = sv.node_stats()
        compiles_before = clock.compiles
        gen, run.traced, trace_dir = drive(
            sv, mix, args.seed, args.seconds,
            min(float(mix.get("trace_seconds", 3.0)), 0.8 * args.seconds)
            if args.trace else 0.0)
        run.stats_after = sv.node_stats()
        in_window = clock.compiles - compiles_before
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs[:cell["chips"]])
    clock.stop()
    run.requests = gen["requests"]
    run.window_s = gen["t_close"] - gen["t_first_due"]
    setup_s = gen["t_first_due"] - T_PROCESS
    if args.keep_requests:
        os.makedirs(args.keep_requests, exist_ok=True)
        with open(os.path.join(
                args.keep_requests, f"{cell['name']}-{args.seed}-"
                f"{args.seconds:g}s-{os.getpid()}.requests.json"), "w") as f:
            json.dump({"seed": args.seed, "seconds": args.seconds,
                       "t_first_due": gen["t_first_due"], "requests": [
                           [r["op"], r["due"], r["sent"], r["done"], r["ok"],
                            r["took"]] for r in run.requests]}, f)
    log(f"window: {len(run.requests)} requests, {len(run.answered())} "
        f"answered in {run.window_s:.3f}s; generator late by p95 "
        f"{gen['late_ms']['p95']:.2f} ms, max {gen['late_ms']['max']:.2f} ms; "
        f"compilations inside the window: {in_window}; set-up "
        f"{setup_s:.1f}s (load {run.load_s:.1f}s, compile "
        f"{run.compile_s:.1f}s in {compiles_before} programs, cache hits "
        f"{clock.cache_hits}, misses {clock.cache_misses})")
    if gen["late_ms"]["p95"] > 5.0:
        log("the generator fell behind: p95 lateness over 5 ms")

    if trace_dir:
        t = time.perf_counter()
        xplane = T.find_xplane(trace_dir)
        run.trace = T.reduce_trace(T.load_xplane(xplane)) if xplane else None
        if args.keep_trace and xplane:
            os.makedirs(args.keep_trace, exist_ok=True)
            shutil.copy(xplane, os.path.join(
                args.keep_trace, f"{cell['name']}-{args.seed}.xplane.pb"))
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace: reduced in {time.perf_counter() - t:.1f}s: "
            + (json.dumps({k: v for k, v in run.trace.items()
                           if k != "breakdown"}) if run.trace
               else "no operation ran on a device"))

    # -- correct: the served answers against the plain reference ----------
    t = time.perf_counter()
    numbers = compare_all(corpus, mix, run.requests)
    attempted = len(run.requests)
    failed = attempted - len(run.answered())
    limits = config["limits"]
    correct = attempted > 0 and failed == 0 and C.judge(numbers, limits)
    log(f"compare: {attempted - failed} of {attempted} answers against the "
        f"reference in {time.perf_counter() - t:.1f}s")
    for r in [r for r in run.requests if not r["ok"]][:5]:
        log(f"failed: request {r['i']} {r['op']} status {r['status']} "
            f"{r.get('error', '')} {r.get('said', '')}")

    # -- metrics -----------------------------------------------------------
    metrics: dict = {}
    if args.trace:
        for m in layer_defs:
            if args.rehearse and m["source"] != "program_counter":
                continue
            value = load_reader(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    elif not args.rehearse:
        values = end_to_end(run.requests)
        values["setup_s"] = setup_s
        for m in e2e_defs:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    device["memory_peak_bytes"] = int(peak)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if args.rehearse:
        result["rehearsal"] = True
    if args.trace and run.trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = run.trace["breakdown"]
    compared = {k: {"value": numbers[k], "limit": limits[k]}
                for k in C.COMPARED}
    compared["failed"] = {"value": failed, "limit": 0}
    result["compared"] = compared
    print(json.dumps(result), flush=True)
    for k, v in compared.items():
        print(f"{tag} compared {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(f"{tag} correct: {correct}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
