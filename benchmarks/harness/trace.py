"""From a profiler trace to numbers: device busy union, idle share,
time per program, the operations that took most time, and the idle gaps
named by what the host was doing.

A trace is held here in a plain form, so that the reduction can be
tested on a small recorded one (`benchmarks/tests/recorded_trace.json`):

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns], ...]}]}]}

`load_xplane` makes that form from the `.xplane.pb` the JAX profiler
writes, keeping the device planes whole and of the host plane only the
spans the reduction reads.

What a v5e trace looks like (looked at by hand, PR 24): one plane per
chip named `/device:TPU:<n>`, with the lines `XLA Modules` (one event
per execution of a jitted program, named `jit_<function>(<fingerprint>)`),
`XLA Ops` (one event per operation inside it, named by its whole HLO
text; a `while` or a `conditional` spans the operations of its body, so
the events nest and only their union is busy time) and `Steps`; host
threads are lines of the plane `/host:CPU`, where a `TraceAnnotation`
shows under its own name. All planes share one clock. The times under
`device_ops` are inclusive: an outer operation counts its body's.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench:window"
NAME_CHARS = 96
HOST_SPAN_PREFIXES = ("query_phase:", "bench:")
# the jitted programs of search/executor.py that score, match and
# aggregate a segment or a pack; the reduction finds them by these
# names because the program gives its device work no other
SCORING_PROGRAMS = ("_segment_program", "_pack_program", "_resident_",
                    "_tiered_")


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def load_xplane(path: str) -> dict:
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            # an op's name is its whole HLO text: the head tells it apart
            events = [[e.name[:NAME_CHARS], int(e.start_ns),
                       int(e.duration_ns)]
                      for e in line.events
                      if device or e.name.startswith(HOST_SPAN_PREFIXES)]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def union_ns(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Sorted, merged [start, end) intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(events, lo, hi):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def program_name(event_name: str) -> str:
    """`jit__segment_program_packed(1234)` -> `_segment_program_packed`."""
    name = event_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def reduce_trace(trace: dict) -> dict | None:
    """The numbers of the traced window, or None where no operation ran
    on a device (a CPU rehearsal has no device plane).

    The window is the `bench:window` span the harness puts around the
    traced part of the run; without it, first to last device event.
    `busy_s` is the union of the `XLA Ops` intervals, averaged over the
    device planes that ran anything.
    """
    host_spans = []
    for plane in trace["planes"]:
        if plane["name"] == HOST_PLANE:
            for line in plane["lines"]:
                host_spans += line["events"]
    window = [(s, s + d) for name, s, d in host_spans if name == WINDOW_SPAN]
    devices = []
    for plane in trace["planes"]:
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        by_line = {ln["name"]: ln["events"] for ln in plane["lines"]}
        if by_line.get(OPS_LINE):
            devices.append((plane["name"], by_line[OPS_LINE],
                            by_line.get(MODULES_LINE, [])))
    if not devices:
        return None
    if window:
        lo, hi = window[0]
    else:
        lo = min(s for _n, ops, _m in devices for _e, s, _d in ops)
        hi = max(s + d for _n, ops, _m in devices for _e, s, d in ops)
    busy, op_time, prog_time, prog_runs = [], {}, {}, {}
    gaps: list[tuple[int, int]] = []
    for _name, ops, modules in devices:
        ops = list(_clip(ops, lo, hi))
        merged = union_ns([(a, b) for _e, a, b in ops])
        busy.append(sum(b - a for a, b in merged))
        edge = lo
        for a, b in merged + [(hi, hi)]:
            if a > edge:
                gaps.append((edge, a))
            edge = max(edge, b)
        for e, a, b in ops:
            op_time[e] = op_time.get(e, 0) + (b - a)
        for e, a, b in _clip(modules, lo, hi):
            p = program_name(e)
            prog_time[p] = prog_time.get(p, 0) + (b - a)
            prog_runs[p] = prog_runs.get(p, 0) + 1
    n = len(devices)
    scoring = [p for p in prog_time
               if any(tag in p for tag in SCORING_PROGRAMS)]
    named = sorted((s, s + d, nm) for nm, s, d in host_spans
                   if nm.startswith("query_phase:"))
    starts = [s for s, _e, _nm in named]
    longest = max((e - s for s, e, _nm in named), default=0)
    gap_by: dict[str, int] = {}
    for a, b in gaps:
        # the span that covers most of the gap names it
        best, cover = "no span", 0
        for s, e, nm in named[bisect.bisect_left(starts, a - longest):
                              bisect.bisect_right(starts, b)]:
            c = min(b, e) - max(a, s)
            if c > cover:
                best, cover = nm, c
        gap_by[best] = gap_by.get(best, 0) + (b - a)

    def top(d: dict) -> list:
        return [[k, v / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {
        "devices": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "scoring_s": sum(prog_time[p] for p in scoring) / n / 1e9,
        "scoring_runs": sum(prog_runs[p] for p in scoring) / n,
        "programs": {p: {"seconds": prog_time[p] / n / 1e9,
                         "runs": prog_runs[p] / n} for p in prog_time},
        "longest_gap_s": max((b - a for a, b in gaps), default=0) / 1e9,
        "breakdown": {"device_ops": top(op_time), "idle_gaps": top(gap_by)},
    }
