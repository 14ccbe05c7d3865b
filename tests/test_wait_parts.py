"""A search's `scheduler_wait` in three parts and its `collect` in two,
timed inside the program (`search/dispatch.py`, `executor._collect`):
the parts tile their phase in every window, a search parked behind a
round is told apart from one queued behind it, the parts stand beside
`phases` and never in it, a sleeping caller is a `wait:scheduler` span,
and the benchmark's five readers take the window's delta of them."""

import glob
import http.client
import importlib.util
import json
import os
import threading
import time
import types

import pytest

from elasticsearch_tpu.node import Node
from elasticsearch_tpu.rest.server import RestServer
from elasticsearch_tpu.search.dispatch import WAIT_PARTS, DispatchScheduler
from elasticsearch_tpu.utils import profiler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COLLECT_PARTS = ("compute", "copy")
SPANS = ("rest_parse", "resolve", "bind", "dispatch", "collect", "unpack",
         "fetch", "reduce", "finish", "respond")
WAITS = ("pool_wait", "scheduler_wait")
# reader -> (the `_nodes/stats/dispatch` section and part it reads, layer)
READERS = {
    "scheduler_parked_ms": ("scheduler_wait", "parked",
                            "dispatch scheduler and shard searcher"),
    "scheduler_queued_ms": ("scheduler_wait", "queued",
                            "dispatch scheduler and shard searcher"),
    "scheduler_woken_ms": ("scheduler_wait", "woken",
                           "dispatch scheduler and shard searcher"),
    "collect_compute_ms": ("collect_parts", "compute", "executor"),
    "collect_copy_ms": ("collect_parts", "copy", "executor"),
}
DOCS = 600
QUERY = {"query": {"term": {"k": "v1"}}, "size": 5}


class Served:
    """A node with one index of `shards` shards behind a RestServer,
    placed one shard a device where `devices` is `shards` (one program
    over the devices a round: the mesh reader's collect)."""

    def __init__(self, shards: int, devices: int, data_path: str):
        self.placed = devices > 1
        self.node = Node({"node.name": f"parts-{shards}",
                          "path.data": data_path})
        self.node.create_index("p", settings={
            "index.number_of_shards": shards,
            "index.routing.allocation.devices": devices})
        self.node.bulk([("index", {"_index": "p", "_id": str(i), "doc": {
            "k": f"v{i % 3}", "n": i}}) for i in range(DOCS)])
        self.node.refresh("p")
        self.server = RestServer(self.node, "127.0.0.1", 0).start()
        self.conn = http.client.HTTPConnection(
            self.server.host, self.server.port, timeout=60)

    def call(self, method: str, path: str, body=None) -> dict:
        self.conn.request(method, path,
                          body=None if body is None else json.dumps(body),
                          headers={"Content-Type": "application/json"})
        r = self.conn.getresponse()
        out = json.loads(r.read())
        assert r.status == 200, out
        return out

    def dispatch_stats(self) -> dict:
        stats = self.call("GET", "/_nodes/stats/dispatch")
        return next(iter(stats["nodes"].values()))["dispatch"]

    def searches(self, callers: int, each: int) -> None:
        """`callers` connections at once, each sending `each` searches
        one after another."""
        def caller():
            conn = http.client.HTTPConnection(self.server.host,
                                              self.server.port, timeout=60)
            try:
                for _ in range(each):
                    conn.request("POST", "/p/_search",
                                 body=json.dumps(QUERY),
                                 headers={"Content-Type": "application/json"})
                    r = conn.getresponse()
                    assert r.status == 200 and r.read()
            finally:
                conn.close()
        threads = [threading.Thread(target=caller) for _ in range(callers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)

    def close(self) -> None:
        self.conn.close()
        self.server.stop()
        self.node.close()


@pytest.fixture(scope="module", params=[(1, 1), (5, 1), (4, 4)],
                ids=["1shard", "5shard", "4shard-placed"])
def served(request, tmp_path_factory):
    shards, devices = request.param
    sv = Served(shards, devices,
                str(tmp_path_factory.mktemp(f"parts{shards}")))
    sv.searches(1, 2)                            # every program compiled
    yield sv
    sv.close()


def moved(before: dict, after: dict, field: str = "sum") -> dict:
    """How far the phases and the parts moved: {name: delta} with the
    parts as `scheduler_wait.<part>` and `collect_parts.<part>`."""
    def flat(section: dict) -> dict:
        out = {n: e[field] for n, e in section["phases"].items()}
        for key in ("scheduler_wait", "collect_parts"):
            out.update({f"{key}.{n}": e[field]
                        for n, e in section[key].items()})
        return out
    b = flat(before)
    return {n: v - b.get(n, 0) for n, v in flat(after).items()}


# -- the parts tile their phase ------------------------------------------------

@pytest.mark.parametrize("callers", [1, 8], ids=["sequential", "concurrent"])
def test_the_three_parts_tile_the_scheduler_wait(served, callers):
    before = served.dispatch_stats()
    served.searches(callers, 12)
    after = served.dispatch_stats()
    seconds, counts = moved(before, after), moved(before, after, "count")
    parts = [seconds["scheduler_wait." + p] for p in WAIT_PARTS]
    assert sum(parts) == pytest.approx(seconds["scheduler_wait"], rel=1e-9)
    assert min(parts) >= 0.0, parts
    # once a search, like the wait, whatever the fan-out
    assert {counts["scheduler_wait." + p] for p in WAIT_PARTS} \
        == {counts["scheduler_wait"]} == {callers * 12}


@pytest.mark.parametrize("callers", [1, 8], ids=["sequential", "concurrent"])
def test_the_two_parts_tile_the_collect(served, callers):
    before = served.dispatch_stats()
    served.searches(callers, 12)
    after = served.dispatch_stats()
    seconds, counts = moved(before, after), moved(before, after, "count")
    parts = [seconds["collect_parts." + p] for p in COLLECT_PARTS]
    # split at the program's end out of the phase's own readings, so
    # they sum to it and not only to within 3%
    assert sum(parts) == pytest.approx(seconds["collect"], rel=1e-9)
    assert min(parts) >= 0.0, parts
    # weighted like the phase: a reader call serving n searches adds n
    assert {counts["collect_parts." + p] for p in COLLECT_PARTS} \
        == {counts["collect"]}
    # a reader call a shard, or one for all shards of a placed index
    assert counts["collect"] >= callers * 12
    assert (after["mesh"]["searches"] > before["mesh"]["searches"]) \
        == served.placed


def test_the_phases_gain_no_name(served):
    before = served.dispatch_stats()
    served.searches(4, 5)
    after = served.dispatch_stats()
    counts = moved(before, after, "count")
    assert {n for n, c in counts.items() if c and "." not in n} \
        == set(SPANS + WAITS + ("request",))
    parts = ({"scheduler_wait." + p for p in WAIT_PARTS} | set(WAIT_PARTS)
             | set(COLLECT_PARTS) | {"collect_parts"})
    assert not set(after["phases"]) & parts


# -- a search parked behind a round --------------------------------------------

class SlowReader:
    """A reader whose first round keeps its leader until a second batch
    is pending, and `seconds` after that; later rounds take `seconds`.
    `rounds` holds each finish's (start, end) readings."""

    def __init__(self, scheduler: DispatchScheduler, seconds: float):
        self.scheduler = scheduler
        self.seconds = seconds
        self.started = threading.Event()
        self.rounds: list[tuple[float, float]] = []

    def msearch_submit(self, bodies, with_partials=False, **kw):
        return SlowPend(self, bodies)


class SlowPend:
    dispatch_count = 1

    def __init__(self, reader: SlowReader, bodies: list):
        self.reader = reader
        self.bodies = bodies
        self.group_sizes = [len(bodies)]

    def finish(self) -> list:
        t0 = time.perf_counter()
        if not self.reader.rounds:
            self.reader.started.set()
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                with self.reader.scheduler._mx:
                    if self.reader.scheduler._pending:
                        break
                time.sleep(0.0005)
        time.sleep(self.reader.seconds)
        self.reader.rounds.append((t0, time.perf_counter()))
        return [{"body": b} for b in self.bodies]


def park() -> tuple:
    """Batch `a` leads a round of about 20 ms; batch `b` arrives during
    it, and no third batch arrives."""
    sched = DispatchScheduler()
    reader = SlowReader(sched, 0.02)
    a, b = sched.batch(), sched.batch()
    ids = (profiler.next_request_id(), profiler.next_request_id())
    a.submit(reader, {"q": "a"}, request=ids[0])
    b.submit(reader, {"q": "b"}, request=ids[1])
    ta = threading.Thread(target=a.dispatch)
    ta.start()
    assert reader.started.wait(10)
    tb = threading.Thread(target=b.dispatch)
    tb.start()
    for t in (ta, tb):
        t.join(timeout=10)
    assert not ta.is_alive() and not tb.is_alive()
    assert [j.result() for j in a.jobs + b.jobs] == [
        {"body": {"q": "a"}}, {"body": {"q": "b"}}]
    return a, b, reader, ids


def test_a_search_arriving_during_a_round_is_parked_until_the_poll():
    """The round's leader returns once its own batch is done; the second
    caller fails its 1 ms re-check while the round runs and sleeps out
    the 50 ms poll: parked from the round's end to then (what S3 takes
    towards 0), queued for the rest of the round ahead of it."""
    a, b, reader, _ids = park()
    (_s0, first_end), (second_start, _e1) = reader.rounds
    assert b.t_entry < first_end <= second_start
    assert b.parts["parked"] >= 0.015, b.parts
    assert b.parts["queued"] == pytest.approx(first_end - b.t_entry,
                                              abs=0.005)
    # its own caller led its round: no one had to wake it
    assert 0.0 <= b.parts["woken"] < 0.005
    # the first caller led at once and waited for nothing
    assert all(0.0 <= v < 0.005 for v in a.parts.values()), a.parts


def test_the_parts_of_each_batch_sum_to_its_recorded_wait():
    before = profiler.phase_stats().get("scheduler_wait", {"sum": 0.0})
    a, b, _reader, _ids = park()
    wait = profiler.phase_stats()["scheduler_wait"]["sum"] - before["sum"]
    assert sum(a.parts.values()) + sum(b.parts.values()) \
        == pytest.approx(wait, rel=1e-9)
    assert set(a.parts) == set(b.parts) == set(WAIT_PARTS)


def host_spans(trace_dir: str, prefix: str) -> list:
    import jax
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    return [(e.name, e.duration_ns / 1e9, dict(e.stats))
            for plane in jax.profiler.ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if e.name.startswith(prefix)]


def test_a_sleeping_caller_is_a_wait_span_under_its_request(tmp_path):
    """`wait:scheduler`, outside the `query_phase:` prefix: the parked
    caller's sleeps, the longest of them the poll; the caller that led
    its own round never slept."""
    profiler.start(str(tmp_path / "trace"))
    try:
        _a, b, _reader, ids = park()
    finally:
        profiler.stop()
    spans = host_spans(str(tmp_path / "trace"), "wait:")
    assert spans and {s[0] for s in spans} == {"wait:scheduler"}
    assert {s[2]["request"] for s in spans} == {ids[1]}
    assert max(s[1] for s in spans) >= 0.015
    assert sum(s[1] for s in spans) <= sum(b.parts.values()) + 0.001


# -- the benchmark's readers ---------------------------------------------------

def reader_module(name: str):
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name, os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def a_run(before: dict, after: dict, answered: int):
    return types.SimpleNamespace(
        stats_before={"dispatch": before}, stats_after={"dispatch": after},
        answered=lambda: [{"ok": True}] * answered)


def timers(section: str, part: str, seconds: float, count: int) -> dict:
    return {section: {part: {"count": count, "sum": seconds,
                             "mean": seconds / count if count else 0.0}}}


@pytest.mark.parametrize("name", READERS)
def test_a_reader_takes_the_window_s_delta_over_the_searches(name):
    section, part, _layer = READERS[name]
    run = a_run(timers(section, part, 1.25, 100),
                timers(section, part, 1.75, 300), answered=250)
    assert reader_module(name).read(run) == pytest.approx(2.0, rel=1e-12)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_in_a_program_without_the_timer(name):
    """The parent of the PR that brought the parts has neither section:
    None, and no exception; nor where no search was answered."""
    section, part, _layer = READERS[name]
    mod = reader_module(name)
    assert mod.read(a_run({}, {}, answered=10)) is None
    assert mod.read(a_run({section: {}}, {section: {}}, answered=10)) is None
    assert mod.read(a_run(timers(section, part, 1.0, 1),
                          timers(section, part, 2.0, 2), answered=0)) is None


@pytest.mark.parametrize("name", READERS)
def test_the_reader_is_the_one_benchmark_json_declares(name):
    mod = reader_module(name)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    # in every cell: no `workloads` key
    assert entries == [{"name": mod.NAME, "unit": mod.UNIT,
                        "better": mod.BETTER, "source": mod.SOURCE,
                        "layer": mod.LAYER, "moves": mod.MOVES}]
    assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.MOVES) == (
        "ms", "lower", "program_span", "search_p50_ms")
    assert mod.LAYER == READERS[name][2]


def test_the_served_stats_feed_the_readers(served):
    """Over the same window, the scheduler's three readers sum to the
    reader of `scheduler_wait` and the collect's two to that of
    `collect`, as `scheduler_wait_ms` and `device_wait_ms` read them."""
    n = 24
    before = served.dispatch_stats()
    served.searches(4, n // 4)
    after = served.dispatch_stats()
    run = a_run(before, after, answered=n)
    got = {name: reader_module(name).read(run) for name in READERS}
    assert all(v is not None and v >= 0.0 for v in got.values()), got

    def phase_ms(name: str) -> float:
        return 1e3 * (after["phases"][name]["sum"]
                      - before["phases"][name]["sum"]) / n
    assert got["scheduler_parked_ms"] + got["scheduler_queued_ms"] \
        + got["scheduler_woken_ms"] == pytest.approx(
            phase_ms("scheduler_wait"), rel=1e-9)
    assert got["collect_compute_ms"] + got["collect_copy_ms"] \
        == pytest.approx(phase_ms("collect"), rel=0.03)
