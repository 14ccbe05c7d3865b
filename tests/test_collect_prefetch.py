"""A launch asks for its result's device-to-host copy
(`executor._start_fetch`), on the cold path as on the resident one, so
that a caller who launches several programs before collecting the first
finds the bytes on the host. What that must not change: the answers,
the breaker holds, the phases. What says that it engaged:
`_nodes/stats/dispatch` -> `collects`, `collect_parts` (the collect's
time up to the program's end and after it), and the benchmark's
`prefetched_collect_pct`.
"""

import copy
import http.client
import importlib.util
import json
import os
import time
import types

import jax
import numpy as np
import pytest

from elasticsearch_tpu.index.engine import Engine
from elasticsearch_tpu.index.mapping import MapperService
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.rest.server import RestServer
from elasticsearch_tpu.search import executor, shard_searcher
from elasticsearch_tpu.utils import profiler
from elasticsearch_tpu.utils.breaker import breaker_service
from elasticsearch_tpu.utils.errors import SearchTimeoutError
from elasticsearch_tpu.utils.settings import Settings

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
DAY = 86_400_000
PROPERTIES = {"body": {"type": "string"}, "tag": {"type": "keyword"},
              "n": {"type": "long"}, "at": {"type": "date"}}
PLANS = {
    "top_k": {"query": {"term": {"tag": "k1"}}, "size": 7},
    "sorted": {"query": {"match_all": {}}, "size": 9,
               "sort": [{"n": "desc"}]},
    "date_histogram": {"size": 0, "query": {"range": {"n": {"gte": 5}}},
                       "aggs": {"by_day": {"date_histogram": {
                           "field": "at", "interval": "day"}}}},
}


def doc(i: int) -> dict:
    return {"body": f"alpha w{i % 5} w{i % 11}", "tag": f"k{i % 3}",
            "n": (i * 7) % 41, "at": 894_000_000_000 + (i % 17) * DAY // 3}


@pytest.fixture(scope="module")
def reader():
    s = Settings({})
    m = MapperService(index_settings=s)
    m.put_type_mapping("doc", {"properties": PROPERTIES})
    eng = Engine("idx", 0, m, settings=s)
    for i in range(120):
        eng.index(f"d{i}", doc(i))
    eng.refresh()
    yield eng.acquire_searcher()
    eng.close()


def cold_launch_args(reader, monkeypatch, body: dict) -> tuple:
    """What the reader hands `execute_segment_async` for this body."""
    seen = []

    def recorder(*args, **kw):
        seen.append((args, dict(kw, bind=None)))
        return executor.execute_segment_async(*args, **kw)

    with monkeypatch.context() as mp:
        mp.setattr(shard_searcher, "execute_segment_async", recorder)
        reader.msearch([copy.deepcopy(body)])
    assert len(seen) == 1
    return seen[0]


def flat(result) -> list:
    """A collect's (top tuple, aggs) as a flat list of host arrays."""
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(result)]


# -- the launch ---------------------------------------------------------------

@pytest.mark.parametrize("plan", PLANS)
def test_a_cold_launch_asks_for_the_copy_once_and_before_its_collect(
        reader, monkeypatch, plan):
    args, kw = cold_launch_args(reader, monkeypatch, PLANS[plan])
    events = []
    start_fetch, collect = executor._start_fetch, executor._collect

    def spy_fetch(buf):
        events.append(("fetch", id(buf)))
        return start_fetch(buf)

    def spy_collect(out, layout, leaf):
        events.append(("collect", id(out)))
        return collect(out, layout, leaf)

    monkeypatch.setattr(executor, "_start_fetch", spy_fetch)
    monkeypatch.setattr(executor, "_collect", spy_collect)
    launches = executor.launch_counts()["unfused"]
    out, layout, n_real = executor.execute_segment_async(*args, **kw)
    assert events == [("fetch", id(out))]
    assert executor.launch_counts()["unfused"] == launches + 1
    assert layout["_prefetched"] is True
    parts = executor.collect_parts()
    executor.collect_segment_result(out, layout, n_real)
    assert events == [("fetch", id(out)), ("collect", id(out))]
    # the one collect is timed once in each of its two parts
    after = executor.collect_parts()
    assert [after[p]["count"] - parts[p]["count"]
            for p in ("compute", "copy")] == [1, 1]


@pytest.mark.parametrize("plan", PLANS)
def test_the_result_is_the_same_bytes_with_the_copy_and_without(
        reader, monkeypatch, plan):
    args, kw = cold_launch_args(reader, monkeypatch, PLANS[plan])
    live = flat(executor.collect_segment_result(
        *executor.execute_segment_async(*args, **kw)))
    with monkeypatch.context() as mp:
        mp.setattr(executor, "_start_fetch", lambda buf: {})
        out, layout, n_real = executor.execute_segment_async(*args, **kw)
        assert "_prefetched" not in layout
        stubbed = flat(executor.collect_segment_result(out, layout, n_real))
    assert len(live) == len(stubbed) and len(live) >= 5
    for a, b in zip(live, stubbed):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()
    assert any(a.size and a.any() for a in live)


class _NoCopy:
    """A launch's result whose runtime offers no asynchronous copy."""


class _RefusedCopy:
    def copy_to_host_async(self):
        raise RuntimeError("Disallowed device-to-host transfer")


@pytest.mark.parametrize("buf", [_NoCopy(), _RefusedCopy()],
                         ids=["no_such_method", "refused"])
def test_a_copy_that_cannot_start_is_counted_as_not_prefetched(buf):
    fields = executor._start_fetch(buf)
    assert fields == {"_prefetched": False}
    before, parts = executor.collect_counts(), executor.collect_parts()
    with profiler.phase("collect", weight=2) as leaf:
        assert executor._collect(np.arange(3), fields, leaf).tolist() \
            == [0, 1, 2]
        assert leaf.name == "unpack"
    after = executor.collect_counts()
    assert after["total"] == before["total"] + 1
    assert after["prefetched"] == before["prefetched"]
    # weighted like the phase: two searches waited through it
    moved = {p: executor.collect_parts()[p]["count"] - parts[p]["count"]
             for p in ("compute", "copy")}
    assert moved == {"compute": 2, "copy": 2}


def test_the_pack_launch_asks_for_the_copy_too():
    """Base + delta in one program (`execute_pack_async`): the other
    cold launch site."""
    s = Settings({"index.streaming.delta": True})
    m = MapperService(index_settings=s)
    m.put_type_mapping("doc", {"properties": PROPERTIES})
    eng = Engine("idx", 0, m, settings=s)
    try:
        for i in range(40):
            eng.index(f"d{i}", doc(i))
        eng.refresh()
        assert eng.compact()
        for i in range(40, 55):
            eng.index(f"d{i}", doc(i))
        eng.refresh()
        before = executor.collect_counts()
        pend = eng.acquire_searcher().msearch_submit(
            [{"query": {"match": {"body": "alpha w3"}}, "size": 6}])
        layouts = [lay for g in pend.groups for _o, lay, _n in g["pending"]]
        assert [lay.get("pack") for lay in layouts] == [True]
        assert layouts[0]["_prefetched"] is True
        assert pend.finish()[0]["hits"]["total"] == 55
        after = executor.collect_counts()
        assert (after["total"] - before["total"],
                after["prefetched"] - before["prefetched"]) == (1, 1)
    finally:
        eng.close()


# -- the breaker --------------------------------------------------------------

def test_a_pend_abandoned_by_its_deadline_releases_every_hold(
        reader, monkeypatch):
    """Launched, the copies in flight, and then never collected: the
    deadline passes before `finish`, which lets go of every hold."""
    started = []
    start_fetch = executor._start_fetch
    monkeypatch.setattr(executor, "_start_fetch",
                        lambda buf: started.append(buf) or start_fetch(buf))
    req = breaker_service().breaker("request")
    used = req.used
    collects = executor.collect_counts()["total"]
    bodies = [copy.deepcopy(PLANS[p]) for p in ("top_k", "sorted")]
    pend = reader.msearch_submit(bodies, deadline=time.monotonic() + 0.05)
    layouts = [lay for g in pend.groups for _o, lay, _n in g["pending"]]
    assert len(layouts) == len(started) == pend.dispatch_count == 2
    assert all(lay["_prefetched"] for lay in layouts)
    holds = [lay["_breaker_hold"] for lay in layouts]
    assert all(h.bytes > 0 for h in holds) and req.used > used
    time.sleep(0.06)
    with pytest.raises(SearchTimeoutError):
        pend.finish()
    assert [h.bytes for h in holds] == [0, 0]
    assert req.used == used
    assert executor.collect_counts()["total"] == collects
    # and the reader still serves
    assert reader.msearch([copy.deepcopy(PLANS["top_k"])])[0][
        "hits"]["total"] == 40


def test_a_device_error_at_the_program_s_end_releases_every_hold(
        reader, monkeypatch):
    """The collect waits for the program (`block_until_ready`) before
    it copies: a device error raised there leaves `finish` with its own
    type, through the same exit, which lets go of every hold."""
    req = breaker_service().breaker("request")
    used = req.used
    bodies = [copy.deepcopy(PLANS[p]) for p in ("top_k", "sorted")]
    pend = reader.msearch_submit(bodies)
    layouts = [lay for g in pend.groups for _o, lay, _n in g["pending"]]
    holds = [lay["_breaker_hold"] for lay in layouts]
    assert len(holds) == 2 and all(h.bytes > 0 for h in holds)

    def fails(x):
        raise jax.errors.JaxRuntimeError(
            "INTERNAL: the program failed on the device")

    parts = executor.collect_parts()
    with monkeypatch.context() as mp:
        mp.setattr(jax, "block_until_ready", fails)
        with pytest.raises(jax.errors.JaxRuntimeError):
            pend.finish()
    assert [h.bytes for h in holds] == [0, 0]
    assert req.used == used
    # the collect that failed is counted once for each of the reader
    # call's two bodies, like its phase, and its time is all `compute`
    after = executor.collect_parts()
    assert [after[p]["count"] - parts[p]["count"]
            for p in ("compute", "copy")] == [2, 2]
    assert after["copy"]["sum"] == parts["copy"]["sum"]
    assert reader.msearch([copy.deepcopy(PLANS["top_k"])])[0][
        "hits"]["total"] == 40


# -- what says that it engaged -------------------------------------------------

class Served:
    def __init__(self, shards: int, data_path: str):
        self.shards = shards
        self.node = Node({"node.name": "prefetch-0", "path.data": data_path})
        self.server = RestServer(self.node, "127.0.0.1", 0).start()
        self.conn = http.client.HTTPConnection(
            self.server.host, self.server.port, timeout=120)
        self.call("PUT", "/logs", {
            "settings": {"number_of_shards": shards,
                         "number_of_replicas": 0},
            "mappings": {"doc": {"properties": PROPERTIES}}})
        lines = []
        for i in range(300):
            lines += [json.dumps({"index": {"_id": f"d{i}",
                                            "_type": "doc"}}),
                      json.dumps(doc(i))]
        assert not self.call("POST", "/logs/_bulk",
                             ("\n".join(lines) + "\n").encode())["errors"]
        self.call("POST", "/logs/_refresh")

    def stop(self) -> None:
        self.conn.close()
        self.server.stop()
        self.node.close()

    def call(self, method: str, path: str, body=None):
        data = body if isinstance(body, bytes) or body is None \
            else json.dumps(body).encode()
        self.conn.request(method, path, body=data,
                          headers={"Content-Type": "application/json"})
        r = self.conn.getresponse()
        out = json.loads(r.read())
        assert r.status == 200, out
        return out

    def dispatch_stats(self) -> dict:
        stats = self.call("GET", "/_nodes/stats/dispatch")
        return next(iter(stats["nodes"].values()))["dispatch"]


@pytest.fixture(scope="module", params=[1, 5], ids=["1shard", "5shard"])
def served(request, tmp_path_factory):
    sv = Served(request.param,
                str(tmp_path_factory.mktemp(f"prefetch{request.param}")))
    yield sv
    sv.stop()


def moved(before: dict, after: dict) -> dict:
    return {
        "launches": sum(after["launches"].values())
        - sum(before["launches"].values()),
        "total": after["collects"]["total"] - before["collects"]["total"],
        "prefetched": after["collects"]["prefetched"]
        - before["collects"]["prefetched"],
        "compute_count": after["collect_parts"]["compute"]["count"]
        - before["collect_parts"]["compute"]["count"],
        "copy_count": after["collect_parts"]["copy"]["count"]
        - before["collect_parts"]["copy"]["count"],
        "parts_sum": sum(after["collect_parts"][p]["sum"]
                         - before["collect_parts"][p]["sum"]
                         for p in ("compute", "copy")),
        "collect_phase": after["phases"]["collect"]["count"]
        - before["phases"].get("collect", {"count": 0})["count"],
        "collect_sum": after["phases"]["collect"]["sum"]
        - before["phases"].get("collect", {"sum": 0.0})["sum"],
    }


def test_over_rest_every_collect_found_its_copy_started(served):
    for body in PLANS.values():
        served.call("POST", "/logs/_search", body)   # every plan compiled
    before = served.dispatch_stats()
    t0 = time.perf_counter()
    n = 0
    for _ in range(4):
        for body in PLANS.values():
            r = served.call("POST", "/logs/_search", body)
            assert r["_shards"]["failed"] == 0 and not r["timed_out"]
            n += 1
    wall = time.perf_counter() - t0
    m = moved(before, served.dispatch_stats())
    assert m["launches"] == n * served.shards
    assert m["total"] == m["prefetched"] == m["compute_count"] \
        == m["copy_count"] == m["launches"]
    assert m["collect_phase"] == m["total"]
    # the two parts tile the phase: host time of the searches' own
    # rounds, on one thread
    assert m["parts_sum"] == pytest.approx(m["collect_sum"], rel=1e-9)
    assert 0 < m["parts_sum"] < wall
    # beside `phases`, never inside: `collects` is no time, and the
    # parts are time `collect` counts already
    phases = served.dispatch_stats()["phases"]
    assert not {"collects", "collect_parts", "compute", "copy"} & set(phases)


def test_an_msearch_batch_collects_what_it_launched(served):
    before = served.dispatch_stats()
    head = json.dumps({"index": "logs"})
    r = served.call("POST", "/_msearch", "".join(
        f"{head}\n{json.dumps(b)}\n" for b in PLANS.values()).encode())
    assert [s["status"] for s in r["responses"]] == [200] * len(PLANS)
    m = moved(before, served.dispatch_stats())
    assert m["launches"] == len(PLANS) * served.shards
    assert m["total"] == m["prefetched"] == m["launches"]
    # weighted like the phase: the reader call serves every body
    assert m["compute_count"] == m["copy_count"] == m["collect_phase"] \
        == m["total"] * len(PLANS)
    assert m["parts_sum"] == pytest.approx(m["collect_sum"], rel=1e-9)


# -- the benchmark's reader ---------------------------------------------------

def _reader_module():
    spec = importlib.util.spec_from_file_location(
        "prefetched_collect_pct", os.path.join(
            BENCH, "layer_metrics", "prefetched_collect_pct.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(before, after):
    def section(collects):
        return {"dispatch": {} if collects is None
                else {"collects": collects}}
    return types.SimpleNamespace(stats_before=section(before),
                                 stats_after=section(after))


@pytest.mark.parametrize("before,after,reads", [
    ({"total": 10, "prefetched": 10}, {"total": 60, "prefetched": 60}, 100.0),
    ({"total": 10, "prefetched": 0}, {"total": 50, "prefetched": 10}, 25.0),
    ({"total": 7, "prefetched": 0}, {"total": 19, "prefetched": 0}, 0.0),
    ({"total": 7, "prefetched": 7}, {"total": 7, "prefetched": 7}, None),
    (None, None, None),
], ids=["all", "a_quarter", "none", "no_collect_in_the_window",
        "a_program_without_the_counter"])
def test_the_benchmark_reads_the_window_s_share(before, after, reads):
    mod = _reader_module()
    assert mod.read(_run(before, after)) == reads


def test_the_reader_is_the_one_benchmark_json_declares():
    mod = _reader_module()
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        entries = [m for m in json.load(f)["per_layer"]
                   if m["name"] == "prefetched_collect_pct"]
    # wherever it stands, and in every cell: no `workloads` key
    assert entries == [{"name": mod.NAME, "unit": mod.UNIT,
                        "better": mod.BETTER, "source": mod.SOURCE,
                        "layer": mod.LAYER, "moves": mod.MOVES}]


def test_the_served_stats_feed_the_reader(served):
    before = {"dispatch": served.dispatch_stats()}
    served.call("POST", "/logs/_search", PLANS["top_k"])
    after = {"dispatch": served.dispatch_stats()}
    run = types.SimpleNamespace(stats_before=before, stats_after=after)
    assert _reader_module().read(run) == 100.0
