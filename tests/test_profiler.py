"""Phase timers and phase spans of the served search (utils/profiler.py):
always-on timers read through `GET /_nodes/stats/dispatch`, and the same
phases as `query_phase:<name>` spans in REST-driven jax.profiler traces
of live search traffic."""

import glob
import http.client
import json
import os
import textwrap
import threading

import pytest

from elasticsearch_tpu.node import Node
from elasticsearch_tpu.rest.server import RestServer
from elasticsearch_tpu.utils import profiler

SPANS = ("rest_parse", "resolve", "bind", "dispatch", "collect", "unpack",
         "fetch", "reduce", "finish", "respond")
WAITS = ("pool_wait", "scheduler_wait")
DOCS = 4096
QUERY = {"query": {"term": {"k": "v1"}}}


class Served:
    """A node with one 4,096-doc shard behind a RestServer."""

    def __init__(self, data_path: str):
        self.node = Node({"node.name": "prof-0", "path.data": data_path,
                          "index.number_of_shards": 1})
        self.node.create_index("p")
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

    def search(self) -> dict:
        return self.call("POST", "/p/_search", QUERY)

    def dispatch_stats(self) -> dict:
        stats = self.call("GET", "/_nodes/stats/dispatch")
        return next(iter(stats["nodes"].values()))["dispatch"]

    def close(self) -> None:
        self.conn.close()
        self.server.stop()
        self.node.close()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    sv = Served(str(tmp_path_factory.mktemp("prof")))
    assert sv.search()["hits"]["total"] == len(range(1, DOCS, 3))
    yield sv
    sv.close()


def moved(before: dict, after: dict, field: str = "sum") -> dict:
    return {name: entry[field] - before.get(name, {field: 0})[field]
            for name, entry in after.items()}


# -- the timers, with no trace active ---------------------------------------

@pytest.fixture(scope="module")
def one_search(served):
    """The section of `_nodes/stats/dispatch` either side of one search."""
    assert not profiler.status()["tracing"]
    before = served.dispatch_stats()
    served.search()
    return before, served.dispatch_stats()


@pytest.mark.parametrize("name", SPANS + WAITS + ("request",))
def test_one_rest_search_counts_each_phase_once(one_search, name):
    before, after = one_search
    assert moved(before["phases"], after["phases"], "count")[name] == 1
    assert moved(before["phases"], after["phases"])[name] > 0
    entry = after["phases"][name]
    assert entry["mean"] == pytest.approx(entry["sum"] / entry["count"])


def test_one_rest_search_is_one_launch_of_its_backend(one_search):
    from elasticsearch_tpu.search.executor import LAUNCH_BACKENDS
    before, after = one_search
    assert set(after["launches"]) == set(LAUNCH_BACKENDS)
    # one program, under the one backend that ran it
    assert sorted(after["launches"][b] - before["launches"][b]
                  for b in LAUNCH_BACKENDS) == [0, 0, 0, 0, 1]
    # the keys the benchmark's harness reads stay as they were
    assert after["batches_dispatched"] - before["batches_dispatched"] == 1


def test_the_phases_tile_the_request(served):
    """The twelve phases and waits account for 90-100% of the `request`
    timer: nothing is counted twice, and little of a search runs
    outside any of them."""
    before = served.dispatch_stats()["phases"]
    for _ in range(200):
        served.search()
    delta = moved(before, served.dispatch_stats()["phases"])
    assert set(delta) >= set(SPANS + WAITS)
    share = sum(delta[n] for n in SPANS + WAITS) / delta["request"]
    assert 0.90 <= share <= 1.0, (share, delta)


def test_the_phases_tile_concurrent_requests_too(served):
    """Eight callers at once: searches share rounds and reader calls,
    whose phases weigh the searches they serve, so the tiling holds."""
    def caller():
        sv = http.client.HTTPConnection(served.server.host,
                                        served.server.port, timeout=60)
        try:
            for _ in range(25):
                sv.request("POST", "/p/_search", body=json.dumps(QUERY),
                           headers={"Content-Type": "application/json"})
                r = sv.getresponse()
                assert r.status == 200 and r.read()
        finally:
            sv.close()
    before = served.dispatch_stats()
    threads = [threading.Thread(target=caller) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    after = served.dispatch_stats()
    delta = moved(before["phases"], after["phases"])
    assert moved(before["phases"], after["phases"], "count")["request"] \
        == 200
    share = sum(delta[n] for n in SPANS + WAITS) / delta["request"]
    assert 0.90 <= share <= 1.0, (share, delta)


def test_fetch_time_is_a_measurement(served):
    """`indices.search.fetch_time_in_millis` is fed the searches' `fetch`
    phase (it was fed 0.0)."""
    def fetch_stats():
        s = served.call("GET", "/p/_stats")["_all"]["total"]["search"]
        return s["fetch_total"], s["fetch_time_in_millis"]
    n0, _ms0 = fetch_stats()
    before = served.dispatch_stats()["phases"]["fetch"]["sum"]
    # enough of them that their fetch phases pass a whole millisecond
    while served.dispatch_stats()["phases"]["fetch"]["sum"] - before < 2e-3:
        served.search()
    n1, ms1 = fetch_stats()
    assert n1 > n0 and ms1 >= 1
    op = served.node.indices["p"].op_stats
    assert op.fetch_time_ms <= 1e3 * (
        served.dispatch_stats()["phases"]["fetch"]["sum"])


def test_an_in_process_search_gets_its_id_at_the_node(served):
    before = served.dispatch_stats()["phases"]
    served.node.search("p", QUERY)
    counts = moved(before, served.dispatch_stats()["phases"], "count")
    assert {n for n, c in counts.items() if c} == set(SPANS + WAITS) - {
        "rest_parse", "respond"}


# -- the spans, under a trace -------------------------------------------------

def host_spans(trace_dir: str) -> dict:
    """{host line: [(name, start_ns, end_ns, {argument: value})]} of the
    trace's `query_phase:`, `request:`, `collect:` and `wait:` spans."""
    import jax
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    lines = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                      dict(e.stats))
                     for e in line.events
                     if e.name.startswith(("query_phase:", "request:",
                                           "collect:", "wait:"))]
            if spans:
                lines[i] = sorted(spans, key=lambda s: s[1])
    return lines


@pytest.fixture(scope="module")
def traced_search(served, tmp_path_factory):
    """One REST search under a trace started through the program's own
    route, with `jax.profiler.start_trace` wrapped the way
    `benchmarks/run.py` wraps it: `profiler.start` must pass it no
    `profiler_options=` of its own."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    plain = jax.profiler.start_trace
    jax.profiler.start_trace = \
        lambda d, **kw: plain(d, profiler_options=opts, **kw)
    try:
        started = served.call("POST", "/_nodes/profiler/start",
                              {"path": "trace"})
    finally:
        jax.profiler.start_trace = plain
    try:
        assert started["tracing"] and profiler.status()["tracing"]
        served.search()
    finally:
        stopped = served.call("POST", "/_nodes/profiler/stop")
    assert stopped["path"] == started["path"]
    assert not profiler.status()["tracing"]
    return host_spans(started["path"])


@pytest.mark.parametrize("name", SPANS)
def test_a_traced_search_has_each_span_phase_under_its_id(traced_search,
                                                          name):
    """One `query_phase:<phase>` per span phase — two where a callee took
    its time out of the block (`bind` lies either side of `dispatch`,
    `rest_parse` either side of the search) — all of one search under the
    same `request` argument."""
    spans = [s for line in traced_search.values() for s in line]
    whole = [s for s in spans if s[0] == "request:search"]
    assert len(whole) == 1
    rid = whole[0][3]["request"]
    mine = [s for s in spans if s[0] == "query_phase:" + name]
    assert len(mine) == (2 if name in ("bind", "rest_parse") else 1)
    assert all(s[3] == {"request": rid} for s in mine)


def test_no_phase_span_encloses_another_on_its_thread(traced_search):
    phases = 0
    for line in traced_search.values():
        leaves = [s for s in line if s[0].startswith("query_phase:")]
        phases += len(leaves)
        for a, b in zip(leaves, leaves[1:]):
            assert a[2] <= b[1], (a, b)
        # the enclosing spans lie outside the prefix and do enclose
        for name, start, end, _args in line:
            if name in ("request:search", "request:round"):
                assert any(start <= s[1] and s[2] <= end for s in leaves)
    assert phases == len(SPANS) + 2


def test_a_traced_search_draws_its_collect_in_two_parts(traced_search):
    """`collect:compute` then `collect:copy`, outside the prefix, inside
    the one `query_phase:collect` of their thread and under its id; and
    no `wait:scheduler`: the search led its own round and never slept."""
    drawn = 0
    for line in traced_search.values():
        collect = [s for s in line if s[0] == "query_phase:collect"]
        parts = [s for s in line if s[0].startswith("collect:")]
        if not collect:
            assert not parts
            continue
        (_name, start, end, args), = collect
        assert [s[0] for s in parts] == ["collect:compute", "collect:copy"]
        assert all(start <= s[1] and s[2] <= end and s[3] == args
                   for s in parts)
        assert parts[0][2] <= parts[1][1]
        drawn += len(parts)
    assert drawn == 2
    assert not [s for line in traced_search.values() for s in line
                if s[0].startswith("wait:")]


def test_the_enclosing_spans_say_what_they_enclosed(traced_search):
    """`request:round` names the batches and reader groups it served,
    `request:merge` the search and the shard results it merged; both lie
    outside the `query_phase:` prefix."""
    spans = [s for line in traced_search.values() for s in line]
    rid = next(s for s in spans if s[0] == "request:search")[3]["request"]
    assert [s[3] for s in spans if s[0] == "request:round"] \
        == [{"batches": 1, "groups": 1}]
    merges = [s for s in spans if s[0] == "request:merge"]
    assert [s[3] for s in merges] == [{"request": rid, "shards": 1}]
    reduce_ = next(s for s in spans if s[0] == "query_phase:reduce")
    assert reduce_[1] <= merges[0][1] and merges[0][2] <= reduce_[2]


def test_profiler_refuses_a_second_start_and_a_second_stop(tmp_path):
    from elasticsearch_tpu.utils.errors import IllegalArgumentError
    trace_dir = str(tmp_path / "trace")
    profiler.start(trace_dir)
    try:
        with pytest.raises(IllegalArgumentError):
            profiler.start(trace_dir)
    finally:
        assert profiler.stop()["path"] == trace_dir
    found = [f for _r, _d, files in os.walk(trace_dir) for f in files]
    assert found, "profiler wrote no trace files"
    with pytest.raises(IllegalArgumentError):
        profiler.stop()


def test_two_coalesced_searches_share_one_dispatch_span(served, tmp_path):
    """Two searches of one plan on one batch ride one device program: its
    spans name both ids, and each job keeps its own share of `fetch`."""
    reader = served.node.indices["p"].shards[0].acquire_searcher()
    batch = served.node._dispatch.batch()
    ids = [profiler.next_request_id(), profiler.next_request_id()]
    jobs = [batch.submit(reader, {"query": {"term": {"k": v}}, "size": 3},
                         with_partials=True, request=rid)
            for rid, v in zip(ids, ("v1", "v2"))]
    launches = sum(served.dispatch_stats()["launches"].values())
    before = served.dispatch_stats()["phases"]
    profiler.start(str(tmp_path / "trace"))
    try:
        batch.dispatch()
    finally:
        profiler.stop()
    # one block each, weighing the two searches it served: both waited
    # through all of it
    after = served.dispatch_stats()["phases"]
    counts = moved(before, after, "count")
    assert {n: c for n, c in counts.items() if c} == {
        "scheduler_wait": 2, "bind": 2, "dispatch": 2, "collect": 2,
        "unpack": 2, "fetch": 2}
    assert [j.result()["hits"]["total"] for j in jobs] == [
        len(range(1, DOCS, 3)), len(range(2, DOCS, 3))]
    assert sum(served.dispatch_stats()["launches"].values()) \
        == launches + 1
    assert all(j.fetch_s > 0 for j in jobs)
    spans = [s for line in host_spans(str(tmp_path / "trace")).values()
             for s in line]
    both = {"requests": f"{ids[0]}|{ids[1]}", "n": 2}
    for name in ("dispatch", "collect", "unpack", "fetch"):
        assert [s[3] for s in spans if s[0] == "query_phase:" + name] \
            == [both], name


# -- the building blocks -------------------------------------------------------

def test_a_paused_block_counts_once_and_leaves_the_pause_out():
    import time
    name = "test_paused_block"
    with profiler.phase(name, request=1) as block:
        block.pause()
        time.sleep(0.02)
        block.resume()
        block.switch(name + "_next")
        time.sleep(0.02)
    stats = profiler.phase_stats()
    assert stats[name]["count"] == 1 and stats[name]["sum"] < 0.01
    assert stats[name + "_next"]["count"] == 1
    assert stats[name + "_next"]["sum"] >= 0.02


def test_timers_lose_no_update_under_contention():
    """More threads than cores, all closing phases of one name: the
    count and the sum are exact (MeanMetric adds under its lock)."""
    import sys
    name, threads, each = "test_contended", 16, 500
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def work():
            for _ in range(each):
                profiler.waited(name, 0.25)
                with profiler.phase(name + "_span"):
                    pass
        ts = [threading.Thread(target=work) for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(old)
    stats = profiler.phase_stats()
    assert stats[name] == {"count": threads * each,
                           "sum": 0.25 * threads * each, "mean": 0.25}
    assert stats[name + "_span"]["count"] == threads * each


@pytest.mark.parametrize("ids,args", [
    ([], {}), ([None], {}), ([7], {"request": 7}),
    ([7, None, 9], {"requests": "7|9", "n": 2})])
def test_request_args_name_the_searches_served(ids, args):
    assert profiler.request_args(ids) == args


# -- what graftlint holds the mechanism to --------------------------------------

@pytest.mark.parametrize("entered", [
    'with phase("bind"): y = x + 1',
    'with _phase("dispatch"): y = x + 1',
    'with _launch(None, "unfused"): y = x + 1',
    'y = x; waited("pool_wait", 0.1)'])
def test_a_phase_inside_a_jitted_function_is_a_trace_purity_finding(entered):
    from tools.graftlint import lint_source
    found = [f for f in lint_source(textwrap.dedent(f"""
        import jax
        @jax.jit
        def f(x):
            {entered}
            return y
    """), "fixture.py") if not f.suppressed]
    assert [f.rule for f in found] == ["trace-purity"]
    assert "host timer" in found[0].message


def test_the_package_enters_no_phase_in_traced_code_and_races_on_no_timer():
    """The module-level registry is under the lockset pass (`profiler`
    is a hot module there), and no traced body enters a phase."""
    from tools.graftlint import lint_package
    from tools.graftlint.rules.shared_state_rules import _HOT_MODULES
    assert "profiler" in _HOT_MODULES
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    found = [f for f in lint_package(repo, "elasticsearch_tpu")
             if f.rule in ("trace-purity", "shared-state-race")
             and ("host timer" in f.message or "profiler" in f.path)]
    assert found == [], [f.render() for f in found]


def test_annotate_is_gone_and_one_site_makes_the_spans():
    assert not hasattr(profiler, "annotate")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sites = []
    for root, _dirs, files in os.walk(os.path.join(repo,
                                                   "elasticsearch_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(root, f)) as fh:
                    sites += [f for ln in fh if "TraceAnnotation" in ln]
    assert sites == ["profiler.py"]
