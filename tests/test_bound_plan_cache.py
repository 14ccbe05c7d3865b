"""A shard reader keeps what its submit makes of a body and the reader
alone (`search/bound_plans.py`: the parsed request and the bound trees
a body, the aggregation context, the packed wire parameters on the
device and the output layout a group), so that the next search with
the same bodies goes from its key to the launches. On the `http_logs`
deployments of `tests/test_fan_out_http_logs.py` (4,096 docs, one shard
and five), over REST and on the readers themselves.

What that must not change: the answers (after a refresh, a delete, a
mapping update and a reopening too), the breaker holds, the per-launch
counters. What says that it engaged: `_nodes/stats/dispatch` ->
`bound_plans`, and the benchmark's `bound_plan_hit_pct`.
"""

import copy
import gc
import importlib.util
import json
import os
import sys
import threading
import types

import pytest

from elasticsearch_tpu.search import bound_plans, executor
from elasticsearch_tpu.utils.breaker import breaker_service
from test_fan_out_http_logs import BENCH, CASES, Served

COUNTS = ("hits", "misses", "bypassed", "evictions", "entries")


@pytest.fixture(scope="module", params=[1, 5], ids=["1shard", "5shard"])
def served(request, tmp_path_factory):
    sv = Served(request.param,
                str(tmp_path_factory.mktemp(f"plans{request.param}")))
    yield sv
    sv.stop()


def counted(served) -> dict:
    return served.dispatch_stats()["bound_plans"]


def moved(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in COUNTS}


def answer(response: dict) -> dict:
    """A response but for its clock."""
    return {k: v for k, v in response.items() if k != "took"}


def readers(served, index: str = "logs") -> list:
    return [eng.acquire_searcher() for _sid, eng in
            sorted(served.node.indices[index].shards.items())]


# -- the same answers, and the counter ----------------------------------------

@pytest.mark.parametrize("name,body,_spec", CASES,
                         ids=[name for name, _b, _s in CASES])
def test_a_body_answered_twice_is_equal_and_the_second_hits_in_every_reader(
        served, name, body, _spec):
    first = served.search(body)
    before = counted(served)
    second = served.search(body)
    assert answer(second) == answer(first)
    assert json.dumps(answer(second)) == json.dumps(answer(first))
    assert moved(before, counted(served)) == {
        "hits": served.shards, "misses": 0, "bypassed": 0, "evictions": 0,
        "entries": 0}


def test_a_new_body_is_a_miss_and_then_a_hit_and_is_kept_once(served):
    body = {"query": {"range": {"size": {"gte": 12345}}}, "size": 4}
    n = served.shards
    before = counted(served)
    first = served.search(body)
    after_first = counted(served)
    # one entry a reader: the body, and with it its group of one
    assert moved(before, after_first) == {
        "hits": 0, "misses": n, "bypassed": 0, "evictions": 0,
        "entries": n}
    assert answer(served.search(body)) == answer(first)
    assert moved(after_first, counted(served)) == {
        "hits": n, "misses": 0, "bypassed": 0, "evictions": 0,
        "entries": 0}


def test_a_reader_called_without_keys_keeps_and_hits(served):
    reader = readers(served)[0]
    body = {"query": {"range": {"size": {"gte": 23456}}}}
    before = bound_plans.counts()
    total = reader.count(body)
    assert reader.count(body) == total
    assert moved(before, bound_plans.counts()) == {
        "hits": 1, "misses": 1, "bypassed": 0, "evictions": 0, "entries": 1}
    # the key the node hands down is the one the reader makes itself
    shard_body = {"query": body["query"], "size": 0}
    key = bound_plans.body_key(shard_body)
    before = bound_plans.counts()
    reader.msearch([shard_body], keys=[key])
    assert moved(before, bound_plans.counts())["hits"] == 1


BYPASSED = {
    "knn": {"knn": {"field": "no_such_vector", "query_vector": [0.5, 0.5],
                    "k": 3}},
    "multi_key_sort": {"query": {"match_all": {}}, "size": 3,
                       "sort": [{"size": "desc"}, {"status": "asc"}]},
    # the origin is the clock's reading at the bind
    "decay_from_now": {"query": {"function_score": {
        "query": {"match_all": {}},
        "functions": [{"gauss": {"@timestamp": {"scale": "10d"}}}]}},
        "size": 3},
    # the bind uploads the script's columns
    "script_query": {"query": {"bool": {"filter": [{"script": {
        "script": "doc['size'].value > 100"}}]}}, "size": 3},
}


@pytest.mark.parametrize("kind", BYPASSED)
def test_a_body_that_leaves_the_kept_path_counts_bypassed_every_time(
        served, kind):
    body = BYPASSED[kind]
    n = served.shards
    first = served.search(body)
    for _ in range(2):
        before = counted(served)
        again = served.search(body)
        assert moved(before, counted(served)) == {
            "hits": 0, "misses": 0, "bypassed": n, "evictions": 0,
            "entries": 0}
        if kind != "decay_from_now":    # the clock has moved: scores too
            assert answer(again) == answer(first)


def test_a_stored_script_is_read_anew_by_every_search(served):
    """The registry changes under a reader: a body that names a stored
    script is parsed every time, and follows the script."""
    reader = readers(served)[0]
    from elasticsearch_tpu.script.service import ScriptService
    svc = ScriptService.instance()
    body = {"query": {"match_all": {}}, "size": 2,
            "script_fields": {"v": {"script": {"id": "plans_test"}}}}
    try:
        svc.put_stored("plans_test", "doc['size'].value + 1")
        before = bound_plans.counts()
        one = reader.search(body)
        svc.put_stored("plans_test", "doc['size'].value + 2")
        two = reader.search(body)
        assert moved(before, bound_plans.counts())["bypassed"] == 2
        assert moved(before, bound_plans.counts())["hits"] == 0
    finally:
        svc.delete_stored("plans_test")
    assert [h["fields"]["v"][0] + 1 for h in one["hits"]["hits"]] \
        == [h["fields"]["v"][0] for h in two["hits"]["hits"]]


def test_two_bodies_that_differ_in_one_constant_are_two_entries(served):
    n = served.shards
    bodies = [{"query": {"range": {"size": {"gte": gte}}}, "size": 5}
              for gte in (34567, 34568)]
    before = counted(served)
    totals = [served.search(b)["hits"]["total"] for b in bodies]
    assert moved(before, counted(served)) == {
        "hits": 0, "misses": 2 * n, "bypassed": 0, "evictions": 0,
        "entries": 2 * n}
    again = [served.search(b)["hits"]["total"] for b in bodies]
    assert again == totals
    docs = served.reference.corpus.cols["size"]
    assert totals == [int((docs >= 34567).sum()), int((docs >= 34568).sum())]


def test_the_same_body_with_other_dfs_statistics_is_another_entry(served):
    """`_dfs_stats` rides in the body the reader receives: a search
    scored with the index's statistics does not launch from the plan of
    one scored with the shard's own."""
    body = {"query": {"term": {"request.raw": {
        "value": "GET / HTTP/1.0"}}}, "size": 5}
    n = served.shards
    local = served.search(body)
    before = counted(served)
    dfs = served.call(
        "POST", "/logs/_search?search_type=dfs_query_then_fetch", body)
    assert moved(before, counted(served))["misses"] == n
    assert moved(before, counted(served))["hits"] == 0
    before = counted(served)
    assert answer(served.call(
        "POST", "/logs/_search?search_type=dfs_query_then_fetch", body)) \
        == answer(dfs)
    assert answer(served.search(body)) == answer(local)
    assert moved(before, counted(served))["hits"] == 2 * n
    assert dfs["hits"]["total"] == local["hits"]["total"]
    if n > 1:       # five idfs against one
        assert [h["_score"] for h in dfs["hits"]["hits"]] \
            != [h["_score"] for h in local["hits"]["hits"]]


def test_an_msearch_of_bodies_of_one_plan_hits_as_a_group(served):
    n = served.shards
    bodies = [{"query": {"term": {"status": s}}, "size": 3}
              for s in (200, 304, 404)]
    head = json.dumps({"index": "logs"})
    payload = "".join(f"{head}\n{json.dumps(b)}\n" for b in bodies).encode()
    alone = [answer(served.search(b)) for b in bodies]
    before, stats = counted(served), served.dispatch_stats()
    first = served.call("POST", "/_msearch", payload)["responses"]
    # the three were bound already; as a group of three they are new,
    # and one program a reader serves them
    assert moved(before, counted(served)) == {
        "hits": 0, "misses": n, "bypassed": 0, "evictions": 0,
        "entries": n}
    assert served.dispatch_stats()["batches_dispatched"] \
        - stats["batches_dispatched"] == n
    before = counted(served)
    second = served.call("POST", "/_msearch", payload)["responses"]
    assert moved(before, counted(served)) == {
        "hits": n, "misses": 0, "bypassed": 0, "evictions": 0, "entries": 0}
    for got in (first, second):
        assert [{k: v for k, v in answer(r).items() if k != "status"}
                for r in got] == alone


# -- what the kept plan may not outlive ---------------------------------------

def test_a_view_added_between_two_searches_does_not_serve_a_stale_layout(
        served):
    """`ensure_agg_views` adds leaves to a segment's column tree and
    switches the program's branches; the layout kept with a plan was
    made for the tree as it was."""
    hist = {"by_day": {"date_histogram": {"field": "@timestamp",
                                          "interval": "day"}}}
    plain = {"size": 0, "query": {"match_all": {}}, "aggs": hist}
    filtered = {"size": 0, "query": {"range": {"size": {"gte": 1000}}},
                "aggs": hist}
    n = served.shards
    first = served.search(plain)
    assert moved(counted(served), counted(served))["hits"] == 0
    before = counted(served)
    assert answer(served.search(plain)) == answer(first)
    assert moved(before, counted(served))["hits"] == n
    segments = [seg for r in readers(served) for seg in r.segments]
    epochs = [seg.device_epoch() for seg in segments]
    served.search(filtered)     # projects `size` into the day layout
    assert all(seg.device_epoch() != e for seg, e in zip(segments, epochs))
    before = counted(served)
    assert answer(served.search(plain)) == answer(first)
    assert moved(before, counted(served)) == {
        "hits": 0, "misses": n, "bypassed": 0, "evictions": 0, "entries": 0}
    before = counted(served)
    assert answer(served.search(plain)) == answer(first)
    assert moved(before, counted(served))["hits"] == n


def test_a_dropped_column_tree_is_not_launched_from(served):
    reader = readers(served)[0]
    body = {"query": {"term": {"status": 404}}, "size": 2}
    first = reader.search(body)
    reader.segments[0].drop_device()
    before = bound_plans.counts()
    assert answer(reader.search(body)) == answer(first)
    assert moved(before, bound_plans.counts())["misses"] == 1
    assert answer(reader.search(body)) == answer(first)
    assert moved(before, bound_plans.counts())["hits"] == 1


def test_a_switch_of_the_executor_empties_what_was_kept(served, monkeypatch):
    """A plan resolved with the fused engines on is not served once
    they are off (`plan_switches`)."""
    reader = readers(served)[0]
    body = {"query": {"match": {"request": "images"}}, "size": 3}
    first = reader.search(body)
    monkeypatch.setenv("ES_TPU_FUSED", "0")
    before = bound_plans.counts()
    assert answer(reader.search(body)) == answer(first)
    assert moved(before, bound_plans.counts())["misses"] == 1
    assert moved(before, bound_plans.counts())["hits"] == 0


def request(served, method: str, path: str, body: dict) -> tuple:
    """`Served.call` for the answers that are not 200."""
    served.conn.request(method, path, body=json.dumps(body).encode(),
                        headers={"Content-Type": "application/json"})
    r = served.conn.getresponse()
    return r.status, json.loads(r.read())


def test_a_forced_backend_is_not_served_the_tuner_s_plan(served, monkeypatch):
    """A fused plan is launched only under the backend switches it was
    resolved under (`segment_plan_valid`)."""
    reader = readers(served)[0]
    body = {"query": {"match": {"request": "images gif"}}, "size": 3}
    first = reader.search(body)
    before = bound_plans.counts()
    assert answer(reader.search(body)) == answer(first)
    assert moved(before, bound_plans.counts())["hits"] == 1
    group = reader._bound_plans.get(bound_plans.body_key(body)).alone
    assert all(p.fused is not None for p in group.plans)
    monkeypatch.setenv("ES_TPU_FUSED_BACKEND", "xla")
    before = bound_plans.counts()
    assert answer(reader.search(body)) == answer(first)
    delta = moved(before, bound_plans.counts())
    assert delta["hits"] + delta["misses"] == 1
    group = reader._bound_plans.get(bound_plans.body_key(body)).alone
    assert all(p.fused[1] == "xla" for p in group.plans)


class Fresh:
    """A small index beside `logs` on the served node, with as many
    shards: what a refresh, a delete, a mapping update and a reopening
    do to a body that is asked again."""

    body = {"query": {"range": {"n": {"gte": 10}}}, "size": 30,
            "sort": [{"n": "asc"}]}

    def __init__(self, served, name: str):
        self.served, self.name = served, name
        served.call("PUT", f"/{name}", {
            "settings": {"number_of_shards": served.shards,
                         "number_of_replicas": 0},
            "mappings": {"properties": {"n": {"type": "integer"},
                                        "tag": {"type": "keyword"}}}})
        for i in range(20):
            self.put(i, {"n": i, "tag": f"t{i % 3}"})
        self.refresh()

    def put(self, i: int, doc: dict) -> None:
        status, out = request(self.served, "PUT",
                              f"/{self.name}/_doc/{i}", doc)
        assert status in (200, 201), out

    def refresh(self) -> None:
        self.served.call("POST", f"/{self.name}/_refresh")

    def search(self, body=None) -> dict:
        return self.served.call("POST", f"/{self.name}/_search",
                                body or self.body)

    def ns(self, body=None) -> list:
        return [h["_source"]["n"] for h in self.search(body)["hits"]["hits"]]


# each returns the `n`s the body finds afterwards, and how many of the
# index's readers the change replaced or emptied

def _index_and_refresh(fresh):
    fresh.put(50, {"n": 50, "tag": "t9"})
    fresh.refresh()
    return list(range(10, 20)) + [50], 1


def _delete_and_refresh(fresh):
    fresh.served.call("DELETE", f"/{fresh.name}/_doc/15")
    fresh.refresh()
    return [n for n in range(10, 20) if n != 15], 1


def _put_mapping(fresh):
    fresh.served.call("PUT", f"/{fresh.name}/_mapping", {
        "properties": {"extra": {"type": "keyword"}}})
    return list(range(10, 20)), fresh.served.shards


def _close_and_reopen(fresh):
    """As a restart does: the node closes and another opens the commit."""
    fresh.served.call("POST", f"/{fresh.name}/_flush")
    fresh.served.stop()
    fresh.served._start()
    return list(range(10, 20)), fresh.served.shards


CHANGES = {"index_and_refresh": _index_and_refresh,
           "delete_and_refresh": _delete_and_refresh,
           "put_mapping": _put_mapping,
           "close_and_reopen": _close_and_reopen}


@pytest.mark.parametrize("change", CHANGES)
def test_after_a_change_the_answer_is_a_fresh_reader_s(served, change):
    fresh = Fresh(served, f"fresh_{change}")
    assert fresh.ns() == list(range(10, 20))
    before = counted(served)
    assert fresh.ns() == list(range(10, 20))
    assert moved(before, counted(served))["hits"] == served.shards
    expected, changed = CHANGES[change](fresh)
    before = counted(served)
    got = fresh.search()
    # nothing kept before the change is launched from: a shard the
    # change did not touch keeps its reader, and what it had kept
    assert moved(before, counted(served))["hits"] == served.shards - changed
    assert moved(before, counted(served))["misses"] == changed
    assert [h["_source"]["n"] for h in got["hits"]["hits"]] == expected
    assert got["hits"]["total"] == len(expected)
    assert got["_shards"]["failed"] == 0
    assert fresh.ns() == expected       # and from what is kept now


def test_a_field_the_mapping_gains_is_seen_by_the_body_asked_before(served):
    """A sort on an unmapped field is an error and is not kept; once the
    mapping has the field the same body is answered, by the same
    reader."""
    fresh = Fresh(served, "fresh_gains")
    body = {"query": {"match_all": {}}, "size": 3,
            "sort": [{"later": "asc"}]}
    assert request(served, "POST", "/fresh_gains/_search", body)[0] == 400
    held = [id(r) for r in readers(served, "fresh_gains")]
    served.call("PUT", "/fresh_gains/_mapping", {
        "properties": {"later": {"type": "integer"}}})
    assert [id(r) for r in readers(served, "fresh_gains")] == held
    got = fresh.search(body)
    assert got["hits"]["total"] == 20 and len(got["hits"]["hits"]) == 3


# -- the capacity -------------------------------------------------------------

def test_the_least_recently_used_entry_goes_at_the_capacity(served,
                                                            monkeypatch):
    reader = readers(served)[0]
    monkeypatch.setattr(bound_plans, "CAPACITY", 3)
    bodies = [{"query": {"range": {"size": {"gte": 45000 + i}}}, "size": 1}
              for i in range(5)]
    held = len(reader._bound_plans)
    gc.collect()    # a reader an earlier test dropped gives its entries
                    # back when it is collected: now, not inside the count
    before = bound_plans.counts()
    for b in bodies:
        reader.search(b)
    delta = moved(before, bound_plans.counts())
    assert delta["misses"] == 5 and delta["hits"] == 0
    assert len(reader._bound_plans) == 3
    assert delta["evictions"] == held + 5 - 3
    assert delta["entries"] == 3 - held
    before = bound_plans.counts()
    reader.search(bodies[-1])       # the newest is there
    assert moved(before, bound_plans.counts())["hits"] == 1
    reader.search(bodies[0])        # the oldest went
    assert moved(before, bound_plans.counts())["misses"] == 1


def test_a_reader_that_is_gone_takes_its_entries_out_of_the_gauge(served):
    fresh = Fresh(served, "fresh_gauge")
    fresh.search()
    held = sum(len(r._bound_plans) for r in readers(served, "fresh_gauge"))
    assert held == served.shards
    gc.collect()        # the readers other tests left behind
    before = bound_plans.counts()["entries"]
    served.call("DELETE", "/fresh_gauge")
    gc.collect()
    assert bound_plans.counts()["entries"] == before - held


# -- per launch, whatever is kept ---------------------------------------------

def launch_counters(served) -> dict:
    stats = served.call("GET", "/_nodes/stats")
    node = next(iter(stats["nodes"].values()))
    d, adm = node["dispatch"], node["fused_scoring"]["admission"]
    return {"launches": sum(d["launches"].values()),
            "collects": d["collects"]["total"],
            "prefetched": d["collects"]["prefetched"],
            "batches_dispatched": d["batches_dispatched"],
            "queries": d["queries"],
            "admitted": adm["admitted"],
            "rejected": sum(adm["rejected"].values()),
            "pallas_rejected": sum(adm["pallas_rejected"].values()),
            "bind": d["phases"]["bind"]["count"],
            "dispatch": d["phases"]["dispatch"]["count"]}


LAUNCHED = {
    "unfused": {"query": {"term": {"status": 206}}, "size": 2},
    "fused": {"query": {"match": {"request": "french images"}}, "size": 2},
    "aggs": {"size": 0, "query": {"range": {"size": {"lt": 300}}},
             "aggs": {"s": {"terms": {"field": "status"}}}},
}


@pytest.mark.parametrize("plan", LAUNCHED)
def test_the_per_launch_counters_advance_on_a_hit_as_on_a_miss(served, plan):
    body = LAUNCHED[plan]
    n = served.shards
    c0, p0 = launch_counters(served), counted(served)
    served.search(body)
    c1, p1 = launch_counters(served), counted(served)
    served.search(body)
    c2, p2 = launch_counters(served), counted(served)
    assert moved(p0, p1)["misses"] == n and moved(p1, p2)["hits"] == n
    on_miss = {k: c1[k] - c0[k] for k in c0}
    on_hit = {k: c2[k] - c1[k] for k in c0}
    assert on_hit == on_miss
    assert on_hit["launches"] == on_hit["collects"] \
        == on_hit["prefetched"] == on_hit["batches_dispatched"] == n
    assert on_hit["admitted"] + on_hit["rejected"] == n
    if plan == "fused":
        assert on_hit["admitted"] == n


def test_a_hit_that_raises_lets_go_of_its_hold_and_of_what_was_kept(
        served, monkeypatch):
    reader = readers(served)[0]
    body = {"query": {"term": {"status": 500}}, "size": 2}
    first = reader.search(body)
    breaker = breaker_service().breaker("request")
    used = breaker.used

    def broken(*args, **kw):
        raise RuntimeError("the launch failed")

    with monkeypatch.context() as mp:
        mp.setattr(executor, "_segment_program_packed", broken)
        before = bound_plans.counts()
        with pytest.raises(RuntimeError, match="the launch failed"):
            reader.search(body)
    assert breaker.used == used
    delta = moved(before, bound_plans.counts())
    assert delta["entries"] == -1       # the body, and its group with it
    before = bound_plans.counts()
    assert answer(reader.search(body)) == answer(first)
    assert moved(before, bound_plans.counts())["misses"] == 1
    assert breaker.used == used


def test_a_caller_s_later_edit_of_its_body_does_not_reach_what_is_kept(
        served):
    reader = readers(served)[0]
    body = {"size": 0, "query": {"term": {"status": 200}},
            "aggs": {"big": {"filter": {"range": {"size": {"gte": 5000}}}}}}
    kept = copy.deepcopy(body)
    first = reader.search(body)
    body["query"]["term"]["status"] = 404       # the caller's dict moves on
    body["aggs"]["big"]["filter"]["range"]["size"]["gte"] = 1
    other = reader.search(body)
    assert other["hits"]["total"] != first["hits"]["total"]
    before = bound_plans.counts()
    assert answer(reader.search(kept)) == answer(first)
    assert moved(before, bound_plans.counts())["hits"] >= 1


def test_readers_on_several_threads_share_what_is_kept(served, monkeypatch):
    """More threads than cores on one reader, six bodies and room for
    four: every answer is the one a lone caller got, the reader never
    holds more than its capacity and the gauge agrees with it."""
    reader = readers(served)[0]
    monkeypatch.setattr(bound_plans, "CAPACITY", 4)
    bodies = [{"query": {"term": {"status": s}}, "size": 3}
              for s in (200, 304, 404, 206, 500, 302)]
    alone = [answer(reader.search(b)) for b in bodies]
    wrong, errors, over = [], [], []

    def work(seed: int) -> None:
        try:
            for i in range(25):
                j = (seed + i) % len(bodies)
                if answer(reader.search(bodies[j])) != alone[j]:
                    wrong.append(j)
                if len(reader._bound_plans) > 4:
                    over.append(j)
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(2 * (os.cpu_count() or 4))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert not errors and not wrong and not over
    assert len(reader._bound_plans) == 4
    gc.collect()
    live = [o for o in gc.get_objects()
            if isinstance(o, bound_plans.BoundPlans)]
    assert bound_plans.counts()["entries"] == sum(len(o) for o in live)


# -- the benchmark's reader ---------------------------------------------------

def _reader_module():
    spec = importlib.util.spec_from_file_location(
        "bound_plan_hit_pct",
        os.path.join(BENCH, "layer_metrics", "bound_plan_hit_pct.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_benchmark_reads_the_window_s_share_of_hits(served):
    mod = _reader_module()
    with open(os.path.join(BENCH, "..", "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"]
                     if m["name"] == mod.NAME)
    assert entry == {"name": mod.NAME, "unit": mod.UNIT,
                     "better": mod.BETTER, "source": mod.SOURCE,
                     "layer": mod.LAYER, "moves": mod.MOVES}
    assert entry["name"] == "bound_plan_hit_pct"
    body = {"query": {"range": {"size": {"gte": 56789}}}, "size": 1}
    before = {"dispatch": served.dispatch_stats()}
    for _ in range(4):
        served.search(body)
    served.search(BYPASSED["multi_key_sort"])
    after = {"dispatch": served.dispatch_stats()}
    run = types.SimpleNamespace(stats_before=before, stats_after=after)
    # a miss, three hits and one bypassed, in every reader
    assert mod.read(run) == pytest.approx(60.0)
    assert mod.read(types.SimpleNamespace(
        stats_before=before, stats_after=before)) is None
    # a program without the counter (the parent) reports nothing
    bare = {"dispatch": {k: v for k, v in before["dispatch"].items()
                         if k != "bound_plans"}}
    assert mod.read(types.SimpleNamespace(
        stats_before=bare, stats_after=after)) is None
