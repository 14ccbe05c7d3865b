"""The `http_logs` deployments of `benchmarks/configs/` at 4,096 seeded
docs on the CPU, through the normal path (`PUT /logs`, `_bulk`,
`_refresh`, `_flush`, close, reopen on the commit, `POST /logs/_search`
over `RestServer`), with one shard and with the track's default five.

The answers: every one is compared with the benchmark's own plain
reference (`benchmarks/harness/corpus.py`: float64 numpy over the
generated documents, DJB2 routing and per-shard statistics of its own,
nothing of the program) under the configuration's `limits`: the mix's
eight operations, and the cases only the coordinator's merge can get
wrong. The accounting: a search of five shard jobs counts one
`scheduler_wait`, and the phases tile its `request` as they do a
search of one.
"""

import http.client
import importlib.util
import json
import os
import time

import numpy as np
import pytest

from elasticsearch_tpu.node import Node
from elasticsearch_tpu.rest.server import RestServer

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
DOCS = 4096
SEED = 2147483711
ROOT = "GET / HTTP/1.0"
TILES = ("rest_parse", "pool_wait", "resolve", "scheduler_wait", "bind",
         "dispatch", "collect", "unpack", "fetch", "reduce", "finish",
         "respond")


def _corpus_module():
    """`benchmarks/harness/corpus.py` by its path: it imports numpy only."""
    spec = importlib.util.spec_from_file_location(
        "bench_corpus", os.path.join(BENCH, "harness", "corpus.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


C = _corpus_module()


def _json(*parts: str) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


MIX = _json("traffic", "track-searches.json")

# what the merge alone can get wrong: (name, REST body, reference spec)
_ROOT_CLAUSE = {"field": "request.raw", "eq": ROOT, "score": "idf"}
_ROOT_QUERY = {"term": {"request.raw": {"value": ROOT}}}
_MAY_2 = {"gte": "1998-05-02T00:00:00Z", "lt": "1998-05-09T00:00:00Z"}
MERGE_CASES = [
    # all scores equal: shard, then doc, and a page that starts past
    # every shard's first hits
    ("match_all_from_7", {"query": {"match_all": {}}, "from": 7,
                          "size": 10},
     {"clauses": [], "size": 17}),
    # each shard scores with its own idf, so whole shards outrank others
    ("term_idf_by_shard", {"query": _ROOT_QUERY, "size": 25},
     {"clauses": [_ROOT_CLAUSE], "size": 25}),
    # more hits asked for than any one shard has matches
    ("size_over_any_shard", {"query": _ROOT_QUERY, "size": 35},
     {"clauses": [_ROOT_CLAUSE], "size": 35}),
    # every 304 has size 0: the best values tie across all shards
    ("asc_sort_size_ties", {"query": {"match_all": {}}, "size": 20,
                            "sort": [{"size": "asc"}]},
     {"clauses": [], "size": 20, "sort": {"field": "size", "order": "asc"}}),
    ("desc_sort_size_ties", {"query": {"match": {"status": "304"}},
                             "size": 20, "sort": [{"size": "desc"}]},
     {"clauses": [{"field": "status", "eq": 304, "score": "one"}],
      "size": 20, "sort": {"field": "size", "order": "desc"}}),
    # a week of hours at 3.7 docs an hour: most buckets have no
    # document in some shard
    ("hourly_agg_sparse", {"size": 0, "query": {"range": {
        "@timestamp": _MAY_2}}, "aggs": {"by_hour": {"date_histogram": {
            "field": "@timestamp", "interval": "hour"}}}},
     {"clauses": [dict(_MAY_2, field="@timestamp", score="one")],
      "size": 0, "histogram": {"name": "by_hour", "field": "@timestamp",
                               "interval_ms": 3600000}}),
]
CASES = [(op["name"], op["body"], op["spec"]) for op in MIX["operations"]] \
    + MERGE_CASES


class Served:
    """One of the two configurations, loaded and reopened as
    `benchmarks/harness/served.py` does it, at 4,096 docs."""

    def __init__(self, shards: int, data_path: str):
        self.config = _json("configs", f"http_logs-{shards}shard.json")
        assert self.config["number_of_shards"] == shards
        self.shards = shards
        self.corpus = C.Corpus(DOCS, SEED, shards, self.config["corpus"])
        self.reference = C.Reference(self.corpus)
        self.data_path = data_path
        self._start()
        self.call("PUT", "/logs", {
            "settings": dict(self.config["index_settings"]),
            "mappings": self.config["mappings"]})
        chunk = self.config["bulk_size"]
        for lo in range(0, DOCS, chunk):
            r = self.call("POST", "/logs/_bulk", self.corpus.bulk_body(
                lo, min(lo + chunk, DOCS)))
            assert not r["errors"]
        for verb in ("_refresh", "_flush"):
            assert not self.call("POST", f"/logs/{verb}")["_shards"]["failed"]
        self.stop()
        self._start()       # on the commit, as a restart does
        assert self.call("GET", "/logs/_count")["count"] == DOCS
        assert len(self.node.indices["logs"].shards) == shards

    def _start(self) -> None:
        self.node = Node({"node.name": "fan-out-0",
                          "path.data": self.data_path})
        self.server = RestServer(self.node, "127.0.0.1", 0).start()
        self.conn = http.client.HTTPConnection(
            self.server.host, self.server.port, timeout=120)

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

    def search(self, body: dict) -> dict:
        r = self.call("POST", "/logs/_search", body)
        assert r["_shards"] == {"total": self.shards,
                                "successful": self.shards, "failed": 0}
        assert r["timed_out"] is False
        return r

    def dispatch_stats(self) -> dict:
        stats = self.call("GET", "/_nodes/stats/dispatch")
        return next(iter(stats["nodes"].values()))["dispatch"]


@pytest.fixture(scope="module", params=[1, 5], ids=["1shard", "5shard"])
def served(request, tmp_path_factory):
    sv = Served(request.param,
                str(tmp_path_factory.mktemp(f"logs{request.param}")))
    yield sv
    sv.stop()


# -- the answers --------------------------------------------------------------

def test_the_corpus_gives_the_merge_cases_what_they_are_for():
    """At five shards: the root's df differs by shard (so its idf
    does), no shard alone has 35 matches, zero sizes and the 304s lie
    in every shard, and some hour of the sparse week has documents in
    fewer shards than there are."""
    c = C.Corpus(DOCS, SEED, 5, _json("configs",
                                      "http_logs-5shard.json")["corpus"])
    root = c.cols["request.raw"] == c.request_key(ROOT)
    df = np.bincount(c.shard[root], minlength=5)
    assert len(set(df.tolist())) > 1 and df.max() < 35 <= root.sum()
    zero = np.bincount(c.shard[c.cols["size"] == 0], minlength=5)
    assert (zero >= 20).all()
    week = (c.cols["@timestamp"] >= C.parse_iso(_MAY_2["gte"])) \
        & (c.cols["@timestamp"] < C.parse_iso(_MAY_2["lt"]))
    hour = c.cols["@timestamp"][week] // 3600000
    in_shards = {h: len(set(c.shard[week][hour == h].tolist()))
                 for h in set(hour.tolist())}
    assert min(in_shards.values()) < 5 and len(in_shards) > 100


@pytest.mark.parametrize("name,body,spec", CASES,
                         ids=[name for name, _b, _s in CASES])
def test_an_answer_is_the_reference_s_under_the_limits(served, name, body,
                                                       spec):
    frm = body.get("from", 0)
    window = dict(body, **{"from": 0, "size": frm + body["size"]}) \
        if frm else body
    got = C.digest(served.search(window))
    numbers = served.reference.compare(spec, got)
    limits = served.config["limits"]
    assert all(numbers[k] <= limits[k] for k in C.COMPARED), (numbers, got)
    match, _score, best = served.reference.of(spec)
    assert got["total"] == int(match.sum()) and got["ids"] == best.tolist()
    if frm:
        # the page itself is the tail of the window compared above
        page = C.digest(served.search(body))
        assert page["ids"] == got["ids"][frm:] and len(page["ids"]) == 10
        assert page["total"] == got["total"]


# -- the accounting -----------------------------------------------------------

def moved(before: dict, after: dict, field: str) -> dict:
    return {name: entry[field] - before.get(name, {field: 0})[field]
            for name, entry in after.items()}


def test_a_search_counts_one_wait_and_its_phases_tile_it(served):
    """N sequential searches over the mix's operations: one
    `scheduler_wait` a search however many shard jobs it is, the twelve
    phases and waits sum to its `request`, and the counters beside
    `phases` say what the fan-out was."""
    bodies = [body for _n, body, _s in CASES] * 5
    for body in bodies[:len(CASES)]:
        served.search(body)             # every plan compiled
    before = served.dispatch_stats()
    t0 = time.perf_counter()
    for body in bodies:
        served.search(body)
    wall = time.perf_counter() - t0
    after = served.dispatch_stats()
    n, shards = len(bodies), served.shards
    counts = moved(before["phases"], after["phases"], "count")
    sums = moved(before["phases"], after["phases"], "sum")
    assert counts["request"] == n
    assert counts["scheduler_wait"] == n
    assert counts["reduce"] == counts["resolve"] == n
    assert counts["bind"] == counts["collect"] == n * shards
    share = sum(sums[name] for name in TILES) / sums["request"]
    assert 0.85 <= share <= 1.02, (share, sums)
    assert after["searches"] - before["searches"] == n
    assert after["queries"] - before["queries"] == n * shards
    merge = {k: after["merge"][k] - before["merge"][k]
             for k in ("count", "sum", "shard_results", "hits")}
    assert merge["count"] == n and merge["shard_results"] == n * shards
    assert 0 < merge["sum"] < sums["reduce"]
    sizes = [body.get("size", 10) + body.get("from", 0) for body in bodies]
    assert 0 < merge["hits"] <= shards * sum(sizes)
    leader = {k: after["leader"][k] - before["leader"][k]
              for k in ("count", "sum", "groups")}
    assert leader["count"] == n and leader["groups"] == n * shards
    assert 0 < leader["sum"] < wall
    # the leader's own time is the leaves it runs, once each
    leaves = sum(sums[name] for name in (
        "bind", "dispatch", "collect", "unpack", "fetch"))
    assert leaves <= leader["sum"] * 1.001


def test_an_msearch_counts_one_wait_a_body(served):
    before = served.dispatch_stats()
    head = json.dumps({"index": "logs"})
    bodies = [body for _n, body, _s in CASES[:4]]
    r = served.call("POST", "/_msearch", "".join(
        f"{head}\n{json.dumps(b)}\n" for b in bodies).encode())
    assert [s["status"] for s in r["responses"]] == [200] * 4
    after = served.dispatch_stats()
    counts = moved(before["phases"], after["phases"], "count")
    assert counts["scheduler_wait"] == 4
    # each reader call served the four of them and weighs four
    assert counts["bind"] == 4 * served.shards
    assert after["searches"] - before["searches"] == 4
    assert after["queries"] - before["queries"] == 4 * served.shards
    assert after["merge"]["count"] - before["merge"]["count"] == 4
    assert after["leader"]["count"] - before["leader"]["count"] == 1
    assert after["leader"]["groups"] - before["leader"]["groups"] \
        == served.shards
