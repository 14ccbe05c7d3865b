"""The `msmarco-passage-1shard` deployment of `benchmarks/configs/` at
2,048 seeded passages on the CPU, through the normal path (`PUT`,
`_bulk`, `_refresh`, `_flush`, close, reopen on the commit, `POST
/msmarco-passage/_search` over `RestServer`), on its one shard and on
five.

Every answer is compared with the benchmark's own plain reference
(`benchmarks/harness/shapes/passages.py`: float64 numpy BM25 over plain
postings of the generated passages, routing and per-shard statistics of
its own, nothing of the program): totals, ids and order exact, scores
within the configuration's `limits`. The reference sums a match's terms
in float64 and the program in float32 (a weight times an eager impact a
term, 3 to 10 terms), so a score may differ by a few float32 roundings
of a value near 10: 1e-6 relative, the configuration's limit, is ten of
them; pruning is exact and adds nothing. Each case also sees the fused
engines take its plans (`fused_scoring.admission.admitted` rises by a
plan a shard), with `match` of 3, 6 and 10 terms under both operators;
a match of 17 terms, which no fused engine takes, holds the same limits;
and one pack whose vocabulary x tiles is over the 2^24 elements at which
the dense block-max summary used to give up.
"""

import http.client
import json
import os
import sys

import numpy as np
import pytest

from elasticsearch_tpu.index import segment as segment_mod
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.rest.server import RestServer

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import corpus as C  # noqa: E402  (numpy only)

SEED = 2147483693
DOCS = 2048
QUERIES = 4             # drawn for each case
OLD_BUDGET = 1 << 24    # elements of the dense [terms, tiles] summary

with open(os.path.join(BENCH, "configs", "msmarco-passage-1shard.json")) as f:
    CONFIG = json.load(f)
INDEX = CONFIG["index"]
CASES = [(op, m) for op in ("or", "and") for m in (3, 6, 10)] + [("term", 1)]


def config_of(shards: int, **corpus) -> dict:
    """The configuration on `shards` primaries, its `corpus` group laid
    over with the case's own."""
    return dict(CONFIG, number_of_shards=shards,
                corpus=dict(CONFIG["corpus"], **corpus),
                index_settings=dict(CONFIG["index_settings"],
                                    **{"index.number_of_shards": shards}))


def drawn(corpus, operator: str, terms: int, count: int, seed: int,
          frequent: float = 0.4) -> list:
    """`count` (REST body, reference spec) pairs as the cell's mix draws
    them, all of one operator and number of terms."""
    op = {"draw": {"clause": "match", "operator_shares": {operator: 1.0},
                   "terms_shares": {str(terms): 1.0}, "frequent": frequent,
                   "frequent_ranks": 64, "size": 10}}
    return corpus.draw(op, count, np.random.default_rng([seed, terms]))


class Served:
    """The configuration at `docs` passages, loaded and reopened as
    `benchmarks/harness/served.py` does it."""

    def __init__(self, config: dict, docs: int, data_path: str):
        self.config, self.data_path = config, data_path
        self.corpus = C.corpus_of(config, docs, SEED)
        self.reference = C.Reference(self.corpus)
        self._start()
        self.call("PUT", f"/{INDEX}", {
            "settings": dict(config["index_settings"]),
            "mappings": config["mappings"]})
        chunk = config["bulk_size"]
        for lo in range(0, docs, chunk):
            r = self.call("POST", f"/{INDEX}/_bulk", self.corpus.bulk_body(
                lo, min(lo + chunk, docs)))
            assert not r["errors"]
        for verb in ("_refresh", "_flush"):
            assert not self.call("POST", f"/{INDEX}/{verb}")[
                "_shards"]["failed"]
        self.close()
        self._start()       # the summary is rebuilt from the commit
        assert self.call("GET", f"/{INDEX}/_count")["count"] == docs

    def _start(self) -> None:
        self.node = Node({"node.name": "passages-0",
                          "path.data": self.data_path,
                          "index.number_of_replicas": 0})
        self.server = RestServer(self.node, "127.0.0.1", 0).start()
        self.conn = http.client.HTTPConnection(
            self.server.host, self.server.port, timeout=120)

    def close(self) -> None:
        self.conn.close()
        self.server.stop()
        self.node.close()

    def call(self, method: str, path: str, body=None) -> dict:
        data = body if isinstance(body, (bytes, type(None))) \
            else json.dumps(body).encode()
        self.conn.request(method, path, body=data,
                          headers={"Content-Type": "application/json"})
        r = self.conn.getresponse()
        raw = r.read()
        assert r.status == 200, (method, path, r.status, raw[:400])
        return json.loads(raw)

    def fused(self) -> dict:
        stats = self.call("GET", "/_nodes/stats")
        return next(iter(stats["nodes"].values()))["fused_scoring"]

    def compared(self, pairs: list) -> dict:
        """The answers to `pairs` against the reference, folded."""
        readings = []
        for body, spec in pairs:
            resp = self.call("POST", f"/{INDEX}/_search", body)
            assert resp["_shards"]["failed"] == 0 and not resp["timed_out"]
            assert resp["_shards"]["total"] == \
                self.config["number_of_shards"]
            readings.append(self.reference.compare(spec, C.digest(resp)))
        return C.fold(readings)


@pytest.fixture(scope="module", params=[1, 5])
def served(request, tmp_path_factory):
    sv = Served(config_of(request.param), DOCS,
                str(tmp_path_factory.mktemp(f"passages{request.param}")))
    yield sv
    sv.close()


@pytest.mark.parametrize("operator,terms", CASES)
def test_a_match_is_the_references_and_runs_fused(served, operator, terms):
    if operator == "term":
        word = drawn(served.corpus, "or", 3, 1, SEED)[0][1][
            "clauses"][0]["match"][-1]
        pairs = [({"query": {"term": {"text": word}}, "size": 10},
                  {"clauses": [{"field": "text", "match": [word],
                                "operator": "or", "score": "bm25"}],
                   "size": 10})]
    else:
        pairs = drawn(served.corpus, operator, terms, QUERIES, SEED)
        assert all(len(s["clauses"][0]["match"]) == terms
                   for _b, s in pairs)
    before = served.fused()
    folded = served.compared(pairs)
    assert C.judge(folded, CONFIG["limits"]), folded
    after = served.fused()
    shards = served.config["number_of_shards"]
    assert after["admission"]["admitted"] \
        - before["admission"]["admitted"] == len(pairs) * shards
    assert after["admission"]["rejected"] == before["admission"]["rejected"]
    # the walks were counted where a run reads them, under served load
    assert after["dispatches"] - before["dispatches"] == len(pairs) * shards
    assert after["tiles"]["examined"] > before["tiles"]["examined"]


def test_a_match_past_the_dense_limit_is_the_references(served):
    """A match of 17 terms is over the binder's dense group of 16 and
    takes the posting-scatter path, unfused: no plan of the cell does,
    and it has to hold the same guarantees (PERF.md fault 17: on the
    chip its kernel's scores were 3e-3 off until the contraction asked
    for float32; tests/test_pallas_scoring.py pins the kernel)."""
    from elasticsearch_tpu.search.executor import _DENSE_GROUP_MAX
    terms = _DENSE_GROUP_MAX + 1
    pairs = drawn(served.corpus, "or", terms, QUERIES, SEED)
    assert all(len(s["clauses"][0]["match"]) == terms for _b, s in pairs)
    before = served.fused()["admission"]
    folded = served.compared(pairs)
    assert C.judge(folded, CONFIG["limits"]), folded
    after = served.fused()["admission"]
    assert after["admitted"] == before["admitted"]
    assert sum(after["rejected"].values()) \
        - sum(before["rejected"].values()) \
        == len(pairs) * served.config["number_of_shards"]


def test_the_summary_on_the_device_is_reported(served):
    """`_nodes/stats` holds what the stored form costs on the device:
    the (term, tile) pairs that occur, at most one a posting, and their
    bytes. A gauge of the process: it rises by a pack's summary when
    the pack goes to the device."""
    served.compared(drawn(served.corpus, "or", 3, 1, SEED))
    fields = [seg.text["text"]
              for sh in served.node.indices[INDEX].shards.values()
              for seg in sh.acquire_searcher().segments]
    held = served.fused()["summary"]
    assert held["entries"] >= sum(pf.tile_max.entries for pf in fields) > 0
    assert held["bytes"] >= sum(pf.tile_max.nbytes for pf in fields)
    assert sum(pf.tile_max.entries for pf in fields) \
        <= sum(len(pf.doc_ids) for pf in fields)
    served.call("PUT", "/late", {"settings": {
        "index.number_of_shards": 1, "index.number_of_replicas": 0},
        "mappings": CONFIG["mappings"]})
    served.call("POST", "/late/_bulk", served.corpus.bulk_body(0, 64))
    served.call("POST", "/late/_refresh")
    served.call("POST", "/late/_search",
                {"query": {"match": {"text": "zaa"}}})
    seg, = served.node.indices["late"].shards[0].acquire_searcher().segments
    tm = seg.text["text"].tile_max
    now = served.fused()["summary"]
    assert now["entries"] - held["entries"] == tm.entries > 0
    assert now["bytes"] - held["bytes"] == tm.nbytes


def test_a_vocabulary_over_the_old_budget_is_admitted(
        tmp_path, monkeypatch):
    """8,192 passages of words drawn evenly from 1.5M hold about 390,000
    distinct terms; in tiles of 128 documents that is 64 tiles, 25M
    (term, tile) cells: over the 2^24 at which the dense summary was not
    built and every match ran unfused. The stored form has at most one
    entry a posting, and the plans are admitted."""
    monkeypatch.setattr(segment_mod, "SCORE_TILE", 128)
    sv = Served(config_of(1, vocabulary=1_500_000, term_zipf=0.0), 8192,
                str(tmp_path / "wide"))
    try:
        seg, = sv.node.indices[INDEX].shards[0].acquire_searcher().segments
        pf = seg.text["text"]
        tm = pf.tile_max
        assert tm is not None and tm.n_tiles == seg.capacity // 128
        assert len(pf.terms) * tm.n_tiles > OLD_BUDGET
        assert tm.entries <= len(pf.doc_ids)
        assert tm.nbytes < len(pf.terms) * tm.n_tiles * 4 // 4  # a quarter
        pairs = [p for op, m in (("or", 6), ("and", 3))
                 for p in drawn(sv.corpus, op, m, 3, SEED, frequent=0.0)]
        folded = sv.compared(pairs)
        assert C.judge(folded, CONFIG["limits"]), folded
        admission = sv.fused()["admission"]
        assert admission["admitted"] >= len(pairs)
        assert "missing_tile_max" not in admission["rejected"]
    finally:
        sv.close()
