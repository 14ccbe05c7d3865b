"""Percolator + significant_terms / percentile_ranks / scripted_metric /
script metric aggregations.

Reference behaviors: percolator/PercolatorService.java,
bucket/significant/ (JLHScore.java), metrics/percentiles/PercentileRanks,
metrics/scripted/ScriptedMetricAggregator.java.
"""

import json

import pytest

from elasticsearch_tpu.index.mapping import MapperService
from elasticsearch_tpu.index.segment import SegmentBuilder
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.search.shard_searcher import ShardReader
from elasticsearch_tpu.utils.settings import Settings


@pytest.fixture()
def node():
    n = Node()
    yield n
    n.close()


class TestPercolator:
    def test_register_and_percolate(self, node):
        node.create_index("alerts", mappings={"properties": {
            "message": {"type": "text"}, "level": {"type": "keyword"}}})
        node.register_percolator("alerts", "q1", {
            "query": {"match": {"message": "error"}}})
        node.register_percolator("alerts", "q2", {
            "query": {"term": {"level": "critical"}}})
        node.register_percolator("alerts", "q3", {
            "query": {"match": {"message": "deploy finished"}}})
        r = node.percolate("alerts", {"doc": {
            "message": "disk error on node 7", "level": "critical"}})
        matched = {m["_id"] for m in r["matches"]}
        assert matched == {"q1", "q2"}
        assert r["total"] == 2

    def test_percolate_count_only(self, node):
        node.create_index("alerts")
        node.register_percolator("alerts", "q1", {
            "query": {"match_all": {}}})
        r = node.percolate("alerts", {"doc": {"x": 1}}, count_only=True)
        assert r["total"] == 1
        assert "matches" not in r

    def test_unregister(self, node):
        node.create_index("alerts")
        node.register_percolator("alerts", "q1",
                                 {"query": {"match_all": {}}})
        assert node.unregister_percolator("alerts", "q1")["found"]
        r = node.percolate("alerts", {"doc": {"x": 1}})
        assert r["total"] == 0

    def test_get_percolator(self, node):
        node.create_index("alerts")
        body = {"query": {"term": {"level": "warn"}}}
        node.register_percolator("alerts", "q9", body)
        got = node.get_percolator("alerts", "q9")
        assert got["found"] and got["_source"] == body

    def test_requires_query(self, node):
        from elasticsearch_tpu.utils.errors import IllegalArgumentError
        node.create_index("alerts")
        with pytest.raises(IllegalArgumentError):
            node.register_percolator("alerts", "bad", {"not_query": 1})

    def test_percolate_filter_ids(self, node):
        node.create_index("alerts")
        node.register_percolator("alerts", "a", {"query": {"match_all": {}}})
        node.register_percolator("alerts", "b", {"query": {"match_all": {}}})
        r = node.percolate("alerts", {
            "doc": {"x": 1}, "filter": {"ids": {"values": ["b"]}}})
        assert [m["_id"] for m in r["matches"]] == ["b"]


def make_reader(docs):
    mapper = MapperService(Settings.EMPTY)
    builder = SegmentBuilder()
    for doc_id, src in docs:
        builder.add(mapper.parse(doc_id, json.dumps(src)))
    return ShardReader("idx", [builder.build()], {}, mapper)


@pytest.fixture(scope="module")
def agg_reader():
    docs = []
    # 20 docs: 5 "crash" docs all tagged kernel; background mostly ui
    for i in range(20):
        tag = "kernel" if i < 5 else ("ui" if i < 15 else "net")
        text = "crash panic" if i < 5 else "click render"
        docs.append((str(i), {"tag": tag, "body": text, "ms": (i + 1) * 10}))
    return make_reader(docs)


class TestSignificantTerms:
    def test_significant_terms_foreground(self, agg_reader):
        r = agg_reader.search({
            "size": 0,
            "query": {"match": {"body": "crash"}},
            "aggs": {"sig": {"significant_terms": {
                "field": "tag", "min_doc_count": 2}}}})
        sig = r["aggregations"]["sig"]
        assert sig["doc_count"] == 5
        keys = [b["key"] for b in sig["buckets"]]
        # kernel is 100% of foreground but only 25% of background
        assert keys and keys[0] == "kernel"
        top = sig["buckets"][0]
        assert top["doc_count"] == 5 and top["bg_count"] == 5
        assert top["score"] > 0

    def test_no_significance_without_skew(self, agg_reader):
        r = agg_reader.search({
            "size": 0, "query": {"match_all": {}},
            "aggs": {"sig": {"significant_terms": {
                "field": "tag", "min_doc_count": 1}}}})
        # foreground == background -> no term scores above zero
        assert r["aggregations"]["sig"]["buckets"] == []


class TestPercentileRanks:
    def test_ranks(self, agg_reader):
        r = agg_reader.search({
            "size": 0,
            "aggs": {"pr": {"percentile_ranks": {
                "field": "ms", "values": [50, 200]}}}})
        vals = r["aggregations"]["pr"]["values"]
        # ms = 10..200; 5 of 20 docs <= 50 -> 25%; all <= 200 -> 100%
        assert vals["50.0"] == pytest.approx(25.0, abs=6.0)
        assert vals["200.0"] == pytest.approx(100.0, abs=1e-6)


class TestScriptedMetric:
    def test_scripted_metric_sum(self, agg_reader):
        r = agg_reader.search({
            "size": 0,
            "aggs": {"total": {"scripted_metric": {
                "map_script": "doc['ms'].value * 2"}}}})
        # sum of ms = 10+..+200 = 2100; x2 = 4200
        assert r["aggregations"]["total"]["value"] == pytest.approx(4200.0)

    def test_metric_agg_with_script(self, agg_reader):
        r = agg_reader.search({
            "size": 0,
            "aggs": {"a": {"avg": {"script": "doc['ms'].value / 10"}}}})
        # avg of 1..20 = 10.5
        assert r["aggregations"]["a"]["value"] == pytest.approx(10.5)

    def test_scripted_metric_respects_query(self, agg_reader):
        r = agg_reader.search({
            "size": 0,
            "query": {"range": {"ms": {"lte": 30}}},
            "aggs": {"t": {"scripted_metric": {
                "map_script": "doc['ms'].value"}}}})
        assert r["aggregations"]["t"]["value"] == pytest.approx(60.0)


class TestPercolatorPruning:
    def test_candidate_pruning_prunes_off_vocabulary_queries(self):
        """1,000 registered alert queries, a doc sharing vocabulary with
        3: only the candidates reach the executor (ref:
        PercolatorService MemoryIndex cheap-reject / query-term
        extraction), results unchanged."""
        import os
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        from elasticsearch_tpu.node import Node
        from elasticsearch_tpu.search.shard_searcher import ShardReader
        n = Node({"index.number_of_shards": 1})
        try:
            n.create_index("alerts", mappings={"properties": {
                "msg": {"type": "string"},
                "tag": {"type": "string", "index": "not_analyzed"}}})
            for i in range(997):
                n.register_percolator(
                    "alerts", f"q{i}",
                    {"query": {"match": {"msg": f"word{i}"}}})
            n.register_percolator(
                "alerts", "hit1",
                {"query": {"match": {"msg": "quantum"}}})
            n.register_percolator(
                "alerts", "hit2",
                {"query": {"bool": {"must": [
                    {"match": {"msg": "quantum"}},
                    {"term": {"tag": "physics"}}]}}})
            n.register_percolator(
                "alerts", "miss1",
                {"query": {"bool": {"must": [
                    {"match": {"msg": "quantum"}},
                    {"term": {"tag": "biology"}}]}}})
            counted = []
            orig = ShardReader.msearch

            def counting(self, bodies, with_partials=False, **kw):
                counted.append(len(bodies))
                return orig(self, bodies, with_partials, **kw)
            ShardReader.msearch = counting
            try:
                r = n.percolate("alerts", {"doc": {
                    "msg": "a quantum leap", "tag": "physics"}})
            finally:
                ShardReader.msearch = orig
            got = {m["_id"] for m in r["matches"]}
            assert got == {"hit1", "hit2"}, got
            # the device saw only the pruned candidate set
            assert sum(counted) <= 5, counted
        finally:
            n.close()

    def test_phrase_prefix_queries_not_falsely_pruned(self):
        import os
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        from elasticsearch_tpu.node import Node
        n = Node({"index.number_of_shards": 1})
        try:
            n.create_index("pp", mappings={"properties": {
                "msg": {"type": "string"}}})
            n.register_percolator("pp", "p1", {"query": {"match": {
                "msg": {"query": "quantum le",
                        "type": "phrase_prefix"}}}})
            r = n.percolate("pp", {"doc": {"msg": "a quantum leap"}})
            assert [m["_id"] for m in r["matches"]] == ["p1"], r
            # and the leading token still prunes honestly
            r = n.percolate("pp", {"doc": {"msg": "great leap"}})
            assert r["total"] == 0
        finally:
            n.close()
