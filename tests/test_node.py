import pytest

from elasticsearch_tpu.node import Node
from elasticsearch_tpu.utils import (IndexNotFoundError, IndexAlreadyExistsError,
                                     DocumentMissingError)


@pytest.fixture()
def node():
    return Node({"index.number_of_shards": 3})


def seed_logs(node, n=60):
    ops = []
    for i in range(n):
        ops.append(("index", {"_index": "logs", "_id": str(i), "doc": {
            "message": f"request number {i} {'error' if i % 5 == 0 else 'ok'}",
            "status": "500" if i % 5 == 0 else "200",
            "size": 100 + i,
        }}))
    r = node.bulk(ops, refresh=True)
    assert not r["errors"]


def test_create_delete_index(node):
    node.create_index("idx1", mappings={"properties": {"f": {"type": "keyword"}}})
    with pytest.raises(IndexAlreadyExistsError):
        node.create_index("idx1")
    assert "idx1" in node.get_mapping()["idx1"]["mappings"]["_doc"] or True
    assert node.get_mapping("idx1")["idx1"]["mappings"]["_doc"]["properties"][
        "f"] == {"type": "keyword"}
    node.delete_index("idx1")
    with pytest.raises(IndexNotFoundError):
        node.delete_index("idx1")


def test_doc_crud_routed_across_shards(node):
    node.create_index("docs")
    for i in range(20):
        node.index_doc("docs", str(i), {"n": i})
    # docs spread over the 3 shards
    counts = [e.doc_count() for e in node.indices["docs"].shards.values()]
    assert sum(counts) == 20 and max(counts) < 20
    g = node.get_doc("docs", "7")
    assert g["found"] and g["_version"] == 1
    node.delete_doc("docs", "7")
    with pytest.raises(DocumentMissingError):
        node.get_doc("docs", "7")


def test_multi_shard_search_merges_correctly(node):
    seed_logs(node)
    r = node.search("logs", {"query": {"match": {"message": "error"}},
                             "size": 20})
    assert r["hits"]["total"] == 12
    assert r["_shards"]["total"] == 3 and r["_shards"]["successful"] == 3
    ids = {h["_id"] for h in r["hits"]["hits"]}
    assert ids == {str(i) for i in range(0, 60, 5)}
    # scores sorted descending across shards
    scores = [h["_score"] for h in r["hits"]["hits"]]
    assert scores == sorted(scores, reverse=True)


def test_multi_shard_pagination_consistent(node):
    seed_logs(node)
    pages = []
    for frm in range(0, 12, 4):
        r = node.search("logs", {"query": {"match": {"message": "error"}},
                                 "from": frm, "size": 4})
        pages.extend(h["_id"] for h in r["hits"]["hits"])
    full = node.search("logs", {"query": {"match": {"message": "error"}},
                                "size": 12})
    assert pages == [h["_id"] for h in full["hits"]["hits"]]


def test_multi_shard_aggs_merge(node):
    seed_logs(node)
    r = node.search("logs", {"size": 0, "aggs": {
        "by_status": {"terms": {"field": "status"},
                      "aggs": {"avg_size": {"avg": {"field": "size"}}}},
        "size_stats": {"stats": {"field": "size"}},
    }})
    buckets = {b["key"]: b for b in r["aggregations"]["by_status"]["buckets"]}
    assert buckets["200"]["doc_count"] == 48
    assert buckets["500"]["doc_count"] == 12
    expected_avg = sum(100 + i for i in range(0, 60, 5)) / 12
    assert buckets["500"]["avg_size"]["value"] == pytest.approx(expected_avg)
    st = r["aggregations"]["size_stats"]
    assert st["count"] == 60 and st["min"] == 100 and st["max"] == 159


def test_multi_shard_sort_by_field(node):
    seed_logs(node)
    r = node.search("logs", {"sort": [{"size": {"order": "desc"}}], "size": 5})
    assert [h["sort"][0] for h in r["hits"]["hits"]] == [159, 158, 157, 156, 155]
    r_asc = node.search("logs", {"sort": [{"size": "asc"}], "size": 3})
    assert [h["sort"][0] for h in r_asc["hits"]["hits"]] == [100, 101, 102]


def test_update_and_bulk_errors(node):
    node.index_doc("u", "1", {"a": 1, "nested": {"x": 1}}, refresh=True)
    node.update_doc("u", "1", {"doc": {"b": 2, "nested": {"y": 2}}})
    import json
    src = json.loads(node.get_doc("u", "1")["_source"])
    assert src == {"a": 1, "b": 2, "nested": {"x": 1, "y": 2}}
    r = node.bulk([("delete", {"_index": "u", "_id": "missing"}),
                   ("index", {"_index": "u", "_id": "2", "doc": {"a": 1}})])
    assert r["items"][0]["delete"]["status"] == 404
    assert r["items"][1]["index"]["status"] == 201


def test_count_and_wildcards(node):
    seed_logs(node)
    node.index_doc("other", "1", {"message": "error here"}, refresh=True)
    assert node.count("logs")["count"] == 60
    assert node.count("_all", {"query": {"match": {"message": "error"}}})["count"] == 13
    assert node.count("lo*")["count"] == 60
    assert node.count("logs,other")["count"] == 61


def test_auto_create_and_dynamic_mapping(node):
    node.index_doc("auto", "1", {"when": "2020-05-05", "n": 3}, refresh=True)
    m = node.get_mapping("auto")["auto"]["mappings"]["_doc"]["properties"]
    assert m["when"] == {"type": "date"}
    assert m["n"] == {"type": "long"}
    r = node.search("auto", {"query": {"range": {"when": {"gte": "2020-01-01"}}}})
    assert r["hits"]["total"] == 1


def test_cluster_health_and_cat(node):
    seed_logs(node, 5)
    h = node.cluster_health()
    assert h["status"] == "green" and h["active_shards"] == 3
    cat = node.cat_indices()
    assert cat[0]["index"] == "logs" and cat[0]["docs.count"] == 5


def test_node_restart_persistence(tmp_path):
    path = str(tmp_path / "data")
    n1 = Node({"path.data": path, "index.number_of_shards": 2})
    n1.create_index("persist", mappings={"properties": {
        "msg": {"type": "text"}, "k": {"type": "keyword"}}})
    for i in range(10):
        n1.index_doc("persist", str(i), {"msg": f"document {i}", "k": f"v{i % 3}"})
    n1.flush()
    n1.index_doc("persist", "10", {"msg": "translog only", "k": "v9"})
    n1.close()

    n2 = Node({"path.data": path, "index.number_of_shards": 2})
    assert "persist" in n2.indices
    r = n2.search("persist", {"query": {"match": {"msg": "document translog"}},
                              "size": 20})
    assert r["hits"]["total"] == 11
    assert n2.get_doc("persist", "10")["found"]


def test_sort_matching_docs_beat_nonmatching_missing(node):
    # review regression: docs matching the query but missing the sort field
    # must still be returned (after valued docs), never displaced by
    # non-matching docs
    node.create_index("sorts", settings={"index.number_of_shards": 1})
    for i in range(5):
        node.index_doc("sorts", f"m{i}", {"tag": "hit", "price": i})
    for i in range(3):
        node.index_doc("sorts", f"x{i}", {"tag": "hit"})      # no price
    for i in range(4):
        node.index_doc("sorts", f"n{i}", {"tag": "miss", "price": 100 + i})
    node.refresh("sorts")
    r = node.search("sorts", {"query": {"term": {"tag.keyword": "hit"}},
                              "sort": [{"price": "desc"}], "size": 10})
    ids = [h["_id"] for h in r["hits"]["hits"]]
    assert ids[:5] == ["m4", "m3", "m2", "m1", "m0"]
    assert set(ids[5:]) == {"x0", "x1", "x2"}
    assert r["hits"]["total"] == 8


def test_msm_percentage_and_terms_size_zero(node):
    seed_logs(node, 30)
    r = node.search("logs", {"query": {"match": {
        "message": {"query": "request number error", "minimum_should_match": "67%"}}},
        "size": 40})
    # 67% of 3 clauses = 2 required
    r2 = node.search("logs", {"query": {"bool": {
        "should": [{"match": {"message": "request"}},
                   {"match": {"message": "number"}},
                   {"match": {"message": "error"}}],
        "minimum_should_match": 2}}, "size": 40})
    assert r["hits"]["total"] == r2["hits"]["total"]
    r3 = node.search("logs", {"size": 0, "aggs": {"all_ids": {
        "terms": {"field": "message.keyword", "size": 0}}}})
    assert len(r3["aggregations"]["all_ids"]["buckets"]) == 30


def test_empty_index_agg_response(node):
    node.create_index("empty")
    r = node.search("empty", {"size": 0, "aggs": {
        "s": {"sum": {"field": "x"}},
        "t": {"terms": {"field": "k"}}}})
    assert r["aggregations"]["s"]["value"] == 0.0
    assert r["aggregations"]["t"]["buckets"] == []


def test_multi_field_subtypes(node):
    node.create_index("mf", mappings={"properties": {
        "status": {"type": "keyword", "fields": {"txt": {"type": "text"}}}}})
    node.index_doc("mf", "1", {"status": "Not Found Error"}, refresh=True)
    r = node.search("mf", {"query": {"match": {"status.txt": "error"}}})
    assert r["hits"]["total"] == 1
    r2 = node.search("mf", {"query": {"term": {"status": "Not Found Error"}}})
    assert r2["hits"]["total"] == 1


def test_bulk_writes_doc_metadata_through_once_per_request(tmp_path,
                                                           monkeypatch):
    """A durable index's per-doc metadata snapshot (_types.json) is
    written through ONCE per bulk request, before the response — not
    once per item (which made a bulk load quadratic in index size) —
    and still holds every item's entry when the bulk returns."""
    import json
    import os

    from elasticsearch_tpu.node import Node

    node = Node({"path.data": str(tmp_path / "data")})
    try:
        node.create_index("logs")
        replaced = []
        real_replace = os.replace

        def counting_replace(src, dst):
            if dst.endswith("_types.json"):
                replaced.append(dst)
            return real_replace(src, dst)

        monkeypatch.setattr(os, "replace", counting_replace)
        r = node.bulk([("index", {"_index": "logs", "_id": str(i),
                                  "doc": {"n": i}}) for i in range(50)])
        assert not r["errors"]
        assert len(replaced) == 1
        with open(replaced[0]) as f:
            assert set(json.load(f)["ts"]) == {str(i) for i in range(50)}
        # a lone write still writes through on its own
        node.index_doc("logs", "solo", {"n": -1})
        assert len(replaced) == 2
    finally:
        node.close()
