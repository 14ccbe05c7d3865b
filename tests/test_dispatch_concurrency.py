"""Dispatch-scheduler serving queue under concurrency: concurrent
searches coalesce into fewer device programs with identical results and
no idle latency (the leader-drain behavior search/dispatch.py inherited
from the retired per-reader micro-batcher), and the bounded search pool
still rejects with 429 at saturation."""

import threading

import pytest

from elasticsearch_tpu.node import Node


@pytest.fixture(scope="module")
def node():
    n = Node({"index.number_of_shards": 1})
    n.create_index("mb", mappings={"properties": {
        "k": {"type": "keyword"}, "n": {"type": "long"}}})
    for i in range(300):
        n.index_doc("mb", str(i), {"k": f"g{i % 7}", "n": i})
    n.refresh("mb")
    return n


def test_lone_query_unchanged(node):
    r = node.search("mb", {"query": {"term": {"k": "g3"}}, "size": 0})
    assert r["hits"]["total"] == len([i for i in range(300)
                                      if i % 7 == 3])


def test_concurrent_queries_coalesce_and_agree(node, monkeypatch):
    import time
    from elasticsearch_tpu.search.shard_searcher import ShardReader
    calls = []
    orig = ShardReader.msearch_submit

    def counting_submit(self, bodies, with_partials=False, **kw):
        calls.append(len(bodies))
        time.sleep(0.02)  # emulate device dispatch time: forces overlap
        return orig(self, bodies, with_partials, **kw)
    monkeypatch.setattr(ShardReader, "msearch_submit", counting_submit)

    n_threads = 24
    results: list = [None] * n_threads
    errors: list = []
    barrier = threading.Barrier(n_threads)

    def worker(i):
        try:
            barrier.wait()
            lo, hi = (i % 5) * 40, (i % 5) * 40 + 80
            r = node.search("mb", {
                "size": 0,
                "query": {"range": {"n": {"gte": lo, "lt": hi}}},
                "aggs": {"g": {"terms": {"field": "k", "size": 10}}}})
            results[i] = (lo, hi, r)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors
    for i, (lo, hi, r) in enumerate(results):
        want = len([x for x in range(300) if lo <= x < hi])
        assert r["hits"]["total"] == want, (i, lo, hi)
        assert sum(b["doc_count"]
                   for b in r["aggregations"]["g"]["buckets"]) == want
    # every request was served...
    assert sum(calls) == n_threads, calls
    # ...and arrivals during an in-flight dispatch coalesced (fewer
    # programs than requests, with at least one multi-body batch). The
    # exact ratio depends on scheduler interleaving with the bounded
    # search pool, so assert the mechanism, not a fraction.
    assert len(calls) < n_threads, calls
    assert max(calls) >= 2, calls


def test_error_propagates_to_every_caller(node):
    with pytest.raises(Exception):
        node.search("mb", {"query": {"range": {"n": {"gte": "zzz"}}}})
    # the reader's batcher survives a failed batch and still serves
    r = node.search("mb", {"size": 0})
    assert r["hits"]["total"] == 300


class TestSearchPoolRejection:
    def test_saturated_search_pool_rejects_429(self):
        """ref: ThreadPool.java bounded SEARCH queue +
        EsRejectedExecutionException -> HTTP 429."""
        import time
        from elasticsearch_tpu.utils.threadpool import (
            EsRejectedExecutionError, NamedPool)
        n = Node({"index.number_of_shards": 1})
        try:
            n.create_index("q")
            n.index_doc("q", "1", {"a": 1})
            n.refresh("q")
            # shrink the search pool to 1 thread / 0 queue
            n.thread_pool.pools["search"] = NamedPool("search", 1, 0)
            gate = threading.Event()
            from elasticsearch_tpu.search.shard_searcher import ShardReader
            orig = ShardReader.msearch_submit

            def slow(self, bodies, with_partials=False, **kw):
                gate.wait(timeout=10)
                return orig(self, bodies, with_partials, **kw)
            ShardReader.msearch_submit = slow
            try:
                t = threading.Thread(
                    target=lambda: n.search("q", {"size": 0}))
                t.start()
                time.sleep(0.1)  # occupy the single worker
                with pytest.raises(EsRejectedExecutionError) as ei:
                    for _ in range(5):
                        n.search("q", {"size": 0})
                assert ei.value.status == 429
            finally:
                gate.set()
                ShardReader.msearch_submit = orig
                t.join(timeout=10)
            assert n.thread_pool.pools["search"].stats()["rejected"] >= 1
        finally:
            n.close()
