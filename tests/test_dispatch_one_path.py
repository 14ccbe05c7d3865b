"""The scheduler serves a round one way for one reader group and for
several (search/dispatch.py `_serve_groups`): `msearch_submit` on every
group, then `finish` on every group, the same counters and the same
error policy on both halves. A one-shard search and a fan-out differ in
the number of groups and in nothing else."""

import time

import pytest

from elasticsearch_tpu.node import Node
from elasticsearch_tpu.search import dispatch
from elasticsearch_tpu.search.shard_searcher import ShardReader
from elasticsearch_tpu.utils.errors import SearchTimeoutError

DOCS = 120
QUERY = {"query": {"term": {"k": "g3"}}, "size": 5}
BAD = {"query": {"range": {"n": {"gte": "zzz"}}}}


@pytest.fixture(scope="module", params=[1, 3], ids=["1shard", "3shards"])
def node(request):
    n = Node({"index.number_of_shards": request.param})
    n.create_index("one", mappings={"properties": {
        "k": {"type": "keyword"}, "n": {"type": "long"}}})
    for i in range(DOCS):
        n.index_doc("one", str(i), {"k": f"g{i % 7}", "n": i})
    n.refresh("one")
    yield n
    n.close()


def shards(node) -> int:
    return len(node.indices["one"].shards)


def readers(node) -> list:
    return [eng.acquire_searcher()
            for _sid, eng in sorted(node.indices["one"].shards.items())]


@pytest.fixture
def reader_calls(monkeypatch):
    """Counts of the two reader entries, spied on the class: `msearch`
    is `msearch_submit(...).finish()`, so a call of the wrapper counts
    under both names."""
    calls = {"msearch": 0, "msearch_submit": 0}
    for name in calls:
        def spy(self, *a, _name=name,
                _orig=getattr(ShardReader, name), **kw):
            calls[_name] += 1
            return _orig(self, *a, **kw)
        monkeypatch.setattr(ShardReader, name, spy)
    return calls


def test_the_scheduler_calls_submit_once_a_shard_and_never_the_wrapper(
        node, reader_calls):
    r = node.search("one", dict(QUERY))
    assert r["hits"]["total"] == len(range(3, DOCS, 7))
    assert r["_shards"]["failed"] == 0
    assert reader_calls == {"msearch": 0, "msearch_submit": shards(node)}


def test_a_round_counts_the_same_for_one_group_and_for_several(node):
    node.search("one", dict(QUERY))  # compiled; the high-water mark set
    stats = node._dispatch.stats
    op = node.indices["one"].op_stats
    before, fetch_ms = stats.snapshot(), op.fetch_time_ms
    node.search("one", dict(QUERY))
    after = stats.snapshot()
    programs = sum(after["launches"].values()) \
        - sum(before["launches"].values())
    assert programs == shards(node)  # one segment a shard
    delta = {k: after[k] - before[k]
             for k in ("searches", "queries", "batches_dispatched",
                       "coalesced_queries")}
    assert delta == {"searches": 1, "queries": shards(node),
                     "batches_dispatched": programs,
                     "coalesced_queries": 0}
    # every program of the round was enqueued before its first collect
    assert after["pipeline_depth"] >= programs
    assert stats.pipeline_depth.last == programs
    # the jobs' fetch seconds reach indices.search.fetch_time_in_millis
    assert op.fetch_time_ms > fetch_ms
    assert after["leader"]["groups"] - before["leader"]["groups"] \
        == shards(node)


def test_a_body_that_does_not_parse_fails_alone(node):
    """Two jobs coalesced into each reader group, one of them
    malformed: the submit raises for the group, each job is retried
    alone, the bad one keeps its error and its batch-mate answers."""
    batch = node._dispatch.batch()
    good = [batch.submit(r, dict(QUERY), with_partials=True)
            for r in readers(node)]
    bad = [batch.submit(r, dict(BAD), with_partials=True)
           for r in readers(node)]
    batch.dispatch()
    assert sum(j.result()["hits"]["total"] for j in good) \
        == len(range(3, DOCS, 7))
    for j in bad:
        with pytest.raises(Exception) as ei:
            j.result()
        assert not isinstance(ei.value, (SearchTimeoutError, RuntimeError))


@pytest.mark.parametrize("half", ["submit", "finish"])
def test_a_timeout_fails_the_groups_jobs_and_is_not_retried(
        node, half, reader_calls, monkeypatch):
    """One policy for both halves: a deadline that has passed cannot
    un-pass, so no job of the group is sent to the reader again."""
    deadline = None
    if half == "submit":
        def raising(self, *a, **kw):
            reader_calls["msearch_submit"] += 1
            raise SearchTimeoutError(self.index_name)
        monkeypatch.setattr(ShardReader, "msearch_submit", raising)
    else:
        # the reader's own cooperative deadline: finish() raises
        deadline = time.monotonic() - 1.0
    batch = node._dispatch.batch()
    jobs = [batch.submit(r, dict(QUERY), with_partials=True,
                         deadline=deadline)
            for r in readers(node) for _ in range(2)]
    batch.dispatch()
    for j in jobs:
        with pytest.raises(SearchTimeoutError):
            j.result()
    assert reader_calls == {"msearch": 0, "msearch_submit": shards(node)}


@pytest.mark.parametrize("name", ["submit_stats", "note_submit_stats"])
def test_no_side_channel_from_reader_to_scheduler(name):
    assert not hasattr(dispatch, name)


def test_no_second_round_executor():
    assert not hasattr(dispatch.DispatchScheduler, "_run_sync")


# what GET /_nodes/stats/dispatch returned before the two round
# executors became one (commit fa237f9), neither guard armed
DISPATCH_KEYS = [
    "adopted_batches", "batches_dispatched", "bound_plans",
    "coalesced_queries", "collect_lead", "collects", "eviction", "failover", "launches",
    "leader", "membership", "merge", "phases", "pipeline_depth",
    "queries", "resident", "searches", "traffic", "window"]


def test_the_stats_keep_their_keys(node):
    snap = node._dispatch.stats.snapshot()
    assert sorted(set(snap) - {"transfer_guard_trips", "recompiles",
                               "race_guard_trips"}) == DISPATCH_KEYS
    assert sorted(snap["window"]) == ["batches", "coalesced", "hit_rate"]
    assert sorted(snap["leader"]) == ["count", "groups", "mean", "sum"]
    assert sorted(snap["merge"]) == ["count", "hits", "mean",
                                     "shard_results", "sum"]
    assert sorted(snap["collects"]) == ["prefetched", "total"]
    assert sorted(snap["bound_plans"]) == ["bypassed", "entries",
                                           "evictions", "hits", "misses"]
    assert sorted(snap["collect_lead"]) == ["count", "mean", "sum"]
    assert node.nodes_stats()["nodes"][node.name]["dispatch"].keys() \
        == snap.keys()
