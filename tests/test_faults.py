"""Partial-failure tolerance under deterministic fault injection.

Contracts (ISSUE 4): an injected shard error yields a PARTIAL response
whose hits/aggs are identical to a search over the surviving shards
only, with structured `_shards.failures`; a missed deadline yields
`timed_out: true` with the laggard failed-by-timeout;
`allow_partial_search_results=false` restores fail-fast; the mesh path
retries a failed shard row on the other replica row; breaker
reservations never leak across failure/timeout exits; batch-mates of a
faulted msearch item stay byte-identical to uninjected runs.
"""

import json
import time

import pytest

from elasticsearch_tpu.node import Node
from elasticsearch_tpu.utils import faults
from elasticsearch_tpu.utils.breaker import breaker_service
from elasticsearch_tpu.utils.errors import (CircuitBreakingError,
                                            FaultInjectedError,
                                            SearchTimeoutError)

import tests.test_search_core as core

BODY = {"query": {"match": {"message": "quick"}}, "size": 8,
        "aggs": {"lv": {"terms": {"field": "level", "size": 5}}}}


def _strip_timing(resp: dict) -> str:
    keep = {k: v for k, v in resp.items() if k not in ("took", "status")}
    return json.dumps(keep, sort_keys=True, default=str)


@pytest.fixture(autouse=True)
def _clean_registry():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture(scope="module")
def node():
    n = Node({"index.number_of_shards": 3})
    n.create_index("logs", mappings=core.MAPPING)
    for d in core.make_docs(240, seed=9):
        d = dict(d)
        did = d.pop("_id")
        n.index_doc("logs", did, d)
    n.refresh("logs")
    # second, single-shard index for cross-index msearch isolation
    n.create_index("other", mappings=core.MAPPING)
    for d in core.make_docs(60, seed=13):
        d = dict(d)
        did = d.pop("_id")
        n.index_doc("other", did, d)
    n.refresh("other")
    # warm the compile caches so deadline tests measure execution, not
    # the first-query jit
    n.search("logs", dict(BODY))
    n.search("other", dict(BODY))
    yield n
    n.close()


def _surviving_readers(node, index: str, dead_shard: int):
    svc = node.indices[index]
    return [(index, eng.acquire_searcher())
            for sid, eng in svc.shards.items() if sid != dead_shard]


class TestShardFailureIsolation:
    def test_partial_response_matches_surviving_shards(self, node):
        want = node._execute_on_readers(
            _surviving_readers(node, "logs", 1), dict(BODY))
        faults.configure("shard_error:shard=1:index=logs")
        got = node.search("logs", dict(BODY))
        # structured failure entry for the dead shard
        sh = got["_shards"]
        assert sh["total"] == 3 and sh["successful"] == 2 \
            and sh["failed"] == 1
        (f,) = sh["failures"]
        assert f["shard"] == 1 and f["index"] == "logs"
        assert f["reason"]["type"] == "FaultInjectedError"
        assert f["status"] == 500
        assert got["timed_out"] is False
        # hits + aggs identical to the surviving-shards-only reduce
        assert got["hits"] == want["hits"]
        assert got["aggregations"] == want["aggregations"]

    def test_disabled_injection_is_byte_identical(self, node):
        want = node.search("logs", dict(BODY))
        faults.configure("shard_error:shard=1:index=logs")
        node.search("logs", dict(BODY))
        faults.clear()
        got = node.search("logs", dict(BODY))
        assert _strip_timing(got) == _strip_timing(want)
        assert set(got["_shards"]) == {"total", "successful", "failed"}

    def test_all_shards_failed_hard_raises(self, node):
        # partial needs at least one survivor (ref: "all shards failed"
        # -> SearchPhaseExecutionException, not an empty 200)
        faults.configure("shard_error:index=logs")
        with pytest.raises(FaultInjectedError):
            node.search("logs", dict(BODY))

    def test_all_shards_timed_out_stays_partial(self, node):
        faults.configure("shard_delay:ms=150:index=logs")
        r = node.search("logs", dict(BODY, timeout="40ms"))
        assert r["timed_out"] is True
        assert r["_shards"]["successful"] == 0
        assert r["hits"]["hits"] == []

    def test_allow_partial_false_fails_fast(self, node):
        faults.configure("shard_error:shard=1:index=logs")
        with pytest.raises(FaultInjectedError):
            node.search("logs", dict(BODY,
                                     allow_partial_search_results=False))

    def test_allow_partial_default_from_settings(self, node):
        n = Node({"index.number_of_shards": 2,
                  "search.default_allow_partial_results": False})
        n.create_index("ff", mappings=core.MAPPING)
        for d in core.make_docs(40, seed=21):
            d = dict(d)
            did = d.pop("_id")
            n.index_doc("ff", did, d)
        n.refresh("ff")
        try:
            faults.configure("shard_error:shard=0:index=ff")
            with pytest.raises(FaultInjectedError):
                n.search("ff", dict(BODY))
            # per-request override wins over the node default
            r = n.search("ff", dict(BODY,
                                    allow_partial_search_results=True))
            assert r["_shards"]["failed"] == 1
        finally:
            faults.clear()
            n.close()

    def test_count_reports_real_shard_accounting(self, node):
        faults.configure("shard_error:shard=1:index=logs")
        r = node.count("logs", {"query": BODY["query"]})
        assert r["_shards"]["failed"] == 1
        assert r["_shards"]["failures"][0]["shard"] == 1

    def test_fault_counters_in_nodes_stats(self, node):
        faults.configure("shard_error:shard=1:index=logs")
        node.search("logs", dict(BODY))
        fi = node.nodes_stats()["nodes"][node.name]["fault_injection"]
        assert fi["enabled"] is True
        assert fi["rules"][0]["kind"] == "shard_error"
        assert fi["rules"][0]["fired"] >= 1


class TestSearchDeadline:
    def test_deadline_marks_timed_out_with_laggard_failed(self, node):
        faults.configure("shard_delay:ms=250:shard=2:index=logs")
        got = node.search("logs", dict(BODY, timeout="60ms"))
        assert got["timed_out"] is True
        sh = got["_shards"]
        assert sh["failed"] >= 1 and sh["successful"] >= 1
        laggard = [f for f in sh["failures"] if f["shard"] == 2]
        assert laggard and laggard[0]["reason"]["type"] == \
            "SearchTimeoutError"
        assert laggard[0]["status"] == 504
        # surviving shards still contribute hits
        assert got["hits"]["total"] > 0

    def test_default_search_timeout_setting(self, node):
        n = Node({"index.number_of_shards": 2,
                  "search.default_search_timeout": "60ms"})
        n.create_index("dt", mappings=core.MAPPING)
        for d in core.make_docs(40, seed=23):
            d = dict(d)
            did = d.pop("_id")
            n.index_doc("dt", did, d)
        n.refresh("dt")
        n.search("dt", dict(BODY))     # warm compiles
        try:
            faults.configure("shard_delay:ms=250:shard=1:index=dt")
            r = n.search("dt", dict(BODY))
            assert r["timed_out"] is True
            # a per-request -1 disables the node default again
            r = n.search("dt", dict(BODY, timeout=-1))
            assert r["timed_out"] is False
            assert r["_shards"]["failed"] == 0
        finally:
            faults.clear()
            n.close()

    def test_deadline_covers_multi_sort_path(self, node):
        # multi-key sorts execute host-side (no device group): the
        # deadline must still be consulted there
        faults.configure("shard_delay:ms=150:index=logs")
        body = {"query": {"match": {"message": "quick"}}, "size": 5,
                "sort": [{"size": "asc"}, {"level": "desc"}]}
        r = node.search("logs", dict(body, timeout="40ms"))
        assert r["timed_out"] is True
        assert r["_shards"]["successful"] == 0

    def test_timeout_param_does_not_change_results(self, node):
        want = node.search("logs", dict(BODY))
        got = node.search("logs", dict(BODY, timeout="30s"))
        assert _strip_timing(got) == _strip_timing(want)

    def test_deadline_traffic_still_coalesces(self, node):
        # identical-shape msearch items carrying the same `timeout`
        # must still share ONE batched dispatch: deadlines bucket in
        # the scheduler group key instead of keying raw floats
        items = [("other", {"query": {"match": {"message": "quick"}},
                            "size": 5, "timeout": "30s"})
                 for _ in range(4)]
        before = node._dispatch.stats.snapshot()
        r = node.msearch(items)
        after = node._dispatch.stats.snapshot()
        assert all(x["timed_out"] is False for x in r["responses"])
        assert after["coalesced_queries"] - before["coalesced_queries"] \
            >= 4

    def test_rest_params_reach_the_body(self):
        from elasticsearch_tpu.rest.server import _search_body
        b = _search_body({"timeout": "50ms",
                          "allow_partial_search_results": "false"}, {})
        assert b["timeout"] == "50ms"
        assert b["allow_partial_search_results"] is False
        b = _search_body({"allow_partial_search_results": "true"}, {})
        assert b["allow_partial_search_results"] is True


class TestCoalescedMsearchIsolation:
    def test_batch_mates_identical_when_one_item_shard_faults(self, node):
        items = [("logs", dict(BODY)),
                 ("other", dict(BODY)),      # <- one of its shards faults
                 ("logs", {"query": {"match": {"message": "lazy"}},
                           "size": 5}),
                 ("logs", dict(BODY))]
        want = node.msearch(items)["responses"]
        faults.configure("shard_error:index=other:shard=0")
        got = node.msearch(items)["responses"]
        for i in (0, 2, 3):
            assert _strip_timing(got[i]) == _strip_timing(want[i])
        # only the faulted index's shard failed, none of the mates'
        assert got[1]["_shards"]["failed"] == 1
        assert got[1]["_shards"]["failures"][0]["index"] == "other"
        faults.clear()
        again = node.msearch(items)["responses"]
        for g, w in zip(again, want):
            assert _strip_timing(g) == _strip_timing(w)


class TestBreakerSemantics:
    def test_breaker_trip_surfaces_and_counts(self, node):
        req = breaker_service().breaker("request")
        trips_before = req.trips
        used_before = req.used
        faults.configure("breaker_trip:breaker=request:shard=0:index=logs")
        got = node.search("logs", dict(BODY))
        assert got["_shards"]["failed"] == 1
        (f,) = got["_shards"]["failures"]
        assert f["reason"]["type"] == "CircuitBreakingError"
        assert f["status"] == 429
        stats = node.nodes_stats()["nodes"][node.name]["breakers"]
        # >=: the scheduler's per-job isolation retry legitimately hits
        # the injected trip a second time
        assert stats["request"]["tripped"] > trips_before
        assert {"limit_size_in_bytes", "estimated_size_in_bytes",
                "tripped"} <= set(stats["request"])
        assert "parent" in stats
        assert req.used == used_before

    def test_no_reservation_leak_on_failure_and_timeout(self, node):
        req = breaker_service().breaker("request")
        base = req.used
        faults.configure("shard_error:shard=1:index=logs")
        node.search("logs", dict(BODY))
        faults.configure("shard_delay:ms=250:shard=2:index=logs")
        r = node.search("logs", dict(BODY, timeout="60ms"))
        assert r["timed_out"] is True
        faults.clear()
        assert req.used == base

    def test_no_reservation_leak_on_collect_phase_fault(self, node):
        # a fault AFTER programs are enqueued (phase=collect) abandons
        # queued device results — their holds must release on the error
        # exit, not wait for the GC backstop
        req = breaker_service().breaker("request")
        base = req.used
        faults.configure("shard_error:phase=collect:shard=1:index=logs")
        r = node.search("logs", dict(BODY))
        assert r["_shards"]["failed"] == 1
        assert r["_shards"]["failures"][0]["shard"] == 1
        faults.clear()
        assert req.used == base


def _answer(resp: dict) -> str:
    """A response as compared: all of it but `took`, the time it took."""
    return json.dumps({k: v for k, v in resp.items() if k != "took"},
                      sort_keys=True)


class TestReplicaFailover:
    @pytest.fixture(scope="class")
    def mesh_node(self):
        n = Node({"index.number_of_shards": 4})
        n.create_index("m", mappings=core.MAPPING)
        for d in core.make_docs(200, seed=31):
            d = dict(d)
            did = d.pop("_id")
            n.index_doc("m", did, d)
        n.refresh("m")
        yield n
        n.close()

    def test_failed_row_retries_on_other_replica(self, mesh_node):
        from elasticsearch_tpu.parallel.mesh import build_mesh
        from elasticsearch_tpu.parallel.distributed import (
            PackedShards, DistributedSearcher)
        from elasticsearch_tpu.search.dispatch import failover_stats
        dist = DistributedSearcher(PackedShards.from_node_index(
            mesh_node, "m", build_mesh(4, 2)))
        body = {"query": {"match": {"message": "quick"}}, "size": 10}
        want = dist.search(body)
        retries = failover_stats.retries.count
        succeeded = failover_stats.succeeded.count
        faults.configure("shard_error:shard=2:replica=0:site=mesh")
        got = dist.search(body)
        assert _answer(got) == _answer(want)
        assert failover_stats.retries.count == retries + 1
        assert failover_stats.succeeded.count == succeeded + 1
        ns = mesh_node.nodes_stats()["nodes"][mesh_node.name]["dispatch"]
        assert ns["failover"]["retries"] >= retries + 1

    def test_single_replica_mesh_has_no_failover(self, mesh_node):
        from elasticsearch_tpu.parallel.mesh import build_mesh
        from elasticsearch_tpu.parallel.distributed import (
            PackedShards, DistributedSearcher)
        from elasticsearch_tpu.search.dispatch import failover_stats
        dist = DistributedSearcher(PackedShards.from_node_index(
            mesh_node, "m", build_mesh(4, 1)))
        failed = failover_stats.failed.count
        faults.configure("shard_error:shard=2:replica=0:site=mesh")
        with pytest.raises(FaultInjectedError):
            dist.search({"query": {"match": {"message": "quick"}},
                         "size": 10})
        # no retry was attempted: single-replica meshes fail the row
        assert failover_stats.failed.count == failed

    def test_collect_time_failure_fails_over(self, mesh_node):
        # jax dispatch is async: real device errors surface at the
        # device_get inside collect — failover must cover that exit too
        from elasticsearch_tpu.parallel.mesh import build_mesh
        from elasticsearch_tpu.parallel.distributed import (
            PackedShards, DistributedSearcher)
        from elasticsearch_tpu.search.dispatch import failover_stats
        dist = DistributedSearcher(PackedShards.from_node_index(
            mesh_node, "m", build_mesh(4, 2)))
        body = {"query": {"match": {"message": "quick"}}, "size": 10}
        want = dist.search(body)
        retries = failover_stats.retries.count
        succeeded = failover_stats.succeeded.count
        faults.configure(
            "shard_error:phase=collect:shard=1:replica=0:site=mesh")
        got = dist.search(body)
        assert _answer(got) == _answer(want)
        assert failover_stats.retries.count == retries + 1
        assert failover_stats.succeeded.count == succeeded + 1

    def test_a_device_error_at_the_program_s_end_fails_over(
            self, mesh_node, monkeypatch):
        """The collect waits for the program (`block_until_ready`)
        before it copies: a device error raised there reaches the
        failover as one raised by the copy did, and the failed collect
        is counted, all of its time `compute`."""
        import jax
        from elasticsearch_tpu.parallel.mesh import build_mesh
        from elasticsearch_tpu.parallel.distributed import (
            PackedShards, DistributedSearcher)
        from elasticsearch_tpu.search import executor
        from elasticsearch_tpu.search.dispatch import failover_stats
        dist = DistributedSearcher(PackedShards.from_node_index(
            mesh_node, "m", build_mesh(4, 2)))
        body = {"query": {"match": {"message": "quick"}}, "size": 10}
        want = dist.msearch_submit([dict(body)]).finish()
        retries = failover_stats.retries.count
        succeeded = failover_stats.succeeded.count
        req = breaker_service().breaker("request")
        base = req.used
        real, calls = jax.block_until_ready, []

        def fails_once(x):
            calls.append(x)
            if len(calls) == 1:
                raise jax.errors.JaxRuntimeError(
                    "INTERNAL: the program failed on the device")
            return real(x)

        monkeypatch.setattr(jax, "block_until_ready", fails_once)
        parts = executor.collect_parts()
        got = dist.msearch_submit([dict(body)]).finish()
        assert calls and [_answer(r) for r in got] \
            == [_answer(r) for r in want]
        assert failover_stats.retries.count == retries + 1
        assert failover_stats.succeeded.count == succeeded + 1
        assert req.used == base
        after = executor.collect_parts()
        assert [after[p]["count"] - parts[p]["count"]
                for p in ("compute", "copy")] == [1, 1]
        assert after["copy"]["sum"] == parts["copy"]["sum"]

    def test_mesh_straggler_delay_fires_at_collect(self, mesh_node):
        from elasticsearch_tpu.parallel.mesh import build_mesh
        from elasticsearch_tpu.parallel.distributed import (
            PackedShards, DistributedSearcher)
        dist = DistributedSearcher(PackedShards.from_node_index(
            mesh_node, "m", build_mesh(4, 1)))
        body = {"query": {"match": {"message": "quick"}}, "size": 10}
        want = dist.search(body)                   # warm compile
        reg = faults.configure("shard_delay:ms=80:shard=1:site=mesh")
        t0 = time.monotonic()
        got = dist.search(body)
        elapsed = time.monotonic() - t0
        assert reg.rules[0].fired >= 1             # not a silent no-op
        assert elapsed >= 0.08
        assert _answer(got) == _answer(want)

    def test_mesh_pending_deadline_raises(self, mesh_node):
        from elasticsearch_tpu.parallel.mesh import build_mesh
        from elasticsearch_tpu.parallel.distributed import (
            PackedShards, DistributedSearcher)
        dist = DistributedSearcher(PackedShards.from_node_index(
            mesh_node, "m", build_mesh(4, 1)))
        pend = dist.msearch_submit(
            [{"query": {"match": {"message": "quick"}}, "size": 5}],
            deadline=time.monotonic() - 0.001)
        with pytest.raises(SearchTimeoutError):
            pend.finish()

    def test_both_replicas_dead_fails_and_counts(self, mesh_node):
        from elasticsearch_tpu.parallel.mesh import build_mesh
        from elasticsearch_tpu.parallel.distributed import (
            PackedShards, DistributedSearcher)
        from elasticsearch_tpu.search.dispatch import failover_stats
        dist = DistributedSearcher(PackedShards.from_node_index(
            mesh_node, "m", build_mesh(4, 2)))
        failed = failover_stats.failed.count
        faults.configure("shard_error:shard=2:site=mesh")
        with pytest.raises(FaultInjectedError):
            dist.search({"query": {"match": {"message": "quick"}},
                         "size": 10})
        assert failover_stats.failed.count == failed + 1


class TestFailoverExhaustion:
    """ISSUE 7 regression: when `_collect_with_failover` exhausts every
    replica row mid-collect, the HARD failure (and the timeout-during-
    failover exit) must release every breaker reservation and never
    burn retries past the deadline."""

    @pytest.fixture(scope="class")
    def mesh_node(self):
        n = Node({"index.number_of_shards": 4})
        n.create_index("fx", mappings=core.MAPPING)
        for d in core.make_docs(160, seed=41):
            d = dict(d)
            did = d.pop("_id")
            n.index_doc("fx", did, d)
        n.refresh("fx")
        yield n
        n.close()

    def _dist(self, mesh_node):
        from elasticsearch_tpu.parallel.mesh import build_mesh
        from elasticsearch_tpu.parallel.distributed import (
            PackedShards, DistributedSearcher)
        return DistributedSearcher(PackedShards.from_node_index(
            mesh_node, "fx", build_mesh(4, 2)))

    def test_all_rows_failed_hard_releases_all_holds(self, mesh_node):
        dist = self._dist(mesh_node)
        body = {"query": {"match": {"message": "quick"}}, "size": 10}
        dist.search(body)                       # warm compile
        req = breaker_service().breaker("request")
        fd = breaker_service().breaker("fielddata")
        base_req, base_fd = req.used, fd.used
        # EVERY replica row fails at collect — the exhaustion exit —
        # with an injected breaker trip in the path for good measure
        faults.configure(
            "shard_error:phase=collect:shard=1:site=mesh,"
            "breaker_trip:breaker=request:shard=0:site=mesh")
        with pytest.raises(CircuitBreakingError):
            dist.search(body)
        faults.configure("shard_error:phase=collect:shard=1:site=mesh")
        with pytest.raises(FaultInjectedError):
            dist.search(body)
        faults.clear()
        assert req.used == base_req
        assert fd.used == base_fd

    def test_timeout_during_failover_stops_retry_loop(self, mesh_node):
        import time as _time
        from elasticsearch_tpu.search.dispatch import failover_stats
        dist = self._dist(mesh_node)
        body = {"query": {"match": {"message": "quick"}}, "size": 10}
        dist.search(body)                       # warm compile
        req = breaker_service().breaker("request")
        base = req.used
        # the straggler burns the whole budget at collect, THEN row 0
        # errors: the failover loop must observe the passed deadline
        # and exit with the timeout (504) instead of re-dispatching
        # against row 1 (which would succeed — but after the cutoff)
        faults.configure(
            "shard_delay:ms=120:shard=0:site=mesh,"
            "shard_error:phase=collect:replica=0:site=mesh")
        pend = dist.msearch_submit(
            [body], deadline=_time.monotonic() + 0.1)
        retries = failover_stats.retries.count
        with pytest.raises(SearchTimeoutError):
            pend.finish()
        faults.clear()
        # no retry was burned after the cutoff, nothing leaked
        assert failover_stats.retries.count == retries
        assert req.used == base

    def test_per_row_failover_counts(self, mesh_node):
        from elasticsearch_tpu.search.dispatch import failover_stats
        dist = self._dist(mesh_node)
        body = {"query": {"match": {"message": "quick"}}, "size": 10}
        dist.search(body)
        faults.configure("shard_error:shard=2:replica=0:site=mesh")
        dist.search(body)
        snap = failover_stats.snapshot()["per_row"]
        # the retry ran against (and succeeded on) physical row 1
        assert snap["1"]["retries"] >= 1
        assert snap["1"]["succeeded"] >= 1

    def test_process_stats_reset_on_owning_node_close(self):
        from elasticsearch_tpu.search import dispatch as dm
        a = Node({"node.name": "stats-a"})
        stats_a = dm.failover_stats
        assert stats_a.retries.count == 0    # fresh install at init
        dm.failover_stats.retries.inc()
        dm.eviction_stats.rows_dead.inc()
        b = Node({"node.name": "stats-b"})
        # node B installed fresh objects: no double-counting across
        # in-process nodes
        assert dm.failover_stats is not stats_a
        assert dm.failover_stats.retries.count == 0
        assert dm.eviction_stats.rows_dead.count == 0
        dm.failover_stats.retries.inc(5)
        b.close()                            # owner: resets
        assert dm.failover_stats.retries.count == 0
        a.close()                            # NOT the owner anymore: keeps
        dm.failover_stats.retries.inc(3)
        stale = dm.failover_stats
        a2 = Node({"node.name": "stats-c"})
        assert dm.failover_stats is not stale
        a2.close()


class TestRegistryDeterminism:
    def test_seeded_rate_sequences_repeat(self):
        from elasticsearch_tpu.utils.faults import FaultRegistry

        def fires(reg, n=200):
            out = []
            for _ in range(n):
                try:
                    reg.on_dispatch("reader", index="x", shard=0)
                    out.append(0)
                except FaultInjectedError:
                    out.append(1)
            return out

        spec = "shard_error:rate=0.4:seed=7"
        a = fires(FaultRegistry.parse(spec))
        b = fires(FaultRegistry.parse(spec))
        assert a == b
        assert 0 < sum(a) < 200
        c = fires(FaultRegistry.parse("shard_error:rate=0.4:seed=8"))
        assert a != c

    def test_selectors_restrict_firing(self):
        from elasticsearch_tpu.utils.faults import FaultRegistry
        reg = FaultRegistry.parse("shard_error:shard=1:index=a")
        reg.on_dispatch("reader", index="a", shard=0)       # no match
        reg.on_dispatch("reader", index="b", shard=1)       # no match
        reg.on_dispatch("mesh", index="a", shard=1,
                        phase="collect")                    # wrong phase
        with pytest.raises(FaultInjectedError):
            reg.on_dispatch("mesh", index="a", shard=1)
        assert reg.rules[0].fired == 1

    def test_unknown_kind_and_selector_rejected(self):
        from elasticsearch_tpu.utils.faults import FaultRegistry
        with pytest.raises(ValueError):
            FaultRegistry.parse("explode")
        with pytest.raises(ValueError):
            FaultRegistry.parse("shard_error:bogus=1")


class TestControlPlaneKinds:
    """Host-level fault kinds (PR 13): host_dead / ctrl_drop /
    ctrl_delay fire at the multihost control-plane boundaries
    (parallel/multihost.py) and NEVER at data-plane dispatch
    boundaries — and vice versa."""

    def test_host_dead_severs_both_directions(self):
        from elasticsearch_tpu.utils.faults import FaultRegistry
        reg = FaultRegistry.parse("host_dead:host=h1")
        # send to h1 AND receive from h1 both fail; other hosts flow
        with pytest.raises(FaultInjectedError):
            reg.on_ctrl("internal:mesh/ping", host="h1")
        with pytest.raises(FaultInjectedError):
            reg.on_ctrl("internal:mesh/exec", host="h1")
        reg.on_ctrl("internal:mesh/ping", host="h2")
        assert reg.rules[0].fired == 2

    def test_host_dead_is_persistent_and_phaseless(self):
        from elasticsearch_tpu.utils.faults import FaultRegistry
        with pytest.raises(ValueError):
            FaultRegistry.parse("host_dead:rate=0.5")
        with pytest.raises(ValueError):
            FaultRegistry.parse("host_dead:phase=collect")
        with pytest.raises(ValueError):
            FaultRegistry.parse("host_dead:shard=1")
        # and data-plane kinds reject the ctrl selectors
        with pytest.raises(ValueError):
            FaultRegistry.parse("shard_error:host=h1")
        with pytest.raises(ValueError):
            FaultRegistry.parse("shard_delay:action=ping:ms=5")

    def test_action_selector_matches_trailing_segment(self):
        from elasticsearch_tpu.utils.faults import FaultRegistry
        reg = FaultRegistry.parse("ctrl_drop:action=ping")
        with pytest.raises(FaultInjectedError):
            reg.on_ctrl("internal:mesh/ping", host="any")
        reg.on_ctrl("internal:mesh/exec", host="any")   # no match
        # the spec grammar splits on ':', so the trailing segment IS
        # the addressable form for namespaced actions
        reg2 = FaultRegistry.parse("ctrl_drop:action=exec")
        with pytest.raises(FaultInjectedError):
            reg2.on_ctrl("internal:mesh/exec", host="any")

    def test_ctrl_delay_sleeps_and_rate_draws_are_seeded(self):
        import time as _t
        from elasticsearch_tpu.utils.faults import FaultRegistry
        reg = FaultRegistry.parse("ctrl_delay:ms=30:host=h2")
        t0 = _t.monotonic()
        reg.on_ctrl("internal:mesh/fetch", host="h2")
        assert _t.monotonic() - t0 >= 0.025
        with pytest.raises(ValueError):
            FaultRegistry.parse("ctrl_delay:host=h2")  # needs ms=

        def fires(r, n=100):
            out = []
            for _ in range(n):
                try:
                    r.on_ctrl("internal:mesh/exec", host="h1")
                    out.append(0)
                except FaultInjectedError:
                    out.append(1)
            return out

        spec = "ctrl_drop:rate=0.4:seed=7"
        a = fires(FaultRegistry.parse(spec))
        b = fires(FaultRegistry.parse(spec))
        assert a == b and 0 < sum(a) < 100

    def test_ctrl_and_dispatch_boundaries_are_disjoint(self):
        from elasticsearch_tpu.utils.faults import FaultRegistry
        # a ctrl rule never fires at a data-plane dispatch boundary
        reg = FaultRegistry.parse("host_dead:host=h1,ctrl_drop")
        reg.on_dispatch("mesh", index="x", shard=0, replica=0)
        reg.on_dispatch("reader", index="x", shard=1, phase="collect")
        # a data-plane rule never fires at a ctrl boundary
        reg2 = FaultRegistry.parse("shard_error,device_dead:site=mesh")
        reg2.on_ctrl("internal:mesh/ping", host="h1")
        assert all(r.fired == 0 for r in reg.rules + reg2.rules)

    def test_host_dead_matches_probe_never_consumes(self):
        from elasticsearch_tpu.utils import faults as F
        F.configure("host_dead:host=h1")
        try:
            assert F.host_dead_matches("h1")
            assert not F.host_dead_matches("h2")
            assert F.active().rules[0].fired == 0
            # an action-pinned host_dead is not a fully dead machine:
            # the probe (a ping) would succeed, so it reports False
            F.configure("host_dead:host=h1:action=exec")
            assert not F.host_dead_matches("h1")
        finally:
            F.clear()

    def test_describe_carries_ctrl_selectors(self):
        from elasticsearch_tpu.utils.faults import FaultRegistry
        reg = FaultRegistry.parse(
            "ctrl_delay:ms=5:host=h2:action=fetch")
        d = reg.snapshot()["rules"][0]
        assert d["host"] == "h2" and d["action"] == "fetch"
        assert d["ms"] == 5.0 and d["kind"] == "ctrl_delay"


class TestBroadcastShardAccounting:
    def test_refresh_flush_report_real_failures(self, node):
        r = node.refresh("logs")
        assert r["_shards"] == {"total": 3, "successful": 3, "failed": 0}
        r = node.flush("logs")
        assert r["_shards"] == {"total": 3, "successful": 3, "failed": 0}
        svc = node.indices["logs"]
        orig = svc.refresh

        def boom():
            raise RuntimeError("disk on fire")

        svc.refresh = boom
        try:
            r = node.refresh("logs")
        finally:
            svc.refresh = orig
        assert r["_shards"]["failed"] == 3
        assert r["_shards"]["successful"] == 0
        assert r["_shards"]["failures"][0]["reason"]["type"] == \
            "RuntimeError"

    def test_mesh_timeouts_settings_driven(self):
        from elasticsearch_tpu.parallel.multihost import mesh_timeouts
        from elasticsearch_tpu.utils.settings import Settings
        t = mesh_timeouts(None)
        assert t == {"pack_send": 5.0, "pack_sync": 60.0,
                     "exec": 120.0, "fetch": 30.0}
        t = mesh_timeouts(Settings({"mesh.pack_sync_timeout": "5m",
                                    "mesh.exec_timeout": 1000}))
        assert t["pack_sync"] == 300.0
        assert t["exec"] == 1.0
