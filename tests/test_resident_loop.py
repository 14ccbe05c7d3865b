"""Resident query loop (search/resident.py + the executor's stepped
AOT entries).

Contracts under test:
  * OFF (ES_TPU_RESIDENT_LOOP unset): responses byte-identical to the
    seed behavior and every resident counter reads zero.
  * ON: responses byte-identical to the cold path — match queries, bool
    clause bundles, k == 0 size-0 aggs, fused+aggs, scroll pages — with
    resident_hits counting pinned-entry reuse.
  * Pack refresh mints a new fingerprint: the stale entry is evicted
    (bytes released) and the new pack re-admits.
  * Preemptive deadline: an injected shard_delay larger than the search
    timeout yields `timed_out: true` FROM THE DEVICE-SIDE per-chunk
    check without waiting out the full delay, and every breaker hold is
    released.
  * Mesh path: resident entry reuse with byte-identical responses.
"""

import gc
import json
import time

import pytest

from elasticsearch_tpu.node import Node
from elasticsearch_tpu.search import resident
from elasticsearch_tpu.utils import faults
from elasticsearch_tpu.utils.breaker import breaker_service

import tests.test_search_core as core


def _comparable(resp: dict) -> str:
    keep = {k: v for k, v in resp.items()
            if k not in ("took", "status", "_scroll_id")}
    return json.dumps(keep, sort_keys=True, default=str)


@pytest.fixture()
def resident_on(monkeypatch):
    """Enable residency with a clean slate; restore + clean after."""
    resident.reset()
    monkeypatch.setenv("ES_TPU_RESIDENT_LOOP", "1")
    yield
    monkeypatch.delenv("ES_TPU_RESIDENT_LOOP", raising=False)
    resident.reset()


@pytest.fixture()
def resident_off(monkeypatch):
    resident.reset()
    monkeypatch.delenv("ES_TPU_RESIDENT_LOOP", raising=False)
    yield
    resident.reset()


@pytest.fixture(scope="module")
def node():
    n = Node({"index.number_of_shards": 1})
    n.create_index("logs", mappings=core.MAPPING)
    for d in core.make_docs(260, seed=9):
        d = dict(d)
        did = d.pop("_id")
        n.index_doc("logs", did, d)
    n.refresh("logs")
    yield n
    n.close()


BODIES = [
    # plain match -> single-clause bundle
    {"query": {"match": {"message": "quick"}}, "size": 5},
    # bool clause bundle: must + boosted should + msm + range filter
    {"query": {"bool": {
        "must": [{"match": {"message": "dog"}}],
        "should": [{"match": {"message": {"query": "fox",
                                          "boost": 2.0}}},
                   {"match": {"message": "lazy"}}],
        "filter": [{"range": {"size": {"gte": 1000}}}],
        "minimum_should_match": 1}}, "size": 7},
    # k == 0: size-0 count + terms agg rides the match-mask engine
    {"size": 0, "query": {"match": {"message": "quick"}},
     "aggs": {"st": {"terms": {"field": "status", "size": 5}}}},
    # fused + aggs (emit-match mode)
    {"query": {"match": {"message": "fox"}}, "size": 4,
     "aggs": {"st": {"terms": {"field": "status", "size": 3}}}},
]


def _resident_counters(n: Node) -> dict:
    return n.nodes_stats()["nodes"][n.name]["dispatch"]["resident"]


class TestDisabledIsInert:
    def test_counters_zero_and_no_entries(self, node, resident_off):
        for b in BODIES:
            node.search("logs", dict(b))
        rs = _resident_counters(node)
        assert rs["resident_hits"] == 0
        assert rs["cold_dispatches"] == 0
        assert rs["preempted_by_deadline"] == 0
        assert rs["entry_count"] == 0
        assert rs["residency_bytes"] == 0


class TestResidentColdIdentity:
    def test_byte_identity_across_plans(self, node, resident_on,
                                        monkeypatch):
        monkeypatch.delenv("ES_TPU_RESIDENT_LOOP", raising=False)
        cold = [node.search("logs", dict(b)) for b in BODIES]
        monkeypatch.setenv("ES_TPU_RESIDENT_LOOP", "1")
        node.search("logs", dict(BODIES[0]))      # entry compile
        warm = [node.search("logs", dict(b)) for b in BODIES]
        warm = [node.search("logs", dict(b)) for b in BODIES]
        for c, w in zip(cold, warm):
            assert _comparable(c) == _comparable(w)
        rs = _resident_counters(node)
        assert rs["resident_hits"] > 0
        assert rs["entry_count"] > 0
        assert rs["residency_bytes"] > 0
        assert all(e["bytes"] >= 0 for e in rs["entries"])

    def test_scroll_pages_identical(self, node, resident_on, monkeypatch):
        body = {"query": {"match": {"message": "quick"}}, "size": 3}
        monkeypatch.delenv("ES_TPU_RESIDENT_LOOP", raising=False)
        c1 = node.search("logs", dict(body), scroll="1m")
        c2 = node.scroll(c1["_scroll_id"])
        monkeypatch.setenv("ES_TPU_RESIDENT_LOOP", "1")
        r1 = node.search("logs", dict(body), scroll="1m")
        r2 = node.scroll(r1["_scroll_id"])
        assert _comparable(c1) == _comparable(r1)
        assert _comparable(c2) == _comparable(r2)

    def test_msearch_identity(self, node, resident_on, monkeypatch):
        monkeypatch.delenv("ES_TPU_RESIDENT_LOOP", raising=False)
        cold = node.msearch([("logs", dict(b)) for b in BODIES])
        monkeypatch.setenv("ES_TPU_RESIDENT_LOOP", "1")
        warm = node.msearch([("logs", dict(b)) for b in BODIES])
        for c, w in zip(cold["responses"], warm["responses"]):
            assert _comparable(c) == _comparable(w)


class TestEvictionLifecycle:
    def test_pack_rebuild_invalidates_and_readmits(self, resident_on):
        """A merge rebuilds the pack under a NEW fingerprint: the stale
        entry can never be keyed again (fingerprint is in the key) and
        the dead-segment sweep evicts it; the rebuilt pack re-admits
        with byte-identical responses. (A plain refresh APPENDS a
        segment — the old segment keeps serving and its entry rightly
        stays pinned.)"""
        n = Node({"index.number_of_shards": 1})
        n.create_index("ev", mappings=core.MAPPING)
        try:
            for d in core.make_docs(120, seed=3):
                d = dict(d)
                did = d.pop("_id")
                n.index_doc("ev", did, d)
            n.refresh("ev")
            body = {"query": {"match": {"message": "quick"}}, "size": 5}
            n.search("ev", dict(body))
            n.search("ev", dict(body))
            rs = _resident_counters(n)
            assert rs["entry_count"] >= 1
            fp_before = {e["fingerprint"] for e in rs["entries"]}

            # new docs + force_merge -> ONE rebuilt segment, new
            # fingerprint; the 120-doc segment is garbage now
            for d in core.make_docs(40, seed=4):
                d = dict(d)
                did = "n" + d.pop("_id")
                n.index_doc("ev", did, d)
            n.refresh("ev")
            n.force_merge("ev")
            warm = n.search("ev", dict(body))
            import os
            os.environ.pop("ES_TPU_RESIDENT_LOOP")
            cold = n.search("ev", dict(body))
            os.environ["ES_TPU_RESIDENT_LOOP"] = "1"
            assert _comparable(cold) == _comparable(warm)
            gc.collect()
            n.search("ev", dict(body))     # admit triggers the sweep
            rs = _resident_counters(n)
            fps = {e["fingerprint"] for e in rs["entries"]}
            assert fps and not (fps & fp_before)
            assert rs["evictions"] >= 1
        finally:
            n.close()

    def test_cache_clear_evicts_pinned_entries(self, resident_on):
        n = Node({"index.number_of_shards": 1})
        n.create_index("cc", mappings=core.MAPPING)
        try:
            for d in core.make_docs(80, seed=5):
                d = dict(d)
                did = d.pop("_id")
                n.index_doc("cc", did, d)
            n.refresh("cc")
            n.search("cc", {"query": {"match": {"message": "quick"}},
                            "size": 5})
            assert _resident_counters(n)["entry_count"] >= 1
            n.clear_cache("cc")
            rs = _resident_counters(n)
            assert rs["entry_count"] == 0
            assert rs["evictions"] >= 1
        finally:
            n.close()

    def test_max_entries_lru_cap(self, resident_on):
        n = Node({"index.number_of_shards": 1,
                  "search.resident.max_entries": 2})
        n.create_index("lru", mappings=core.MAPPING)
        try:
            for d in core.make_docs(80, seed=6):
                d = dict(d)
                did = d.pop("_id")
                n.index_doc("lru", did, d)
            n.refresh("lru")
            # three distinct plan shapes -> three entries vs cap of 2
            for k in (3, 3, 5, 9):
                n.search("lru", {"query": {"match": {"message": "dog"}},
                                 "size": k})
            rs = _resident_counters(n)
            assert rs["entry_count"] <= 2
            assert rs["evictions"] >= 1
        finally:
            n.close()


@pytest.fixture()
def big_node():
    """~5k docs -> capacity 8192 -> 8 score tiles, so the stepped
    program has real chunks to preempt between."""
    n = Node({"index.number_of_shards": 1})
    n.create_index("big", mappings=core.MAPPING)
    docs = core.make_docs(200, seed=7)
    ops = []
    for i in range(5000):
        d = dict(docs[i % len(docs)])
        d.pop("_id")
        ops.append(("index", {"_index": "big", "_id": str(i), "doc": d}))
    n.bulk(ops, refresh=True)
    yield n
    n.close()


class TestPreemptiveDeadline:
    def test_device_side_timeout_cuts_injected_delay(self, big_node,
                                                     resident_on):
        n = big_node
        body = {"query": {"match": {"message": "quick"}}, "size": 5}
        n.search("big", dict(body))            # pin the entry
        req = breaker_service().breaker("request")
        used_before = req.used
        try:
            faults.configure("shard_delay:ms=3000:index=big")
            t0 = time.monotonic()
            r = n.search("big", dict(body, timeout="100ms"))
            elapsed_ms = (time.monotonic() - t0) * 1000.0
        finally:
            faults.clear()
        assert r["timed_out"] is True
        assert r["_shards"]["failed"] == 1
        assert r["_shards"]["failures"][0]["reason"]["type"] \
            == "SearchTimeoutError"
        # preempted within ~one chunk (3000/8 = 375ms) + overhead —
        # nowhere near the full 3000ms the cooperative path would sleep
        assert elapsed_ms < 1500, elapsed_ms
        assert resident.stats.preempted_by_deadline.count >= 1
        # every breaker hold released despite the timeout exit
        assert req.used == used_before

    def test_cooperative_parity_without_residency(self, big_node,
                                                  resident_off):
        """PR 4 semantics unchanged on the cold path: same rules, same
        timed_out response shape, full delay slept at collect."""
        n = big_node
        body = {"query": {"match": {"message": "quick"}}, "size": 5}
        try:
            faults.configure("shard_delay:ms=400:index=big")
            r = n.search("big", dict(body, timeout="50ms"))
        finally:
            faults.clear()
        assert r["timed_out"] is True
        assert r["_shards"]["failed"] == 1
        assert resident.stats.preempted_by_deadline.count == 0

    def test_no_deadline_sleeps_full_delay_on_device(self, big_node,
                                                     resident_on):
        """A straggler WITHOUT a timeout still waits the full injected
        delay (parity with the collect-boundary sleep) — the step loop
        meters it but nothing preempts."""
        n = big_node
        body = {"query": {"match": {"message": "quick"}}, "size": 5}
        n.search("big", dict(body))
        try:
            faults.configure("shard_delay:ms=300:index=big")
            t0 = time.monotonic()
            r = n.search("big", dict(body))
            elapsed_ms = (time.monotonic() - t0) * 1000.0
        finally:
            faults.clear()
        assert r["timed_out"] is False
        assert elapsed_ms >= 280, elapsed_ms


@pytest.fixture()
def pallas_forced(monkeypatch):
    """Force the Pallas engine (interpret mode off-TPU) for the fused
    path, clearing the availability caches on both edges."""
    from elasticsearch_tpu.ops import pallas_scoring as ps
    ps.pallas_enabled.cache_clear()
    ps.interpret_mode.cache_clear()
    monkeypatch.setenv("ES_TPU_PALLAS", "1")
    monkeypatch.setenv("ES_TPU_FUSED_BACKEND", "pallas")
    ps.pallas_enabled.cache_clear()
    ps.interpret_mode.cache_clear()
    yield
    monkeypatch.delenv("ES_TPU_PALLAS", raising=False)
    monkeypatch.delenv("ES_TPU_FUSED_BACKEND", raising=False)
    ps.pallas_enabled.cache_clear()
    ps.interpret_mode.cache_clear()


class TestPallasResident:
    """Pallas residency: with the kernel forced (interpret mode — the
    coverage is identical to a real TPU, only slower), fused plans pin
    Pallas STEPPED executables instead of falling back to cold
    dispatch, with byte-identical responses and a working preemptive
    deadline — the engines are interchangeable under residency."""

    def test_resident_pallas_byte_identity(self, node, pallas_forced,
                                           resident_on, monkeypatch):
        from elasticsearch_tpu.ops.pallas_scoring import resident_step_ok
        assert resident_step_ok(), "kernels must be steppable when on"
        monkeypatch.delenv("ES_TPU_RESIDENT_LOOP", raising=False)
        cold = [node.search("logs", dict(b)) for b in BODIES]
        monkeypatch.setenv("ES_TPU_RESIDENT_LOOP", "1")
        node.search("logs", dict(BODIES[0]))      # entry compile
        warm = [node.search("logs", dict(b)) for b in BODIES]
        warm = [node.search("logs", dict(b)) for b in BODIES]
        for c, w in zip(cold, warm):
            assert _comparable(c) == _comparable(w)
        rs = _resident_counters(node)
        assert rs["resident_hits"] > 0
        assert rs["entry_count"] > 0
        # the pinned entries must actually run the KERNEL engine — not
        # silently fall back to XLA
        assert all(e["backend"] == "pallas" for e in rs["entries"]), \
            rs["entries"]

    def test_untuned_pallas_candidate_goes_cold_then_resident(
            self, node, resident_on, monkeypatch):
        """Off-TPU without forcing, the kernel is no candidate -> every
        fused shape resolves to the XLA engine and residency admits it
        immediately; the _resident_backend contract (None = cold until
        tuned) is what the forced-pallas test above exercises."""
        body = {"query": {"match": {"message": "dog"}}, "size": 3}
        node.search("logs", dict(body))
        node.search("logs", dict(body))
        assert _resident_counters(node)["resident_hits"] > 0

    def test_forced_pallas_without_kernels_enabled_still_resident(
            self, node, resident_on, monkeypatch):
        """ES_TPU_FUSED_BACKEND=pallas WITHOUT ES_TPU_PALLAS: the
        forced engine must still reach the stepped resident path (the
        chunked walk runs in interpret mode like the forced cold path
        does) — not silently pin every dispatch to cold."""
        from elasticsearch_tpu.ops import pallas_scoring as ps
        ps.pallas_enabled.cache_clear()
        ps.interpret_mode.cache_clear()
        monkeypatch.setenv("ES_TPU_FUSED_BACKEND", "pallas")
        try:
            body = {"query": {"match": {"message": "lazy"}}, "size": 3}
            node.search("logs", dict(body))
            node.search("logs", dict(body))
            rs = _resident_counters(node)
            assert rs["resident_hits"] > 0
            assert any(e["backend"] == "pallas" for e in rs["entries"])
        finally:
            monkeypatch.delenv("ES_TPU_FUSED_BACKEND")
            ps.pallas_enabled.cache_clear()
            ps.interpret_mode.cache_clear()

    def test_pallas_preemptive_deadline_cuts_injected_delay(
            self, big_node, pallas_forced, resident_on):
        """Preemptive-deadline parity Pallas-vs-XLA: the chunked
        pallas_call walk hosts the same per-chunk check, so an injected
        straggler larger than the timeout is cut short from the device
        on this engine too."""
        n = big_node
        body = {"query": {"match": {"message": "quick"}}, "size": 5}
        n.search("big", dict(body))            # pin the pallas entry
        req = breaker_service().breaker("request")
        used_before = req.used
        try:
            faults.configure("shard_delay:ms=3000:index=big")
            t0 = time.monotonic()
            r = n.search("big", dict(body, timeout="100ms"))
            elapsed_ms = (time.monotonic() - t0) * 1000.0
        finally:
            faults.clear()
        assert r["timed_out"] is True
        assert r["_shards"]["failures"][0]["reason"]["type"] \
            == "SearchTimeoutError"
        # preempted within ~one chunk (3000/8 = 375ms) + interpret-mode
        # overhead — nowhere near the full 3000ms cooperative sleep
        assert elapsed_ms < 2000, elapsed_ms
        assert resident.stats.preempted_by_deadline.count >= 1
        assert req.used == used_before


class TestMeshSteppedDeadline:
    """The mesh path's collective-safe stepped deadline: a deadline-
    carrying fused search runs the chunked program form whose per-chunk
    verdict is psum'd over both mesh axes — byte-identical results when
    the deadline holds, a device-reported SearchTimeoutError when it
    does not (mesh timeouts were purely cooperative before)."""

    @pytest.fixture()
    def dist(self):
        from elasticsearch_tpu.parallel.mesh import build_mesh
        from elasticsearch_tpu.parallel.distributed import (
            PackedShards, DistributedSearcher)
        n = Node({"index.number_of_shards": 4})
        n.create_index("slogs", mappings=core.MAPPING)
        try:
            for d in core.make_docs(240, seed=23):
                d = dict(d)
                did = d.pop("_id")
                n.index_doc("slogs", did, d)
            n.refresh("slogs")
            mesh = build_mesh(4, 2)
            packed = PackedShards.from_node_index(n, "slogs", mesh)
            yield DistributedSearcher(packed)
        finally:
            n.close()

    BODY = {"query": {"match": {"message": "quick"}}, "size": 10}

    def test_stepped_program_byte_identity(self, dist):
        plain = dist.search(dict(self.BODY))
        stepped = dist.msearch([dict(self.BODY)],
                               deadline=time.monotonic() + 300)[0]
        assert _comparable(plain) == _comparable(stepped)

    def test_device_verdict_raises_timeout(self, dist):
        from elasticsearch_tpu.utils.errors import SearchTimeoutError
        st = dist._dispatch_uniform([dict(self.BODY)],
                                    deadline=time.monotonic() - 1.0)
        assert st["stepped"]
        before = resident.stats.preempted_by_deadline.count
        with pytest.raises(SearchTimeoutError):
            dist._collect_uniform(st)
        assert resident.stats.preempted_by_deadline.count == before + 1

    def test_env_kill_switch_stays_cooperative(self, dist, monkeypatch):
        monkeypatch.setenv("ES_TPU_MESH_STEPPED", "0")
        st = dist._dispatch_uniform([dict(self.BODY)],
                                    deadline=time.monotonic() + 300)
        assert not st["stepped"]
        raws = dist._collect_uniform(st)
        assert raws and raws[0]["total"] >= 0


class TestMeshResidentReuse:
    def test_mesh_entry_reuse_parity(self, resident_on, monkeypatch):
        from elasticsearch_tpu.parallel.mesh import build_mesh
        from elasticsearch_tpu.parallel.distributed import (
            PackedShards, DistributedSearcher)
        n = Node({"index.number_of_shards": 4})
        n.create_index("mlogs", mappings=core.MAPPING)
        try:
            for d in core.make_docs(240, seed=13):
                d = dict(d)
                did = d.pop("_id")
                n.index_doc("mlogs", did, d)
            n.refresh("mlogs")
            mesh = build_mesh(4, 2)
            packed = PackedShards.from_node_index(n, "mlogs", mesh)
            dist = DistributedSearcher(packed)
            body = {"query": {"match": {"message": "quick"}}, "size": 10}

            monkeypatch.delenv("ES_TPU_RESIDENT_LOOP", raising=False)
            cold = dist.search(dict(body))
            monkeypatch.setenv("ES_TPU_RESIDENT_LOOP", "1")
            first = dist.search(dict(body))
            hits_before = resident.stats.resident_hits.count
            again = dist.search(dict(body))
            assert _comparable(cold) == _comparable(first)
            assert _comparable(first) == _comparable(again)
            # the pinned shard_map entry (keyed on per-shard-row
            # fingerprints) was reused, not recompiled
            assert resident.stats.resident_hits.count > hits_before
        finally:
            n.close()
