"""Streaming write path (ROADMAP item 1): delta packs + generation-
preserving refresh.

Covers the PR's acceptance surface:

  * byte-identity of search responses across the (buffered -> refreshed
    delta -> compacted base) lifecycle for fused bundles, aggregations,
    k == 0, and field-sort plans — compaction is the impact-preserving
    concat (index/segment.concat_segments), so even BM25 scores are
    preserved bit-for-bit;
  * byte-identity of the base+delta ONE-dispatch pack path
    (executor.execute_pack_async) against the per-segment fallback;
  * a refresh with pending buffered docs performs ZERO autotune
    re-tunes, ZERO resident-executable evictions, and ZERO XLA
    recompiles (asserted via the new refresh_reuses counter and the
    trace_guarded fixture's recompile count); only compaction re-keys;
  * mesh pinned-program survival across a MeshIndex tail refresh;
  * satellites: monotonic tombstone GC clock, autotune store sweep +
    load-time cap, run_build_aside abort discipline;
  * a seeded concurrent writer+searcher soak (slow-marked) asserting
    no torn reads and monotonic visibility.
"""

import copy
import json
import os
import threading

import numpy as np
import pytest

from elasticsearch_tpu.index.engine import Engine
from elasticsearch_tpu.index.mapping import MapperService
from elasticsearch_tpu.index.segment import (SegmentBuilder,
                                             concat_segments,
                                             pad_delta_shapes)
from elasticsearch_tpu.search import executor, resident
from elasticsearch_tpu.utils.settings import Settings

WORDS = ["alpha", "beta", "gamma", "delta", "epsilon", "zeta", "eta"]

MAPPING = {"doc": {"properties": {
    "body": {"type": "string"},
    "tag": {"type": "keyword"},
    "n": {"type": "long"}}}}


def make_engine(**over) -> Engine:
    conf = {"index.streaming.delta": True}
    conf.update(over)
    s = Settings(conf)
    m = MapperService(index_settings=s)
    m.put_type_mapping("doc", MAPPING["doc"])
    return Engine("idx", 0, m, settings=s)


def fill(eng: Engine, lo: int, hi: int) -> None:
    for i in range(lo, hi):
        eng.index(f"d{i}", {
            "body": " ".join(WORDS[j % 7] for j in range(i, i + 4)),
            "tag": f"k{i % 3}", "n": i})


def strip(resp: dict) -> dict:
    out = copy.deepcopy(resp)
    out.pop("took", None)
    return out


QUERIES = [
    # fused bool bundle: must scoring clause + range filter
    {"query": {"bool": {"must": [{"match": {"body": "alpha beta"}}],
                        "filter": [{"range": {"n": {"gte": 3,
                                                    "lte": 50}}}]}},
     "size": 12},
    # aggs ride the emit-match engine
    {"query": {"match": {"body": "gamma"}}, "size": 5,
     "aggs": {"t": {"terms": {"field": "tag"}},
              "h": {"histogram": {"field": "n", "interval": 10}}}},
    # k == 0 (match-mask-only engine)
    {"query": {"match": {"body": "zeta"}}, "size": 0},
    # field sort (unfused path; delta is just another segment)
    {"query": {"match": {"body": "epsilon"}},
     "sort": [{"n": {"order": "desc"}}], "size": 6},
    # must_not + msm
    {"query": {"bool": {"should": [{"match": {"body": "alpha"}},
                                   {"match": {"body": "eta"}}],
                        "minimum_should_match": 1,
                        "must_not": [{"range": {"n": {"gte": 48}}}]}},
     "size": 10},
]


class TestDeltaLifecycle:
    def test_refresh_is_epoch_bump_not_a_segment_append(self):
        eng = make_engine()
        fill(eng, 0, 20)
        eng.refresh()
        assert len(eng.segments) == 1
        gen0 = eng.base_generation()
        epoch0 = eng._delta_epoch
        fill(eng, 20, 30)
        eng.refresh()
        # still ONE delta segment (rebuilt), not an appended chain
        assert len(eng.segments) == 1
        assert eng.segments[-1].delta_parent == gen0
        assert eng._delta_epoch == epoch0 + 1
        assert eng.base_generation() == gen0
        # delta cache key is epoch-independent within a capacity bucket
        assert eng.segments[-1].cache_key().startswith(
            f"delta({gen0}):c")

    def test_buffered_docs_invisible_until_refresh(self):
        eng = make_engine()
        fill(eng, 0, 8)
        eng.refresh()
        r = eng.acquire_searcher()
        t0 = strip(r.search({"query": {"match_all": {}}, "size": 0}))
        fill(eng, 8, 12)
        assert strip(r.search({"query": {"match_all": {}},
                               "size": 0})) == t0  # buffered: invisible
        eng.refresh()
        t1 = eng.acquire_searcher().search(
            {"query": {"match_all": {}}, "size": 0})
        assert t1["hits"]["total"] == 12

    def test_update_and_delete_across_epochs(self):
        eng = make_engine()
        fill(eng, 0, 10)
        eng.refresh()
        eng.delete("d3")
        eng.index("d4", {"body": "alpha alpha alpha", "tag": "kX",
                         "n": 400})
        eng.refresh()
        r = eng.acquire_searcher()
        with pytest.raises(Exception):
            eng.get("d3")
        got = eng.get("d4")
        assert got["_version"] == 2
        total = r.search({"query": {"match_all": {}},
                          "size": 0})["hits"]["total"]
        assert total == 9
        # compaction folds the same state
        assert eng.compact()
        assert eng.doc_count() == 9
        assert eng.get("d4")["_version"] == 2

    def test_byte_identity_buffered_delta_compacted(self):
        eng = make_engine()
        fill(eng, 0, 40)
        eng.refresh()
        assert eng.compact()          # a real base generation
        fill(eng, 40, 55)
        # BUFFERED state: responses reflect the base only
        r = eng.acquire_searcher()
        buffered = [strip(r.search(copy.deepcopy(q))) for q in QUERIES]
        eng.refresh()
        # DELTA state
        r = eng.acquire_searcher()
        delta = [strip(r.search(copy.deepcopy(q))) for q in QUERIES]
        for b, d in zip(buffered, delta):
            assert b != d or b["hits"]["total"] == d["hits"]["total"]
        # COMPACTED state must be byte-identical to the delta state —
        # the impact-preserving concat keeps every score bit-for-bit
        assert eng.compact()
        r = eng.acquire_searcher()
        compacted = [strip(r.search(copy.deepcopy(q))) for q in QUERIES]
        assert delta == compacted

    def test_delta_state_matches_full_rebuild_oracle(self):
        eng = make_engine()
        fill(eng, 0, 30)
        eng.refresh()
        assert eng.compact()
        # three refresh epochs of writes
        for lo, hi in ((30, 34), (34, 40), (40, 43)):
            fill(eng, lo, hi)
            eng.refresh()
        # oracle: the same final doc set, ONE refresh (base + one delta)
        oracle = make_engine()
        fill(oracle, 0, 30)
        oracle.refresh()
        assert oracle.compact()
        fill(oracle, 30, 43)
        oracle.refresh()
        ra = eng.acquire_searcher()
        rb = oracle.acquire_searcher()
        for q in QUERIES:
            assert strip(ra.search(copy.deepcopy(q))) == \
                strip(rb.search(copy.deepcopy(q)))

    def test_compaction_threshold_auto_triggers(self):
        eng = make_engine(**{"index.delta.min_compact_docs": 8,
                             "index.delta.compact_ratio": 0.25})
        fill(eng, 0, 6)
        eng.refresh()
        assert eng._compactions == 0
        fill(eng, 6, 24)
        eng.refresh()      # delta (24 docs) > max(8, 0) -> sync compact
        assert eng._compactions == 1
        assert len(eng.segments) == 1
        assert eng.segments[0].delta_parent is None
        st = eng.segment_stats()["streaming"]
        assert st["compactions"] == 1 and st["delta_docs"] == 0

    def test_concat_preserves_positions_for_phrases(self):
        eng = make_engine()
        eng.index("p1", {"body": "alpha beta gamma"})
        eng.index("p2", {"body": "beta alpha gamma"})
        eng.refresh()
        q = {"query": {"match_phrase": {"body": "alpha beta"}},
             "size": 5}
        before = strip(eng.acquire_searcher().search(copy.deepcopy(q)))
        assert before["hits"]["total"] == 1
        assert eng.compact()
        after = strip(eng.acquire_searcher().search(copy.deepcopy(q)))
        assert before == after


class TestPackDispatch:
    """Base+delta searched in ONE device dispatch, byte-identical to
    the per-segment fallback."""

    @pytest.fixture()
    def pair_engine(self):
        eng = make_engine()
        fill(eng, 0, 40)
        eng.refresh()
        assert eng.compact()
        fill(eng, 40, 55)
        eng.delete("d5")
        eng.refresh()
        assert len(eng.segments) == 2
        assert eng.segments[1].delta_parent is not None
        return eng

    def test_pack_vs_per_segment_byte_identity(self, pair_engine,
                                               monkeypatch):
        r = pair_engine.acquire_searcher()
        packed = r.msearch([copy.deepcopy(q) for q in QUERIES])
        monkeypatch.setenv("ES_TPU_PACK_DISPATCH", "0")
        pair_engine.invalidate_reader()
        r2 = pair_engine.acquire_searcher()
        plain = r2.msearch([copy.deepcopy(q) for q in QUERIES])
        for a, b in zip(packed, plain):
            assert strip(a) == strip(b)

    def test_pack_is_one_dispatch(self, pair_engine):
        r = pair_engine.acquire_searcher()
        pend = r.msearch_submit([copy.deepcopy(QUERIES[0])])
        try:
            # base + delta, fused-admitted -> ONE enqueued program
            assert pend.dispatch_count == 1
            assert pend.groups[0]["pending"][0][1].get("pack") is True
        finally:
            pend.finish()

    def test_unfused_plan_falls_back_to_per_segment(self, pair_engine):
        r = pair_engine.acquire_searcher()
        pend = r.msearch_submit([copy.deepcopy(QUERIES[3])])  # sort
        try:
            assert pend.dispatch_count == 2
        finally:
            pend.finish()


class TestEpochBumpCaches:
    """The refresh-storm fix, provable from stats: an epoch bump
    re-tunes nothing, evicts nothing, recompiles nothing."""

    def test_zero_retune_zero_eviction_zero_recompile(self,
                                                      trace_guarded):
        eng = make_engine()
        fill(eng, 0, 40)
        eng.refresh()
        assert eng.compact()
        fill(eng, 40, 45)
        eng.refresh()
        q = {"query": {"match": {"body": "alpha beta"}}, "size": 8}
        r = eng.acquire_searcher()
        r.search(copy.deepcopy(q))       # cold: compiles + pins
        r.search(copy.deepcopy(q))       # warm resident
        snap0 = resident.resident_stats()
        tunes0 = len(executor._autotune_choices)
        trace_guarded.reset_counters()
        # refresh with PENDING BUFFERED DOCS — the acceptance event
        fill(eng, 45, 49)
        eng.refresh()
        r2 = eng.acquire_searcher()
        resp = r2.search(copy.deepcopy(q))
        snap1 = resident.resident_stats()
        tg = trace_guarded.snapshot()
        assert len(executor._autotune_choices) == tunes0, \
            "refresh re-tuned an autotune key"
        assert snap1["evictions"] == snap0["evictions"] == 0
        assert snap1["cold_dispatches"] == snap0["cold_dispatches"], \
            "refresh forced a resident recompile"
        assert snap1["refresh_reuses"] >= 1
        assert tg["recompiles"] == 0, tg
        assert resp["hits"]["total"] > 0
        # structured entry info carries the generation + epoch
        entry = snap1["entries"][0]
        assert entry["generation"].startswith("delta(")
        assert entry["delta_epoch"] == eng._delta_epoch

    def test_compaction_is_the_only_rekey(self, trace_guarded):
        eng = make_engine()
        fill(eng, 0, 30)
        eng.refresh()
        assert eng.compact()
        fill(eng, 30, 36)
        eng.refresh()
        q = {"query": {"match": {"body": "gamma delta"}}, "size": 6}
        r = eng.acquire_searcher()
        before = strip(r.search(copy.deepcopy(q)))
        r.search(copy.deepcopy(q))
        snap0 = resident.resident_stats()
        assert snap0["compaction_evictions"] == 0
        assert eng.compact()
        snap1 = resident.resident_stats()
        assert snap1["compaction_evictions"] >= 1, \
            "compaction must evict the folded generation's entries"
        r2 = eng.acquire_searcher()
        after = strip(r2.search(copy.deepcopy(q)))
        assert before == after   # identity across the re-key

    def test_force_merge_rekeys_like_compaction(self, trace_guarded):
        """force_merge retires the generation too: its delta resident
        entries (no seg weakref) must be evicted, not stranded holding
        compiled executables + breaker bytes until LRU pressure."""
        eng = make_engine()
        fill(eng, 0, 30)
        eng.refresh()
        assert eng.compact()
        fill(eng, 30, 36)
        eng.refresh()
        q = {"query": {"match": {"body": "gamma delta"}}, "size": 6}
        r = eng.acquire_searcher()
        before = strip(r.search(copy.deepcopy(q)))
        r.search(copy.deepcopy(q))           # pin base+delta residency
        snap0 = resident.resident_stats()
        eng.force_merge(max_num_segments=1)
        snap1 = resident.resident_stats()
        assert snap1["compaction_evictions"] > \
            snap0["compaction_evictions"], \
            "force_merge must evict the retired generation's entries"
        after = strip(eng.acquire_searcher().search(copy.deepcopy(q)))
        # merge_segments RECOMPUTES impacts under the merged stats —
        # scores (and with them the top-k ranking) legitimately shift,
        # exactly as across a legacy merge; the MATCH SET is what holds
        assert after["hits"]["total"] == before["hits"]["total"]
        assert len(after["hits"]["hits"]) == len(before["hits"]["hits"])

    def test_resident_survival_across_many_epochs(self, trace_guarded):
        eng = make_engine()
        fill(eng, 0, 32)
        eng.refresh()
        assert eng.compact()
        fill(eng, 32, 36)
        eng.refresh()
        q = {"query": {"match": {"body": "beta"}}, "size": 4}
        eng.acquire_searcher().search(copy.deepcopy(q))  # pin
        colds = resident.resident_stats()["cold_dispatches"]
        for lo in range(36, 48, 4):
            fill(eng, lo, lo + 4)
            eng.refresh()
            eng.acquire_searcher().search(copy.deepcopy(q))
        snap = resident.resident_stats()
        assert snap["cold_dispatches"] == colds
        assert snap["refresh_reuses"] >= 3
        assert snap["evictions"] == 0


class TestMeshSurvival:
    def test_tail_programs_survive_refresh(self):
        from elasticsearch_tpu.node import Node
        from elasticsearch_tpu.parallel.mesh import build_mesh
        from elasticsearch_tpu.parallel.distributed import MeshIndex

        n = Node({"index.number_of_shards": 1})
        try:
            n.create_index("live", mappings={"doc": {"properties": {
                "body": {"type": "string"}, "v": {"type": "long"}}}})
            for i in range(30):
                n.index_doc("live", f"d{i}", {
                    "body": " ".join(WORDS[j % 5] for j in range(i, i + 3)),
                    "v": i})
            n.refresh("live")
            mi = MeshIndex(n, "live", build_mesh(1, 1))
            q = {"query": {"match": {"body": "alpha"}}, "size": 5}
            for i in range(30, 34):
                n.index_doc("live", f"d{i}", {
                    "body": " ".join(WORDS[j % 5] for j in range(i, i + 3)),
                    "v": i})
            st1 = mi.refresh()
            assert st1["mode"] == "tail"
            searcher = mi.tail_searcher
            r1 = mi.search(copy.deepcopy(q))
            programs = dict(searcher._jit_cache)
            for i in range(34, 38):
                n.index_doc("live", f"d{i}", {
                    "body": " ".join(WORDS[j % 5] for j in range(i, i + 3)),
                    "v": i})
            st2 = mi.refresh()
            assert st2["tail_programs_reused"] is True
            assert mi.tail_searcher is searcher
            r2 = mi.search(copy.deepcopy(q))
            for key, fn in programs.items():
                assert searcher._jit_cache[key] is fn, \
                    "pinned mesh program was recompiled by a refresh"
            assert r2["hits"]["total"] >= r1["hits"]["total"]
        finally:
            n.close()


class TestSatellites:
    def test_tombstone_gc_uses_monotonic_clock(self, monkeypatch):
        from elasticsearch_tpu.index import engine as engine_mod
        eng = make_engine(**{"index.gc_deletes": "10s"})
        fill(eng, 0, 3)
        eng.refresh()
        clock = [1000.0]
        monkeypatch.setattr(engine_mod.time, "monotonic",
                            lambda: clock[0])
        # a WALL-clock jump must be irrelevant
        monkeypatch.setattr(engine_mod.time, "time",
                            lambda: 4e9)
        eng.delete("d1")
        eng.refresh()
        assert "d1" in eng.versions        # tombstone retained
        clock[0] += 5.0
        eng.index("dx", {"body": "alpha"})
        eng.refresh()
        assert "d1" in eng.versions        # still inside the window
        clock[0] += 6.0                    # now past gc_deletes
        eng.index("dy", {"body": "beta"})
        eng.refresh()
        assert "d1" not in eng.versions

    def test_autotune_store_sweep_and_load_cap(self, tmp_path):
        store = str(tmp_path / "fused_autotune.json")
        data = {
            repr(("livefp", 128, ("x",), 8, False)):
                {"choice": "xla", "timings_ms": None},
            repr(("deadfp", 128, ("x",), 8, False)):
                {"choice": "pallas", "timings_ms": None},
            repr(("livefp+delta(g):c128", 256, ("x",), 8, False)):
                {"choice": "xla", "timings_ms": None},
            repr(("deadfp+delta(g):c128", 256, ("x",), 8, False)):
                {"choice": "xla", "timings_ms": None},
            "not-a-tuple-key": "xla",
        }
        with open(store, "w") as f:
            json.dump(data, f)
        prev = executor.autotune_persistence_path()
        try:
            assert executor.configure_autotune_persistence(store)
            swept = executor.sweep_autotune_store(
                {"livefp", "delta(g):c128"})
            assert swept == 3
            with open(store) as f:
                left = json.load(f)
            assert set(left) == {
                repr(("livefp", 128, ("x",), 8, False)),
                repr(("livefp+delta(g):c128", 256, ("x",), 8, False))}
            # load-time FIFO cap: an oversized store truncates on load
            big = {repr((f"fp{i}", 128, ("x",), 8, False)):
                   {"choice": "xla", "timings_ms": None}
                   for i in range(executor._AUTOTUNE_PERSIST_CAP + 7)}
            with open(store, "w") as f:
                json.dump(big, f)
            assert executor.configure_autotune_persistence(store)
            assert len(executor._autotune_persisted) == \
                executor._AUTOTUNE_PERSIST_CAP
        finally:
            executor.configure_autotune_persistence(prev)

    def test_run_build_aside_abort_keeps_serving(self):
        from elasticsearch_tpu.parallel.repack import run_build_aside
        from elasticsearch_tpu.utils.errors import CircuitBreakingError
        aborted = []

        def build():
            raise CircuitBreakingError("request", 1, 0)

        assert run_build_aside("t", build, lambda _r: True,
                               on_abort=aborted.append) is False
        assert len(aborted) == 1
        # swap veto (the world moved on) also reports not-published
        assert run_build_aside("t", lambda: 1,
                               lambda _r: False) is False
        assert run_build_aside("t", lambda: 1, lambda _r: True) is True

    def test_compaction_aborts_when_refresh_wins_the_race(self):
        eng = make_engine()
        fill(eng, 0, 20)
        eng.refresh()
        # sabotage: mutate the segment list between snapshot and swap
        # by interleaving a refresh inside the build
        import elasticsearch_tpu.index.engine as engine_mod
        orig = engine_mod.concat_segments

        def racing_concat(*a, **kw):
            out = orig(*a, **kw)
            fill(eng, 20, 22)
            eng.refresh()                  # replaces the delta mid-build
            return out

        engine_mod.concat_segments = racing_concat
        try:
            assert eng.compact() is False  # aborted, not corrupted
        finally:
            engine_mod.concat_segments = orig
        assert eng.doc_count() == 22
        # the next attempt (no race) succeeds
        assert eng.compact() is True
        assert eng.doc_count() == 22


class TestCrashRecovery:
    """The streaming paths must never delete a store file the last
    commit point still references: the translog rotates at the commit,
    so a crash between the deletion and the next flush would lose the
    committed docs outright."""

    @staticmethod
    def _persistent_engine(path: str) -> Engine:
        s = Settings({"index.streaming.delta": True})
        m = MapperService(index_settings=s)
        m.put_type_mapping("doc", MAPPING["doc"])
        return Engine("idx", 0, m, path=path, settings=s)

    def test_committed_delta_file_survives_refresh(self, tmp_path):
        path = str(tmp_path / "shard")
        eng = self._persistent_engine(path)
        fill(eng, 0, 30)
        eng.refresh()
        assert eng.compact()          # a real base generation
        fill(eng, 30, 40)
        eng.refresh()                 # delta carries docs 30..39
        eng.flush()                   # commit lists base + delta and
                                      # ROTATES the translog
        fill(eng, 40, 45)             # post-commit docs: translog-only
        eng.refresh()                 # epoch bump rebuilds the delta —
                                      # the committed delta's file must
                                      # survive until the next commit
        # simulated crash: recover a fresh engine from the same store
        eng2 = self._persistent_engine(path)
        assert eng2.doc_count() == 45
        r = eng2.acquire_searcher()
        assert r.search({"query": {"match_all": {}},
                         "size": 0})["hits"]["total"] == 45

    def test_committed_base_file_survives_compaction(self, tmp_path):
        path = str(tmp_path / "shard")
        eng = self._persistent_engine(path)
        fill(eng, 0, 20)
        eng.refresh()
        eng.flush()                   # commit lists the base segment
        fill(eng, 20, 30)
        eng.refresh()
        assert eng.compact()          # swaps in a NEW base — the
                                      # committed old base's file must
                                      # survive (docs 0..19 left the
                                      # translog at the flush)
        eng2 = self._persistent_engine(path)
        assert eng2.doc_count() == 30

    def test_committed_files_survive_force_merge(self, tmp_path):
        path = str(tmp_path / "shard")
        eng = self._persistent_engine(path)
        fill(eng, 0, 20)
        eng.refresh()
        eng.flush()                   # commit lists the segments and
                                      # rotates the translog
        fill(eng, 20, 26)
        eng.refresh()
        eng.force_merge(max_num_segments=1)   # must NOT delete the
                                              # committed files
        eng2 = self._persistent_engine(path)
        assert eng2.doc_count() == 26

    def test_compacted_base_scores_survive_restart(self, tmp_path):
        """Compaction preserves impacts computed under the SOURCE
        segments' field stats; the store persists them so a reload
        cannot silently re-derive different BM25 scores from the merged
        field's own doc_count/avg_len."""
        path = str(tmp_path / "shard")
        eng = self._persistent_engine(path)
        fill(eng, 0, 40)
        eng.refresh()
        assert eng.compact()          # a real base: docs 0..39 scored
                                      # under doc_count=40 field stats
        fill(eng, 40, 60)
        eng.refresh()
        assert eng.compact()          # impact-preserving concat of two
                                      # sub-segments with DIFFERENT
                                      # field stats
        before = [strip(eng.acquire_searcher().search(copy.deepcopy(q)))
                  for q in QUERIES]
        eng.flush()
        eng2 = self._persistent_engine(path)
        after = [strip(eng2.acquire_searcher().search(copy.deepcopy(q)))
                 for q in QUERIES]
        assert before == after


@pytest.mark.slow
class TestConcurrentSoak:
    def test_writer_searcher_soak_no_torn_reads(self):
        """Seeded concurrent writer + searcher: every response must be
        internally consistent (hits <= total, every hit resolvable) and
        visibility MONOTONIC (append-only corpus => match_all totals
        never decrease across sequential searches)."""
        from elasticsearch_tpu.node import Node
        rng = np.random.default_rng(1234)
        n = Node({"index.number_of_shards": 1})
        try:
            n.create_index(
                "soak", settings={"index.streaming.delta": True,
                                  "index.delta.min_compact_docs": 64},
                mappings={"doc": {"properties": {
                    "body": {"type": "string"},
                    "n": {"type": "long"}}}})
            errors: list[BaseException] = []
            stop = threading.Event()

            def writer():
                try:
                    i = 0
                    while not stop.is_set() and i < 600:
                        n.index_doc("soak", f"d{i}", {
                            "body": " ".join(
                                WORDS[int(j) % 7] for j in
                                rng.integers(0, 7, size=6)),
                            "n": i})
                        i += 1
                        if i % 20 == 0:
                            n.refresh("soak")
                    n.refresh("soak")
                except BaseException as e:  # noqa: BLE001
                    errors.append(e)

            totals: list[int] = []

            def searcher():
                try:
                    while not stop.is_set():
                        r = n.search("soak", {
                            "query": {"match": {"body": "alpha"}},
                            "size": 5})
                        assert len(r["hits"]["hits"]) <= max(
                            r["hits"]["total"], 5)
                        for h in r["hits"]["hits"]:
                            assert h["_id"].startswith("d")
                        t = n.search("soak", {
                            "query": {"match_all": {}},
                            "size": 0})["hits"]["total"]
                        totals.append(t)
                except BaseException as e:  # noqa: BLE001
                    errors.append(e)

            wt = threading.Thread(target=writer)
            st = threading.Thread(target=searcher)
            wt.start()
            st.start()
            wt.join(timeout=240.0)
            stop.set()
            st.join(timeout=60.0)
            assert not errors, errors[:1]
            assert totals, "searcher made no progress"
            # monotonic visibility: totals never go backwards
            assert all(a <= b for a, b in zip(totals, totals[1:])), \
                "visibility went backwards during the soak"
            assert n.search("soak", {"query": {"match_all": {}},
                                     "size": 0})["hits"]["total"] == 600
            st_stats = n.indices["soak"].shard(0).segment_stats()
            assert st_stats["streaming"]["compactions"] >= 1
        finally:
            n.close()


class TestChainedTopkOps:
    """Ops-level contract of the base->delta walk chaining: the merged
    selection equals the union of per-segment top-k's truncated
    host-side, on BOTH engines."""

    def _cols(self, rng, cap, T, L, n_tiles):
        import jax
        import jax.numpy as jnp
        from elasticsearch_tpu.index.segment import (TileSummary,
                                                     build_tile_max)
        tids = rng.integers(-1, T, size=(cap, L)).astype(np.int32)
        imps = np.where(tids >= 0,
                        rng.random((cap, L)).astype(np.float32),
                        0).astype(np.float32)
        tile = cap // n_tiles
        tm = np.zeros((T, n_tiles), np.float32)
        for j in range(n_tiles):
            tt = tids[j * tile:(j + 1) * tile].ravel()
            ii = imps[j * tile:(j + 1) * tile].ravel()
            ok = tt >= 0
            np.maximum.at(tm[:, j], tt[ok], ii[ok])
        # the stored form, laid out here from the dense oracle and not
        # by the program's builder: a CSR of the cells that hold a
        # posting, padded by a window of the grid
        at_t, at_j = np.nonzero(tm)
        start = np.zeros(T + 1, np.int32)
        np.cumsum(np.bincount(at_t, minlength=T), out=start[1:])
        summary = TileSummary(
            start,
            np.concatenate([at_j, np.full(n_tiles, n_tiles)]).astype(
                np.int32),
            np.concatenate([tm[at_t, at_j],
                            np.zeros(n_tiles, np.float32)]), n_tiles)
        assert np.array_equal(summary.dense(), tm)
        assert np.array_equal(
            build_tile_max(tids, imps, T, cap, tile=tile).dense(), tm)
        return {"fwd_tids": jnp.asarray(tids),
                "fwd_imps": jnp.asarray(imps),
                "tile_max": jax.device_put(summary)}

    def test_chained_equals_union_and_engines_agree(self):
        import jax.numpy as jnp
        from elasticsearch_tpu.ops.scoring import score_topk_bundle_fused
        from elasticsearch_tpu.ops.pallas_scoring import \
            fused_topk_bundle_pallas
        from elasticsearch_tpu.ops.topk import running_topk_init
        rng = np.random.default_rng(0)
        B, k, T = 3, 10, 16
        base = {"f": self._cols(rng, 4096, T, 8, 4)}
        delta = {"f": self._cols(rng, 256, T, 8, 1)}
        live_b = jnp.ones(4096, bool)
        live_d = jnp.ones(256, bool)
        clauses = (("should", "terms_dense", "f", False),)
        qt = jnp.asarray(rng.integers(0, T, size=(B, 4)).astype(np.int32))
        cl = ((qt, jnp.ones((B, 4), jnp.float32),
               jnp.ones((B,), jnp.int32), jnp.ones((B,), jnp.float32)),)
        msm = jnp.ones((B,), jnp.int32)
        s0, i0 = running_topk_init(B, k)
        ts, ti, _tb, _ = score_topk_bundle_fused(
            base, {}, clauses, cl, msm, None, live_b, k,
            init_topk=(s0, i0))
        ts2, ti2, _td, _ = score_topk_bundle_fused(
            delta, {}, clauses, cl, msm, None, live_d, k,
            init_topk=(ts, ti), idx_offset=4096)
        as_, ai, _, _ = score_topk_bundle_fused(
            base, {}, clauses, cl, msm, None, live_b, k)
        bs_, bi, _, _ = score_topk_bundle_fused(
            delta, {}, clauses, cl, msm, None, live_d, k)
        for b in range(B):
            union = sorted(
                [(-float(s), int(i)) for s, i in
                 zip(np.asarray(as_)[b], np.asarray(ai)[b])
                 if np.isfinite(s)] +
                [(-float(s), int(i) + 4096) for s, i in
                 zip(np.asarray(bs_)[b], np.asarray(bi)[b])
                 if np.isfinite(s)])[:k]
            got = [(-float(s), int(i)) for s, i in
                   zip(np.asarray(ts2)[b], np.asarray(ti2)[b])
                   if np.isfinite(s)]
            assert union == got
        # pallas (interpret) chains identically — thresholds seeded
        # from the base walk's k-th best, base-first tie order
        ps, pi, _, _ = fused_topk_bundle_pallas(
            base, {}, clauses, cl, msm, None, live_b, k, interpret=True)
        ps2, pi2, _, _ = fused_topk_bundle_pallas(
            delta, {}, clauses, cl, msm, None, live_d, k,
            interpret=True, init_topk=(ps, pi), idx_offset=4096)
        assert np.allclose(np.asarray(ps2), np.asarray(ts2))
        assert (np.asarray(pi2) == np.asarray(ti2)).all()


class TestConcatSegmentsUnit:
    def test_concat_drops_dead_and_preserves_impacts(self):
        from elasticsearch_tpu.index.mapping import (ParsedDocument,
                                                     ParsedField, TEXT)
        from elasticsearch_tpu.index.segment import extract_flat_impacts

        def doc(i, toks):
            return ParsedDocument(doc_id=f"d{i}", source=b"{}", fields=[
                ParsedField(name="body", type=TEXT, tokens=toks)])

        b1 = SegmentBuilder()
        for i in range(5):
            b1.add(doc(i, ["alpha", "beta"] if i % 2
                       else ["alpha", "gamma"]))
        s1 = b1.build("s1")
        b2 = SegmentBuilder()
        for i in range(5, 8):
            b2.add(doc(i, ["beta", "delta"]))
        s2 = b2.build("s2")
        live = {"s1": np.array([True] * 5 + [False] * (s1.capacity - 5)),
                "s2": np.array([True] * 3 + [False] * (s2.capacity - 3))}
        live["s1"][2] = False
        m = concat_segments([s1, s2], "m", live)
        assert m.num_docs == 7
        assert m.ids == ["d0", "d1", "d3", "d4", "d5", "d6", "d7"]
        pf = m.text["body"]
        fm = extract_flat_impacts(pf)
        f1 = extract_flat_impacts(s1.text["body"])
        t = pf.term_index["alpha"]
        s, e = int(pf.indptr[t]), int(pf.indptr[t + 1])
        t1 = s1.text["body"].term_index["alpha"]
        s_, e_ = (int(s1.text["body"].indptr[t1]),
                  int(s1.text["body"].indptr[t1 + 1]))
        # impacts preserved bit-for-bit (d2's posting dropped)
        kept = [imp for d, imp in zip(s1.text["body"].doc_ids[s_:e_],
                                      f1[s_:e_]) if d != 2]
        assert list(fm[s:e]) == kept
        assert m.text["body"].tile_max is not None

    @pytest.mark.parametrize("docs", [1, 3, 6])
    def test_a_growing_delta_keeps_its_summarys_shapes(self, docs):
        """The summary's three arrays are bucketed with the term rows,
        so a delta that grows inside a bucket keeps every program's
        shape signature; the rows read the same as before the pad."""
        from elasticsearch_tpu.index.mapping import (ParsedDocument,
                                                     ParsedField, TEXT)
        b = SegmentBuilder()
        for i in range(docs):
            b.add(ParsedDocument(doc_id=str(i), source=b"{}", fields=[
                ParsedField(name="body", type=TEXT,
                            tokens=["a", f"w{i}"])]))
        seg = b.build("d")
        before = seg.text["body"].tile_max.dense()
        pad_delta_shapes(seg)
        tm = seg.text["body"].tile_max
        assert (len(tm.start), len(tm.tiles), len(tm.vals)) \
            == (9, 1024, 1024)
        assert np.array_equal(tm.dense()[:before.shape[0]], before)
        assert not tm.dense()[before.shape[0]:].any()

    def test_pad_delta_shapes_buckets_term_arrays(self):
        from elasticsearch_tpu.index.mapping import (ParsedDocument,
                                                     ParsedField, TEXT)
        b = SegmentBuilder()
        b.add(ParsedDocument(doc_id="x", source=b"{}", fields=[
            ParsedField(name="body", type=TEXT,
                        tokens=["a", "b", "c"])]))
        seg = b.build("x1")
        pad_delta_shapes(seg)
        pf = seg.text["body"]
        assert len(pf.tile_max.start) == 9        # pow2 floor
        assert len(pf.block_start) == 9
        # the stored entries are bucketed like the term rows
        n = len(pf.tile_max.tiles)
        assert n == len(pf.tile_max.vals) and n & (n - 1) == 0
        # padded rows bound to zero impact: they can never un-prune
        assert float(pf.tile_max.dense()[3:].max()) == 0.0
