"""Tiered tile residency (index/tiering.py): beyond-HBM packs with
prune-aware paging.

Covers the PR's acceptance surface:

  * byte-identity of search responses between a PAGED pack (forward
    index host-resident, tiles streamed through the LRU pager) and the
    fully-resident path — across bool bundles (msm, must_not, range
    filters, wrapped bool-in-bool boosts), aggregations (emit-match),
    k == 0 (match-mask-only), delta packs (PR 9), and the Pallas
    engine (forced, interpret mode);
  * the survivor oracle: the HOST bound computation
    (ops/scoring.bundle_tile_bounds_np) agrees tile-for-tile with the
    device bundle_tile_bounds can_match — pruning as an I/O filter is
    exact, and prune_skipped_fetches counts real never-fetched tiles;
  * LRU eviction under a seeded thrash workload whose working set
    exceeds the HBM budget, with identity preserved and the pager
    respecting the budget;
  * breaker hygiene: paged-tile holds release on drop_device (and on
    the GC backstop, idempotently — no double-release), and a
    fault-injected breaker_trip at the tile-fetch boundary leaks
    nothing;
  * zero autotune re-tunes / resident evictions / XLA recompiles /
    transfer-guard trips caused by page events (trace_guarded);
  * the fully-resident fast path when the pack fits the budget, and
    the counted full-upload fallback for non-fused plans;
  * stats plumbing: nodes_stats()["fused_scoring"]["tiering"] and the
    fielddata breaker's summary-vs-paged split.
"""

import copy
import gc
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from elasticsearch_tpu.index import tiering  # noqa: E402
from elasticsearch_tpu.index.engine import Engine  # noqa: E402
from elasticsearch_tpu.index.mapping import MapperService  # noqa: E402
from elasticsearch_tpu.index.segment import build_tile_max  # noqa: E402
from elasticsearch_tpu.ops.scoring import (  # noqa: E402
    bundle_tile_bounds, bundle_tile_bounds_np)
from elasticsearch_tpu.utils.settings import Settings  # noqa: E402

WORDS = ["alpha", "beta", "gamma", "delta", "epsilon", "zeta", "eta"]

MAPPING = {"doc": {"properties": {
    "body": {"type": "string"},
    "tag": {"type": "keyword"},
    "n": {"type": "long"}}}}

N_DOCS = 2600          # -> capacity 4096, a 4-tile SCORE_TILE grid

# every fused admission class: bundles, range filter, must_not + msm,
# wrapped bool-in-bool boost, aggs (emit-match), k == 0 (mask-only),
# k == 0 + aggs
FUSED_QUERIES = [
    {"query": {"bool": {"must": [{"match": {"body": "alpha beta"}}],
                        "filter": [{"range": {"n": {"gte": 3,
                                                    "lte": 1500}}}]}},
     "size": 12},
    {"query": {"match": {"body": "gamma"}}, "size": 5,
     "aggs": {"t": {"terms": {"field": "tag"}},
              "h": {"histogram": {"field": "n", "interval": 200}}}},
    {"query": {"match": {"body": "zeta"}}, "size": 0},
    {"query": {"match": {"body": "zeta"}}, "size": 0,
     "aggs": {"t": {"terms": {"field": "tag"}}}},
    {"query": {"bool": {"should": [{"match": {"body": "alpha"}},
                                   {"match": {"body": "eta"}}],
                        "minimum_should_match": 1,
                        "must_not": [{"range": {"n": {"gte": 2000}}}]}},
     "size": 10},
    {"query": {"bool": {"should": [
        {"bool": {"should": [{"match": {"body": "beta"}}],
                  "boost": 2.5}},
        {"match": {"body": "delta"}}]}}, "size": 7},
    {"query": {"match": {"body": "epsilon gamma eta"}}, "size": 200},
]


def make_engine(delta=False, **over) -> Engine:
    conf = {"index.streaming.delta": True} if delta else {}
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


def run_queries(eng: Engine, queries=FUSED_QUERIES) -> list[dict]:
    r = eng.acquire_searcher()
    return [strip(r.search(copy.deepcopy(q))) for q in queries]


_TIER_ENV = ("ES_TPU_TIERED_PACK", "ES_TPU_TIERED_BUDGET_BYTES",
             "ES_TPU_TIERED_CHUNK_TILES", "ES_TPU_FUSED_BACKEND",
             "ES_TPU_PALLAS")


@pytest.fixture(scope="module")
def baseline():
    """Fully-resident engine + its responses, built with tiering
    provably off (env cleared for the duration of the build)."""
    saved = {k: os.environ.pop(k, None) for k in _TIER_ENV}
    try:
        tiering.reset()
        eng = make_engine()
        fill(eng, 0, N_DOCS)
        eng.refresh()
        resps = run_queries(eng)
    finally:
        for k, v in saved.items():
            if v is not None:
                os.environ[k] = v
    return eng, resps


@pytest.fixture()
def tiered_env(monkeypatch):
    """Paged mode: a budget far below the pack's forward-index bytes
    (one 1024-doc tile is 64KB at the 8-slot width) so the 4-tile grid
    genuinely pages, 2-tile chunks so multi-chunk walks happen."""
    tiering.reset()
    monkeypatch.setenv("ES_TPU_TIERED_PACK", "1")
    monkeypatch.setenv("ES_TPU_TIERED_BUDGET_BYTES", "200000")
    monkeypatch.setenv("ES_TPU_TIERED_CHUNK_TILES", "2")
    yield
    tiering.reset()


# ---------------------------------------------------------------------------
# byte identity: paged vs fully resident
# ---------------------------------------------------------------------------


class TestByteIdentity:
    def test_fused_matrix_identical_xla(self, baseline, tiered_env):
        _eng, base_resps = baseline
        eng = make_engine()
        fill(eng, 0, N_DOCS)
        eng.refresh()
        assert run_queries(eng) == base_resps
        snap = tiering.stats_snapshot()
        assert snap["tiered_dispatches"] >= len(FUSED_QUERIES) - 1
        assert snap["tile_misses"] > 0
        # the I/O filter worked: some tiles were never fetched because
        # the resident summaries proved no query could match in them
        assert snap["prune_skipped_fetches"] > 0
        assert snap["unfused_full_uploads"] == 0

    def test_fused_matrix_identical_pallas(self, baseline, tiered_env,
                                           monkeypatch):
        _eng, base_resps = baseline
        monkeypatch.setenv("ES_TPU_FUSED_BACKEND", "pallas")
        monkeypatch.setenv("ES_TPU_PALLAS", "1")
        eng = make_engine()
        fill(eng, 0, N_DOCS)
        eng.refresh()
        assert run_queries(eng) == base_resps
        assert tiering.stats_snapshot()["tiered_dispatches"] > 0

    def test_delta_pack_identity(self, tiered_env):
        """A paged BASE generation + live delta: the pack dispatch
        declines (per-segment fallback) and the tiered walk serves the
        base — responses identical to a fully-resident delta-mode
        engine over the same docs."""
        def build():
            eng = make_engine(delta=True)
            fill(eng, 0, N_DOCS)
            eng.refresh()
            assert eng.compact()
            fill(eng, N_DOCS, N_DOCS + 80)
            eng.refresh()
            return eng

        tiered = run_queries(build())
        saved = os.environ.pop("ES_TPU_TIERED_PACK")
        try:
            resident = run_queries(build())
        finally:
            os.environ["ES_TPU_TIERED_PACK"] = saved
        assert tiered == resident

    def test_deletes_respected_through_live_mask(self, tiered_env):
        """The gathered per-chunk live mask honors deletions exactly."""
        def build():
            eng = make_engine()
            fill(eng, 0, N_DOCS)
            eng.refresh()
            for i in range(0, N_DOCS, 7):
                eng.delete(f"d{i}")
            eng.refresh()
            return eng

        tiered = run_queries(build())
        saved = os.environ.pop("ES_TPU_TIERED_PACK")
        try:
            resident = run_queries(build())
        finally:
            os.environ["ES_TPU_TIERED_PACK"] = saved
        assert tiered == resident


# ---------------------------------------------------------------------------
# the survivor oracle
# ---------------------------------------------------------------------------


class TestSurvivorOracle:
    def test_host_can_match_equals_device(self):
        rng = np.random.default_rng(7)
        cap, slots, n_terms, b, q, tile = 4096, 4, 60, 3, 3, 1024
        fwd_tids = np.argsort(rng.random((cap, n_terms)),
                              axis=1)[:, :slots].astype(np.int32)
        fwd_tids[rng.random((cap, slots)) < 0.3] = -1
        fwd_imps = rng.random((cap, slots), dtype=np.float32)
        fwd_imps[fwd_tids < 0] = 0.0
        # concentrate a rare term into one tile so hard skips exist
        fwd_tids[: cap - tile][fwd_tids[: cap - tile] == 0] = -1
        tm = build_tile_max(fwd_tids, fwd_imps, n_terms, cap, tile=tile)
        vals = rng.integers(0, 1000, cap).astype(np.int32)
        exists = rng.random(cap) < 0.9
        from elasticsearch_tpu.index.segment import build_tile_minmax
        lo_hi = build_tile_minmax(vals, exists, cap, tile=tile)
        clauses = (("must", "terms_dense", "f", False),
                   ("filter", "range_int", "g", False),
                   ("should", "terms_dense", "f", True))
        for trial in range(8):
            qt = rng.integers(-1, n_terms, size=(b, q)).astype(np.int32)
            wq = (rng.random((b, q), dtype=np.float32) + 0.01)
            wq[qt < 0] = 0.0
            qt2 = rng.integers(-1, n_terms, size=(b, 2)).astype(np.int32)
            wq2 = (rng.random((b, 2), dtype=np.float32) + 0.01)
            wq2[qt2 < 0] = 0.0
            lo = rng.integers(0, 500, b).astype(np.int32)
            hi = lo + rng.integers(0, 600, b).astype(np.int32)
            msm_c = rng.integers(0, 2, b).astype(np.int32)
            boost_c = (rng.random(b) + 0.5).astype(np.float32)
            msm = rng.integers(0, 2, b).astype(np.int32)
            boost = (rng.random(b) + 0.5).astype(np.float32)
            ones_i = np.ones(b, np.int32)
            ones_f = np.ones(b, np.float32)
            cl_np = ((qt, wq, ones_i, ones_f), (lo, hi),
                     (qt2, wq2, msm_c, boost_c))
            can_h, _ = bundle_tile_bounds_np(
                clauses, cl_np, {"f": tm},
                {"g": lo_hi}, msm, boost)
            can_d, _ = bundle_tile_bounds(
                clauses,
                tuple(tuple(jnp.asarray(x) for x in inp)
                      for inp in cl_np),
                {"f": {"tile_max": jax.device_put(tm)}},
                {"g": {"tile_lo": jnp.asarray(lo_hi[0]),
                       "tile_hi": jnp.asarray(lo_hi[1])}},
                jnp.asarray(msm), jnp.asarray(boost))
            assert np.array_equal(can_h, np.asarray(can_d)), \
                f"survivor oracle diverged on trial {trial}"


    @pytest.mark.parametrize("chunk", [
        [3, 0, 2], [1, 1, 3, 0], [2, 4, 4, 4], [0, 1, 2, 3, 4, 4, 4, 4]])
    def test_a_chunks_summary_is_the_packs_cut_to_its_tiles(self, chunk):
        """The tiered walk hands a compacted chunk the pack's summary
        cut to the chunk's tiles (`TileSummary.take`; a pad slot names
        the tile past the grid and reads 0): the rows are the dense
        summary's columns, on the device and on the host."""
        from elasticsearch_tpu.ops.scoring import (dense_tile_bounds,
                                                   tile_max_rows)
        rng = np.random.default_rng(11)
        cap, slots, n_terms, tile = 4096, 4, 60, 1024
        fwd_tids = np.argsort(rng.random((cap, n_terms)),
                              axis=1)[:, :slots].astype(np.int32)
        fwd_tids[rng.random((cap, slots)) < 0.5] = -1
        fwd_tids[: cap - tile][fwd_tids[: cap - tile] < 5] = -1
        fwd_imps = rng.random((cap, slots), dtype=np.float32)
        fwd_imps[fwd_tids < 0] = 0.0
        tm = build_tile_max(fwd_tids, fwd_imps, n_terms, cap, tile=tile)
        dense = np.concatenate([tm.dense(), np.zeros((n_terms, 1),
                                                     np.float32)], axis=1)
        cols = np.asarray(chunk, np.int32)
        tids = np.asarray([0, 3, 17, 59], np.int32)
        cut = jax.jit(lambda t, c, q: tile_max_rows(t.take(c), q))(
            jax.device_put(tm), jnp.asarray(cols), jnp.asarray(tids))
        assert np.array_equal(np.asarray(cut), dense[tids][:, cols])
        assert np.array_equal(tm.take(cols).rows(tids),
                              dense[tids][:, cols])
        assert tm.take(cols).n_tiles == len(chunk)
        ub = jax.jit(lambda t, c, q, w: dense_tile_bounds(t.take(c), q, w))(
            jax.device_put(tm), jnp.asarray(cols), jnp.asarray(tids)[:, None],
            jnp.ones((4, 1), jnp.float32))
        assert np.array_equal(np.asarray(ub) > 0, dense[tids][:, cols] > 0)


# ---------------------------------------------------------------------------
# LRU, thrash, breaker hygiene
# ---------------------------------------------------------------------------


def _fielddata_used() -> int:
    from elasticsearch_tpu.utils.breaker import breaker_service
    return breaker_service().breaker("fielddata").used


class TestResidencyLifecycle:
    def test_thrash_evicts_and_stays_identical(self, baseline,
                                               monkeypatch):
        """Seeded thrash: budget below ONE chunk's working set, so
        every chunk evicts its predecessor — identity must hold and
        the pager must settle at/below budget (modulo the pinned
        working chunk)."""
        _eng, base_resps = baseline
        tiering.reset()
        monkeypatch.setenv("ES_TPU_TIERED_PACK", "1")
        monkeypatch.setenv("ES_TPU_TIERED_BUDGET_BYTES", "70000")
        monkeypatch.setenv("ES_TPU_TIERED_CHUNK_TILES", "2")
        eng = make_engine()
        fill(eng, 0, N_DOCS)
        eng.refresh()
        rng = np.random.default_rng(11)
        order = rng.permutation(len(FUSED_QUERIES) * 2) \
            % len(FUSED_QUERIES)
        r = eng.acquire_searcher()
        for qi in order:
            got = strip(r.search(copy.deepcopy(FUSED_QUERIES[qi])))
            assert got == base_resps[qi], f"thrash mismatch on q{qi}"
        snap = tiering.stats_snapshot()
        assert snap["tile_evictions"] > 0
        # budget respected up to the pinned working chunk (2 tiles)
        assert snap["resident_bytes"] <= 70000 + 2 * 65536
        tiering.reset()

    def test_drop_device_releases_paged_holds(self, tiered_env):
        eng = make_engine()
        fill(eng, 0, N_DOCS)
        eng.refresh()
        before = _fielddata_used()
        r = eng.acquire_searcher()
        r.search(copy.deepcopy(FUSED_QUERIES[0]))
        paged = tiering.pager.resident_bytes
        assert paged > 0
        mid = _fielddata_used()
        assert mid > before
        seg = eng.segments[0]
        seg.drop_device()
        assert tiering.pager.resident_bytes == 0
        # the paged-tile holds released NOW (the column hold itself
        # releases at segment GC, as on the ordinary path)
        after_drop = _fielddata_used()
        assert after_drop <= mid - paged
        # idempotent: a second drop (or the GC backstop finding the
        # tiles already gone) must not double-release
        seg.drop_device()
        assert _fielddata_used() == after_drop
        del seg, r, eng
        gc.collect()
        assert _fielddata_used() <= before

    def test_gc_backstop_releases_without_drop(self, tiered_env):
        before_tiles = tiering.pager.resident_tiles()
        eng = make_engine()
        fill(eng, 0, N_DOCS)
        eng.refresh()
        r = eng.acquire_searcher()
        r.search(copy.deepcopy(FUSED_QUERIES[0]))
        assert tiering.pager.resident_tiles() > before_tiles
        del r, eng
        gc.collect()
        assert tiering.pager.resident_tiles() == before_tiles

    def test_breaker_trip_at_fetch_leaks_nothing(self, tiered_env):
        from elasticsearch_tpu.utils import faults
        from elasticsearch_tpu.utils.errors import CircuitBreakingError
        eng = make_engine()
        fill(eng, 0, N_DOCS)
        eng.refresh()
        r = eng.acquire_searcher()
        before_upload = _fielddata_used()
        faults.configure(
            "breaker_trip:breaker=fielddata:site=tiering:phase=fetch")
        try:
            with pytest.raises(CircuitBreakingError):
                r.search(copy.deepcopy(FUSED_QUERIES[0]))
            # the resident-column hold legitimately appeared with the
            # upload; the TILE path must have held nothing
            assert tiering.pager.resident_bytes == 0
            used1 = _fielddata_used()
            # repeated faulted dispatches accumulate NOTHING
            with pytest.raises(CircuitBreakingError):
                r.search(copy.deepcopy(FUSED_QUERIES[0]))
            assert _fielddata_used() == used1
            assert tiering.pager.resident_bytes == 0
        finally:
            faults.configure(None)
        # the path recovers cleanly once the fault clears
        ok = strip(r.search(copy.deepcopy(FUSED_QUERIES[0])))
        assert ok["hits"]["total"] > 0
        # and every hold (columns + tiles) returns at segment death
        del ok, r, eng
        gc.collect()
        assert _fielddata_used() <= before_upload


# ---------------------------------------------------------------------------
# page events never re-key anything
# ---------------------------------------------------------------------------


class TestNoRekeyOnPageEvents:
    def test_zero_recompiles_retunes_evictions(self, baseline,
                                               tiered_env,
                                               trace_guarded):
        """Page events (tile fetch/evict) under the armed transfer
        guard: ZERO implicit transfers, ZERO XLA recompiles after
        warm-up, ZERO new autotune keys, ZERO resident evictions —
        residency state is invisible to every cache key."""
        from elasticsearch_tpu.search import executor as ex
        from elasticsearch_tpu.search import resident
        eng = make_engine()
        fill(eng, 0, N_DOCS)
        eng.refresh()
        r = eng.acquire_searcher()
        warm = [{"query": {"match": {"body": w}}, "size": 5}
                for w in WORDS]
        # warm: compile the chunk programs once per shape
        r.search(copy.deepcopy(warm[0]))
        r.search(copy.deepcopy(warm[1]))
        keys0 = set(ex._autotune_choices)
        ev0 = resident.stats.evictions.count
        trace_guarded.reset_counters()
        misses0 = tiering.stats.tile_misses.count
        evict0 = tiering.stats.tile_evictions.count
        for q in warm[2:] + warm[:2]:
            r.search(copy.deepcopy(q))
        snap = trace_guarded.snapshot()
        assert snap["transfer_guard_trips"] == 0
        assert snap["recompiles"] == 0
        assert set(ex._autotune_choices) == keys0
        assert resident.stats.evictions.count == ev0
        # ...while REAL page events happened during the window
        assert tiering.stats.tile_misses.count > misses0 \
            or tiering.stats.tile_evictions.count >= evict0

    def test_cache_keys_unaffected_by_residency(self, tiered_env):
        eng = make_engine()
        fill(eng, 0, N_DOCS)
        eng.refresh()
        seg = eng.segments[0]
        fp0 = seg.fingerprint()
        ck0 = seg.cache_key()
        r = eng.acquire_searcher()
        r.search(copy.deepcopy(FUSED_QUERIES[0]))   # pages tiles in
        assert seg.fingerprint() == fp0
        assert seg.cache_key() == ck0


# ---------------------------------------------------------------------------
# admission edges: fast path + unfused fallback + stats surfaces
# ---------------------------------------------------------------------------


class TestAdmissionAndStats:
    def test_fast_path_when_pack_fits(self, monkeypatch):
        tiering.reset()
        monkeypatch.setenv("ES_TPU_TIERED_PACK", "1")
        monkeypatch.setenv("ES_TPU_TIERED_BUDGET_BYTES",
                           str(1 << 30))
        eng = make_engine()
        fill(eng, 0, N_DOCS)
        eng.refresh()
        r = eng.acquire_searcher()
        r.search(copy.deepcopy(FUSED_QUERIES[0]))
        snap = tiering.stats_snapshot()
        assert snap["fast_path_full_resident"] >= 1
        assert snap["tiered_dispatches"] == 0
        assert snap["resident_bytes"] == 0
        tiering.reset()

    def test_unfused_plan_triggers_counted_full_upload(self, baseline,
                                                       tiered_env):
        """A field-sorted (unfused) plan against a paged pack uploads
        the forward index after all — counted, breaker-accounted, and
        byte-identical; the pack serves fully resident afterwards."""
        _eng, _ = baseline
        sort_q = {"query": {"match": {"body": "epsilon"}},
                  "sort": [{"n": {"order": "desc"}}], "size": 6}
        saved = os.environ.pop("ES_TPU_TIERED_PACK")
        try:
            eng_ref = make_engine()
            fill(eng_ref, 0, N_DOCS)
            eng_ref.refresh()
            want = strip(eng_ref.acquire_searcher().search(
                copy.deepcopy(sort_q)))
        finally:
            os.environ["ES_TPU_TIERED_PACK"] = saved
        eng = make_engine()
        fill(eng, 0, N_DOCS)
        eng.refresh()
        r = eng.acquire_searcher()
        # page tiles in first, then un-page via the fallback
        r.search(copy.deepcopy(FUSED_QUERIES[0]))
        assert tiering.pager.resident_bytes > 0
        got = strip(r.search(copy.deepcopy(sort_q)))
        assert got == want
        snap = tiering.stats_snapshot()
        assert snap["unfused_full_uploads"] == 1
        # the paged tiles were dropped with the un-page
        assert snap["resident_bytes"] == 0
        # and later fused plans take the ordinary resident path
        t0 = snap["tiered_dispatches"]
        r.search(copy.deepcopy(FUSED_QUERIES[0]))
        assert tiering.stats_snapshot()["tiered_dispatches"] == t0

    def test_node_stats_and_breaker_split(self, tmp_path):
        pytest.importorskip("jax")
        from elasticsearch_tpu.node import Node
        tiering.reset()
        node = Node({"index.number_of_shards": 1,
                     "path.data": str(tmp_path / "data"),
                     "index.tiering.enabled": True,
                     "index.tiering.budget_bytes": 200000,
                     "index.tiering.chunk_tiles": 2})
        try:
            node.create_index("t", mappings={"properties": {
                "body": {"type": "text"}, "n": {"type": "long"}}})
            for i in range(N_DOCS):
                node.index_doc("t", f"d{i}", {
                    "body": " ".join(WORDS[j % 7]
                                     for j in range(i, i + 4)),
                    "n": i})
            node.refresh("t")
            node.search("t", {"query": {"match": {"body": "alpha"}},
                              "size": 5})
            stats = node.nodes_stats()["nodes"][node.name]
            tb = stats["fused_scoring"]["tiering"]
            assert tb["enabled"] is True
            assert tb["tiered_dispatches"] >= 1
            assert tb["tile_misses"] >= 1
            assert tb["resident_bytes"] > 0
            assert tb["summary_bytes"] > 0
            split = stats["breakers"]["fielddata"]["tiering"]
            assert split["paged_bytes"] == tb["resident_bytes"]
            assert split["summary_bytes"] == tb["summary_bytes"]
            # chunk_tiles is pow2-bucketed whatever the setting says
            assert tb["chunk_tiles"] & (tb["chunk_tiles"] - 1) == 0
        finally:
            node.close()
        # node close (the configuring owner) resets the subsystem
        assert tiering.stats_snapshot()["tiered_dispatches"] == 0

    def test_chunk_tiles_env_is_pow2_bucketed(self, monkeypatch):
        monkeypatch.setenv("ES_TPU_TIERED_CHUNK_TILES", "5")
        assert tiering.chunk_tiles() == 8
