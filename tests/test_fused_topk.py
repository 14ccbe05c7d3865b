"""Fused block-max BM25 score+top-k: backend parity + autotuner smoke.

Parity contract: fused-pallas (interpret mode), fused-xla, and the
reference unfused path (full [B, cap] score matrix + lax.top_k) must
return identical top-k doc ids — including across ties, empty queries,
and k > n_docs — with scores within 1e-5. The bench-smoke test builds a
10k-doc pack and asserts the per-pack backend autotuner records a
choice and a nonzero block-prune rate in the node stats API.

The bundle classes cover the block-max-WAND generalization: bool
must/should mixes with minimum_should_match, boosted wrappers (incl.
bool-in-bool), filter/must_not masks with numeric-range tile pruning,
and the fused+aggs emit-match mode — all gated on exact doc-id/score
identity with the unfused path, across the xla and (forced, interpret)
pallas backends.
"""

import os
import random

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from elasticsearch_tpu.index.segment import build_tile_max  # noqa: E402
from elasticsearch_tpu.ops.scoring import (  # noqa: E402
    score_topk_dense_fused, score_topk_bundle_fused,
    match_mask_bundle_fused)
from elasticsearch_tpu.ops.pallas_scoring import (  # noqa: E402
    fused_topk_dense_pallas, fused_topk_bundle_pallas,
    match_mask_bundle_pallas, _CK_UNROLL)


def _reference_topk(fwd_tids, fwd_imps, qt, wq, live, k,
                    msm=None, boost=None):
    """Unfused semantics: full score matrix -> masked lax.top_k (the
    exact tie-breaking the fused paths must reproduce)."""
    b, cap = qt.shape[0], fwd_tids.shape[0]
    score = np.zeros((b, cap), np.float32)
    for qi in range(qt.shape[1]):
        contrib = ((fwd_tids[None] == qt[:, qi][:, None, None])
                   * fwd_imps[None]).sum(-1)
        score += contrib * wq[:, qi][:, None]
    match_score = score  # match signal: pre-boost, like eval_node
    if boost is not None:
        # eval_node applies boost AFTER the sum: fl(sum(w*imp)) * boost
        score = score * boost[:, None]
    if msm is None:
        msm = np.ones(b, np.int32)
    match = (((match_score > 0) | (msm <= 0)[:, None])
             & (msm <= 1)[:, None] & live[None, :])
    masked = np.where(match, score, -np.inf).astype(np.float32)
    k_eff = min(k, cap)
    top_s, top_i = jax.lax.top_k(jnp.asarray(masked), k_eff)
    total = match.sum(axis=-1).astype(np.int32)
    return np.asarray(top_s), np.asarray(top_i), total


def _case(rng, cap=2048, slots=4, n_terms=40, b=3, q=3, tile=512,
          seed_live=None):
    # per-doc DISTINCT term ids (the forward-index invariant the fused
    # pruning relies on — a real segment packs one slot per distinct
    # term), with ~20% of slots knocked out to -1 padding
    fwd_tids = np.argsort(rng.random((cap, n_terms)), axis=1)[
        :, :slots].astype(np.int32)
    fwd_tids[rng.random((cap, slots)) < 0.2] = -1
    fwd_imps = rng.random((cap, slots), dtype=np.float32)
    fwd_imps[fwd_tids < 0] = 0.0
    qt = rng.integers(-1, n_terms, size=(b, q)).astype(np.int32)
    wq = rng.random((b, q), dtype=np.float32) + 0.01
    wq[qt < 0] = 0.0
    live = np.ones(cap, bool) if seed_live is None else seed_live
    tm = build_tile_max(fwd_tids, fwd_imps, n_terms, cap, tile=tile)
    assert tm is not None and tm.n_tiles == cap // tile \
        and len(tm.start) == n_terms + 1
    return fwd_tids, fwd_imps, tm, qt, wq, live


def _assert_tri_parity(fwd_tids, fwd_imps, tm, qt, wq, live, k,
                       msm=None, boost=None):
    ref_s, ref_i, ref_t = _reference_topk(fwd_tids, fwd_imps, qt, wq,
                                          live, k, msm, boost)
    args = (jnp.asarray(fwd_tids), jnp.asarray(fwd_imps),
            jax.device_put(tm), jnp.asarray(qt), jnp.asarray(wq),
            jnp.asarray(live), min(k, fwd_tids.shape[0]))
    kw = {"msm": None if msm is None else jnp.asarray(msm),
          "boost": None if boost is None else jnp.asarray(boost)}
    for name, got in (
            ("xla", score_topk_dense_fused(*args, **kw)),
            ("pallas", fused_topk_dense_pallas(*args, interpret=True,
                                               **kw))):
        g_s, g_i, g_t, pruned = (np.asarray(x) for x in got)
        assert (g_t == ref_t).all(), (name, g_t, ref_t)
        for row in range(qt.shape[0]):
            n = min(int(ref_t[row]), ref_s.shape[1])
            assert (g_i[row, :n] == ref_i[row, :n]).all(), \
                (name, row, g_i[row, :n], ref_i[row, :n])
            np.testing.assert_allclose(g_s[row, :n], ref_s[row, :n],
                                       atol=1e-5, rtol=1e-5,
                                       err_msg=f"{name} row {row}")
            assert np.isneginf(g_s[row, n:]).all(), (name, row)
        assert pruned.shape == (3,)
        assert int(pruned[2]) > 0  # tiles were examined


@pytest.fixture()
def rng():
    # function-scoped: each test draws from a fresh seeded stream, so
    # corpora do not depend on which other tests ran before it
    return np.random.default_rng(7)


class TestBackendParity:
    def test_random_corpus(self, rng):
        _assert_tri_parity(*_case(rng), k=10)

    def test_skewed_corpus_prunes(self, rng):
        # one rare term confined to a single tile: the other tiles must
        # hard-skip, and pruning must not change the result
        case = _case(rng, n_terms=40)
        fwd_tids, fwd_imps, _tm, qt, wq, live = case
        fwd_tids[:] = -1
        fwd_imps[:] = 0.0
        fwd_tids[100:110, 0] = 39
        fwd_imps[100:110, 0] = 1.5
        tm = build_tile_max(fwd_tids, fwd_imps, 40, fwd_tids.shape[0],
                            tile=512)
        qt[:] = -1
        qt[:, 0] = 39
        wq[:] = 0.0
        wq[:, 0] = 1.0
        _assert_tri_parity(fwd_tids, fwd_imps, tm, qt, wq, live, k=5)
        _, _, _, pruned = (np.asarray(x) for x in score_topk_dense_fused(
            jnp.asarray(fwd_tids), jnp.asarray(fwd_imps), jax.device_put(tm),
            jnp.asarray(qt), jnp.asarray(wq), jnp.asarray(live), 5))
        assert int(pruned[0]) == 3  # 3 of 4 tiles hard-skipped

    def test_ties_resolve_to_lower_doc_ids(self, rng):
        # identical docs -> identical scores: tie order must match the
        # unfused lax.top_k (ascending doc id) exactly
        cap, slots = 1024, 2
        fwd_tids = np.zeros((cap, slots), np.int32)
        fwd_tids[:, 1] = -1
        fwd_imps = np.full((cap, slots), 0.5, np.float32)
        fwd_imps[:, 1] = 0.0
        tm = build_tile_max(fwd_tids, fwd_imps, 4, cap, tile=256)
        qt = np.zeros((2, 1), np.int32)
        wq = np.ones((2, 1), np.float32)
        live = np.ones(cap, bool)
        _assert_tri_parity(fwd_tids, fwd_imps, tm, qt, wq, live, k=7)

    def test_empty_query(self, rng):
        fwd_tids, fwd_imps, tm, qt, wq, live = _case(rng)
        qt[:] = -1
        wq[:] = 0.0
        _assert_tri_parity(fwd_tids, fwd_imps, tm, qt, wq, live, k=10)

    def test_k_exceeds_n_docs(self, rng):
        _assert_tri_parity(*_case(rng, cap=256, tile=256, b=2), k=500)

    def test_msm_match_all_and_match_none(self, rng):
        fwd_tids, fwd_imps, tm, qt, wq, live = _case(rng, b=4)
        msm = np.asarray([0, 1, 2, 0], np.int32)  # 0: all, 2: none
        # 0.3 is deliberately not a power of two: boost must be applied
        # post-selection (as eval_node does) for scores to stay exact
        boost = np.asarray([1.0, 2.0, 0.3, 0.5], np.float32)
        _assert_tri_parity(fwd_tids, fwd_imps, tm, qt, wq, live, k=10,
                           msm=msm, boost=boost)

    def test_dead_docs_excluded(self, rng):
        live = np.ones(2048, bool)
        live[::3] = False
        _assert_tri_parity(*_case(rng, seed_live=live), k=10)


class TestAutotunerSmoke:
    """Bench-smoke (tier-1, CPU): a 10k-doc pack through the executor
    must leave an autotuner backend choice and a nonzero block-prune
    rate in the node stats API."""

    def _build_pack(self, n_docs=10_000):
        from elasticsearch_tpu.index.mapping import MapperService
        from elasticsearch_tpu.index.segment import SegmentBuilder
        rng = random.Random(5)
        vocab = [f"w{i:03d}" for i in range(60)]
        svc = MapperService(mapping={"properties": {
            "message": {"type": "text"}}})
        builder = SegmentBuilder()
        for i in range(n_docs):
            words = rng.choices(vocab, k=4)
            if i % 2500 == 0:
                words.append("needleterm")
            builder.add(svc.parse(str(i), {"message": " ".join(words)}))
        seg = builder.build("smoke")
        live = np.zeros(seg.capacity, bool)
        live[: seg.num_docs] = True
        return svc, seg, live

    def test_autotune_choice_and_prune_rate_in_node_stats(self):
        from elasticsearch_tpu.node import Node
        from elasticsearch_tpu.search import executor as ex
        from elasticsearch_tpu.search.query_dsl import QueryParser
        svc, seg, live = self._build_pack()
        assert seg.text["message"].tile_max is not None
        ex._fused_stats.reset()
        parser = QueryParser(svc)
        binder = ex.QueryBinder(seg, svc)
        # a rare term matches a handful of tiles: the rest hard-skip
        bounds = [binder.bind(parser.parse({"bool": {
            "should": [{"match": {"message": "needleterm"}}],
            "minimum_should_match": 1}})) for _ in range(4)]
        (ts, _tk, ti, tt, _tm), _aggs = ex.execute_segment(
            seg, live, bounds, 10)
        assert int(tt[0]) == 4 and set(ti[0][:4].tolist()) == \
            {0, 2500, 5000, 7500}
        stats = ex.fused_scoring_stats()
        assert stats["backend_choices"], "autotuner recorded no choice"
        choice = next(iter(stats["backend_choices"].values()))
        assert choice["backend"] in ("pallas", "xla")
        assert stats["tiles"]["examined"] > 0
        assert stats["prune_rate"] > 0.0, stats
        # ... and the choice + prune rate are visible via node stats
        n = Node()
        try:
            ns = n.nodes_stats()["nodes"][n.name]["fused_scoring"]
            assert ns["backend_choices"]
            assert ns["prune_rate"] > 0.0
        finally:
            n.close()

    def test_fusion_disable_env_matches_fused_results(self):
        from elasticsearch_tpu.search import executor as ex
        from elasticsearch_tpu.search.query_dsl import QueryParser
        svc, seg, live = self._build_pack(n_docs=3000)
        parser = QueryParser(svc)
        binder = ex.QueryBinder(seg, svc)
        bounds = [binder.bind(parser.parse(
            {"match": {"message": f"w00{i} needleterm"}}))
            for i in range(3)]
        (ts, _tk, ti, tt, _tm), _ = ex.execute_segment(seg, live, bounds,
                                                       10)
        os.environ["ES_TPU_FUSED"] = "0"
        try:
            (ts2, _tk2, ti2, tt2, _tm2), _ = ex.execute_segment(
                seg, live, bounds, 10)
        finally:
            os.environ.pop("ES_TPU_FUSED", None)
        assert (tt == tt2).all()
        for row in range(3):
            n = min(int(tt[row]), 10)
            assert (ti[row, :n] == ti2[row, :n]).all()
            np.testing.assert_allclose(ts[row, :n], ts2[row, :n],
                                       atol=1e-5)


# ---------------------------------------------------------------------------
# bool clause bundles (block-max WAND)
# ---------------------------------------------------------------------------


def _np_bundle_reference(clauses, cl_inputs, text_np, num_cols,
                         msm, boost, live, k):
    """eval_node bool semantics in numpy over the full doc space, then a
    masked lax.top_k — the exact contract every fused backend must hit.
    text_np: {field: (fwd_tids, fwd_imps)} — clauses may score ANY mix
    of text fields (the multi-field coverage the Pallas kernel grew)."""
    cap = live.shape[0]
    b = msm.shape[0]
    score = np.zeros((b, cap), np.float32)
    must_ok = np.ones((b, cap), bool)
    not_any = np.zeros((b, cap), bool)
    cnt = np.zeros((b, cap), np.int32)
    for (role, kind, field, _w), inp in zip(clauses, cl_inputs):
        if kind in ("terms_dense", "term_text"):
            fwd_tids, fwd_imps = text_np[field]
            qt, wq, msm_c, boost_c = inp
            s_leaf = np.zeros((b, cap), np.float32)
            for qi in range(qt.shape[1]):
                contrib = ((fwd_tids[None] == qt[:, qi][:, None, None])
                           * fwd_imps[None]).sum(-1)
                s_leaf += (contrib * wq[:, qi][:, None]).astype(np.float32)
            m_leaf = s_leaf > 0
            m = (m_leaf | (msm_c <= 0)[:, None]) & (msm_c <= 1)[:, None]
            s = np.where(m_leaf, s_leaf, 0.0) * boost_c[:, None]
        else:
            lo, hi = inp
            vals, exists = num_cols[field]
            m = ((vals[None] >= lo[:, None]) & (vals[None] <= hi[:, None])
                 & exists[None])
            s = None
        if role == "must":
            score += np.where(m, s, 0.0)
            must_ok &= m
        elif role == "filter":
            must_ok &= m
        elif role == "must_not":
            not_any |= m
        else:
            if s is not None:
                score += np.where(m, s, 0.0)
            cnt += m.astype(np.int32)
    match = must_ok & ~not_any & (cnt >= msm[:, None]) & live[None, :]
    score = score * boost[:, None]
    masked = np.where(match, score, -np.inf).astype(np.float32)
    top_s, top_i = jax.lax.top_k(jnp.asarray(masked), min(k, cap))
    return (np.asarray(top_s), np.asarray(top_i),
            match.sum(axis=-1).astype(np.int32), match)


def _random_bundle(rng, b, n_terms, roles, wrapped_mask):
    """Random per-clause inputs for a role tuple (dense clauses only)."""
    clauses = []
    cl_inputs = []
    for role, wrapped in zip(roles, wrapped_mask):
        q = int(rng.integers(1, 4))
        qt = rng.integers(-1, n_terms, size=(b, q)).astype(np.int32)
        wq = (rng.random((b, q), dtype=np.float32) + 0.01)
        wq[qt < 0] = 0.0
        if wrapped:
            msm_c = rng.integers(0, 3, size=b).astype(np.int32)
            boost_c = (rng.random(b, dtype=np.float32) * 2.5
                       + 0.1).astype(np.float32)
        else:
            msm_c = np.ones(b, np.int32)
            boost_c = np.ones(b, np.float32)
        clauses.append((role, "terms_dense", "f", bool(wrapped)))
        cl_inputs.append((qt, wq, msm_c, boost_c))
    return tuple(clauses), tuple(cl_inputs)


class TestBundleOpsParity:
    """score_topk_bundle_fused / fused_topk_bundle_pallas vs the numpy
    bool reference on randomized small packs."""

    ROLE_SETS = [
        ("must", "should"),
        ("must", "should", "should"),
        ("must", "must", "should"),
        ("must_not", "should", "should"),
        ("must", "must_not", "should"),
        ("should",),
    ]

    def _check(self, rng, roles, k=10, msm_max=3):
        fwd_tids, fwd_imps, tm, _qt, _wq, live = _case(rng)
        b = 4
        n_terms = len(tm.start) - 1
        wrapped = rng.random(len(roles)) < 0.5
        clauses, cl_inputs = _random_bundle(rng, b, n_terms, roles,
                                            wrapped)
        msm = rng.integers(0, msm_max, size=b).astype(np.int32)
        boost = (rng.random(b, dtype=np.float32) * 2.0 + 0.1
                 ).astype(np.float32)
        ref_s, ref_i, ref_t, _m = _np_bundle_reference(
            clauses, cl_inputs, {"f": (fwd_tids, fwd_imps)}, {}, msm,
            boost, live, k)
        j_inputs = tuple(tuple(jnp.asarray(a) for a in inp)
                         for inp in cl_inputs)
        text_cols = {"f": {"fwd_tids": jnp.asarray(fwd_tids),
                           "fwd_imps": jnp.asarray(fwd_imps),
                           "tile_max": jax.device_put(tm)}}
        got = {}
        got["xla"] = score_topk_bundle_fused(
            text_cols, {}, clauses, j_inputs, jnp.asarray(msm),
            jnp.asarray(boost), jnp.asarray(live), k)
        # pallas kernel (interpret): the SAME calling convention as the
        # XLA engine — clause stacking happens inside the entry
        got["pallas"] = fused_topk_bundle_pallas(
            text_cols, {}, clauses, j_inputs, jnp.asarray(msm),
            jnp.asarray(boost), jnp.asarray(live), k, interpret=True)
        for name, out in got.items():
            g_s, g_i, g_t, pruned = (np.asarray(x) for x in out[:4])
            assert (g_t == ref_t).all(), (name, roles, g_t, ref_t)
            for row in range(b):
                n = min(int(ref_t[row]), k)
                assert (g_i[row, :n] == ref_i[row, :n]).all(), \
                    (name, roles, row)
                np.testing.assert_allclose(g_s[row, :n], ref_s[row, :n],
                                           atol=1e-5, rtol=1e-5,
                                           err_msg=f"{name} {roles}")
                assert np.isneginf(g_s[row, n:]).all()

    def test_randomized_role_mixes(self, rng):
        for i, roles in enumerate(self.ROLE_SETS):
            self._check(np.random.default_rng(100 + i), roles)

    def test_range_filter_prunes_tiles(self, rng):
        # a numeric filter confined to the first tile: every other tile
        # must hard-skip via the pack-time [tile_lo, tile_hi] extrema,
        # and results must still match the reference exactly
        from elasticsearch_tpu.index.segment import build_tile_minmax
        fwd_tids, fwd_imps, tm, _qt, _wq, live = _case(rng)
        cap = fwd_tids.shape[0]
        b, n_terms = 3, len(tm.start) - 1
        clauses, cl_inputs = _random_bundle(
            rng, b, n_terms, ("must", "should"), [False, True])
        vals = np.arange(cap, dtype=np.int32)
        exists = np.ones(cap, bool)
        exists[::7] = False
        lo = np.zeros(b, np.int32)
        hi = np.full(b, 400, np.int32)        # tile 0 only (tile=512)
        clauses = clauses + (("filter", "range_int", "n", False),)
        cl_inputs = cl_inputs + ((lo, hi),)
        msm = np.zeros(b, np.int32)
        boost = np.ones(b, np.float32)
        ref_s, ref_i, ref_t, ref_m = _np_bundle_reference(
            clauses, cl_inputs, {"f": (fwd_tids, fwd_imps)},
            {"n": (vals, exists)}, msm, boost, live, 10)
        tlo, thi = build_tile_minmax(vals, exists, cap, tile=512)
        num_cols = {"n": {"values": jnp.asarray(vals),
                          "exists": jnp.asarray(exists),
                          "tile_lo": jnp.asarray(tlo),
                          "tile_hi": jnp.asarray(thi)}}
        text_cols = {"f": {"fwd_tids": jnp.asarray(fwd_tids),
                           "fwd_imps": jnp.asarray(fwd_imps),
                           "tile_max": jax.device_put(tm)}}
        j_inputs = tuple(tuple(jnp.asarray(a) for a in inp)
                         for inp in cl_inputs)
        g_s, g_i, g_t, pruned, match = score_topk_bundle_fused(
            text_cols, num_cols, clauses, j_inputs, jnp.asarray(msm),
            jnp.asarray(boost), jnp.asarray(live), 10, emit_match=True)
        g_s, g_i, g_t, pruned, match = (np.asarray(x) for x in
                                        (g_s, g_i, g_t, pruned, match))
        assert (g_t == ref_t).all()
        assert int(pruned[0]) == 3            # 3 of 4 tiles hard-skipped
        assert (match == ref_m).all()         # emit-match mode is exact
        for row in range(b):
            n = min(int(ref_t[row]), 10)
            assert (g_i[row, :n] == ref_i[row, :n]).all()

    def test_nan_value_does_not_poison_tile_extrema(self, rng):
        # one NaN doc must not make the whole tile's [lo, hi] empty —
        # the other docs in its tile still match the range filter
        from elasticsearch_tpu.index.segment import build_tile_minmax
        cap = 2048
        vals = np.arange(cap, dtype=np.float32)
        vals[100] = np.nan
        exists = np.ones(cap, bool)
        tlo, thi = build_tile_minmax(vals, exists, cap, tile=512)
        assert np.isfinite(tlo).all() and np.isfinite(thi).all()
        assert tlo[0] == 0.0 and thi[0] == 511.0


def _two_field_case(rng, cap=2048, tile=512):
    """Two text fields + one int column: the full-coverage kernel shapes
    (multi-field, range masks) in one fixture."""
    from elasticsearch_tpu.index.segment import build_tile_minmax

    def field(slots=4, n_terms=40):
        tids = np.argsort(rng.random((cap, n_terms)), axis=1)[
            :, :slots].astype(np.int32)
        tids[rng.random((cap, slots)) < 0.2] = -1
        imps = rng.random((cap, slots), dtype=np.float32)
        imps[tids < 0] = 0.0
        tm = build_tile_max(tids, imps, n_terms, cap, tile=tile)
        return {"fwd_tids": jnp.asarray(tids),
                "fwd_imps": jnp.asarray(imps),
                "tile_max": jax.device_put(tm)}, (tids, imps)

    f_dev, f_np = field()
    g_dev, g_np = field(slots=3)
    vals = np.arange(cap, dtype=np.int32)
    exists = np.ones(cap, bool)
    exists[::7] = False
    tlo, thi = build_tile_minmax(vals, exists, cap, tile=tile)
    text_cols = {"f": f_dev, "g": g_dev}
    text_np = {"f": f_np, "g": g_np}
    num_cols = {"n": {"values": jnp.asarray(vals),
                      "exists": jnp.asarray(exists),
                      "tile_lo": jnp.asarray(tlo),
                      "tile_hi": jnp.asarray(thi)}}
    num_np = {"n": (vals, exists)}
    return text_cols, text_np, num_cols, num_np


def _dense_inp(rng, b, q, n_terms=40):
    qt = rng.integers(-1, n_terms, size=(b, q)).astype(np.int32)
    wq = (rng.random((b, q), dtype=np.float32) + 0.01)
    wq[qt < 0] = 0.0
    return (qt, wq, np.ones(b, np.int32), np.ones(b, np.float32))


class TestPallasFullBundleParity:
    """The newly admitted kernel shapes — multi-text-field bundles,
    range filter/must_not masks, emit-match, the mask-only k == 0 grid,
    multi-pass selection past the unroll cap, and the stepped chunked
    walk — each gated on exact identity with the XLA engine and the
    numpy reference."""

    CLAUSES = (("must", "terms_dense", "f", False),
               ("filter", "range_int", "n", False),
               ("must_not", "terms_dense", "g", False),
               ("should", "terms_dense", "g", False),
               ("should", "terms_dense", "f", False))

    def _inputs(self, rng, b=3):
        text_cols, text_np, num_cols, num_np = _two_field_case(rng)
        cl_inputs = (_dense_inp(rng, b, 2),
                     (np.zeros(b, np.int32), np.full(b, 900, np.int32)),
                     _dense_inp(rng, b, 1), _dense_inp(rng, b, 3),
                     _dense_inp(rng, b, 2))
        msm = rng.integers(0, 2, size=b).astype(np.int32)
        boost = (rng.random(b, dtype=np.float32) + 0.2).astype(np.float32)
        live = np.ones(2048, bool)
        live[::11] = False
        j_inputs = tuple(tuple(jnp.asarray(a) for a in inp)
                         for inp in cl_inputs)
        return (text_cols, text_np, num_cols, num_np, cl_inputs,
                j_inputs, msm, boost, live)

    def _tri(self, rng, k, emit_match=False, step=None):
        (text_cols, text_np, num_cols, num_np, cl_inputs, j_inputs,
         msm, boost, live) = self._inputs(rng)
        ref = _np_bundle_reference(self.CLAUSES, cl_inputs, text_np,
                                   num_np, msm, boost, live, k)
        args = (text_cols, num_cols, self.CLAUSES, j_inputs,
                jnp.asarray(msm), jnp.asarray(boost), jnp.asarray(live),
                k)
        got = {"xla": score_topk_bundle_fused(*args,
                                              emit_match=emit_match),
               "pallas": fused_topk_bundle_pallas(
                   *args, emit_match=emit_match, step=step,
                   interpret=True)}
        ref_s, ref_i, ref_t, ref_m = ref
        for name, out in got.items():
            out = list(out)
            if name == "pallas" and step is not None:
                assert not bool(out[-1]), "spurious timed_out"
                out = out[:-1]
            g_s, g_i, g_t = (np.asarray(x) for x in out[:3])
            assert (g_t == ref_t).all(), (name, g_t, ref_t)
            if emit_match:
                assert (np.asarray(out[4]) == ref_m).all(), name
            for row in range(g_t.shape[0]):
                n = min(int(ref_t[row]), min(k, 2048))
                assert (g_i[row, :n] == ref_i[row, :n]).all(), (name, row)
                np.testing.assert_allclose(g_s[row, :n], ref_s[row, :n],
                                           atol=1e-5, rtol=1e-5)
                assert np.isneginf(g_s[row, n:]).all(), (name, row)
        return got

    def test_multi_field_range_masks(self, rng):
        self._tri(rng, k=10)

    def test_emit_match_mask_exact(self, rng):
        self._tri(rng, k=7, emit_match=True)

    def test_multi_pass_selection_past_unroll_cap(self, rng):
        # ck = min(k, tile) = 200 > _CK_UNROLL: the kernel's fori_loop
        # selection path must produce the identical candidate order
        assert _CK_UNROLL < 200
        self._tri(rng, k=200)

    def test_k_zero_mask_only_grid(self, rng):
        (text_cols, text_np, num_cols, num_np, cl_inputs, j_inputs,
         msm, boost, live) = self._inputs(rng)
        _s, _i, ref_t, ref_m = _np_bundle_reference(
            self.CLAUSES, cl_inputs, text_np, num_np, msm, boost,
            live, 1)
        args = (text_cols, num_cols, self.CLAUSES, j_inputs,
                jnp.asarray(msm), jnp.asarray(boost), jnp.asarray(live))
        x_t, _xp, x_m = match_mask_bundle_fused(*args, emit_match=True)
        p_t, _pp, p_m = match_mask_bundle_pallas(*args, emit_match=True,
                                                 interpret=True)
        assert (np.asarray(x_t) == ref_t).all()
        assert (np.asarray(p_t) == ref_t).all()
        assert (np.asarray(x_m) == ref_m).all()
        assert (np.asarray(p_m) == ref_m).all()

    def test_stepped_chunk_parity_and_threshold_carry(self, rng):
        """A chunked walk (chunk_tiles=1 — every tile boundary is a
        chunk boundary) must be bit-identical to the single-call walk,
        INCLUDING the thresholded-prune count: a tile thresholded by a
        running threshold established in an EARLIER chunk proves the
        carry survives the chunk split."""
        def never(c, st):
            return jnp.bool_(False), st

        plain = self._tri(np.random.default_rng(41), k=3)
        stepped = self._tri(np.random.default_rng(41), k=3,
                            step=(1, 0, never))
        p_prune = np.asarray(plain["pallas"][3])
        s_prune = np.asarray(stepped["pallas"][3])
        assert (p_prune == s_prune).all(), (p_prune, s_prune)
        for a, b in zip(plain["pallas"], stepped["pallas"][:-1]):
            assert (np.asarray(a) == np.asarray(b)).all()

    def test_stepped_threshold_actually_prunes_across_chunks(self):
        # tile 0 outscores every later tile -> after chunk 0 the running
        # threshold (1.0) exceeds the later tiles' slack-inflated bound
        # (~0.5), so every later chunk's tiles threshold-prune; losing
        # the carry at the chunk boundary would zero this counter
        cap, tile = 2048, 512
        fwd_tids = np.zeros((cap, 2), np.int32)
        fwd_tids[:, 1] = -1
        fwd_imps = np.full((cap, 2), 0.5, np.float32)
        fwd_imps[:tile, 0] = 1.0
        fwd_imps[:, 1] = 0.0
        tm = build_tile_max(fwd_tids, fwd_imps, 4, cap, tile=tile)
        text_cols = {"f": {"fwd_tids": jnp.asarray(fwd_tids),
                           "fwd_imps": jnp.asarray(fwd_imps),
                           "tile_max": jax.device_put(tm)}}
        clauses = (("should", "terms_dense", "f", False),)
        b = 2
        cl_inputs = ((jnp.zeros((b, 1), jnp.int32),
                      jnp.ones((b, 1), jnp.float32),
                      jnp.ones((b,), jnp.int32),
                      jnp.ones((b,), jnp.float32)),)
        msm = jnp.ones((b,), jnp.int32)
        live = jnp.ones(cap, bool)

        def never(c, st):
            return jnp.bool_(False), st

        out = fused_topk_bundle_pallas(
            text_cols, {}, clauses, cl_inputs, msm, None, live, 3,
            step=(1, 0, never), interpret=True)
        top_s, top_i, total, pruned, timed = out
        assert not bool(timed)
        assert int(np.asarray(total)[0]) == cap
        assert (np.asarray(top_i)[0] == [0, 1, 2]).all()
        # 4 tiles: tile 0 examined, tiles 1..3 thresholded via the
        # carried running threshold
        assert float(np.asarray(pruned)[1]) == 3.0, np.asarray(pruned)

    def test_stepped_timeout_reports_from_chunk_boundary(self, rng):
        (text_cols, _tn, num_cols, _nn, _ci, j_inputs, msm, boost,
         live) = self._inputs(rng)

        def after_first(c, st):
            return jnp.asarray(c >= 1), st

        out = fused_topk_bundle_pallas(
            text_cols, num_cols, self.CLAUSES, j_inputs,
            jnp.asarray(msm), jnp.asarray(boost), jnp.asarray(live), 5,
            step=(1, 0, after_first), interpret=True)
        assert bool(out[-1]), "timed_out verdict lost"
        # the mask-only grid steps the same way
        m_out = match_mask_bundle_pallas(
            text_cols, num_cols, self.CLAUSES, j_inputs,
            jnp.asarray(msm), jnp.asarray(boost), jnp.asarray(live),
            emit_match=False, step=(1, 0, after_first), interpret=True)
        assert bool(m_out[-1])

    def test_stepped_xla_vs_pallas_verdict_parity(self, rng):
        """The XLA stepped loop and the chunked Pallas walk must agree
        on the timed_out verdict AND (un-timed) on every result byte —
        the resident loop swaps between them per the autotuned choice."""
        (text_cols, _tn, num_cols, _nn, _ci, j_inputs, msm, boost,
         live) = self._inputs(rng)
        args = (text_cols, num_cols, self.CLAUSES, j_inputs,
                jnp.asarray(msm), jnp.asarray(boost), jnp.asarray(live),
                5)

        def never(c, st):
            return jnp.bool_(False), st

        x = score_topk_bundle_fused(*args, step=(2, 0, never))
        p = fused_topk_bundle_pallas(*args, step=(2, 0, never),
                                     interpret=True)
        assert not bool(x[-1]) and not bool(p[-1])
        for a, b in zip(x[:3], p[:3]):
            assert (np.asarray(a) == np.asarray(b)).all()

        def always(c, st):
            return jnp.bool_(True), st

        x_t = score_topk_bundle_fused(*args, step=(2, 0, always))
        p_t = fused_topk_bundle_pallas(*args, step=(2, 0, always),
                                       interpret=True)
        assert bool(x_t[-1]) and bool(p_t[-1])


class TestExecutorBundleIdentity:
    """Full-executor identity: fused bool plans (admitted by the
    classifier) vs the unfused path, on both the autotuned backend and
    a forced pallas (interpret) backend, plus the fused+aggs mode."""

    def _build(self, n_docs=4000):
        from elasticsearch_tpu.index.mapping import MapperService
        from elasticsearch_tpu.index.segment import SegmentBuilder
        rng = random.Random(17)
        vocab = [f"w{i:03d}" for i in range(50)]
        svc = MapperService(mapping={"properties": {
            "message": {"type": "text"},
            "status": {"type": "keyword"},
            "size": {"type": "long"},
            "ts": {"type": "date"}}})
        builder = SegmentBuilder()
        base = 1420070400000
        for i in range(n_docs):
            builder.add(svc.parse(str(i), {
                "message": " ".join(rng.choices(vocab, k=6)),
                "status": rng.choice(["ok", "err", "warn"]),
                "size": rng.randint(0, 1000),
                "ts": base + rng.randint(0, 90 * 86400) * 1000}))
        seg = builder.build("bundle")
        live = np.zeros(seg.capacity, bool)
        live[: seg.num_docs] = True
        return svc, seg, live

    BODIES = [
        {"bool": {"must": [{"match": {"message": "w001"}}],
                  "should": [{"match": {"message": "w002 w003"}}]}},
        {"bool": {"must": [{"match": {
            "message": {"query": "w004 w005", "boost": 2.5}}}],
            "should": [{"match": {"message": "w006"}}]}},
        {"bool": {"should": [{"match": {"message": "w001 w007"}},
                             {"match": {"message": "w002"}},
                             {"match": {"message": "w003"}}],
                  "minimum_should_match": 2}},
        {"bool": {"must": [{"match": {"message": "w008 w009"}}],
                  "filter": [{"range": {"size": {"gte": 100,
                                                 "lt": 700}}}],
                  "must_not": [{"match": {"message": "w010"}}]}},
        {"bool": {"must": [{"match": {"message": "w011"}}],
                  "should": [{"match": {"message": "w012 w013"}}],
                  "boost": 0.3}},
    ]

    def _identity(self, svc, seg, live, body, k=10):
        from elasticsearch_tpu.search import executor as ex
        from elasticsearch_tpu.search.query_dsl import QueryParser
        parser = QueryParser(svc)
        binder = ex.QueryBinder(seg, svc)
        bounds = [binder.bind(parser.parse(body)) for _ in range(3)]
        (ts, _tk, ti, tt, _tm), _ = ex.execute_segment(seg, live,
                                                       bounds, k)
        os.environ["ES_TPU_FUSED"] = "0"
        try:
            (ts2, _tk2, ti2, tt2, _), _ = ex.execute_segment(
                seg, live, bounds, k)
        finally:
            os.environ.pop("ES_TPU_FUSED", None)
        assert (tt == tt2).all(), body
        for row in range(3):
            n = min(int(tt[row]), k)
            assert (ti[row, :n] == ti2[row, :n]).all(), (body, row)
            assert (ts[row, :n] == ts2[row, :n]).all(), (body, row)

    def test_bool_mixes_fused_identical_to_unfused(self):
        from elasticsearch_tpu.search import executor as ex
        svc, seg, live = self._build()
        ex._fused_stats.reset()
        for body in self.BODIES:
            self._identity(svc, seg, live, body)
        stats = ex.fused_scoring_stats()
        # every shape above must actually have been ADMITTED (one fused
        # run per body; the ES_TPU_FUSED=0 reruns count as 'disabled')
        assert stats["admission"]["admitted"] >= len(self.BODIES), stats
        assert stats["dispatches"] >= len(self.BODIES)

    def test_forced_pallas_backend_identity(self):
        from elasticsearch_tpu.search import executor as ex
        svc, seg, live = self._build(2000)
        os.environ["ES_TPU_FUSED_BACKEND"] = "pallas"
        try:
            # single-text-field bundles: the pallas kernel serves them
            # in interpret mode off-TPU; identity must still be exact
            for body in self.BODIES[:3]:
                self._identity(svc, seg, live, body, k=5)
        finally:
            os.environ.pop("ES_TPU_FUSED_BACKEND", None)

    def test_k_and_aggs_served_fused_identical(self):
        from elasticsearch_tpu.search import executor as ex
        from elasticsearch_tpu.search.shard_searcher import ShardReader
        svc, seg, live = self._build()
        reader = ShardReader("idx", [seg], {seg.seg_id: live}, svc)
        body = {"size": 5,
                "query": {"bool": {
                    "must": [{"match": {"message": "w001"}}],
                    "should": [{"match": {"message": "w002 w003"}}]}},
                "aggs": {
                    "by_status": {"terms": {"field": "status"}},
                    "per_week": {"date_histogram": {"field": "ts",
                                                    "interval": "week"}}}}
        ex._fused_stats.reset()
        r1 = reader.search(dict(body))
        stats = ex.fused_scoring_stats()
        # the acceptance criterion: a k>0 search WITH terms +
        # date_histogram aggs is served by the fused path
        assert stats["admission"]["admitted"] > 0, stats["admission"]
        assert stats["dispatches"] > 0
        os.environ["ES_TPU_FUSED"] = "0"
        try:
            r2 = reader.search(dict(body))
        finally:
            os.environ.pop("ES_TPU_FUSED", None)
        assert r1["hits"]["total"] == r2["hits"]["total"]
        assert [h["_id"] for h in r1["hits"]["hits"]] == \
            [h["_id"] for h in r2["hits"]["hits"]]
        assert r1["aggregations"] == r2["aggregations"]


class TestAutotunerTiming:
    """Warmup + best-of-N timing (an earlier round's mischoice fix) and the
    persisted choice store."""

    def _fresh_key(self, tag):
        import uuid
        return (f"test-{tag}", uuid.uuid4().hex, 1024, 8, 4)

    def test_warmup_absorbs_first_execution_skew(self, monkeypatch):
        from elasticsearch_tpu.search import executor as ex
        monkeypatch.setattr(ex, "fused_pallas_ok", lambda ck: True)
        monkeypatch.setenv("ES_TPU_AUTOTUNE_REPS", "3")
        calls = {"xla": 0, "pallas": 0}
        import time as _t

        def run(backend):
            calls[backend] += 1
            if backend == "xla":
                # first post-compile execution pays a one-time cost —
                # the skew that made an earlier round's run commit to
                # pallas; steady state xla is the faster backend
                _t.sleep(0.02 if calls["xla"] == 2 else 0.001)
            else:
                _t.sleep(0.005)

        choice = ex.resolve_fused_backend(self._fresh_key("skew"), 8,
                                          run)
        assert choice == "xla"
        # compile + warmup + N timed runs per backend
        assert calls["xla"] == 5 and calls["pallas"] == 5

    def test_choices_persist_and_invalidate_by_fingerprint(
            self, tmp_path, monkeypatch):
        from elasticsearch_tpu.search import executor as ex
        monkeypatch.setattr(ex, "fused_pallas_ok", lambda ck: True)
        store = str(tmp_path / "fused_autotune.json")
        key = self._fresh_key("persist")
        try:
            ex.configure_autotune_persistence(store)
            import time as _t

            def run_slow_pallas(backend):
                _t.sleep(0.004 if backend == "pallas" else 0.001)

            assert ex.resolve_fused_backend(key, 8,
                                            run_slow_pallas) == "xla"
            assert os.path.exists(store)
            # simulate a restart: in-memory cache gone, store reloaded
            ex._autotune_choices.clear()
            ex.configure_autotune_persistence(store)

            def run_must_not_time(_backend):
                raise AssertionError("persisted choice must skip timing")

            assert ex.resolve_fused_backend(key, 8,
                                            run_must_not_time) == "xla"
            # a refreshed pack = new fingerprint = new key: re-tunes
            key2 = self._fresh_key("persist")
            calls = []

            def run_count(backend):
                calls.append(backend)
                _t.sleep(0.001 if backend == "pallas" else 0.004)

            assert ex.resolve_fused_backend(key2, 8,
                                            run_count) == "pallas"
            assert calls, "new fingerprint must re-tune"
        finally:
            ex.configure_autotune_persistence(None)

    def test_loss_audit_reports_pallas_losing_by_over_10pct(
            self, monkeypatch):
        """The ROADMAP item-3 regression signal: a shape where the
        Pallas candidate loses to XLA by >10% lands in
        nodes_stats()['fused_scoring']['loss_audit'] with both timings,
        whichever backend won."""
        from elasticsearch_tpu.search import executor as ex
        monkeypatch.setattr(ex, "fused_pallas_ok", lambda ck: True)
        ex._fused_stats.reset()
        import time as _t

        def pallas_2x(backend):
            _t.sleep(0.004 if backend == "pallas" else 0.002)

        ex.resolve_fused_backend(self._fresh_key("audit"), 8, pallas_2x)

        def close_race(backend):
            _t.sleep(0.002)

        ex.resolve_fused_backend(self._fresh_key("close"), 8, close_race)
        audit = ex.fused_scoring_stats()["loss_audit"]
        assert audit["count"] == 1, audit
        shape = audit["shapes"][0]
        assert shape["ratio"] > 1.1
        assert shape["pallas_ms"] > shape["xla_ms"]
        assert shape["backend"] == "xla"

    def test_forced_env_does_not_clobber_audited_timings(
            self, monkeypatch):
        """ES_TPU_FUSED_BACKEND outranks a cached tuned choice on every
        path (resident and cold agree), but a forced dispatch must not
        overwrite the tuned entry's timings — the shape would silently
        drop out of the loss audit."""
        from elasticsearch_tpu.search import executor as ex
        monkeypatch.setattr(ex, "fused_pallas_ok", lambda ck: True)
        ex._fused_stats.reset()
        key = self._fresh_key("forced-audit")
        import time as _t

        def pallas_2x(backend):
            _t.sleep(0.004 if backend == "pallas" else 0.002)

        assert ex.resolve_fused_backend(key, 8, pallas_2x) == "xla"
        assert ex.fused_scoring_stats()["loss_audit"]["count"] == 1
        monkeypatch.setenv("ES_TPU_FUSED_BACKEND", "pallas")
        # forced wins over the cached tuned choice...
        assert ex.resolve_fused_backend(key, 8, pallas_2x) == "pallas"
        # ...but the audited timings survive the forced dispatch
        assert ex.fused_scoring_stats()["loss_audit"]["count"] == 1
        monkeypatch.delenv("ES_TPU_FUSED_BACKEND")
        # unsetting restores the tuned choice
        assert ex.resolve_fused_backend(key, 8, pallas_2x) == "xla"

    def test_persisted_store_keeps_both_timings(self, tmp_path,
                                                monkeypatch):
        """The store persists per-backend best-of-N (not just the
        winner) and reloads it into the loss audit; pre-timings plain
        string entries still load."""
        import json as _json
        from elasticsearch_tpu.search import executor as ex
        monkeypatch.setattr(ex, "fused_pallas_ok", lambda ck: True)
        store = str(tmp_path / "fused_autotune.json")
        key = self._fresh_key("timings")
        try:
            ex.configure_autotune_persistence(store)
            import time as _t

            def pallas_slow(backend):
                _t.sleep(0.004 if backend == "pallas" else 0.001)

            ex.resolve_fused_backend(key, 8, pallas_slow)
            with open(store) as f:
                data = _json.load(f)
            entry = next(iter(data.values()))
            assert entry["choice"] == "xla"
            assert set(entry["timings_ms"]) == {"pallas", "xla"}
            # restart: reloaded timings re-enter the audit without
            # re-timing
            ex._autotune_choices.clear()
            ex._fused_stats.reset()
            ex.configure_autotune_persistence(store)

            def must_not_time(_backend):
                raise AssertionError("persisted choice must skip timing")

            assert ex.resolve_fused_backend(key, 8,
                                            must_not_time) == "xla"
            assert ex.fused_scoring_stats()["loss_audit"]["count"] == 1
            # legacy plain-string entries load as choice-only
            with open(store, "w") as f:
                _json.dump({"legacy-key": "pallas"}, f)
            ex.configure_autotune_persistence(store)
            assert ex.resolve_fused_backend(
                self._fresh_key("legacy"), 8,
                persist_keys=("legacy-key",)) == "pallas"
        finally:
            ex.configure_autotune_persistence(None)


class TestKZeroMaskOnly:
    """k == 0 plans (size-0 counts / filtered aggs): the match-mask-only
    fused pass must admit what the classifier accepts and produce
    results identical to the unfused path — totals, per-bucket aggs —
    while never touching the score matrix."""

    def _reader(self, n_docs=2500):
        from elasticsearch_tpu.search.shard_searcher import ShardReader
        svc, seg, live = TestExecutorBundleIdentity()._build(n_docs)
        return ShardReader("idx", [seg], {seg.seg_id: live}, svc)

    BODIES = [
        {"size": 0, "query": {"match": {"message": "w001 w002"}}},
        {"size": 0, "query": {"bool": {
            "must": [{"match": {"message": "w003"}}],
            "filter": [{"range": {"size": {"gte": 100, "lt": 800}}}]}},
         "aggs": {"s": {"terms": {"field": "status", "size": 5}}}},
        {"size": 0, "query": {"bool": {
            "should": [{"match": {"message": "w004 w005"}},
                       {"match": {"message": "w006"}}],
            "minimum_should_match": 1}},
         "aggs": {"w": {"date_histogram": {"field": "ts",
                                           "interval": "week"}}}},
    ]

    def test_identity_and_admission(self):
        from elasticsearch_tpu.search import executor as ex
        reader = self._reader()
        ex._fused_stats.reset()
        fused = [reader.search(dict(b)) for b in self.BODIES]
        stats = ex.fused_scoring_stats()
        assert stats["admission"]["admitted"] >= len(self.BODIES), stats
        assert stats["admission"]["rejected"].get("k_zero", 0) == 0
        os.environ["ES_TPU_FUSED"] = "0"
        try:
            plain = [reader.search(dict(b)) for b in self.BODIES]
        finally:
            os.environ.pop("ES_TPU_FUSED", None)
        for f, p, b in zip(fused, plain, self.BODIES):
            assert f["hits"]["total"] == p["hits"]["total"], b
            assert f.get("aggregations") == p.get("aggregations"), b

    def test_count_through_node(self):
        from elasticsearch_tpu.search import executor as ex
        reader = self._reader(1200)
        ex._fused_stats.reset()
        got = reader.count({"query": {"match": {"message": "w007"}}})
        assert ex.fused_scoring_stats()["admission"]["admitted"] >= 1
        os.environ["ES_TPU_FUSED"] = "0"
        try:
            want = reader.count({"query": {"match": {"message": "w007"}}})
        finally:
            os.environ.pop("ES_TPU_FUSED", None)
        assert got == want


class TestMeshPersistedChoice:
    """The mesh path must reuse a persisted single-chip choice for an
    identical pack fingerprint instead of the static
    pallas-when-eligible pick."""

    def test_persist_keys_reused_without_timing(self, tmp_path,
                                                monkeypatch):
        from elasticsearch_tpu.search import executor as ex
        monkeypatch.setattr(ex, "fused_pallas_ok", lambda ck: True)
        store = str(tmp_path / "fused_autotune.json")
        desc = ("bool", (), (("terms_dense", "message", 4),), (), ())
        pkey = ex.autotune_persist_key("fp-abc", 4096, desc, 10, False)
        try:
            ex.configure_autotune_persistence(store)
            import time as _t

            def run_slow_pallas(backend):
                _t.sleep(0.004 if backend == "pallas" else 0.001)

            # "single-chip" timed tune persists under the canonical key
            assert ex.resolve_fused_backend(
                ("chip", "fp-abc", 4096, desc, 10), 8, run_slow_pallas,
                persist_keys=(pkey,)) == "xla"
            # "mesh" lookup: same pack fingerprint, no run_backend —
            # must take the persisted choice, not the static pallas pick.
            # (mesh k is pow2-padded: 16 buckets to the same key as 10)
            mesh_keys = tuple(ex.autotune_persist_key(
                fp, 4096, desc, 16, False) for fp in ("fp-zzz", "fp-abc"))
            assert ex.resolve_fused_backend(
                ("mesh", "idx", 4096, desc, 16), 8,
                persist_keys=mesh_keys) == "xla"
            # an unknown fingerprint still gets the static choice
            assert ex.resolve_fused_backend(
                ("mesh", "idx2", 4096, desc, 16), 8,
                persist_keys=(ex.autotune_persist_key(
                    "fp-new", 4096, desc, 16, False),)) == "pallas"
        finally:
            ex.configure_autotune_persistence(None)


class TestRejectionCounters:
    """nodes_stats()['fused_scoring']['admission'] must say WHY plans
    fell back, by reason."""

    def test_reasons_by_plan_shape(self):
        from elasticsearch_tpu.search import executor as ex
        from elasticsearch_tpu.search.shard_searcher import ShardReader
        svc, seg, live = TestExecutorBundleIdentity()._build(1000)
        reader = ShardReader("idx", [seg], {seg.seg_id: live}, svc)
        ex._fused_stats.reset()
        # k == 0 (aggs-only): served by the match-mask-only fused
        # engine now — it must ADMIT, not count a k_zero rejection
        reader.search({"size": 0,
                       "query": {"match": {"message": "w001 w002"}},
                       "aggs": {"s": {"terms": {"field": "status"}}}})
        # non-score sort
        reader.search({"size": 3, "sort": [{"size": "desc"}],
                       "query": {"match": {"message": "w001 w002"}}})
        # unsupported clause kind (keyword term inside the bool)
        reader.search({"size": 3, "query": {"bool": {
            "must": [{"match": {"message": "w001 w002"}}],
            "should": [{"term": {"status": "ok"}}]}}})
        stats = ex.fused_scoring_stats()
        rej = stats["admission"]["rejected"]
        assert rej.get("k_zero", 0) == 0, rej
        assert stats["admission"]["admitted"] >= 1, stats["admission"]
        assert rej.get("sort", 0) >= 1, rej
        assert rej.get("clause:term_kw", 0) >= 1, rej
        # and the reasons surface through the node stats API
        from elasticsearch_tpu.node import Node
        n = Node()
        try:
            ns = n.nodes_stats()["nodes"][n.name]["fused_scoring"]
            assert ns["admission"]["rejected"].get("sort", 0) >= 1
        finally:
            n.close()

    def test_pallas_rejection_reasons_by_tag(self, monkeypatch):
        """Per-reason PALLAS rejection counters: with the kernel pinned
        to its legacy (PR 2) coverage, each newly-covered shape class
        reports its tag under admission.pallas_rejected — the coverage
        gaps are observable, not inferred from bench diffs."""
        from elasticsearch_tpu.search import executor as ex
        from elasticsearch_tpu.search.shard_searcher import ShardReader
        svc, seg, live = TestExecutorBundleIdentity()._build(1000)
        reader = ShardReader("idx", [seg], {seg.seg_id: live}, svc)
        monkeypatch.setenv("ES_TPU_PALLAS_COVERAGE", "legacy")
        ex._fused_stats.reset()
        # k>0 + aggs -> agg_emit_match
        reader.search({"size": 3,
                       "query": {"match": {"message": "w001"}},
                       "aggs": {"s": {"terms": {"field": "status"}}}})
        # k == 0 -> k_zero
        reader.search({"size": 0,
                       "query": {"match": {"message": "w002"}}})
        # range filter -> range_mask
        reader.search({"size": 3, "query": {"bool": {
            "must": [{"match": {"message": "w003"}}],
            "filter": [{"range": {"size": {"gte": 10, "lt": 900}}}]}}})
        rej = ex.fused_scoring_stats()["admission"]["pallas_rejected"]
        assert rej.get("agg_emit_match", 0) >= 1, rej
        assert rej.get("k_zero", 0) >= 1, rej
        assert rej.get("range_mask", 0) >= 1, rej
        # full coverage (default): the same shapes stop rejecting for
        # shape reasons — only availability can reject
        monkeypatch.delenv("ES_TPU_PALLAS_COVERAGE")
        ex._fused_stats.reset()
        reader.search({"size": 3,
                       "query": {"match": {"message": "w004"}},
                       "aggs": {"s": {"terms": {"field": "status"}}}})
        rej = ex.fused_scoring_stats()["admission"]["pallas_rejected"]
        assert "agg_emit_match" not in rej and "range_mask" not in rej
        # ck past the hard cap -> ck_cap (shape reasons outrank
        # availability so the tag is visible off-TPU too)
        monkeypatch.setattr(ex, "_FUSED_PALLAS_CK_MAX", 2)
        ex._fused_stats.reset()
        reader.search({"size": 5,
                       "query": {"match": {"message": "w005"}}})
        rej = ex.fused_scoring_stats()["admission"]["pallas_rejected"]
        assert rej.get("ck_cap", 0) >= 1, rej


def _dense_summary(fwd_tids, fwd_imps, n_terms, cap, tile):
    """What the summary held before it was a CSR: the dense
    [terms, tiles] array, one np.maximum.at a tile."""
    out = np.zeros((n_terms, cap // tile), np.float32)
    for j in range(cap // tile):
        tids = fwd_tids[j * tile:(j + 1) * tile].ravel()
        ok = tids >= 0
        np.maximum.at(out[:, j], tids[ok],
                      fwd_imps[j * tile:(j + 1) * tile].ravel()[ok])
    return out


@jax.jit
def _dense_bounds(dense, qt, wq):
    """dense_tile_bounds as it read the dense array."""
    from elasticsearch_tpu.ops.scoring import BOUND_SLACK
    safe = jnp.clip(qt, 0, dense.shape[0] - 1)
    ub = jnp.zeros((qt.shape[0], dense.shape[1]), jnp.float32)
    for q in range(qt.shape[1]):
        w = jnp.where(qt[:, q] >= 0, wq[:, q], 0.0)
        ub = ub + dense[safe[:, q]] * w[:, None]
    return ub * jnp.float32(BOUND_SLACK)


class TestTileSummary:
    """The stored form of the block-max summary (a CSR of the (term,
    tile) pairs that occur) against the dense array it replaced."""

    SHAPES = [  # cap, slots, n_terms, tile
        (2048, 4, 40, 512), (4096, 8, 300, 256), (1024, 2, 7, 128),
        (256, 4, 5000, 256), (8192, 16, 64, 1024), (512, 1, 3, 128)]

    @pytest.mark.parametrize("cap,slots,n_terms,tile", SHAPES)
    def test_rows_and_bounds_equal_the_dense_summary(self, rng, cap, slots,
                                                     n_terms, tile):
        from elasticsearch_tpu.ops.scoring import (dense_tile_bounds,
                                                   tile_max_rows)
        fwd_tids, fwd_imps, tm, _qt, _wq, _live = _case(
            rng, cap=cap, slots=min(slots, n_terms), n_terms=n_terms,
            tile=tile)
        dense = _dense_summary(fwd_tids, fwd_imps, n_terms, cap, tile)
        assert np.array_equal(tm.dense(), dense)
        # at most one entry a posting, never terms x tiles
        assert tm.entries == int((dense > 0).sum()) \
            <= int((fwd_tids >= 0).sum())
        assert len(tm.tiles) == len(tm.vals) >= tm.entries + tm.n_tiles
        dev = jax.device_put(tm)
        qt = rng.integers(-2, n_terms + 2, size=(5, 6)).astype(np.int32)
        wq = rng.random((5, 6), dtype=np.float32) + 0.01
        safe = np.clip(qt, 0, n_terms - 1)
        for q in range(qt.shape[1]):
            rows = np.asarray(jax.jit(tile_max_rows)(dev, qt[:, q]))
            assert np.array_equal(rows, dense[safe[:, q]])
            assert np.array_equal(tm.rows(safe[:, q]), dense[safe[:, q]])
        # the bound the kernels are handed: bit for bit what it was
        got = np.asarray(jax.jit(dense_tile_bounds)(
            dev, jnp.asarray(qt), jnp.asarray(wq)))
        assert np.array_equal(got, np.asarray(_dense_bounds(
            jnp.asarray(dense), jnp.asarray(qt), jnp.asarray(wq))))
        # 0 exactly where no term of the query occurs in the tile
        present = np.zeros(got.shape, bool)
        for q in range(qt.shape[1]):
            present |= (dense[safe[:, q]] > 0) & (qt[:, q] >= 0)[:, None]
        assert np.array_equal(got > 0, present)

    def test_no_size_at_which_the_summary_is_not_built(self, rng):
        """262,144 terms x 128 tiles is 2^25 cells, twice what the dense
        form was built up to; the stored form follows the postings."""
        cap, tile, n_terms = 16384, 128, 1 << 18
        fwd_tids = rng.integers(0, n_terms, size=(cap, 4)).astype(np.int32)
        fwd_tids[:, 1:][fwd_tids[:, 1:] == fwd_tids[:, :1]] = -1
        fwd_imps = np.where(fwd_tids >= 0, rng.random(
            (cap, 4), dtype=np.float32) + 0.1, 0).astype(np.float32)
        tm = build_tile_max(fwd_tids, fwd_imps, n_terms, cap, tile=tile)
        assert tm is not None and n_terms * tm.n_tiles == 1 << 25
        assert tm.entries <= cap * 4
        assert tm.nbytes < 4 * (n_terms + 1) + 8 * (cap * 4 + 2 * 1024)
        qt = fwd_tids[rng.integers(0, cap, 3), 0][:, None]
        wq = np.ones((3, 1), np.float32)
        live = np.ones(cap, bool)
        _assert_tri_parity(fwd_tids, fwd_imps, tm, qt, wq, live, k=5)

    @pytest.mark.parametrize("order", ["term_major", "shuffled"])
    def test_host_and_device_builders_agree(self, rng, order):
        """The integer half is shared (tile_runs); the float half is a
        max a run on the host and a scatter-max on the device: the same
        bytes, whatever order the postings come in."""
        from elasticsearch_tpu.index.segment import (tile_runs,
                                                     tile_summary)
        from elasticsearch_tpu.ops.build import scatter_tile_max
        cap, tile, n_terms, nnz = 4096, 256, 500, 20000
        tids = np.sort(rng.integers(0, n_terms, nnz))
        docs = rng.integers(0, cap, nnz).astype(np.int32)
        imps = rng.random(nnz, dtype=np.float32)
        if order == "shuffled":
            turn = rng.permutation(nnz)
            tids, docs, imps = tids[turn], docs[turn], imps[turn]
        host = tile_summary(tids, docs, imps, n_terms, cap, tile)
        _o, _h, run, start, run_tiles = tile_runs(
            tids, docs // tile, n_terms, cap // tile)
        vals = np.asarray(scatter_tile_max(
            jnp.asarray(run.astype(np.int32)), jnp.asarray(imps),
            entry_cap=1 << 15))[:len(run_tiles)]
        assert np.array_equal(start, host.start)
        assert np.array_equal(run_tiles, host.tiles[:host.entries])
        assert np.array_equal(vals, host.vals[:host.entries])
        # and the forward index gives the same summary as its postings
        dense = np.zeros((n_terms, cap // tile), np.float32)
        np.maximum.at(dense, (tids, docs // tile), imps)
        assert np.array_equal(host.dense(), dense)

    def test_fused_is_bit_identical_to_unfused(self, rng):
        """Scores and ids of the fused walk against the unfused program
        (the full score matrix, then lax.top_k), bit for bit; with a
        summary that bounds every tile at infinity nothing is pruned
        and nothing changes."""
        from elasticsearch_tpu.index.segment import TileSummary
        from elasticsearch_tpu.ops.scoring import _dense_tile_scores
        fwd_tids, fwd_imps, _tm, qt, wq, live = _case(rng, cap=4096, b=4,
                                                      q=4, tile=256)
        # the queries' terms (0 and 1) occur in two of the 16 tiles
        # only, so the true summary lets the walk skip the others
        for lo, hi in ((0, 512), (768, 1280), (1536, 4096)):
            rare = fwd_tids[lo:hi] < 2
            fwd_tids[lo:hi][rare] = -1
            fwd_imps[lo:hi][rare] = 0.0
        qt[:] = rng.integers(0, 2, size=qt.shape)
        qt[:, 1:][qt[:, 1:] == qt[:, :1]] = -1
        wq[qt < 0] = 0.0
        tm = build_tile_max(fwd_tids, fwd_imps, 40, 4096, tile=256)
        n_terms, grid = len(tm.start) - 1, tm.n_tiles
        unbounded = TileSummary(
            (np.arange(n_terms + 1) * grid).astype(np.int32),
            np.tile(np.arange(grid, dtype=np.int32), n_terms + 1),
            np.full((n_terms + 1) * grid, 3e38, np.float32), grid)
        args = (jnp.asarray(fwd_tids), jnp.asarray(fwd_imps))
        rest = (jnp.asarray(qt), jnp.asarray(wq), jnp.asarray(live), 10)

        @jax.jit
        def unfused(tids, imps, qt, wq, live):
            score = _dense_tile_scores(tids, imps, qt, wq)
            match = (score > 0) & live[None, :]
            top_s, top_i = jax.lax.top_k(
                jnp.where(match, score, -jnp.inf), 10)
            return top_s, top_i, match.sum(axis=-1, dtype=jnp.int32)

        ref_s, ref_i, ref_t = (np.asarray(x) for x in
                               unfused(*args, *rest[:3]))
        for summary, prunes in ((tm, True), (unbounded, False)):
            g_s, g_i, g_t, pruned = (np.asarray(x) for x in
                                     score_topk_dense_fused(
                *args, jax.device_put(summary), *rest))
            assert np.array_equal(g_t, ref_t)
            for row in range(qt.shape[0]):
                n = min(int(ref_t[row]), 10)
                assert np.array_equal(g_i[row, :n], ref_i[row, :n])
                assert np.array_equal(g_s[row, :n], ref_s[row, :n])
            assert int(pruned[2]) == grid
            assert int(pruned[0]) == (14 if prunes else 0)

    def test_a_wide_forward_index_is_no_kernel_candidate(self, monkeypatch):
        """At 256 forward slots the kernel's dense clause does not
        compile for a batch (tests/test_tpu_compile.py has the compile);
        the bundle runs the XLA walk and says why."""
        from elasticsearch_tpu.search import executor as ex
        monkeypatch.setattr(ex, "fused_pallas_ok", lambda ck: True)
        bundle = (("should", "terms_dense", "f", False),)
        cols = {"f": {"fwd_tids": np.zeros((8, 256), np.int32)}}
        assert ex._bundle_fwd_width(bundle, cols) == 256
        assert ex._bundle_pallas_reason(bundle, (), 10, 0, 256) \
            == "forward_width"
        assert ex._bundle_pallas_reason(bundle, (), 10, 0, 128) is None


class TestProfilerPathRestriction:
    """POST /_nodes/profiler/start must resolve the trace dir under the
    node's data_path and reject escapes."""

    def test_rejects_absolute_and_escaping_paths(self, tmp_path):
        from elasticsearch_tpu.node import Node
        from elasticsearch_tpu.rest.server import RestDispatcher
        from elasticsearch_tpu.utils.errors import IllegalArgumentError
        node = Node({"path.data": str(tmp_path / "data")})
        d = RestDispatcher(node)
        try:
            for bad in ("/tmp/evil", "../evil", "a/../../evil"):
                with pytest.raises(IllegalArgumentError):
                    d.dispatch("POST", "/_nodes/profiler/start", {},
                               {"path": bad})
        finally:
            node.close()

    def test_relative_path_resolves_under_data_path(self, tmp_path):
        from elasticsearch_tpu.node import Node
        from elasticsearch_tpu.rest.server import RestDispatcher
        from elasticsearch_tpu.utils import profiler
        node = Node({"path.data": str(tmp_path / "data")})
        d = RestDispatcher(node)
        try:
            r = d.dispatch("POST", "/_nodes/profiler/start", {},
                           {"path": "traces/t1"})
            assert r["path"].startswith(
                os.path.realpath(str(tmp_path / "data")))
        finally:
            if profiler.status()["tracing"]:
                profiler.stop()
            node.close()

    def test_requires_data_path(self):
        from elasticsearch_tpu.node import Node
        from elasticsearch_tpu.rest.server import RestDispatcher
        from elasticsearch_tpu.utils.errors import IllegalArgumentError
        node = Node()
        d = RestDispatcher(node)
        try:
            with pytest.raises(IllegalArgumentError):
                d.dispatch("POST", "/_nodes/profiler/start", {},
                           {"path": "traces"})
        finally:
            node.close()


# ---------------------------------------------------------------------------
# Several tiles a step (ops/scoring.walk_step_tiles): the XLA walk of a
# large pack scores a slab of G tiles an iteration, with bounds per tile
# and candidates extracted only where a passage beats a query's k-th best.
# Every answer is the one-tile walk's, bit for bit.
# ---------------------------------------------------------------------------

STEP_TILE = 128            # the narrowest tile build_tile_max takes
FREQUENT = 16              # terms 0..15 occur in most passages
RARE = 8                   # the vocabulary's last terms: a few tiles each


@pytest.mark.parametrize("n_tiles,chunk_tiles,want", [
    (1, None, 1), (2, None, 1), (4, None, 1), (8, None, 1), (16, None, 1),
    (32, None, 2), (48, None, 2), (64, None, 4), (128, None, 8),
    (256, None, 16), (2048, None, 16), (256, 32, 16), (256, 8, 8),
    (64, 8, 4), (64, 2, 2), (256, 3, 1)])
def test_walk_step_tiles_follows_the_shapes(n_tiles, chunk_tiles, want):
    from elasticsearch_tpu.ops.scoring import walk_step_tiles
    assert walk_step_tiles(n_tiles, chunk_tiles) == want


def _passage_pack(rng, n_tiles, slots=24, vocab=200, tile=STEP_TILE):
    """A forward index of distinct term ids a passage: each frequent
    term in 90% of passages, the other slots from the middle of the
    vocabulary (a fifth of them empty), and each rare term in 30
    passages of two tiles, so that a query of it skips most steps."""
    cap = n_tiles * tile
    tids = np.full((cap, slots), -1, np.int32)
    freq = rng.random((cap, FREQUENT)) < 0.9
    tids[:, :FREQUENT] = np.where(freq, np.arange(FREQUENT), -1)
    mid = vocab - RARE - FREQUENT
    other = np.argsort(rng.random((cap, mid)), axis=1)[
        :, :slots - FREQUENT] + FREQUENT
    other[rng.random(other.shape) < 0.2] = -1
    tids[:, FREQUENT:] = other
    for r in range(vocab - RARE, vocab):
        for t in rng.choice(n_tiles, 2, replace=False):
            docs = t * tile + rng.choice(tile, 30, replace=False)
            tids[docs, -1] = r
    imps = np.where(tids >= 0, rng.random((cap, slots), dtype=np.float32)
                    + 0.1, 0.0).astype(np.float32)
    return tids, imps, build_tile_max(tids, imps, vocab, cap, tile=tile)


def _match_bundle(rng, op, terms, b, vocab=200):
    """A `match` of `terms` terms the way the binder gives it: one dense
    should clause under `or`, a must clause a term under `and` (mostly
    frequent terms, so that conjunctions match; every other row, the
    first among them, brings one rare term, so that most steps skip)."""
    rows = []
    for row in range(b):
        if op == "or":
            pick = rng.choice(vocab, terms, replace=False)
        else:
            pick = rng.choice(FREQUENT, terms - 1, replace=False).tolist()
            pick.append(next(t for t in range(FREQUENT) if t not in pick)
                        if row % 2 else vocab - 1 - row % RARE)
        rows.append(np.asarray(pick, np.int32))
    qt = np.stack(rows)
    wq = (rng.random(qt.shape, dtype=np.float32) + 0.5).astype(np.float32)
    ones_i, ones_f = np.ones(b, np.int32), np.ones(b, np.float32)
    if op == "or":
        return ((("should", "terms_dense", "f", False),),
                ((qt, wq, ones_i, ones_f),), ones_i)
    return (tuple(("must", "term_text", "f", False) for _ in range(terms)),
            tuple((qt[:, i:i + 1], wq[:, i:i + 1], ones_i, ones_f)
                  for i in range(terms)),
            np.zeros(b, np.int32))


def _step_args(tids, imps, tm, clauses, cl_inputs, msm, live, k):
    text_cols = {"f": {"fwd_tids": jnp.asarray(tids),
                       "fwd_imps": jnp.asarray(imps),
                       "tile_max": jax.device_put(tm)}}
    return (text_cols, {}, clauses,
            tuple(tuple(jnp.asarray(a) for a in inp) for inp in cl_inputs),
            jnp.asarray(msm), None, jnp.asarray(live), k)


def _walked(args, step_tiles=None, **kw):
    return [np.asarray(x) for x in score_topk_bundle_fused(
        *args, step_tiles=step_tiles, **kw)]


def _assert_same_walk(stepped, one):
    """Scores, ids, totals (and the match mask) bit for bit."""
    for a, b in zip(stepped[:3], one[:3]):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    if len(stepped) > 4:
        assert np.array_equal(stepped[4], one[4])


STEP_CASES = [(n, op, m, b, emit)
              for n, batches in ((256, (1, 2, 4, 8)), (64, (8, 4, 2, 1)))
              for op in ("or", "and")
              for m, b, emit in zip((3, 6, 10, 16), batches,
                                    (False, True, False, True))]


@pytest.mark.parametrize("n_tiles,op,terms,b,emit", STEP_CASES)
def test_a_stepped_walk_is_the_one_tile_walk(n_tiles, op, terms, b, emit):
    from elasticsearch_tpu.ops.scoring import walk_step_tiles
    rng = np.random.default_rng([n_tiles, terms, b])
    tids, imps, tm = _passage_pack(rng, n_tiles)
    clauses, cl_inputs, msm = _match_bundle(rng, op, terms, b)
    live = rng.random(tids.shape[0]) > 0.05
    args = _step_args(tids, imps, tm, clauses, cl_inputs, msm, live, 10)
    g = walk_step_tiles(n_tiles)
    assert g == {256: 16, 64: 4}[n_tiles]
    stepped = _walked(args, emit_match=emit)
    one = _walked(args, 1, emit_match=emit)
    _assert_same_walk(stepped, one)
    # the plain reference: totals and the mask exact, each hit's score
    ref_s, _ref_i, ref_t, ref_m = _np_bundle_reference(
        clauses, cl_inputs, {"f": (tids, imps)}, {}, msm,
        np.ones(b, np.float32), live, 10)
    s, i, t = stepped[:3]
    assert np.array_equal(t, ref_t) and t.max() > 0
    if emit:
        assert np.array_equal(stepped[4], ref_m)
    for row in range(b):
        n = min(int(ref_t[row]), 10)
        np.testing.assert_allclose(s[row, :n], ref_s[row, :n], rtol=1e-6)
        assert np.isneginf(s[row, n:]).all()
        assert ref_m[row, i[row, :n]].all()
    # counters count tiles; a skip is a whole step's
    hard, thr, examined = stepped[3]
    assert examined == one[3][2] == n_tiles
    assert hard % g == 0 and hard <= one[3][0]


def _tied_pack(n_tiles, tile=STEP_TILE, late=(3, 50, 100, 101, 150, 255)):
    """Every passage holds term 0 at 0.5; two passages in each of the
    `late` tiles hold term 1 at 0.25 too. Sums are exact, so a `match`
    of both terms scores 0.75 there and 0.5 elsewhere: twelve passages
    tie for the top, and ten of them fit in k."""
    cap = n_tiles * tile
    tids = np.full((cap, 2), -1, np.int32)
    imps = np.zeros((cap, 2), np.float32)
    tids[:, 0], imps[:, 0] = 0, 0.5
    for t in late:
        if t < n_tiles:
            for d in (t * tile + 7, t * tile + 90):
                tids[d, 1], imps[d, 1] = 1, 0.25
    return tids, imps, build_tile_max(tids, imps, 2, cap, tile=tile)


@pytest.mark.parametrize("op", ["or", "and"])
def test_a_tie_at_the_kth_place_resolves_as_the_one_tile_walk(op):
    tids, imps, tm = _tied_pack(256)
    b = 2
    qt = np.asarray([[0, 1], [1, 0]], np.int32)
    wq = np.ones((b, 2), np.float32)
    ones_i, ones_f = np.ones(b, np.int32), np.ones(b, np.float32)
    if op == "or":
        clauses = (("should", "terms_dense", "f", False),)
        cl_inputs = ((qt, wq, ones_i, ones_f),)
        msm = ones_i
    else:
        clauses = (("must", "term_text", "f", False),) * 2
        cl_inputs = tuple((qt[:, i:i + 1], wq[:, i:i + 1], ones_i, ones_f)
                          for i in range(2))
        msm = np.zeros(b, np.int32)
    live = np.ones(tids.shape[0], bool)
    args = _step_args(tids, imps, tm, clauses, cl_inputs, msm, live, 10)
    stepped, one = _walked(args, emit_match=True), _walked(args, 1,
                                                           emit_match=True)
    _assert_same_walk(stepped, one)
    ref_s, ref_i, ref_t, _m = _np_bundle_reference(
        clauses, cl_inputs, {"f": (tids, imps)}, {}, msm, ones_f, live, 10)
    assert np.array_equal(stepped[0], ref_s)
    assert np.array_equal(stepped[1], ref_i)
    assert np.array_equal(stepped[2], ref_t)
    top = np.sort(np.concatenate([t * STEP_TILE + np.asarray([7, 90])
                                  for t in (3, 50, 100, 101, 150)]))
    assert (stepped[1][:, :10] == top).all() and (stepped[0] == 0.75).all()


@pytest.mark.parametrize("delta_tiles", [4, 64])
def test_a_base_to_delta_chain_is_the_one_tile_chain(delta_tiles):
    """Base (256 tiles) then delta with the base's top-k as its opening
    state and its ids past the base: the pack program's chain. The
    delta's passages tie with the base's and lose to them."""
    from elasticsearch_tpu.ops.topk import running_topk_init
    rng = np.random.default_rng(delta_tiles)
    b, k = 4, 10
    tb, ib, mb = _tied_pack(256, late=(9, 200))
    td, idd, md = _tied_pack(delta_tiles, late=(0, 1, 2, 3))
    qt = np.asarray([[0, 1]] * b, np.int32)
    wq = np.ones((b, 2), np.float32)
    # eighths: every product and sum is exact, so the reference agrees
    # to the bit
    wq[1:] = rng.integers(4, 13, (b - 1, 2)) / 8
    ones_i, ones_f = np.ones(b, np.int32), np.ones(b, np.float32)
    clauses = (("should", "terms_dense", "f", False),)
    cl_inputs = ((qt, wq, ones_i, ones_f),)

    def chain(step_tiles):
        base = _walked(_step_args(tb, ib, mb, clauses, cl_inputs, ones_i,
                                  np.ones(tb.shape[0], bool), k),
                       step_tiles, init_topk=running_topk_init(b, k))
        delta = _walked(_step_args(td, idd, md, clauses, cl_inputs, ones_i,
                                   np.ones(td.shape[0], bool), k),
                        step_tiles, init_topk=(jnp.asarray(base[0]),
                                               jnp.asarray(base[1])),
                        idx_offset=tb.shape[0])
        return base, delta

    (base_s, delta_s), (base_1, delta_1) = chain(None), chain(1)
    _assert_same_walk(base_s, base_1)
    _assert_same_walk(delta_s, delta_1)
    both = (np.concatenate([tb, td]), np.concatenate([ib, idd]))
    ref_s, ref_i, ref_t, _m = _np_bundle_reference(
        clauses, cl_inputs, {"f": both}, {}, ones_i, ones_f,
        np.ones(both[0].shape[0], bool), k)
    assert np.array_equal(delta_s[0], ref_s)
    assert np.array_equal(delta_s[1], ref_i)
    assert np.array_equal(base_s[2] + delta_s[2], ref_t)
    # the row of weights 1: four base passages, then six of the delta's
    # eight, which tie with each other and lose to the base's
    assert (delta_s[1][0, :4] < tb.shape[0]).all()
    assert (delta_s[1][0, 4:] >= tb.shape[0]).all()


@pytest.mark.parametrize("fires", [False, True])
def test_a_resident_step_ends_where_the_one_tile_walk_ends(fires):
    """A stepped walk at 256 tiles in the resident loop's chunks (G must
    divide them, so a chunk ends on a step): the same answers and the
    same timed-out verdict as one tile a step, for a check that never
    fires and for one that fires after the first chunk."""
    from elasticsearch_tpu.ops.scoring import walk_step_tiles
    from elasticsearch_tpu.search import executor as ex
    rng = np.random.default_rng(11)
    tids, imps, tm = _passage_pack(rng, 256)
    clauses, cl_inputs, msm = _match_bundle(rng, "or", 6, 4)
    live = np.ones(tids.shape[0], bool)
    args = _step_args(tids, imps, tm, clauses, cl_inputs, msm, live, 10)
    chunk = ex._resident_chunk_tiles(256)
    assert (ex._RESIDENT_CHUNKS, chunk) == (8, 32)
    assert walk_step_tiles(256, chunk) == 16

    def check(c, st):
        return (jnp.asarray(c >= 1) if fires else jnp.bool_(False)), st

    stepped = _walked(args, step=(chunk, 0, check))
    one = _walked(args, 1, step=(chunk, 0, check))
    _assert_same_walk(stepped, one)
    assert bool(stepped[-1]) == bool(one[-1]) == fires
    assert stepped[3][2] == one[3][2] == (chunk if fires else 256)


def _pack_of_words(n_docs, seed=5):
    """A segment of `n_docs` short texts (four or five of 60 words; the
    tile 128 wide where the test narrows it) and its live mask."""
    from elasticsearch_tpu.index.mapping import MapperService
    from elasticsearch_tpu.index.segment import SegmentBuilder
    rng = random.Random(seed)
    vocab = [f"w{i:03d}" for i in range(60)]
    svc = MapperService(mapping={"properties": {
        "message": {"type": "text"}}})
    builder = SegmentBuilder()
    for i in range(n_docs):
        builder.add(svc.parse(str(i), {"message": " ".join(
            rng.choices(vocab, k=4 + i % 2))}))
    seg = builder.build("steps")
    live = np.zeros(seg.capacity, bool)
    live[: seg.num_docs] = True
    return svc, seg, live


@pytest.mark.parametrize("body", [
    {"match": {"message": "w001 w002 w003"}},
    {"match_phrase": {"message": "w004 w005"}}])
def test_the_breaker_and_the_chunking_size_a_step(monkeypatch, body):
    """At 256 tiles the one estimate of a fused dispatch's transient is
    the step's slab (16 tiles) and its strip (16 steps of min(k, slab)
    candidates), and a positional clause's decoded slab is the step's
    too; the breaker's estimate (execute_segment_async) and the
    chunking decision (_segment_body, traced anew here) read the same
    number for a batch of 8."""
    from elasticsearch_tpu.index import segment as segment_mod
    from elasticsearch_tpu.search import executor as ex
    from elasticsearch_tpu.search.query_dsl import QueryParser
    monkeypatch.setattr(segment_mod, "SCORE_TILE", STEP_TILE)
    svc, seg, live = _pack_of_words(16_500)
    assert seg.text["message"].tile_max.n_tiles == 256
    sized = []
    real = ex._fused_row_elems

    def recorded(*a, **kw):
        sized.append(real(*a, **kw))
        return sized[-1]

    monkeypatch.setattr(ex, "_fused_row_elems", recorded)
    monkeypatch.setattr(ex, "_out_layout_cache", {})
    binder = ex.QueryBinder(seg, svc)
    bounds = [binder.bind(QueryParser(svc).parse(body)) for _ in range(8)]
    ex._fused_stats.reset()
    (_ts, _tk, _ti, tt, _tm), _ = ex.execute_segment(seg, live, bounds, 10)
    assert ex.fused_scoring_stats()["admission"]["admitted"] == 1
    pf = seg.text["message"]
    # a phrase of two terms decodes P positions a term and passage
    pos = 2 * (pf.fwd_pos.shape[1] // pf.fwd_tids.shape[1]) \
        if "match_phrase" in body else 0
    slab = 16 * STEP_TILE
    assert len(sized) >= 2 and set(sized) == {slab + 16 * 10 + pos * slab}
    assert ex._chunk_b(8, sized[0]) == 8 and int(tt[0]) > 0


def test_the_steps_are_counted_in_node_stats(tmp_path, monkeypatch):
    """A served `match` over a pack of 256 tiles walks 16 tiles a step,
    and `_nodes/stats` -> `fused_scoring.steps` says so: its tiles are
    every tile the search examined. A pack of 4 tiles walks one a step
    and adds nothing there."""
    import http.client
    import json
    from elasticsearch_tpu.index import segment as segment_mod
    from elasticsearch_tpu.node import Node
    from elasticsearch_tpu.rest.server import RestServer
    monkeypatch.setattr(segment_mod, "SCORE_TILE", STEP_TILE)
    node = Node({"path.data": str(tmp_path / "data"),
                 "index.number_of_replicas": 0})
    server = RestServer(node, "127.0.0.1", 0).start()
    conn = http.client.HTTPConnection(server.host, server.port, timeout=120)

    def call(method, path, body=None):
        data = body if isinstance(body, (bytes, type(None))) \
            else json.dumps(body).encode()
        conn.request(method, path, body=data,
                     headers={"Content-Type": "application/json"})
        r = conn.getresponse()
        raw = r.read()
        assert r.status == 200, (method, path, raw[:300])
        return json.loads(raw)

    def fused():
        return next(iter(call("GET", "/_nodes/stats")["nodes"].values())
                    )["fused_scoring"]

    rng = random.Random(3)
    vocab = [f"w{i:03d}" for i in range(60)]
    try:
        for index, docs in (("big", 16_500), ("small", 400)):
            call("PUT", f"/{index}", {
                "settings": {"index.number_of_shards": 1,
                             "index.number_of_replicas": 0},
                "mappings": {"properties": {"message": {"type": "text"}}}})
            lines = []
            for i in range(docs):
                lines += [json.dumps({"index": {"_id": str(i)}}),
                          json.dumps({"message": " ".join(
                              rng.choices(vocab, k=5))})]
            assert not call("POST", f"/{index}/_bulk",
                            ("\n".join(lines) + "\n").encode())["errors"]
            call("POST", f"/{index}/_refresh")
        grids = {index: seg.text["message"].tile_max.n_tiles
                 for index in ("big", "small")
                 for seg in node.indices[index].shards[0]
                 .acquire_searcher().segments}
        assert grids == {"big": 256, "small": 4}
        for index, stepped in (("big", True), ("small", False)):
            before = fused()
            r = call("POST", f"/{index}/_search",
                     {"query": {"match": {"message": "w001 w002"}}})
            total = r["hits"]["total"]
            assert (total["value"] if isinstance(total, dict) else total)
            after = fused()
            examined = after["tiles"]["examined"] \
                - before["tiles"]["examined"]
            steps = {key: after["steps"][key] - before["steps"][key]
                     for key in ("walks", "steps", "stepped_tiles")}
            assert examined == grids[index]
            if stepped:
                assert steps == {"walks": 1, "steps": 16,
                                 "stepped_tiles": examined}
            else:
                assert steps == {"walks": 0, "steps": 0,
                                 "stepped_tiles": 0}
    finally:
        conn.close()
        server.stop()
        node.close()


@pytest.mark.parametrize("step_tiles,thresholded", [(None, 240), (1, 255)])
def test_thresholded_counts_the_tiles_of_a_scored_step(step_tiles,
                                                       thresholded):
    """Tile 0 outscores every later tile: once the k-th best is 1.0 no
    later tile's bound (0.5) beats it. One tile a step, that is every
    tile after the first; 16 a step, every tile after the first step,
    whose tiles were judged against the k-th best at its start."""
    cap = 256 * STEP_TILE
    tids = np.zeros((cap, 1), np.int32)
    imps = np.full((cap, 1), 0.5, np.float32)
    imps[:STEP_TILE] = 1.0
    tm = build_tile_max(tids, imps, 1, cap, tile=STEP_TILE)
    b = 2
    ones_i, ones_f = np.ones(b, np.int32), np.ones(b, np.float32)
    args = _step_args(tids, imps, tm, (("should", "terms_dense", "f",
                                         False),),
                      ((np.zeros((b, 1), np.int32),
                        np.ones((b, 1), np.float32), ones_i, ones_f),),
                      ones_i, np.ones(cap, bool), 3)
    top_s, top_i, total, pruned = _walked(args, step_tiles)
    assert (top_i == [0, 1, 2]).all() and (total == cap).all()
    assert pruned.tolist() == [0, thresholded, 256]
