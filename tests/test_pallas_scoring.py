"""Pallas scoring kernels vs the jnp reference (interpret mode on CPU).

Ref test strategy: the numerics-oracle approach of SURVEY.md §7 step 2 —
kernels must reproduce the pure-JAX reference implementation exactly
(same padding semantics, same drop rules) before they earn the hot path.
"""

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from elasticsearch_tpu.ops.scoring import (batched_scatter_add,  # noqa: E402
                                           score_term, score_terms_fused)
from elasticsearch_tpu.ops.pallas_scoring import (  # noqa: E402
    scatter_add_pallas, score_terms_dense_pallas, score_term_pallas,
    score_terms_fused_pallas)
from elasticsearch_tpu.index.segment import BLOCK  # noqa: E402


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(42)


class TestScatterAdd:
    def test_matches_reference(self, rng):
        cap, b, n = 1024, 4, 640
        docs = np.sort(rng.integers(0, cap, size=(b, n)),
                       axis=1).astype(np.int32)
        vals = rng.random((b, n), dtype=np.float32)
        ref = np.asarray(batched_scatter_add(
            jnp.asarray(docs), jnp.asarray(vals), cap))
        got = np.asarray(scatter_add_pallas(
            jnp.asarray(docs), jnp.asarray(vals), cap, interpret=True))
        np.testing.assert_allclose(got, ref, atol=1e-4)

    def test_padding_dropped(self, rng):
        cap, b, n = 256, 2, 256
        docs = np.full((b, n), cap, np.int32)      # all padding
        docs[:, :10] = np.arange(10)
        vals = np.ones((b, n), np.float32)
        got = np.asarray(scatter_add_pallas(
            jnp.asarray(docs), jnp.asarray(vals), cap, interpret=True))
        assert got[:, :10].sum() == 20
        assert got[:, 10:].sum() == 0

    def test_unsorted_input_still_correct(self, rng):
        # sortedness is a performance hint only
        cap, b, n = 512, 2, 384
        docs = rng.integers(0, cap, size=(b, n)).astype(np.int32)
        vals = rng.random((b, n), dtype=np.float32)
        ref = np.asarray(batched_scatter_add(
            jnp.asarray(docs), jnp.asarray(vals), cap))
        got = np.asarray(scatter_add_pallas(
            jnp.asarray(docs), jnp.asarray(vals), cap, interpret=True))
        np.testing.assert_allclose(got, ref, atol=1e-4)


    def test_the_contraction_keeps_float32(self):
        """The one-hot contraction runs on the MXU, whose default takes
        float32 operands in one bfloat16 pass: on the chip every score
        of the posting-scatter path came out 3e-3 off float32 BM25 (PR
        33, PERF.md fault 17), and interpret mode, which computes in
        float32 whatever is asked, never showed it. So the kernel has
        to ASK for float32 passes; tests/test_tpu_compile.py compiles
        it so for the chip."""
        import jax

        def precisions(jaxpr, out):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "dot_general":
                    out.append(eqn.params["precision"])
                for v in eqn.params.values():
                    for sub in v if isinstance(v, (list, tuple)) else [v]:
                        sub = getattr(sub, "jaxpr", sub)   # closed or not
                        sub = getattr(sub, "jaxpr", sub)
                        if hasattr(sub, "eqns"):
                            precisions(sub, out)
            return out

        traced = jax.make_jaxpr(
            lambda d, v: scatter_add_pallas(d, v, 256, interpret=True))(
            jnp.zeros((2, 256), jnp.int32), jnp.zeros((2, 256), jnp.float32))
        highest = jax.lax.Precision.HIGHEST
        assert precisions(traced.jaxpr, []) == [(highest, highest)]

    def test_full_mantissas_survive(self, rng):
        # impacts with all 24 bits of mantissa in use, one a doc: the
        # kernel returns each as it went in
        cap, n = 512, 384
        docs = rng.permutation(cap)[:n].astype(np.int32)[None, :]
        vals = (rng.random((1, n), dtype=np.float32) + np.float32(1.0)) \
            * np.float32(1.0000001)
        got = np.asarray(scatter_add_pallas(
            jnp.asarray(docs), jnp.asarray(vals), cap, interpret=True))
        assert np.array_equal(got[0, docs[0]], vals[0])


class TestDenseKernel:
    def test_matches_reference_loop(self, rng):
        cap, lanes, b, q = 1024, 8, 3, 5
        tids = rng.integers(-1, 60, size=(cap, lanes)).astype(np.int32)
        imps = rng.random((cap, lanes), dtype=np.float32)
        imps[tids < 0] = 0.0
        qt = rng.integers(-1, 60, size=(b, q)).astype(np.int32)
        wq = rng.random((b, q), dtype=np.float32)
        wq[qt < 0] = 0.0
        ref = np.zeros((b, cap), np.float32)
        for bi in range(b):
            for qi in range(q):
                ref[bi] += ((tids == qt[bi, qi]) * imps).sum(-1) \
                    * wq[bi, qi]
        got = np.asarray(score_terms_dense_pallas(
            jnp.asarray(tids), jnp.asarray(imps), jnp.asarray(qt),
            jnp.asarray(wq), interpret=True))
        np.testing.assert_allclose(got, ref, atol=1e-4)


class TestDropInEntryPoints:
    def _blocks(self, rng, nb, cap):
        docs = np.sort(rng.integers(0, cap, size=(nb, BLOCK)),
                       axis=None).reshape(nb, BLOCK).astype(np.int32)
        imps = rng.random((nb, BLOCK), dtype=np.float32)
        return jnp.asarray(docs), jnp.asarray(imps)

    def test_score_term_parity(self, rng):
        cap, nb, b, nb_pad = 512, 12, 3, 4
        block_docs, block_imps = self._blocks(rng, nb, cap)
        block_lo = jnp.asarray(rng.integers(0, nb - nb_pad, size=b),
                               dtype=jnp.int32)
        nb_valid = jnp.asarray(rng.integers(1, nb_pad + 1, size=b),
                               dtype=jnp.int32)
        weight = jnp.asarray(rng.random(b), dtype=jnp.float32)
        ref = np.asarray(score_term(block_docs, block_imps, block_lo,
                                    nb_valid, weight, nb_pad, cap))
        got = np.asarray(score_term_pallas(block_docs, block_imps,
                                           block_lo, nb_valid, weight,
                                           nb_pad, cap, interpret=True))
        np.testing.assert_allclose(got, ref, atol=1e-4)

    def test_score_terms_fused_parity(self, rng):
        cap, nb, b, m = 512, 10, 2, 6
        block_docs, block_imps = self._blocks(rng, nb, cap)
        gather = rng.integers(-1, nb, size=(b, m)).astype(np.int32)
        weights = rng.random((b, m), dtype=np.float32)
        ref = np.asarray(score_terms_fused(
            block_docs, block_imps, jnp.asarray(gather),
            jnp.asarray(weights), cap))
        got = np.asarray(score_terms_fused_pallas(
            block_docs, block_imps, jnp.asarray(gather),
            jnp.asarray(weights), cap, interpret=True))
        np.testing.assert_allclose(got, ref, atol=1e-4)
