"""Compile the served path's device programs for a DESCRIBED TPU v5e.

Interpret mode cannot see what the chip's compiler refuses (PR 22: the
fused bundle kernel's per-tile block specs were rejected by the Mosaic
lowering while every interpret-mode parity test passed). The TPU
compiler is installed in the sandbox and compiles for a chip that is
described, not attached — so these tests lower + compile the kernels and
the whole per-segment search program of chip_smoke.py's plans at the
size the smoke serves (capacity 2^20), with no chip time.

A compile that passes is not a chip run: nothing executes here.

Rules this file keeps (the on-chip-measurement guide, section 2): ONE
file; the topology is described inside a module-scoped fixture that
skips when it cannot be, never at import; compiles happen in the test's
own process; the persistent compile cache stays off; widths come from
the code (score_tile_size, the slot width the segment builder gives the
smoke's corpus and a 64-term passage); interpret_mode / pallas_enabled
are steered here with monkeypatch, not through an option.
"""

import functools
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import chip_smoke as cs
from elasticsearch_tpu.index.segment import (TileSummary, next_pow2,
                                             score_tile_size)
from elasticsearch_tpu.ops import pallas_scoring as ps
from elasticsearch_tpu.ops import scoring
from elasticsearch_tpu.search import executor as ex

CAP = 1 << 20                 # the smoke's one-shard capacity
SMALL_DOCS = 4096             # the capture index (same builder code)
MSEARCH_B = 64                # the smoke's widest coalesced dispatch


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def cache_off():
    """Described-chip compiles must not touch the persistent cache (an
    entry written for a described chip cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prior)
    cc.reset_cache()


@pytest.fixture()
def as_on_tpu(monkeypatch):
    """What the predicates answer on the chip: kernels on, Mosaic
    lowering (not the interpreter)."""
    monkeypatch.delenv("ES_TPU_PALLAS", raising=False)
    monkeypatch.setattr(ps, "pallas_enabled", lambda: True)
    monkeypatch.setattr(ps, "interpret_mode", lambda: False)
    monkeypatch.setattr(ex, "pallas_enabled", lambda: True)
    monkeypatch.setattr(ex, "interpret_mode", lambda: False)


@pytest.fixture(scope="module")
def smoke_plans():
    """chip_smoke.py's five plan shapes, bound by the real code path on
    a small index of the smoke's own corpus: the (args, static kwargs)
    of every `_segment_program_packed` call, as shapes, at batch 1 (the
    single searches) and MSEARCH_B (the `_msearch` groups). Also the
    slot width the builder gives a 64-distinct-term passage."""
    from elasticsearch_tpu.node import Node
    node = Node({"index.number_of_shards": 1})
    corpus = cs.Corpus(SMALL_DOCS, seed=0)
    try:
        node.create_index("logs", mappings=cs.MAPPING)
        node.create_index("passage", mappings=cs.MAPPING)
        lines = corpus.bulk_body(0, corpus.n).decode().split("\n")
        node.bulk([("index", {"_index": "logs",
                              "_id": json.loads(a)["index"]["_id"],
                              "doc": json.loads(b)})
                   for a, b in zip(lines[0::2], lines[1::2]) if a])
        node.index_doc("passage", "0", {
            "message": " ".join(corpus.vocab[:64])})
        node.refresh("logs")
        node.refresh("passage")
        seg = node.indices["passage"].shards[0].acquire_searcher(
            ).segments[0]
        passage_slots = seg.text["message"].fwd_tids.shape[1]

        calls: dict = {}
        current: list = [None]           # (shape, batch) being captured
        orig = ex._segment_program_packed

        def spy(*args, **kw):
            shapes = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
            calls.setdefault(current[0], (shapes, kw))
            return orig(*args, **kw)

        singles, multi = cs.make_workload(corpus, seed=0)
        prior = os.environ.get("ES_TPU_FUSED_BACKEND")
        os.environ["ES_TPU_FUSED_BACKEND"] = "xla"
        ex._segment_program_packed = spy
        try:
            for shape, body, _spec in singles:
                current[0] = (shape, 1)
                node.search("logs", body)
            for shape in cs.SHAPES:
                current[0] = (shape, MSEARCH_B)
                node.msearch([("logs", b) for s, b, _spec in multi
                              if s == shape][:MSEARCH_B])
        finally:
            ex._segment_program_packed = orig
            if prior is None:
                os.environ.pop("ES_TPU_FUSED_BACKEND", None)
            else:
                os.environ["ES_TPU_FUSED_BACKEND"] = prior
    finally:
        node.close()
    return {"calls": calls, "passage_slots": passage_slots,
            "small_cap": next_pow2(SMALL_DOCS)}


def _at_real_size(shapes, kw, small_cap: int, sharding):
    """The captured small-index shapes rescaled to capacity CAP: every
    axis that IS the capacity grows to CAP, the per-tile summaries get
    CAP's tile count, and the posting-block rows (unused by fused
    plans, but arguments all the same) get the pow2 bucket a 2^20-doc
    pack of this corpus needs (~8 postings per doc / 128 lanes, plus a
    partial block per term)."""
    n_tiles = CAP // score_tile_size(CAP)

    def grow(path, leaf):
        name = jax.tree_util.keystr(path)
        shape = list(leaf.shape)
        if "tile_max" in name:
            # the summary's entries follow the postings (about 8 a doc)
            # and its term rows the dictionary, which does not grow
            if not name.endswith(".start"):
                shape[0] = next_pow2(CAP * 8)
        elif "tile_lo" in name or "tile_hi" in name:
            shape[0] = n_tiles
        elif "block_docs" in name or "block_imps" in name:
            shape[0] = next_pow2(CAP * 8 // 128 + 4096)
        else:
            shape = [CAP if d == small_cap else d for d in shape]
        return jax.ShapeDtypeStruct(tuple(shape), leaf.dtype,
                                    sharding=sharding)

    def regrid(x):
        return TileSummary(x.start, x.tiles, x.vals, n_tiles) \
            if isinstance(x, TileSummary) else x

    grown = jax.tree_util.tree_map_with_path(grow, shapes)
    return (jax.tree_util.tree_map(
        regrid, grown, is_leaf=lambda x: isinstance(x, TileSummary)),
        {**kw, "cap": CAP})


def _summary_sds(sds, n_tiles: int, lead: tuple = ()) -> TileSummary:
    """A block-max summary of 4,096 terms as shapes: `sds(shape, dtype)`
    makes one, `lead` is a stacked pack's leading axes."""
    return TileSummary(sds(lead + (4097,), jnp.int32),
                       sds(lead + (1 << 16,), jnp.int32),
                       sds(lead + (1 << 16,), jnp.float32), n_tiles)


def _compiled(fn, *args, **kw):
    return fn.lower(*args, **kw).compile()


def _assert_kernel(compiled, want: bool = True) -> None:
    assert ("tpu_custom_call" in compiled.as_text()) == want


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


# ---------------------------------------------------------------------------
# the whole per-segment program of every smoke plan, both engines
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["pallas", "xla"])
@pytest.mark.parametrize("shape", cs.SHAPES)
def test_smoke_plan_compiles_at_real_size(shape, backend, one_chip,
                                          cache_off, as_on_tpu,
                                          smoke_plans):
    """match / bool+range / match_phrase / size-0 aggs (the k == 0
    emit-match grid) / aggs + top-10 (emit-match): the MSEARCH_B-wide
    `_segment_program_packed` at capacity 2^20 on either engine."""
    shapes, kw = smoke_plans["calls"][(shape, MSEARCH_B)]
    assert kw["fused"] is not None, f"{shape} was not fused-admitted"
    args, kw = _at_real_size(shapes, kw, smoke_plans["small_cap"],
                             one_chip)
    bundle = kw["fused"][0]
    kw["fused"] = (bundle, backend)
    compiled = _compiled(ex._segment_program_packed, *args, **kw)
    # the phrase plan is the one coverage gap: its kernel variant has no
    # Mosaic lowering (see test_positional_kernel_has_no_mosaic_lowering),
    # so on a TPU it is rejected with a reason and even a forced pallas
    # choice runs the fused XLA engine
    gap = ex._bundle_pallas_reason(bundle, kw["agg_desc"], kw["k"])
    assert gap == ("positional_mosaic" if shape == "phrase" else None)
    _assert_kernel(compiled, backend == "pallas" and gap is None)
    mem = compiled.memory_analysis()
    # one program must fit the 16 GB chip next to the resident pack
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        + mem.output_size_in_bytes < 12 << 30, mem


def test_positional_kernel_has_no_mosaic_lowering(one_chip, cache_off,
                                                  monkeypatch,
                                                  smoke_plans):
    """Pins WHY executor._positional_needs_xla exists: the kernel body
    of a phrase clause (ops/scoring.positional_tile_scores: cumsum,
    int16 reductions, gathers) is refused by the Mosaic lowering. When
    this starts compiling, drop the gate and let the autotuner time
    the kernel for positional plans too."""
    monkeypatch.setattr(ex, "_positional_needs_xla", lambda bundle: False)
    monkeypatch.setattr(ex, "interpret_mode", lambda: False)
    shapes, kw = smoke_plans["calls"][("phrase", MSEARCH_B)]
    args, kw = _at_real_size(shapes, kw, smoke_plans["small_cap"],
                             one_chip)
    kw["fused"] = (kw["fused"][0], "pallas")

    # a fresh jit: the module's own cache already holds this plan's
    # (gated, demoted) trace from the test above
    def program(*a):
        return ex._segment_program_packed.__wrapped__(*a, **kw)

    with pytest.raises(NotImplementedError):
        jax.jit(program).lower(*args)


def test_single_search_program_compiles(one_chip, cache_off, as_on_tpu,
                                        smoke_plans):
    """Batch 1 (a lone `_search`): the batch tile is the whole batch."""
    shapes, kw = smoke_plans["calls"][("aggs10", 1)]
    args, kw = _at_real_size(shapes, kw, smoke_plans["small_cap"],
                             one_chip)
    kw["fused"] = (kw["fused"][0], "pallas")
    _assert_kernel(_compiled(ex._segment_program_packed, *args, **kw))


# ---------------------------------------------------------------------------
# kernel entries at widths the plans above do not reach
# ---------------------------------------------------------------------------


def _dense_case(one_chip, slots: int, b: int, q: int = 4):
    n_tiles = CAP // score_tile_size(CAP)
    text_cols = {"f": {"fwd_tids": _sds(one_chip, (CAP, slots), jnp.int32),
                       "fwd_imps": _sds(one_chip, (CAP, slots),
                                        jnp.float32),
                       "tile_max": _summary_sds(
                           functools.partial(_sds, one_chip), n_tiles)}}
    clauses = (("should", "terms_dense", "f", False),)
    cl_inputs = ((_sds(one_chip, (b, q), jnp.int32),
                  _sds(one_chip, (b, q), jnp.float32),
                  _sds(one_chip, (b,), jnp.int32),
                  _sds(one_chip, (b,), jnp.float32)),)
    return (text_cols, clauses, cl_inputs,
            _sds(one_chip, (b,), jnp.int32),
            _sds(one_chip, (b,), jnp.float32),
            _sds(one_chip, (CAP,), jnp.bool_))


def test_bundle_kernel_k100_passage_width(one_chip, cache_off,
                                          smoke_plans):
    """k = 100 over the slot width of a 64-term passage, batch 256."""
    text_cols, clauses, cl_inputs, msm, boost, live = _dense_case(
        one_chip, smoke_plans["passage_slots"], 256)

    def run(tc, ci, msm, boost, live):
        return ps.fused_topk_bundle_pallas(tc, {}, clauses, ci, msm,
                                           boost, live, 100)

    _assert_kernel(_compiled(jax.jit(run), text_cols, cl_inputs, msm,
                             boost, live))


def test_bundle_kernel_stepped(one_chip, cache_off, smoke_plans):
    """The stepped (chunked, deadline-checked) walk the resident loop
    and the mesh use: one pallas_call per chunk under lax.cond."""
    text_cols, clauses, cl_inputs, msm, boost, live = _dense_case(
        one_chip, 16, 64)
    n_tiles = CAP // score_tile_size(CAP)

    def never(c, st):
        return jnp.bool_(False), st

    def run(tc, ci, msm, boost, live):
        return ps.fused_topk_bundle_pallas(
            tc, {}, clauses, ci, msm, boost, live, 10,
            step=(n_tiles // 4, 0, never))

    _assert_kernel(_compiled(jax.jit(run), text_cols, cl_inputs, msm,
                             boost, live))


def test_dense_score_kernel(one_chip, cache_off, smoke_plans):
    slots = smoke_plans["passage_slots"]
    _assert_kernel(_compiled(
        ps.score_terms_dense_pallas,
        _sds(one_chip, (CAP, slots), jnp.int32),
        _sds(one_chip, (CAP, slots), jnp.float32),
        _sds(one_chip, (256, 4), jnp.int32),
        _sds(one_chip, (256, 4), jnp.float32)))


def test_scatter_kernel(one_chip, cache_off):
    # 64 posting blocks per query row, the block width from the code
    n = 64 * ps.LANES
    _assert_kernel(_compiled(
        ps.scatter_add_pallas, _sds(one_chip, (64, n), jnp.int32),
        _sds(one_chip, (64, n), jnp.float32), cap=1 << 17))


def test_xla_dense_engine(one_chip, cache_off, smoke_plans):
    slots = smoke_plans["passage_slots"]
    n_tiles = CAP // score_tile_size(CAP)
    _assert_kernel(_compiled(
        jax.jit(scoring.score_topk_dense_fused, static_argnames=("k",)),
        _sds(one_chip, (CAP, slots), jnp.int32),
        _sds(one_chip, (CAP, slots), jnp.float32),
        _summary_sds(functools.partial(_sds, one_chip), n_tiles),
        _sds(one_chip, (256, 4), jnp.int32),
        _sds(one_chip, (256, 4), jnp.float32),
        _sds(one_chip, (CAP,), jnp.bool_), k=100), want=False)


def test_bundle_kernel_does_not_compile_at_256_forward_slots(one_chip,
                                                            cache_off):
    """Pins WHY executor._FWD_PALLAS_SLOTS_MAX exists: over a forward
    index of 256 slots (passages of up to 256 distinct words: the
    `msmarco-passage-1shard` cell) the kernel's dense clause unrolls
    256 slot compares a query term, and the compile of a batch runs out
    of scoped VMEM. When this starts compiling, raise the gate and let
    the autotuner time the kernel on such packs too."""
    text_cols, clauses, cl_inputs, msm, boost, live = _dense_case(
        one_chip, 256, 8)

    def run(tc, ci, msm, boost, live):
        return ps.fused_topk_bundle_pallas(tc, {}, clauses, ci, msm,
                                           boost, live, 10)

    with pytest.raises(jax.errors.JaxRuntimeError, match="vmem"):
        _compiled(jax.jit(run), text_cols, cl_inputs, msm, boost, live)
    assert ex._bundle_pallas_reason(clauses, (), 10, 0, 256) \
        == "forward_width"


def test_xla_walk_of_a_ten_term_conjunction_at_256_slots(one_chip,
                                                         cache_off):
    """What the `msmarco-passage-1shard` cell's widest plan runs: ten
    must clauses of one term each (a `match` with operator and) over 256
    forward slots, batch 8, on the XLA engine, with the block-max
    summary read through its CSR."""
    n_tiles = CAP // score_tile_size(CAP)
    sds = functools.partial(_sds, one_chip)
    text_cols = {"f": {"fwd_tids": sds((CAP, 256), jnp.int32),
                       "fwd_imps": sds((CAP, 256), jnp.float32),
                       "tile_max": _summary_sds(sds, n_tiles)}}
    clauses = tuple(("must", "term_text", "f", False) for _ in range(10))
    cl_inputs = tuple((sds((8, 1), jnp.int32), sds((8, 1), jnp.float32),
                       sds((8,), jnp.int32), sds((8,), jnp.float32))
                      for _ in range(10))

    def run(tc, ci, msm, live):
        return scoring.score_topk_bundle_fused(tc, {}, clauses, ci, msm,
                                               None, live, 10)

    compiled = _compiled(jax.jit(run), text_cols, cl_inputs,
                         sds((8,), jnp.int32), sds((CAP,), jnp.bool_))
    _assert_kernel(compiled, want=False)
    # the summary's reader densifies a term's window by a one-hot max:
    # fused into its reduce it is a few MB of transient (2.2 MB here);
    # materialised it would be [8, 1024, 1024] f32 a clause, 32 MB
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 24


def test_bundle_kernel_under_shard_map_on_four_chips(topo, cache_off):
    """The mesh path (parallel/distributed.py) picks the kernel
    statically and runs it inside a shard_map over ("replica", "shard"):
    one pack row per device, candidates all_gathered over the shard
    axis. Compile that shape of program for the described 2x2 host."""
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4),
                ("replica", "shard"))
    cap = CAP // 4
    n_tiles = cap // score_tile_size(cap)
    clauses = (("should", "terms_dense", "f", False),)
    b, q, slots = MSEARCH_B, 4, 16

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    def per_device(tids, imps, tmax, qt, wq, live):
        text_cols = {"f": {"fwd_tids": tids[0], "fwd_imps": imps[0],
                           "tile_max": jax.tree_util.tree_map(
                               lambda a: a[0], tmax)}}
        ones = jnp.ones((b,), jnp.int32)
        cl_inputs = ((qt, wq, ones, ones.astype(jnp.float32)),)
        top_s, top_i, total, _pruned = ps.fused_topk_bundle_pallas(
            text_cols, {}, clauses, cl_inputs, ones, None, live[0], 10)
        return (jax.lax.all_gather(top_s, "shard"),
                jax.lax.all_gather(top_i, "shard"),
                jax.lax.psum(total, "shard"))

    row = P("shard", None, None)
    program = jax.jit(shard_map(
        per_device, mesh=mesh,
        in_specs=(row, row, P("shard", None), P(), P(),
                  P("shard", None)),
        out_specs=(P(), P(), P()), check_vma=False))
    compiled = program.lower(
        sds((4, cap, slots), jnp.int32, row),
        sds((4, cap, slots), jnp.float32, row),
        _summary_sds(lambda shape, dtype: sds(
            shape, dtype, P("shard", None)), n_tiles, lead=(4,)),
        sds((b, q), jnp.int32, P()), sds((b, q), jnp.float32, P()),
        sds((4, cap), jnp.bool_, P("shard", None))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-gather" in text
