"""Pallas TPU kernels for the BM25 scoring hot loop.

The reference's per-shard hot loop (search/query/QueryPhase.java:153 —
BulkScorer iterating postings, BM25 Similarity, TopScoreDocCollector)
maps to two dense-tensor formulations here, each with a fused kernel:

* `score_terms_dense_pallas` — the forward-index path (`terms_dense` /
  `term_text` in the executor): score[b, d] = sum over the doc's
  (term, impact) slots of impact * weight where the slot's term id is
  one of the query's. One pass over the [cap, L] forward index per doc
  tile, all B queries and Q terms consumed from VMEM — the [B, cap, L]
  broadcast intermediate the jnp version materializes never exists.

* `scatter_add_pallas` — the posting-scatter path (`term_text_sc` /
  `terms_fused`): scores[b, docs[b, n]] += vals[b, n]. TPUs have no
  vector scatter, so each 128-posting chunk becomes a one-hot compare
  against a 128-doc tile contracted on the MXU; because postings are
  doc-sorted within a term, a prefetched per-chunk [min, max] doc range
  skips every (tile, chunk) pair that cannot intersect, making the work
  near-linear in postings instead of postings x doc-tiles.

* `fused_topk_bundle_pallas` / `match_mask_bundle_pallas` — the fused
  block-max-WAND bundle engine (see ops/scoring.py for the reference
  semantics): one kernel family covering the FULL bundle admission
  matrix — multi-text-field clause bundles, numeric range masks in
  VMEM, emit-match, the mask-only k == 0 grid — with an in-VMEM
  running top-k threshold, plus a stepped chunked form that carries
  the threshold across pallas_call boundaries so the resident loop
  and the mesh can host per-chunk deadline checks between kernels.

The jnp implementations in ops/scoring.py remain the reference
semantics (and the CPU path); tests run these kernels in interpret mode
against them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..index.segment import BLOCK

LANES = 128          # TPU lane width = posting block width
_DOC_TILE = 512      # docs scored per dense-kernel grid step
_BATCH_TILE = 64     # queries scored per dense-kernel grid step — the
                     # kernel's [b_tile, doc_tile, L] compare/accumulate
                     # working set must stay well inside scoped VMEM
                     # (64*512*8*4B = 1MB per term step)


# ---------------------------------------------------------------------------
# forward-index (dense) scoring kernel
# ---------------------------------------------------------------------------


def _dense_kernel(qt_ref, wq_ref, tids_ref, imps_ref, out_ref):
    """One (batch tile, doc tile): out[b, t] = sum_q wq[b,q] * sum_l
    (tids[t, l] == qt[b, q]) * imps[t, l]. Both the term count Q and
    the forward-slot count L are small static ints, so they unroll;
    every live buffer stays 2-D [b_tile, doc_tile] — a 3-D [.., .., L]
    intermediate would be lane-padded L->128 by the TPU tiling and blow
    the scoped-VMEM budget 16x."""
    tids = tids_ref[...]                       # [L, TILE] int32
    imps = imps_ref[...]                       # [L, TILE] f32
    qt = qt_ref[...]                           # [Bt, Q] int32
    wq = wq_ref[...]                           # [Bt, Q] f32
    b_n, q_n = qt.shape
    n_slots, tile = tids.shape
    acc = jnp.zeros((b_n, tile), jnp.float32)
    for q in range(q_n):
        tq = qt[:, q]                          # [Bt]
        hit = jnp.zeros((b_n, tile), jnp.float32)
        for l in range(n_slots):
            # row slices of the slot-major layout are contiguous lane
            # vectors (a [TILE, L] column slice would stride the padded
            # minor dim and spill registers catastrophically)
            eq = tids[l][None, :] == tq[:, None]      # [Bt, TILE]
            hit = hit + jnp.where(eq, imps[l][None, :], 0.0)
        acc = acc + hit * wq[:, q][:, None]
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("interpret",))
def score_terms_dense_pallas(fwd_tids: jax.Array, fwd_imps: jax.Array,
                             qt: jax.Array, wq: jax.Array,
                             interpret: bool = False) -> jax.Array:
    """[cap, L] forward index x [B, Q] query terms -> [B, cap] scores.

    Query term ids use -1 for padding (matches only zero-impact slots,
    exactly like the jnp path, since tids padding is also -1 with 0
    impact — weights for padded terms must be 0, which bind guarantees).
    """
    cap, lanes = fwd_tids.shape
    b = qt.shape[0]
    tile = min(_DOC_TILE, cap)
    btile = min(_BATCH_TILE, b)
    pad_b = (-b) % btile
    if pad_b:
        # pad the query axis up to the tile (padded rows score against
        # weight 0 and are sliced off)
        qt = jnp.pad(qt, ((0, pad_b), (0, 0)), constant_values=-1)
        wq = jnp.pad(wq, ((0, pad_b), (0, 0)))
    bp = b + pad_b
    # slot-major layout: kernel blocks slice slot ROWS (contiguous lane
    # vectors); XLA hoists + caches this transpose across calls
    tids_t = fwd_tids.T                        # [L, cap]
    imps_t = fwd_imps.T
    grid = (bp // btile, cap // tile)
    out = pl.pallas_call(
        _dense_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((btile, qt.shape[1]), lambda bi, i: (bi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((btile, wq.shape[1]), lambda bi, i: (bi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((lanes, tile), lambda bi, i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((lanes, tile), lambda bi, i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((btile, tile), lambda bi, i: (bi, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((bp, cap), jnp.float32),
        interpret=interpret,
    )(qt, wq, tids_t, imps_t)
    return out[:b] if pad_b else out


# ---------------------------------------------------------------------------
# posting-scatter kernel (one-hot MXU scatter with sorted-range skip)
# ---------------------------------------------------------------------------


_BROWS = 8  # batch rows per scatter block (TPU sublane granularity)


def _scatter_kernel(cmin_ref, cmax_ref, docs_ref, vals_ref, out_ref):
    b = pl.program_id(0)
    t = pl.program_id(1)
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    tile_lo = t * LANES
    # whole-block skip: does ANY of the 8 rows' chunk range touch this
    # doc tile? (rows are independent queries; posting chunks are
    # doc-sorted so the [min, max] test prunes most (tile, chunk) pairs)
    hit = jnp.zeros((), jnp.bool_)
    for r in range(_BROWS):
        row = b * _BROWS + r
        hit = hit | ((cmax_ref[row, c] >= tile_lo)
                     & (cmin_ref[row, c] < tile_lo + LANES))

    @pl.when(hit)
    def _accumulate():
        docs = docs_ref[...]                   # [8, 128] int32
        vals = vals_ref[...]                   # [8, 128] f32
        local = docs - tile_lo
        iota = jax.lax.broadcasted_iota(jnp.int32, (_BROWS, LANES, LANES),
                                        2)
        onehot = (local[:, :, None] == iota).astype(jnp.float32)
        # contribution[r, j] = sum_i vals[r, i] * onehot[r, i, j]
        # (batched MXU contract over the 8 rows). HIGHEST: the MXU's
        # default takes f32 operands in one bfloat16 pass, which cut
        # every impact to 8 bits of mantissa (scores 3e-3 off float32
        # BM25 on the chip, exact in interpret mode)
        contrib = jax.lax.dot_general(
            vals[:, None, :], onehot,
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)   # [8, 1, 128]
        out_ref[...] += contrib[:, 0, :]


@functools.partial(jax.jit, static_argnames=("cap", "interpret"))
def scatter_add_pallas(docs: jax.Array, vals: jax.Array, cap: int,
                       interpret: bool = False) -> jax.Array:
    """scores[b, docs[b, n]] += vals[b, n]; docs >= cap (padding) drop.

    docs: int32 [B, N] sorted non-decreasing per (query, term) run —
    segment posting blocks are doc-sorted, which is what makes the
    per-chunk [min, max] tile skip effective. Correctness does NOT
    depend on sortedness, only performance.
    """
    b, n = docs.shape
    n_pad = -(-n // LANES) * LANES
    cap_pad = -(-cap // LANES) * LANES
    b_pad = -(-b // _BROWS) * _BROWS
    if n_pad != n:
        docs = jnp.pad(docs, ((0, 0), (0, n_pad - n)),
                       constant_values=cap_pad)
        vals = jnp.pad(vals, ((0, 0), (0, n_pad - n)))
    if b_pad != b:
        docs = jnp.pad(docs, ((0, b_pad - b), (0, 0)),
                       constant_values=cap_pad)
        vals = jnp.pad(vals, ((0, b_pad - b), (0, 0)))
    # OOB padding (== cap) must never land in a tile: clamp into a
    # sentinel range past cap_pad so the range skip drops those chunks
    docs = jnp.where(docs >= cap, cap_pad + LANES, docs)
    chunks = docs.reshape(b_pad, n_pad // LANES, LANES)
    cmin = chunks.min(axis=-1).astype(jnp.int32)     # [B, C]
    cmax = chunks.max(axis=-1).astype(jnp.int32)
    # padded chunk rows (all sentinel) have cmin > cap_pad -> skipped
    grid = (b_pad // _BROWS, cap_pad // LANES, n_pad // LANES)
    out = pl.pallas_call(
        _scatter_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((_BROWS, LANES),
                             lambda b_, t, c, *_: (b_, c)),
                pl.BlockSpec((_BROWS, LANES),
                             lambda b_, t, c, *_: (b_, c)),
            ],
            out_specs=pl.BlockSpec((_BROWS, LANES),
                                   lambda b_, t, c, *_: (b_, t)),
        ),
        out_shape=jax.ShapeDtypeStruct((b_pad, cap_pad), jnp.float32),
        interpret=interpret,
    )(cmin, cmax, docs.reshape(b_pad, n_pad), vals.reshape(b_pad, n_pad))
    return out[:b, :cap]


# ---------------------------------------------------------------------------
# fused block-max score + top-k kernel (forward-index path, bool bundles)
# ---------------------------------------------------------------------------
#
# One kernel walks (batch tile, doc tile) grid cells. The doc-tile axis
# is the INNER grid dimension, which TPU executes sequentially, so a
# VMEM scratch row carries each query's running top-k threshold across
# the tiles of its batch tile ("running per-query threshold in on-chip
# memory"). Per tile the kernel evaluates the WHOLE clause bundle (see
# ops/scoring.py: must/should scoring clauses over ANY mix of text
# fields + dense or numeric-range filter/must_not masks, single-should
# wrappers with per-clause msm/boost) and emits the tile-local top-k
# candidates (ck = min(k, tile) values + doc ids), the exact match
# count, a prune flag, and — in emit-match mode — the exact per-tile
# match mask (so k>0+aggs plans stay fused on Pallas; a downstream
# aggregation pass consumes the mask). A single cheap lax.top_k over
# the [B, n_tiles * ck] candidate strip — ~k/tile the size of the
# [B, cap] matrix the unfused path materializes — merges them.
# Candidate order (tile-ascending, within-tile ties doc-ascending)
# makes the merge reproduce the global lax.top_k tie-breaking exactly.
# A ck == 0 build of the same kernel is the mask-only k == 0 grid:
# no candidates, no threshold, just exact counts + mask.
#
# The per-tile can_match/bound vectors are precomputed OUTSIDE the
# kernel (ops/scoring.bundle_tile_bounds — [B, J] is tiny, and SHARED
# with the XLA engine so both backends prune identically); range masks
# are then re-evaluated per doc inside the kernel from the numeric
# columns in VMEM, exactly like ops/scoring.bundle_tile_eval.
#
# The in-kernel threshold is the max over processed tiles of the tile's
# k-th best score — a lower bound on the global k-th best backed by k
# lower-doc-id candidates, so `bound <= thr` tiles can skip extraction
# without changing the result (ties lose to the earlier docs anyway).
# It is only maintained when ck == k; a narrower tile cannot witness k
# candidates and the threshold stays -inf (no threshold pruning). The
# STEPPED form (step != None) partitions the doc-tile grid into chunks
# of pallas_call invocations and threads the threshold through a
# [B, 1] in/out pair, so pruning state survives the chunk boundary —
# a chunked walk is bit-identical to the single-call walk.

# per-tile selection unrolls (max, lowest-argmax, mask) passes up to
# this ck; beyond it a lax.fori_loop runs the same passes with a
# carried candidate buffer — the multi-pass form that lifts the old
# hard ck cap without minting pathological unrolled programs
_CK_UNROLL = 128


def _meta_for(clauses: tuple) -> tuple[tuple, tuple, tuple]:
    """Static kernel layout of a clause bundle: (text_fields,
    num_fields, pos_fields) in first-occurrence order. Dense clauses
    index text_fields (slot-major forward blocks); positional clauses
    index pos_fields (doc-major tids + positions + per-doc norms);
    everything else is a range clause and indexes num_fields (and its
    own (lo, hi) input pair). Positional kinds MUST be carved out here:
    their `field` slot can be a tuple (bm25f) and they carry no (lo,
    hi) pair, so lumping them with ranges would desync the ref walk."""
    from .scoring import (DENSE_CLAUSE_KINDS, bundle_pos_fields,
                          positional_prefix)
    text_fields = tuple(dict.fromkeys(
        f for _r, kd, f, _w in clauses if kd in DENSE_CLAUSE_KINDS))
    num_fields = tuple(dict.fromkeys(
        f for _r, kd, f, _w in clauses
        if kd not in DENSE_CLAUSE_KINDS and not positional_prefix(kd)))
    return text_fields, num_fields, bundle_pos_fields(clauses)


def _pos_param_arrays(clauses: tuple, cl_inputs: tuple
                      ) -> tuple[list, tuple]:
    """Flatten positional clause params into kernel-ready [B, x]
    columns, in clause order. Returns (arrays, pad_values) — the pad
    value feeds _pad_bundle_rows (qt pads -1 so inert batch rows decode
    zero positions and zero frequency; everything else pads 0).

    Per phrase/span clause (7 arrays): qt [B, n] i32, wb [B, n] f32,
    idf_sum / slop / pboost / msm_c / boost_c as [B, 1] columns.
    Per bm25f clause (6 arrays): qt [B, nf*nt] i32 (the [B, nf, nt]
    cube flattened — the kernel re-folds it from the static kind),
    idf [B, nt] f32, wf [B, nf] f32, pboost / msm_c / boost_c
    [B, 1]."""
    from .scoring import positional_prefix
    flat: list = []
    pads: list = []

    def _put(a, pad=0):
        flat.append(a)
        pads.append(pad)

    for (_r, kind, _f, _w), inp in zip(clauses, cl_inputs):
        head = positional_prefix(kind)
        if head is None:
            continue
        if head == "bm25f":
            qt, idf, wf, pb, mc, bc = inp
            b = qt.shape[0]
            _put(jnp.asarray(qt).reshape(b, -1), -1)
            _put(jnp.asarray(idf))
            _put(jnp.asarray(wf))
        else:
            qt, wb, idf_sum, slop, pb, mc, bc = inp
            _put(jnp.asarray(qt), -1)
            _put(jnp.asarray(wb))
            _put(jnp.asarray(idf_sum)[:, None])
            _put(jnp.asarray(slop)[:, None].astype(jnp.int32))
        _put(jnp.asarray(pb)[:, None].astype(jnp.float32))
        _put(jnp.asarray(mc)[:, None].astype(jnp.int32))
        _put(jnp.asarray(bc)[:, None].astype(jnp.float32))
    return flat, tuple(pads)


def _make_bundle_kernel(clauses: tuple, *, qm: int, ck: int,
                        update_thr: bool, emit_match: bool, tile: int,
                        t0: int):
    """Build the fused-bundle kernel for one (clauses, shape) pair.

    Ref layout (inputs): qt, wq [bt, Cd*qm]; msmc, boostc [bt, Cd];
    msm, boost, canm, ub [bt, 1]; (thr_in [bt, 1] when ck > 0); one
    (lo, hi) [bt, 1] pair per range clause; the flat positional param
    columns (_pos_param_arrays order) per positional clause; one
    (tids, imps) [L_f, tile] pair per text field; one (tids [tile, L_f]
    doc-major, pos [tile, L_f*P], k1ln [1, tile], lnorm [1, tile])
    quad per positional field; one (vals, exists) [1, tile] pair per
    numeric field; live [1, tile]. Outputs: (cs, ci [bt, ck], when
    ck > 0); cnt, flag [bt, 1]; (thr_out [bt, 1] when ck > 0); (match
    [bt, tile] i32 when emit_match). Scratch: thr [bt, LANES] when
    ck > 0. `t0` is the chunk's first tile (static): candidate doc ids
    are global, so chunked and single-call walks emit identical ids."""
    from .scoring import (DENSE_CLAUSE_KINDS, positional_prefix,
                          positional_tile_scores)
    text_fields, num_fields, pos_fields = _meta_for(clauses)
    n_range = len([1 for _r, kd, _f, _w in clauses
                   if kd not in DENSE_CLAUSE_KINDS
                   and not positional_prefix(kd)])
    pos_widths = [(6 if positional_prefix(kd) == "bm25f" else 7)
                  for _r, kd, _f, _w in clauses if positional_prefix(kd)]

    def kernel(*refs):
        it = iter(refs)
        qt_ref, wq_ref, msmc_ref, boostc_ref = (next(it) for _ in range(4))
        msm_ref, boost_ref, canm_ref, ub_ref = (next(it) for _ in range(4))
        thr_in_ref = next(it) if ck > 0 else None
        range_refs = [(next(it), next(it)) for _ in range(n_range)]
        pos_param_refs = [tuple(next(it) for _ in range(w))
                          for w in pos_widths]
        text_refs = {f: (next(it), next(it)) for f in text_fields}
        pos_refs = {f: tuple(next(it) for _ in range(4))
                    for f in pos_fields}
        num_refs = {f: (next(it), next(it)) for f in num_fields}
        live_ref = next(it)
        cs_ref = ci_ref = thr_out_ref = thr_scr = None
        if ck > 0:
            cs_ref, ci_ref = next(it), next(it)
        cnt_ref, flag_ref = next(it), next(it)
        if ck > 0:
            thr_out_ref = next(it)
        match_ref = next(it) if emit_match else None
        if ck > 0:
            thr_scr = next(it)

        j = pl.program_id(1)
        if ck > 0:
            @pl.when(j == 0)
            def _seed_thr():
                # chunked walks seed from the previous chunk's final
                # threshold; the first chunk (and the un-stepped single
                # call) seeds -inf from the caller
                thr_scr[...] = jnp.broadcast_to(thr_in_ref[...],
                                                thr_scr.shape)

        ub = ub_ref[...]                       # [bt, 1] f32 tile bound
        can_hit = canm_ref[...] > 0            # [bt, 1] msm-aware prune
        thr = thr_scr[:, 0:1] if ck > 0 else None
        any_hit = jnp.any(can_hit)

        @pl.when(jnp.logical_not(any_hit))
        def _hard_skip():
            # no query can match in this tile: nothing to score OR
            # count, and the mask rows provably stay zero
            if ck > 0:
                cs_ref[...] = jnp.full_like(cs_ref, -jnp.inf)
                ci_ref[...] = jnp.zeros_like(ci_ref)
            cnt_ref[...] = jnp.zeros_like(cnt_ref)
            flag_ref[...] = jnp.full_like(flag_ref, 2)
            if emit_match:
                match_ref[...] = jnp.zeros_like(match_ref)

        @pl.when(any_hit)
        def _score():
            qt = qt_ref[...]                   # [bt, Cd*qm]
            wq = wq_ref[...]
            msmc = msmc_ref[...]               # [bt, Cd] i32
            boostc = boostc_ref[...]           # [bt, Cd] f32
            b_n = qt.shape[0]
            acc = jnp.zeros((b_n, tile), jnp.float32)
            must_ok = jnp.ones((b_n, tile), bool)
            not_any = jnp.zeros((b_n, tile), bool)
            scnt = jnp.zeros((b_n, tile), jnp.int32)
            # positional columns for this doc tile, in the exact shapes
            # positional_tile_scores (the shared leaf evaluator — also
            # what bundle_tile_eval runs on the XLA engine) consumes:
            # text view (t_tids [tile, L], imps unused), pos view
            # (t_pos [tile, L*P], k1ln [tile], lnorm [tile])
            ptext = {f: (pos_refs[f][0][...], None) for f in pos_fields}
            ptiles = {f: (pos_refs[f][1][...], pos_refs[f][2][...][0],
                          pos_refs[f][3][...][0]) for f in pos_fields}
            # static clause unroll in eval_node order (must, filter,
            # must_not, should — the caller guarantees the ordering);
            # per-clause ops mirror ops/scoring.bundle_tile_eval so
            # fused-pallas scores stay identical to fused-xla
            dc = ri = pc = 0
            for role, kind, field, _w in clauses:
                if kind in DENSE_CLAUSE_KINDS:
                    tids_ref, imps_ref = text_refs[field]
                    tids = tids_ref[...]       # [L_f, tile] slot-major
                    imps = imps_ref[...]
                    n_slots = tids.shape[0]
                    s_leaf = jnp.zeros((b_n, tile), jnp.float32)
                    for q in range(qm):
                        tq = qt[:, dc * qm + q]
                        hit = jnp.zeros((b_n, tile), jnp.float32)
                        for l in range(n_slots):
                            eq = tids[l][None, :] == tq[:, None]
                            hit = hit + jnp.where(eq, imps[l][None, :],
                                                  0.0)
                        s_leaf = s_leaf + hit * wq[:, dc * qm + q][:, None]
                    m_leaf = s_leaf > 0.0
                    msm_c = msmc[:, dc:dc + 1]
                    m = (m_leaf | (msm_c <= 0)) & (msm_c <= 1)
                    s = jnp.where(m_leaf, s_leaf, 0.0) \
                        * boostc[:, dc:dc + 1]
                    dc += 1
                elif positional_prefix(kind):
                    # phrase / span / bm25f leaf: delegate to the SHARED
                    # evaluator (ops/scoring.positional_tile_scores) so
                    # the in-kernel f32 chain is op for op the XLA
                    # engine's — padded batch rows carry qt = -1 and
                    # decode zero frequency, exactly like dense pads
                    prefs = pos_param_refs[pc]
                    pc += 1
                    if positional_prefix(kind) == "bm25f":
                        nf = len(field)
                        qt_p = prefs[0][...]
                        inp = (qt_p.reshape(b_n, nf,
                                            qt_p.shape[1] // nf),
                               prefs[1][...], prefs[2][...],
                               prefs[3][...][:, 0], None, None)
                        msm_p, boost_p = prefs[4][...], prefs[5][...]
                    else:
                        inp = (prefs[0][...], prefs[1][...],
                               prefs[2][...][:, 0], prefs[3][...][:, 0],
                               prefs[4][...][:, 0], None, None)
                        msm_p, boost_p = prefs[5][...], prefs[6][...]
                    s_leaf, m_leaf = positional_tile_scores(
                        kind, field, inp, ptext, ptiles)
                    m = (m_leaf | (msm_p <= 0)) & (msm_p <= 1)
                    s = jnp.where(m_leaf, s_leaf, 0.0) * boost_p
                else:
                    # numeric range mask, evaluated per doc in VMEM —
                    # the same compare bundle_tile_eval runs, in the
                    # column's device dtype
                    lo_ref, hi_ref = range_refs[ri]
                    vals_ref, ex_ref = num_refs[field]
                    ri += 1
                    vals = vals_ref[...]       # [1, tile]
                    m = ((vals >= lo_ref[...]) & (vals <= hi_ref[...])
                         & (ex_ref[...] > 0))
                    s = None
                if role == "must":
                    acc = acc + jnp.where(m, s, 0.0)
                    must_ok = must_ok & m
                elif role == "filter":
                    must_ok = must_ok & m
                elif role == "must_not":
                    not_any = not_any | m
                else:
                    if s is not None:
                        acc = acc + jnp.where(m, s, 0.0)
                    scnt = scnt + m.astype(jnp.int32)
            live = live_ref[...] > 0           # [1, tile]
            match = (must_ok & jnp.logical_not(not_any)
                     & (scnt >= msm_ref[...]) & live)
            acc = acc * boost_ref[...]         # post-accum outer boost
            cnt_ref[...] = jnp.sum(match, axis=1, keepdims=True
                                   ).astype(jnp.int32)
            if emit_match:
                # exact mask regardless of threshold pruning below —
                # the aggregation pass consumes every tile's mask
                match_ref[...] = match.astype(jnp.int32)
            if ck == 0:
                # mask-only grid: counting + mask IS the result
                flag_ref[...] = jnp.zeros_like(flag_ref)
                return
            can_top = can_hit & (ub > thr)
            any_top = jnp.any(can_top)

            @pl.when(jnp.logical_not(any_top))
            def _thresholded():
                # exact counting happened above; candidates cannot
                # improve any query's top-k, skip the extraction
                cs_ref[...] = jnp.full_like(cs_ref, -jnp.inf)
                ci_ref[...] = jnp.zeros_like(ci_ref)
                flag_ref[...] = jnp.ones_like(flag_ref)

            @pl.when(any_top)
            def _select():
                # ck passes of (max, lowest-argmax, mask): ties come
                # out in ascending doc order, matching lax.top_k's tie
                # rule. Unrolled while small; a fori_loop with a
                # carried candidate buffer past _CK_UNROLL (identical
                # passes, bounded program size).
                cand = jnp.where(match, acc, -jnp.inf)
                idx = jax.lax.broadcasted_iota(jnp.int32, (b_n, tile), 1)
                if ck <= _CK_UNROLL:
                    vs = []
                    ps = []
                    for _s in range(ck):
                        mx = jnp.max(cand, axis=1, keepdims=True)
                        pos = jnp.min(jnp.where(cand == mx, idx, tile),
                                      axis=1, keepdims=True)
                        vs.append(mx)
                        ps.append(pos)
                        cand = jnp.where(idx == pos, -jnp.inf, cand)
                    v = jnp.concatenate(vs, axis=1)            # [bt,ck]
                    p = jnp.concatenate(ps, axis=1)
                else:
                    def sel_body(s, carry):
                        cand, v, p = carry
                        mx = jnp.max(cand, axis=1, keepdims=True)
                        pos = jnp.min(jnp.where(cand == mx, idx, tile),
                                      axis=1, keepdims=True)
                        v = jax.lax.dynamic_update_slice(v, mx, (0, s))
                        p = jax.lax.dynamic_update_slice(p, pos, (0, s))
                        cand = jnp.where(idx == pos, -jnp.inf, cand)
                        return cand, v, p
                    _, v, p = jax.lax.fori_loop(
                        0, ck, sel_body,
                        (cand, jnp.full((b_n, ck), -jnp.inf, jnp.float32),
                         jnp.zeros((b_n, ck), jnp.int32)))
                cs_ref[...] = v
                ci_ref[...] = jnp.where(v > -jnp.inf,
                                        p + (j + t0) * tile, 0)
                flag_ref[...] = jnp.zeros_like(flag_ref)
                if update_thr:
                    thr_scr[:, 0:1] = jnp.maximum(thr, v[:, ck - 1:ck])

        if ck > 0:
            # written every grid step (last j wins — the inner grid is
            # sequential): the chunk's final per-query threshold, fed
            # to the next chunk's thr_in
            thr_out_ref[...] = thr_scr[:, 0:1]

    return kernel


def _pad_bundle_rows(arrs: dict, pad_b: int) -> dict:
    """Pad the batch axis with INERT rows: can_match=0 keeps them out of
    every batch-wide prune vote, and msm=2 with zero should votes
    matches nothing, so their exact counts (and mask rows) are 0."""
    out = dict(arrs)
    out["qt"] = jnp.pad(arrs["qt"], ((0, pad_b), (0, 0)),
                        constant_values=-1)
    out["wq"] = jnp.pad(arrs["wq"], ((0, pad_b), (0, 0)))
    out["msmc"] = jnp.pad(arrs["msmc"], ((0, pad_b), (0, 0)),
                          constant_values=1)
    out["boostc"] = jnp.pad(arrs["boostc"], ((0, pad_b), (0, 0)),
                            constant_values=1.0)
    out["msm"] = jnp.pad(arrs["msm"], ((0, pad_b), (0, 0)),
                         constant_values=2)
    out["boost"] = jnp.pad(arrs["boost"], ((0, pad_b), (0, 0)),
                           constant_values=1.0)
    out["can"] = jnp.pad(arrs["can"], ((0, pad_b), (0, 0)))
    out["ub"] = jnp.pad(arrs["ub"], ((0, pad_b), (0, 0)))
    out["ranges"] = tuple(
        (jnp.pad(lo, ((0, pad_b), (0, 0))),
         jnp.pad(hi, ((0, pad_b), (0, 0))))
        for lo, hi in arrs["ranges"])
    out["pos"] = tuple(
        jnp.pad(a, ((0, pad_b), (0, 0)), constant_values=c)
        for a, c in zip(arrs["pos"], arrs["pos_pad"]))
    return out


def _bundle_chunk_call(clauses: tuple, arrs: dict, text_cols: dict,
                       num_cols: dict, live: jax.Array, *, qm: int,
                       ck: int, update_thr: bool, emit_match: bool,
                       tile: int, t0: int, nt: int, btile: int, bp: int,
                       interpret: bool, thr=None):
    """One pallas_call over the doc-tile span [t0, t0 + nt): the whole
    grid when step is None, one chunk of the stepped walk otherwise.
    Returns (cs, ci,)? cnt, flags (, match)? (, thr_out)? — candidate
    strips and counters covering this span only."""
    text_fields, num_fields, pos_fields = _meta_for(clauses)
    kern = _make_bundle_kernel(clauses, qm=qm, ck=ck,
                               update_thr=update_thr,
                               emit_match=emit_match, tile=tile, t0=t0)
    qw = arrs["qt"].shape[1]
    n_dense = arrs["msmc"].shape[1]

    def _bcast(bi, j):
        return (bi, 0)

    # per-(batch tile, doc tile) operands narrower than a lane tile —
    # the [bt, 1] bound columns in, the [bt, ck] / [bt, 1] strips out —
    # carry the doc-tile axis as a LEADING squeezed dimension
    # ([n_tiles, bp, x], block (None, bt, x)): Mosaic requires a block's
    # last two dims to be (8, 128)-divisible or span the whole array,
    # which a (bt, 1) window into [bp, n_tiles] is not. The kernel body
    # sees the same [bt, x] refs either way.
    def _per_tile(bi, j, t0=t0):
        return (j + t0, bi, 0)

    def _col(bi, j, t0=t0):
        return (0, j + t0)

    def _out(bi, j):
        return (bi, j)

    def _out_tile(bi, j):
        return (j, bi, 0)

    in_specs = [
        pl.BlockSpec((btile, max(qw, 1)), _bcast, memory_space=pltpu.VMEM),
        pl.BlockSpec((btile, max(qw, 1)), _bcast, memory_space=pltpu.VMEM),
        pl.BlockSpec((btile, max(n_dense, 1)), _bcast,
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((btile, max(n_dense, 1)), _bcast,
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((btile, 1), _bcast, memory_space=pltpu.VMEM),
        pl.BlockSpec((btile, 1), _bcast, memory_space=pltpu.VMEM),
        pl.BlockSpec((None, btile, 1), _per_tile, memory_space=pltpu.VMEM),
        pl.BlockSpec((None, btile, 1), _per_tile, memory_space=pltpu.VMEM),
    ]
    inputs = [arrs["qt"], arrs["wq"], arrs["msmc"], arrs["boostc"],
              arrs["msm"], arrs["boost"],
              arrs["can"].T[:, :, None], arrs["ub"].T[:, :, None]]
    if ck > 0:
        in_specs.append(pl.BlockSpec((btile, 1), _bcast,
                                     memory_space=pltpu.VMEM))
        inputs.append(thr)
    for lo, hi in arrs["ranges"]:
        in_specs.extend([
            pl.BlockSpec((btile, 1), _bcast, memory_space=pltpu.VMEM),
            pl.BlockSpec((btile, 1), _bcast, memory_space=pltpu.VMEM)])
        inputs.extend([lo, hi])
    for a in arrs["pos"]:
        in_specs.append(pl.BlockSpec((btile, a.shape[1]), _bcast,
                                     memory_space=pltpu.VMEM))
        inputs.append(a)
    for f in text_fields:
        slots = text_cols[f]["fwd_tids"].shape[1]
        in_specs.extend([
            pl.BlockSpec((slots, tile), _col, memory_space=pltpu.VMEM),
            pl.BlockSpec((slots, tile), _col, memory_space=pltpu.VMEM)])
        inputs.extend([text_cols[f]["fwd_tids"].T,
                       text_cols[f]["fwd_imps"].T])
    for f in pos_fields:
        # doc-major blocks: positional decoding reads whole doc rows
        # (tids to locate the term's slot window, pos for the deltas),
        # so each grid step slices a [tile, ...] row band instead of
        # the dense path's slot-major columns
        def _row(bi, j, t0=t0):
            return (j + t0, 0)
        slots = text_cols[f]["fwd_tids"].shape[1]
        pw = text_cols[f]["fwd_pos"].shape[1]
        in_specs.extend([
            pl.BlockSpec((tile, slots), _row, memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, pw), _row, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile), _col, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile), _col, memory_space=pltpu.VMEM)])
        inputs.extend([text_cols[f]["fwd_tids"],
                       text_cols[f]["fwd_pos"],
                       text_cols[f]["k1ln"][None, :],
                       text_cols[f]["lnorm"][None, :]])
    for f in num_fields:
        in_specs.extend([
            pl.BlockSpec((1, tile), _col, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile), _col, memory_space=pltpu.VMEM)])
        inputs.extend([num_cols[f]["values"][None, :],
                       num_cols[f]["exists"].astype(jnp.int32)[None, :]])
    in_specs.append(pl.BlockSpec((1, tile), _col,
                                 memory_space=pltpu.VMEM))
    inputs.append(live.astype(jnp.int32)[None, :])

    out_specs = []
    out_shape = []
    if ck > 0:
        out_specs.extend([
            pl.BlockSpec((None, btile, ck), _out_tile,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, btile, ck), _out_tile,
                         memory_space=pltpu.VMEM)])
        out_shape.extend([
            jax.ShapeDtypeStruct((nt, bp, ck), jnp.float32),
            jax.ShapeDtypeStruct((nt, bp, ck), jnp.int32)])
    out_specs.extend([
        pl.BlockSpec((None, btile, 1), _out_tile, memory_space=pltpu.VMEM),
        pl.BlockSpec((None, btile, 1), _out_tile, memory_space=pltpu.VMEM)])
    out_shape.extend([
        jax.ShapeDtypeStruct((nt, bp, 1), jnp.int32),
        jax.ShapeDtypeStruct((nt, bp, 1), jnp.int32)])
    if ck > 0:
        out_specs.append(pl.BlockSpec((btile, 1), _bcast,
                                      memory_space=pltpu.VMEM))
        out_shape.append(jax.ShapeDtypeStruct((bp, 1), jnp.float32))
    if emit_match:
        out_specs.append(pl.BlockSpec((btile, tile), _out,
                                      memory_space=pltpu.VMEM))
        out_shape.append(jax.ShapeDtypeStruct((bp, nt * tile), jnp.int32))
    scratch = [pltpu.VMEM((btile, LANES), jnp.float32)] if ck > 0 else []
    out = list(pl.pallas_call(
        kern,
        grid=(bp // btile, nt),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(*inputs))
    # back to the batch-major strips callers concatenate across chunks:
    # [nt, bp, x] -> [bp, nt * x] (tile-major within a row, as before)
    for i in range((2 if ck > 0 else 0) + 2):
        out[i] = out[i].transpose(1, 0, 2).reshape(bp, -1)
    return out


def _stack_bundle_inputs(clauses: tuple, cl_inputs: tuple):
    """Clause-stacked kernel inputs: every dense clause padded to
    qm = max clause width (tid -1 / weight 0 padding contributes an
    exact 0.0); range clauses contribute their (lo, hi) pairs as
    [B, 1] columns. Positional clauses ride their own flat param
    columns (_pos_param_arrays) and contribute nothing here; a bundle
    with NO dense clause (pure phrase / span / bm25f) gets one inert
    dummy column (qt = -1, weight 0) so the fixed leading refs keep
    their shapes."""
    from .scoring import DENSE_CLAUSE_KINDS, positional_prefix
    dense = [(inp if kind in DENSE_CLAUSE_KINDS else None)
             for (r, kind, f, w), inp in zip(clauses, cl_inputs)]
    qm = max((inp[0].shape[1] for inp in dense if inp is not None),
             default=1)
    qts, wqs, msmcs, boostcs, ranges = [], [], [], [], []
    for (r, kind, f, w), inp in zip(clauses, cl_inputs):
        if kind in DENSE_CLAUSE_KINDS:
            qt, wq, msm_c, boost_c = inp
            pad = qm - qt.shape[1]
            if pad:
                qt = jnp.pad(qt, ((0, 0), (0, pad)), constant_values=-1)
                wq = jnp.pad(wq, ((0, 0), (0, pad)))
            qts.append(qt)
            wqs.append(wq)
            msmcs.append(msm_c)
            boostcs.append(boost_c)
        elif positional_prefix(kind):
            continue
        else:
            lo, hi = inp
            ranges.append((lo[:, None], hi[:, None]))
    if not qts:
        b = cl_inputs[0][0].shape[0]
        return (qm, jnp.full((b, qm), -1, jnp.int32),
                jnp.zeros((b, qm), jnp.float32),
                jnp.ones((b, 1), jnp.int32),
                jnp.ones((b, 1), jnp.float32), tuple(ranges))
    return (qm, jnp.concatenate(qts, axis=1), jnp.concatenate(wqs, axis=1),
            jnp.stack(msmcs, axis=1),
            jnp.stack(boostcs, axis=1).astype(jnp.float32), tuple(ranges))


def _bundle_pallas_walk(text_cols: dict, num_cols: dict, clauses: tuple,
                        cl_inputs: tuple, msm: jax.Array,
                        boost: jax.Array | None, live: jax.Array, *,
                        ck: int, update_thr: bool, emit_match: bool,
                        step, interpret: bool, thr_init=None):
    """ONE driver for both public entries (k>0 candidates and the
    ck == 0 mask-only grid): bounds, clause stacking, inert-row
    padding, and the walk — a single pallas_call over the whole grid,
    or the STEPPED chunk loop (one pallas_call per chunk, running
    threshold carried through a [B, 1] in/out pair, candidates /
    counts / prune flags concatenated across chunks, `check` hosted
    between kernel invocations with a FINAL check after the last chunk
    — the ops/scoring._stepped_tile_loop contract). Returns
    (cs, ci, cnt, flags, match, timed, b, btile, bp); cs/ci are None
    when ck == 0, match when not emit_match, timed when step is None."""
    from .scoring import bundle_tile_bounds, bundle_primary_field
    cap = live.shape[0]
    field0 = bundle_primary_field(clauses)
    n_tiles = text_cols[field0]["tile_max"].n_tiles
    tile = cap // n_tiles
    b = msm.shape[0]
    can_match, ub = bundle_tile_bounds(clauses, cl_inputs, text_cols,
                                       num_cols, msm, boost)
    boost_arr = boost if boost is not None \
        else jnp.ones((b,), jnp.float32)
    qm, qt_all, wq_all, msmc, boostc, ranges = _stack_bundle_inputs(
        clauses, cl_inputs)
    pos_flat, pos_pads = _pos_param_arrays(clauses, cl_inputs)
    # positional decoding materializes [bt, tile, ..] position cubes in
    # the kernel; shrink the batch tile so the working set stays inside
    # scoped VMEM (the admission gate bounds L*P separately)
    btile = min(8 if pos_flat else _BATCH_TILE, b)
    pad_b = (-b) % btile
    arrs = {"qt": qt_all, "wq": wq_all, "msmc": msmc, "boostc": boostc,
            "msm": msm[:, None].astype(jnp.int32),
            "boost": boost_arr[:, None].astype(jnp.float32),
            "can": can_match.astype(jnp.int32), "ub": ub,
            "ranges": ranges, "pos": tuple(pos_flat),
            "pos_pad": pos_pads}
    if pad_b:
        arrs = _pad_bundle_rows(arrs, pad_b)
    bp = b + pad_b
    chunk = functools.partial(
        _bundle_chunk_call, clauses, arrs, text_cols, num_cols, live,
        qm=qm, ck=ck, update_thr=update_thr, emit_match=emit_match,
        tile=tile, btile=btile, bp=bp, interpret=interpret)
    # fixed slots in a chunk call's output list: candidates only exist
    # for ck > 0, the threshold rides behind the counters
    n_cand = 2 if ck > 0 else 0
    thr0 = (jnp.full((bp, 1), -jnp.inf, jnp.float32) if ck > 0 else None)
    if ck > 0 and thr_init is not None:
        # delta-walk threshold seed (streaming write path): the base
        # walk's k-th best opens this walk's threshold, so delta tiles
        # prune against the base exactly as base tiles prune against
        # each other; a tied delta doc loses the merge anyway (base
        # candidates concatenate first), so seeding stays exact
        thr0 = thr0.at[: thr_init.shape[0]].set(thr_init)

    def _unpack(out):
        cs = out[0] if ck > 0 else None
        ci = out[1] if ck > 0 else None
        cnt, flags = out[n_cand], out[n_cand + 1]
        thr = out[n_cand + 2] if ck > 0 else None
        match = out[-1] if emit_match else None
        return cs, ci, cnt, flags, thr, match

    if step is None:
        out = chunk(t0=0, nt=n_tiles, thr=thr0) if ck > 0 \
            else chunk(t0=0, nt=n_tiles)
        cs, ci, cnt, flags, _thr, match = _unpack(list(out))
        return cs, ci, cnt, flags, match, None, b, btile, bp

    chunk_tiles, ck0, check = step
    n_chunks = -(-n_tiles // chunk_tiles)
    parts: list[list] = [[], [], [], [], []]       # cs ci cnt flags match
    thr = thr0
    st = ck0
    timed = jnp.bool_(False)
    for c in range(n_chunks):
        t0 = c * chunk_tiles
        nt = min(chunk_tiles, n_tiles - t0)
        timed, st = check(c, st)

        def _run(thr, t0=t0, nt=nt):
            return tuple(chunk(t0=t0, nt=nt, thr=thr)) if ck > 0 \
                else tuple(chunk(t0=t0, nt=nt))

        def _skip(thr, nt=nt):
            # a preempted chunk's tiles report as thresholded; the
            # caller discards the whole result on timed_out anyway
            out = ()
            if ck > 0:
                out = (jnp.full((bp, nt * ck), -jnp.inf, jnp.float32),
                       jnp.zeros((bp, nt * ck), jnp.int32))
            out = out + (jnp.zeros((bp, nt), jnp.int32),
                         jnp.ones((bp, nt), jnp.int32))
            if ck > 0:
                out = out + (thr,)
            if emit_match:
                out = out + (jnp.zeros((bp, nt * tile), jnp.int32),)
            return out

        out = jax.lax.cond(timed, _skip, _run, thr)
        cs_c, ci_c, cnt_c, flags_c, thr, match_c = _unpack(list(out))
        for dst, val in zip(parts, (cs_c, ci_c, cnt_c, flags_c,
                                    match_c)):
            if val is not None:
                dst.append(val)
    # one FINAL check after the last chunk (the same contract as
    # ops/scoring._stepped_tile_loop): a deadline expiring during the
    # last chunk's kernel must still report timed_out
    final, _st = check(n_chunks, st)
    timed = timed | final
    cat = [jnp.concatenate(p, axis=1) if p else None for p in parts]
    return cat[0], cat[1], cat[2], cat[3], cat[4], timed, b, btile, bp


def fused_topk_bundle_pallas(text_cols: dict, num_cols: dict,
                             clauses: tuple, cl_inputs: tuple,
                             msm: jax.Array, boost: jax.Array | None,
                             live: jax.Array, k: int,
                             emit_match: bool = False, step=None,
                             interpret: bool = False,
                             init_topk=None, idx_offset: int = 0):
    """Pallas counterpart of ops.scoring.score_topk_bundle_fused — the
    SAME calling convention, covering the full bundle admission matrix:
    multi-text-field bundles (one forward-index block pair per field),
    dense + numeric-range filter/must_not masks (evaluated per tile in
    VMEM from the same columns the XLA engine reads), and emit-match
    mode (exact [B, cap] match mask for a downstream aggregation pass).

    can_match/ub come from bundle_tile_bounds — shared with the XLA
    engine so both backends prune identically. Returns (top_s [B,k],
    top_i [B,k], total [B], prune_stats f32 [3] = (hard, thresholded,
    examined) in doc-tile units: per-(batch-tile, doc-tile) decisions
    are averaged over batch tiles so examined == n_tiles, matching the
    XLA backend's batch-wide counters), plus the match mask [B, cap]
    bool when emit_match, plus the timed_out scalar when a `step` (see
    ops/scoring._stepped_tile_loop) is given — the stepped form runs
    one pallas_call per chunk with the running threshold, candidates,
    and prune counters carried across chunk boundaries, hosting the
    per-chunk deadline callback BETWEEN kernel invocations."""
    from .scoring import bundle_primary_field, running_topk_merge
    cap = live.shape[0]
    k = min(k, cap) if init_topk is None else init_topk[0].shape[1]
    k_sel = min(k, cap)
    n_tiles = text_cols[bundle_primary_field(clauses)]["tile_max"].n_tiles
    ck = min(k_sel, cap // n_tiles)
    cs, ci, cnt, flags, match, timed, b, btile, bp = _bundle_pallas_walk(
        text_cols, num_cols, clauses, cl_inputs, msm, boost, live,
        ck=ck, update_thr=(ck == k_sel), emit_match=emit_match, step=step,
        interpret=interpret,
        thr_init=(None if init_topk is None
                  else init_topk[0][:, -1:]))
    # tile-major candidate strip: global top_k tie-breaks by flat index,
    # i.e. (tile asc, within-tile rank) — lower doc ids win ties, the
    # same order one lax.top_k over the full score matrix produces
    top_s, pos = jax.lax.top_k(cs[:b], min(k_sel, cs.shape[1]))
    top_i = jnp.take_along_axis(ci[:b], pos, axis=1) + idx_offset
    if init_topk is not None:
        # chain onto the earlier (base) walk's selection: existing
        # state first, so base docs win ties — the same merge rule the
        # XLA engine's carried running top-k applies
        top_s, top_i = running_topk_merge(init_topk[0], init_topk[1],
                                          top_s, top_i)
    total = cnt[:b].sum(axis=1)
    pruned = _normalize_prune(flags, btile, bp)
    out = (top_s, top_i, total, pruned)
    if emit_match:
        out = out + ((match[:b] != 0),)
    return out if timed is None else out + (timed,)


def _normalize_prune(flags: jax.Array, btile: int, bp: int) -> jax.Array:
    """Prune decisions happen per (batch-tile, doc-tile) grid cell here
    but per doc-tile in the XLA backend; normalize by the batch-tile
    count so both report in doc-tile units (examined == n_tiles) and
    prune rates stay comparable when the autotuner mixes backends."""
    reps = flags[::btile]                       # one row per batch tile
    n_btiles = bp // btile
    return (jnp.stack([(reps == 2).sum(), (reps == 1).sum(),
                       jnp.int32(reps.size)]).astype(jnp.float32)
            / n_btiles)


def match_mask_bundle_pallas(text_cols: dict, num_cols: dict,
                             clauses: tuple, cl_inputs: tuple,
                             msm: jax.Array, boost: jax.Array | None,
                             live: jax.Array, emit_match: bool = True,
                             step=None, interpret: bool = False):
    """Pallas counterpart of ops.scoring.match_mask_bundle_fused — the
    mask-only k == 0 grid: a ck == 0 build of the bundle kernel that
    emits exact counts (and, when emit_match, the exact match mask) with
    msm-aware hard-skips and NO candidate selection or threshold state.
    Match semantics are exact per ops/scoring.bundle_tile_match: a dense
    clause's match is `score > 0`, which the kernel evaluates with the
    same compare/accumulate ops, so totals and masks are bit-identical
    to the XLA engine. Returns (total [B], prune_stats f32 [3])
    (+ match [B, cap] bool)(+ timed_out when stepped)."""
    _cs, _ci, cnt, flags, match, timed, b, btile, bp = \
        _bundle_pallas_walk(
            text_cols, num_cols, clauses, cl_inputs, msm, boost, live,
            ck=0, update_thr=False, emit_match=emit_match, step=step,
            interpret=interpret)
    total = cnt[:b].sum(axis=1)
    pruned = _normalize_prune(flags, btile, bp)
    out = (total, pruned)
    if emit_match:
        out = out + ((match[:b] != 0),)
    return out if timed is None else out + (timed,)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def fused_topk_dense_pallas(fwd_tids: jax.Array, fwd_imps: jax.Array,
                            tile_max, qt: jax.Array,
                            wq: jax.Array, live: jax.Array, k: int,
                            msm: jax.Array | None = None,
                            boost: jax.Array | None = None,
                            interpret: bool = False
                            ) -> tuple[jax.Array, jax.Array, jax.Array,
                                       jax.Array]:
    """Single-dense-clause entry (PR 1 signature): a thin wrapper over
    the bundle kernel — one should clause, the enclosing bool node's
    dynamic msm/boost as the outer params. Like the XLA wrapper, boost
    now applies BEFORE selection in eval_node's exact op order, so doc
    ids and ties match the unfused path for any boost > 0."""
    b = qt.shape[0]
    if msm is None:
        msm = jnp.ones((b,), jnp.int32)
    clauses = (("should", "terms_dense", "f", False),)
    cl_inputs = ((qt, wq, jnp.ones((b,), jnp.int32),
                  jnp.ones((b,), jnp.float32)),)
    text_cols = {"f": {"fwd_tids": fwd_tids, "fwd_imps": fwd_imps,
                       "tile_max": tile_max}}
    return fused_topk_bundle_pallas(text_cols, {}, clauses, cl_inputs,
                                    msm, boost, live, k,
                                    interpret=interpret)


# ---------------------------------------------------------------------------
# drop-in counterparts for ops/scoring.py entry points
# ---------------------------------------------------------------------------


def score_term_pallas(block_docs: jax.Array, block_imps: jax.Array,
                      block_lo: jax.Array, nb_valid: jax.Array,
                      weight: jax.Array, nb_pad: int, cap: int,
                      interpret: bool = False) -> jax.Array:
    """Pallas-backed ops.scoring.score_term: XLA block gather (regular,
    already efficient) + fused one-hot scatter."""
    from .scoring import gather_term_blocks
    docs, imps = gather_term_blocks(block_docs, block_imps, block_lo,
                                    nb_valid, nb_pad, cap)
    return scatter_add_pallas(docs, imps * weight[:, None], cap,
                              interpret=interpret)


def score_terms_fused_pallas(block_docs: jax.Array, block_imps: jax.Array,
                             gather_idx: jax.Array, weights: jax.Array,
                             cap: int, interpret: bool = False) -> jax.Array:
    """Pallas-backed ops.scoring.score_terms_fused."""
    from .scoring import gather_fused_blocks
    docs, vals = gather_fused_blocks(block_docs, block_imps, gather_idx,
                                     weights, cap)
    return scatter_add_pallas(docs, vals, cap, interpret=interpret)


# ---------------------------------------------------------------------------
# dispatch: use the kernels on real TPU, jnp elsewhere
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=1)
def pallas_enabled() -> bool:
    """Kernels engage on an actual TPU backend unless ES_TPU_PALLAS=0;
    ES_TPU_PALLAS=1 forces them even off-TPU (in interpret mode — far
    slower than the XLA fallback, for validation only)."""
    import os
    flag = os.environ.get("ES_TPU_PALLAS", "auto").lower()
    if flag in ("0", "false", "off"):
        return False
    if flag in ("1", "true", "on"):
        return True
    # a backend that cannot initialise raises here, on the served path:
    # answering "no kernels" would quietly serve everything from XLA
    return jax.default_backend() == "tpu"


def resident_step_ok() -> bool:
    """May a resident stepped entry (search/resident.py) run through a
    Pallas kernel? Yes, whenever the kernels are enabled at all: the
    stepped form of fused_topk_bundle_pallas / match_mask_bundle_pallas
    partitions the doc-tile grid into chunks of pallas_call invocations
    and hosts the per-chunk deadline callback BETWEEN kernel chunks at
    the jit level (a Mosaic kernel body still cannot host a callback
    mid-grid — the chunk boundary is the preemption point), with the
    running threshold and prune counters carried across the boundary.
    Exists as a named predicate so the executor's admission reads as
    policy, not accident."""
    return pallas_enabled()


@functools.lru_cache(maxsize=1)
def interpret_mode() -> bool:
    """Forced-on kernels off-TPU must run the Pallas interpreter —
    Mosaic lowering only exists for TPU backends."""
    return jax.default_backend() != "tpu"
