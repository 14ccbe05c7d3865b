"""Batched posting-scatter scoring primitives (pure JAX).

These replace the Lucene hot loop the reference runs per shard
(search/query/QueryPhase.java:153 — BulkScorer iterating postings with
BM25 Similarity into TopScoreDocCollector). The TPU formulation is
BM25S-style eager scoring (PAPERS.md): per-posting BM25 impacts are
precomputed at index time, so a query is

    gather posting blocks -> weight -> scatter-add into dense per-doc scores

which is batched over queries ([B, ...]) and vectorized over the 128-lane
posting blocks. On a real TPU backend the executor dispatches these
clause kinds to the fused Pallas kernels in ops/pallas_scoring.py
(one-hot MXU scatter with sorted-range tile skip; tiled forward-index
compare+FMA); these jnp versions are the reference semantics, the CPU
path, and what the kernels are tested against in interpret mode.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..index.segment import BLOCK, BM25_K1, TileSummary
from .topk import NEG_INF, running_topk_init, running_topk_merge


def batched_scatter_add(ids: jax.Array, vals: jax.Array, cap: int) -> jax.Array:
    """scores[b, ids[b, n]] += vals[b, n]; ids == cap (or any OOB) dropped.

    ids: int32 [B, N], vals: float32 [B, N] -> [B, cap] float32.
    """

    def one(i, v):
        return jnp.zeros((cap,), jnp.float32).at[i].add(v, mode="drop")

    return jax.vmap(one)(ids, vals)


def gather_term_blocks(block_docs: jax.Array, block_imps: jax.Array,
                       block_lo: jax.Array, nb_valid: jax.Array,
                       nb_pad: int, cap: int) -> tuple[jax.Array, jax.Array]:
    """Gather a term's posting blocks per batched query.

    block_docs/block_imps: [NB, 128] segment posting storage.
    block_lo: [B] first block of this term, nb_valid: [B] how many blocks.
    Returns (docs [B, nb_pad*128] padded with `cap`, imps [B, nb_pad*128]).
    """
    iota = jnp.arange(nb_pad, dtype=jnp.int32)
    idx = block_lo[:, None] + iota[None, :]                   # [B, nb_pad]
    ok = iota[None, :] < nb_valid[:, None]
    safe = jnp.where(ok, idx, 0)
    docs = block_docs[safe]                                   # [B, nb_pad, 128]
    imps = block_imps[safe]
    docs = jnp.where(ok[..., None], docs, cap)                # padded -> dropped
    b = block_lo.shape[0]
    return docs.reshape(b, nb_pad * BLOCK), imps.reshape(b, nb_pad * BLOCK)


def score_term(block_docs: jax.Array, block_imps: jax.Array,
               block_lo: jax.Array, nb_valid: jax.Array, weight: jax.Array,
               nb_pad: int, cap: int) -> jax.Array:
    """Score one text-term clause for a batch of queries -> [B, cap].

    weight multiplies the precomputed BM25 impact (query boost; the idf is
    already inside the impact). score > 0 wherever the term matched, so
    the same array doubles as the match mask (bind clamps weight > 0).
    """
    docs, imps = gather_term_blocks(block_docs, block_imps, block_lo, nb_valid,
                                    nb_pad, cap)
    return batched_scatter_add(docs, imps * weight[:, None], cap)


def gather_fused_blocks(block_docs: jax.Array, block_imps: jax.Array,
                        gather_idx: jax.Array, weights: jax.Array,
                        cap: int) -> tuple[jax.Array, jax.Array]:
    """Gather + weight the blocks of a fused disjunction group.

    gather_idx: [B, M] absolute block indices (-1 = padding);
    weights: [B, M] per-block clause weight.
    Returns (docs [B, M*128] padded with cap, vals [B, M*128]) — the
    single shared preamble for both the jnp and Pallas scatter backends.
    """
    ok = gather_idx >= 0
    safe = jnp.where(ok, gather_idx, 0)
    docs = block_docs[safe]                                   # [B, M, 128]
    imps = block_imps[safe]
    docs = jnp.where(ok[..., None], docs, cap)
    vals = imps * weights[..., None]
    b, m = gather_idx.shape
    return docs.reshape(b, m * BLOCK), vals.reshape(b, m * BLOCK)


def score_terms_fused(block_docs: jax.Array, block_imps: jax.Array,
                      gather_idx: jax.Array, weights: jax.Array,
                      cap: int) -> jax.Array:
    """Score MANY term clauses of one disjunction group in a single scatter.

    Used for `should`-group fusion (a match query's terms all land in one
    scatter) — the common fast path for the http_logs bench query.
    """
    docs, vals = gather_fused_blocks(block_docs, block_imps, gather_idx,
                                     weights, cap)
    return batched_scatter_add(docs, vals, cap)


# ---------------------------------------------------------------------------
# Fused block-max score + top-k (forward-index path)
#
# The unfused pipeline materializes a full [B, cap] score matrix and runs
# lax.top_k over it. The fused pipeline walks SCORE_TILE-doc tiles with a
# fori_loop carrying a running top-k, and uses the pack-time block-max
# summaries (index/segment.build_tile_max) to skip tiles that cannot
# change the result — the block-max WAND idea (arxiv 1910.11028) mapped
# onto dense tiles, generalized to whole bool plans (the BM-WAND family):
# a CLAUSE BUNDLE of must/should scoring clauses plus filter/must_not
# match-mask clauses is evaluated per tile, the tile bound is the sum of
# per-clause block-max bounds, and minimum-should-match-aware pruning
# drops a tile when fewer than msm should clauses can possibly match in
# it. Two prune levels per tile, both decided batch-wide
# (per-lane skipping saves nothing on SIMD hardware):
#
#   hard skip:  no query's bound is > 0 in this tile -> no doc can match;
#               the tile contributes nothing, not even to total hits.
#   threshold:  every query's bound is <= its running k-th best score ->
#               the tile is scored for EXACT hit counting, but the
#               per-tile top-k extraction + merge is skipped.
#
# Tie safety: a tile is threshold-pruned only when each doc's score is
# <= the query's current k-th best, which came from LOWER doc ids
# (tiles run in doc order) — and lax.top_k breaks ties toward the lower
# index, so a tied pruned doc would have lost anyway.
#
# The XLA top-k walk (score_topk_bundle_fused) scores a step of
# walk_step_tiles tiles an iteration on a large pack: it hard-skips a
# step whose tiles all skip, and extracts candidates where a scored doc
# beats its query's k-th best, which is the threshold rule read from
# the scores instead of the bounds (the same tie argument holds).
# ---------------------------------------------------------------------------


# relative slack applied to the tile bounds before THRESHOLD compares:
# the bound and the score loops accumulate in the same q order, but the
# compilers (XLA for the bounds, XLA or Mosaic for the scores) may
# contract one side's mul+add into an FMA and not the other's, letting
# a tile's best doc round a few ULPs ABOVE its bound. 32 eps covers any
# realistic query-term count; scores are nonnegative, so scaling the
# bound up only makes pruning more conservative. Hard-skip (ub > 0)
# needs no slack: every per-term product of the bound dominates the
# corresponding per-doc product under monotone f32 rounding, so ub == 0
# forces all doc scores to 0 regardless of contraction.
BOUND_SLACK = 1.0 + 32 * float(jnp.finfo(jnp.float32).eps)


def tile_max_rows(tile_max: TileSummary, tids: jax.Array) -> jax.Array:
    """[B] term ids -> [B, n_tiles] rows of the block-max summary: the
    largest impact of each term in each tile, 0 where it does not occur
    (what `tile_max[tids]` read of the dense [T, n_tiles] array). The
    ONE reader of the stored form on the device; a term id outside the
    dictionary reads the nearest row and the caller masks it, as
    before. A row's entries are a window of `grid` stored entries from
    its start, cut to its length; each tile takes the entry that names
    it (at most one does, so the max is that entry's value, exactly)."""
    grid = tile_max.grid
    safe = jnp.clip(tids, 0, max(tile_max.start.shape[0] - 2, 0))
    lo = tile_max.start[safe]
    n = tile_max.start[safe + 1] - lo

    def window(a):
        return jax.vmap(
            lambda s: jax.lax.dynamic_slice(a, (s,), (grid,)))(lo)

    e = jnp.arange(grid, dtype=jnp.int32)
    tiles = jnp.where(e[None, :] < n[:, None],
                      window(tile_max.tiles), grid)           # [B, E]
    rows = jnp.max(
        jnp.where(tiles[:, :, None] == e[None, None, :],
                  window(tile_max.vals)[:, :, None], 0.0), axis=1)
    if tile_max.cols is None:
        return rows
    return jnp.take(rows, tile_max.cols, axis=1, mode="fill",
                    fill_value=0.0)


def dense_tile_bounds(tile_max: TileSummary, qt: jax.Array, wq: jax.Array
                      ) -> jax.Array:
    """block-max summary x [B, Q] query -> [B, J] score bounds
    (BOUND_SLACK-inflated, see above). Padded/absent terms (qt < 0)
    contribute 0, mirroring their zero-impact matches."""
    b, q_n = qt.shape
    ub = jnp.zeros((b, tile_max.n_tiles), jnp.float32)
    for q in range(q_n):
        tm = tile_max_rows(tile_max, qt[:, q])          # [B, J]
        w = jnp.where(qt[:, q] >= 0, wq[:, q], 0.0)
        ub = ub + tm * w[:, None]
    return ub * jnp.float32(BOUND_SLACK)


def _dense_tile_scores(t_tids: jax.Array, t_imps: jax.Array,
                       qt: jax.Array, wq: jax.Array) -> jax.Array:
    """One tile of the forward-index scoring loop: [tile, L] x [B, Q] ->
    [B, tile], with the same reduction order as the unfused jnp path so
    fused and unfused scores are bit-identical."""
    b = qt.shape[0]
    tile = t_tids.shape[0]
    score = jnp.zeros((b, tile), jnp.float32)
    for q in range(qt.shape[1]):
        tq = qt[:, q][:, None, None]                    # [B, 1, 1]
        contrib = jnp.sum(
            jnp.where(t_tids[None] == tq, t_imps[None], 0.0), axis=-1)
        score = score + contrib * wq[:, q][:, None]
    return score


# A clause bundle is a STATIC tuple of clause descriptors
#
#     (role, kind, field, wrapped)
#
# role ∈ {"must", "filter", "must_not", "should"}; kind is a scoring
# dense-text kind ("terms_dense" / "term_text") or a numeric range mask
# ("range_int" / "range_f32", filter/must_not roles only); `wrapped`
# marks a clause that binds as a single-should bool wrapper carrying its
# own dynamic (msm, boost). Clauses MUST be ordered (must, filter,
# must_not, should) with source order preserved inside each role — that
# is eval_node's accumulation order, and reproducing it keeps fused and
# unfused scores bit-identical.
#
# Per-clause dynamic inputs (parallel tuple `cl_inputs`):
#   dense: (qt [B, Q] int32, wq [B, Q] f32, msm_c [B] int32,
#           boost_c [B] f32)  — unwrapped clauses pass msm_c = 1,
#           boost_c = 1.0 (both exact no-ops in f32)
#   range: (lo [B], hi [B]) in the column's device dtype
#
# `text_cols[field]` carries fwd_tids/fwd_imps/tile_max; `num_cols
# [field]` carries values/exists plus the pack-time per-tile extrema
# tile_lo/tile_hi (index/segment.build_tile_minmax) that let range
# filters prune tiles on mask density.

# the ONE definition of which desc kinds are dense scoring clauses vs
# numeric range masks vs vector scoring clauses — the executor's
# admission classifier imports these, so the two layers cannot drift
DENSE_CLAUSE_KINDS = ("terms_dense", "term_text")
RANGE_CLAUSE_KINDS = ("range_int", "range_f32")
# vector similarity as a bundle scoring clause (must/should roles): the
# executor precomputes the whole-capacity similarity column INSIDE the
# fused program (one MXU matmul — search/executor._vec_clause_inputs)
# and the tile walk slices it, so a hybrid BM25+vector bool plan stays
# ONE device dispatch. Per-clause dynamic input:
#   (col [B, cap] f32  — transformed similarity, boost-folded, 0 where
#                        the doc has no vector,
#    exists [cap] bool — the clause's match mask,
#    ub [B, J] f32     — per-tile max of col, BOUND_SLACK-inflated:
#                        an EXACT per-query tile bound, the tile_max
#                        analog computed at query time)
VEC_CLAUSE_KINDS = ("knn_vec",)
_DENSE_KINDS = DENSE_CLAUSE_KINDS
_VEC_KINDS = VEC_CLAUSE_KINDS

# Positional scoring clauses evaluate adjacency over the positions
# column family (index/segment.pack_positions: fwd_pos [cap, L*P]
# int16 per-posting delta lists forward-aligned with the fwd_tids
# slots, plus the pack-time k1ln/lnorm norm columns). The clause
# STATICS ride inside the kind string itself, so clauses with
# different term counts get different trace signatures and are never
# batched together (no padding semantics to define):
#
#   "phrase_pos:{n}:{e|s}"  n-term match_phrase; 'e' = exact
#                           adjacency (slop == 0), 's' = the sloppy
#                           pointer sweep (slop stays DYNAMIC — one
#                           compile serves every slop value)
#   "span_pos:{n}:{o|u}"    span_near over n same-field span_term
#                           children, ordered / unordered
#   "bm25f:{nf}:{nt}"       multi-field multi_match as true BM25F:
#                           nf fields x nt terms, shared idf,
#                           per-field length norms + weights; the
#                           clause's `field` slot holds the TUPLE of
#                           field names
#
# Per-clause dynamic inputs (cl_inputs entry):
#   phrase/span: (qt [B, n] i32, wb [B, n] f32 bound weights
#                 f32(idf_sum / idf_i), idf_sum [B] f32, slop [B] i32,
#                 pboost [B] f32 clause boost, msm_c [B] i32,
#                 boost_c [B] f32 — wrapper dynamics as for dense)
#   bm25f:       (qt [B, nf, nt] i32, idf [B, nt] f32, wf [B, nf] f32,
#                 pboost [B] f32, msm_c [B] i32, boost_c [B] f32)
POSITIONAL_PREFIXES = ("phrase_pos", "span_pos", "bm25f")

# decoded-position pad sentinel: far above any real position
# (POS_MAX_ENC = 32767) yet small enough that sentinel +/- small-int
# arithmetic stays well inside int32
_POS_BIG = 1 << 30


def positional_prefix(kind: str) -> str | None:
    """The positional family of a clause kind, or None for the rest."""
    head = kind.split(":", 1)[0]
    return head if head in POSITIONAL_PREFIXES else None


def phrase_kind(n: int, sloppy: bool) -> str:
    return f"phrase_pos:{n}:{'s' if sloppy else 'e'}"


def span_kind(n: int, in_order: bool) -> str:
    return f"span_pos:{n}:{'o' if in_order else 'u'}"


def bm25f_kind(nf: int, nt: int) -> str:
    return f"bm25f:{nf}:{nt}"


def parse_positional_kind(kind: str) -> tuple[str, int, str]:
    """"head:a:b" -> (head, int(a), b)."""
    head, a, bv = kind.split(":")
    return head, int(a), bv


def clause_fields(field) -> tuple:
    """A clause's fields as a tuple (bm25f stores a field TUPLE in the
    `field` slot; every other kind a single str)."""
    return field if isinstance(field, tuple) else (field,)


def bundle_primary_field(clauses: tuple) -> str:
    """Field of the first dense or positional scoring clause (defines
    the tile grid — every field of a segment shares cap and tile
    size, so any of them pins the same grid)."""
    for _role, kind, field, _w in clauses:
        if kind in _DENSE_KINDS:
            return field
        if positional_prefix(kind):
            return clause_fields(field)[0]
    raise ValueError("bundle has no dense scoring clause")


def bundle_text_fields(clauses: tuple) -> tuple:
    """Fields whose forward text columns (fwd_tids/fwd_imps) the tile
    walk must slice — dense clause fields plus every field of every
    positional clause (the slot compare that locates a term's
    position window reads fwd_tids)."""
    return tuple(dict.fromkeys(
        f for _r, kd, fld, _w in clauses
        if kd in _DENSE_KINDS or positional_prefix(kd)
        for f in clause_fields(fld)))


def bundle_pos_fields(clauses: tuple) -> tuple:
    """Fields whose positions columns (fwd_pos/k1ln/lnorm) the tile
    walk must slice."""
    return tuple(dict.fromkeys(
        f for _r, kd, fld, _w in clauses if positional_prefix(kd)
        for f in clause_fields(fld)))


# ---------------------------------------------------------------------------
# Positional tile evaluation
#
# Device mirrors of search/phrase.py's host loops, restated as fixed-
# shape array programs over one [tile] doc slab. Every op is per-doc
# (elementwise over the doc axis, reductions only over position/term
# axes), so evaluating tile-by-tile is bit-identical to evaluating the
# whole capacity at once — eval_node's unfused reference calls the
# same helpers full-cap. All frequency computations are exact integer
# programs; the single f32 impact formula at the end is shared op for
# op with search/phrase.phrase_impacts, which keeps fused == unfused
# == host-oracle byte identity.
# ---------------------------------------------------------------------------


def _term_positions(t_tids: jax.Array, t_pos: jax.Array, tq: jax.Array
                    ) -> tuple[jax.Array, jax.Array]:
    """Decode one query term's positions for every doc in a tile.

    t_tids [tile, L] slot term ids; t_pos [tile, L*P] int16 delta
    lists (slot l owns columns [l*P, (l+1)*P)); tq [B] query term id.
    Returns (pos [B, tile, P] int32 ascending, pads -> _POS_BIG;
    tf [B, tile] int32 valid-position count). A doc's slots hold
    DISTINCT term ids, so at most one slot matches and a masked max
    over the slot axis selects it without any L-unrolled loop; tq < 0
    (inert padded batch rows) matches nothing — fwd_tids pads are -1,
    hence the explicit tq >= 0 guard."""
    tile, n_slots = t_tids.shape
    p_width = t_pos.shape[1] // n_slots
    pos3 = t_pos.reshape(tile, n_slots, p_width)
    hit = (t_tids[None] == tq[:, None, None]) \
        & (tq >= 0)[:, None, None]                       # [B, tile, L]
    enc = jnp.where(hit[..., None], pos3[None],
                    jnp.int16(-1)).max(axis=2)           # [B, tile, P]
    valid = enc >= 0
    pos = jnp.cumsum(jnp.where(valid, enc.astype(jnp.int32), 0), axis=-1)
    pos = jnp.where(valid, pos, _POS_BIG)
    return pos, valid.sum(axis=-1, dtype=jnp.int32)


def _phrase_freq_exact(pos: jax.Array, tf: jax.Array) -> jax.Array:
    """Exact-adjacency phrase frequency (host mirror: phrase_match's
    slop <= 0 branch — a start p survives iff term i occurs at p + i).

    pos [B, tile, n, P], tf [B, tile, n] -> freq [B, tile] i32.
    Pad starts (_POS_BIG) self-eliminate for n >= 2: _POS_BIG + i
    equals neither a real position nor _POS_BIG."""
    n = pos.shape[2]
    if n == 1:
        return tf[..., 0]
    starts = pos[:, :, 0, :]                             # [B, tile, P]
    alive = starts < _POS_BIG
    for i in range(1, n):
        member = jnp.any(
            pos[:, :, i, None, :] == (starts + i)[..., :, None], axis=-1)
        alive = alive & member
    return alive.sum(axis=-1, dtype=jnp.int32)


def _phrase_freq_sloppy(pos: jax.Array, tf: jax.Array, slop: jax.Array
                        ) -> jax.Array:
    """Sloppy phrase frequency — the _sloppy_match pointer sweep run
    for all docs in lockstep: n*P fixed iterations, each testing the
    current window (min/max of the n adjusted head positions, repeats
    must land on distinct raw tokens) and advancing the FIRST pointer
    holding the minimum (host `vals.index(lo)`; jnp.argmin breaks
    ties to the first index identically). Docs whose sweep finishes
    early go inactive (`ptr < tf` fails) and simply stop counting —
    the remaining iterations are no-ops for them, so the final count
    equals the host loop's."""
    b, tile, n, p_width = pos.shape
    adj = pos - jnp.arange(n, dtype=jnp.int32)[None, None, :, None]

    def body(_it, st):
        ptr, freq = st
        safe = jnp.clip(ptr, 0, p_width - 1)
        vals = jnp.take_along_axis(adj, safe[..., None], axis=-1)[..., 0]
        active = jnp.all(ptr < tf, axis=-1)              # [B, tile]
        lo = vals.min(axis=-1)
        hi = vals.max(axis=-1)
        raw = vals + jnp.arange(n, dtype=jnp.int32)[None, None, :]
        distinct = jnp.ones((b, tile), bool)
        for i in range(n):
            for j in range(i + 1, n):
                distinct = distinct & (raw[..., i] != raw[..., j])
        hit = active & ((hi - lo) <= slop[:, None]) & distinct
        freq = freq + hit.astype(jnp.int32)
        amin = jnp.argmin(vals, axis=-1)
        adv = jnp.arange(n, dtype=jnp.int32)[None, None, :] \
            == amin[..., None]
        ptr = ptr + jnp.where(active[..., None] & adv, 1, 0)
        return ptr, freq

    st0 = (jnp.zeros((b, tile, n), jnp.int32),
           jnp.zeros((b, tile), jnp.int32))
    _ptr, freq = jax.lax.fori_loop(0, n * p_width, body, st0)
    return freq


def _span_freq_ordered(pos: jax.Array, tf: jax.Array, slop: jax.Array
                       ) -> jax.Array:
    """Ordered span_near frequency over n width-1 children (host
    mirror: _near_ordered + the set dedupe of envelopes).

    The host recursion emits DISTINCT envelopes (first_start,
    prev_end); with width-1 children an envelope is a (p0, pl) pair
    with p0 in A_0, pl in A_{n-1}, and SOME ascending chain through
    A_1..A_{n-2}. A chain exists iff the GREEDY minimal chain fits
    under pl: x_1 = min{p in A_1 : p >= p0 + 1}, x_{i+1} likewise
    above x_i; pl must exceed x_{n-2}. The window test is the host's
    gap = (pl + 1 - p0) - n <= slop (every child has length 1, so
    len_sum == n). Pads: a _POS_BIG p0 makes every x and the pl > x
    test fail; a _POS_BIG pl fails the window test (slop is a real
    query int, far below the sentinel)."""
    n = pos.shape[2]
    if n == 1:
        return tf[..., 0]
    p0 = pos[:, :, 0, :]                                 # [B, tile, P]
    x = p0
    for i in range(1, n - 1):
        ai = pos[:, :, i, :]
        cand = jnp.where(ai[:, :, None, :] >= x[..., :, None] + 1,
                         ai[:, :, None, :], _POS_BIG)    # [B,tile,P0,P]
        x = cand.min(axis=-1)
    pl = pos[:, :, n - 1, :]
    ok = (pl[:, :, None, :] > x[..., :, None]) \
        & (((pl[:, :, None, :] + 1 - p0[..., :, None]) - n)
           <= slop[:, None, None, None])
    return ok.sum(axis=(-1, -2), dtype=jnp.int32)


def _span_freq_unordered(pos: jax.Array, tf: jax.Array, slop: jax.Array
                         ) -> jax.Array:
    """Unordered span_near frequency over n width-1 children (host
    mirror: _near_unordered + its set dedupe). Pointer sweep: window
    (min start, max start + 1) tested against (hi - lo) - n <= slop,
    then the first pointer holding the earliest start advances. The
    host dedupes via a set; here duplicates of an emitted (lo, hi)
    are provably ADJACENT among emissions (lo is non-decreasing over
    the sweep, and within equal lo the emitted hi is non-decreasing),
    so comparing against the last emitted pair counts exactly the
    distinct windows."""
    b, tile, n, p_width = pos.shape
    if n == 1:
        return tf[..., 0]

    def body(_it, st):
        ptr, freq, last_lo, last_hi = st
        safe = jnp.clip(ptr, 0, p_width - 1)
        starts = jnp.take_along_axis(pos, safe[..., None], axis=-1)[..., 0]
        active = jnp.all(ptr < tf, axis=-1)
        lo = starts.min(axis=-1)
        hi = starts.max(axis=-1) + 1
        win = active & (((hi - lo) - n) <= slop[:, None])
        new = win & ((lo != last_lo) | (hi != last_hi))
        freq = freq + new.astype(jnp.int32)
        last_lo = jnp.where(win, lo, last_lo)
        last_hi = jnp.where(win, hi, last_hi)
        amin = jnp.argmin(starts, axis=-1)
        adv = jnp.arange(n, dtype=jnp.int32)[None, None, :] \
            == amin[..., None]
        ptr = ptr + jnp.where(active[..., None] & adv, 1, 0)
        return ptr, freq, last_lo, last_hi

    st0 = (jnp.zeros((b, tile, n), jnp.int32),
           jnp.zeros((b, tile), jnp.int32),
           jnp.full((b, tile), -1, jnp.int32),
           jnp.full((b, tile), -1, jnp.int32))
    _ptr, freq, _ll, _lh = jax.lax.fori_loop(0, n * p_width, body, st0)
    return freq


def positional_tile_freqs(kind: str, qt: jax.Array, slop: jax.Array,
                          t_tids: jax.Array, t_pos: jax.Array
                          ) -> jax.Array:
    """Phrase/span occurrence counts for one doc tile -> [B, tile]
    i32. kind selects the algorithm (see POSITIONAL_PREFIXES)."""
    head, n, variant = parse_positional_kind(kind)
    per = [_term_positions(t_tids, t_pos, qt[:, i]) for i in range(n)]
    pos = jnp.stack([p for p, _t in per], axis=2)        # [B,tile,n,P]
    tf = jnp.stack([t for _p, t in per], axis=2)         # [B,tile,n]
    if head == "phrase_pos":
        if variant == "e":
            return _phrase_freq_exact(pos, tf)
        return _phrase_freq_sloppy(pos, tf, slop)
    if variant == "o":
        return _span_freq_ordered(pos, tf, slop)
    return _span_freq_unordered(pos, tf, slop)


def positional_impacts(freq: jax.Array, idf_sum: jax.Array,
                       k1ln: jax.Array) -> jax.Array:
    """Phrase frequency -> BM25 impact, op for op the f32 chain of
    search/phrase.phrase_impacts (the byte-identity oracle): freq == 0
    falls out as 0 / (0 + k1ln) = 0 with no masking (k1ln > 0 by
    construction). freq [B, tile] i32, idf_sum [B] f32, k1ln [tile]
    f32 (the pack-time k1 * lnorm column — packed as its own column
    precisely so no compiler can contract a tf + k1*lnorm mul-add
    into an FMA and break host/device identity)."""
    tf32 = freq.astype(jnp.float32)
    num = (idf_sum[:, None] * tf32) * jnp.float32(BM25_K1 + 1.0)
    return num / (tf32 + k1ln[None, :])


def bm25f_tile_scores(fields: tuple, qt: jax.Array, idf: jax.Array,
                      wf: jax.Array, text_tiles: dict, pos_tiles: dict
                      ) -> jax.Array:
    """BM25F scores for one doc tile -> [B, tile] f32, op for op the
    host oracle search/phrase.bm25f_scores (field-then-term f32
    accumulation). Per-field tf comes from the positions column's
    valid-count — identical to the host's pf.tfs because the pack
    stores every occurrence (pos_pack_width admits a field only when
    max tf <= POS_CAP)."""
    b = qt.shape[0]
    nf, nt = qt.shape[1], qt.shape[2]
    tile = pos_tiles[fields[0]][2].shape[0]
    k1_32 = jnp.float32(BM25_K1)
    total = jnp.zeros((b, tile), jnp.float32)
    for ti in range(nt):
        acc = jnp.zeros((b, tile), jnp.float32)
        for fi in range(nf):
            f = fields[fi]
            t_tids, _t_imps = text_tiles[f]
            t_pos, _k1ln, lnorm = pos_tiles[f]
            _pos, tf = _term_positions(t_tids, t_pos, qt[:, fi, ti])
            acc = acc + (wf[:, fi, None] * tf.astype(jnp.float32)) \
                / lnorm[None, :]
        total = total + (idf[:, ti, None] * acc) / (k1_32 + acc)
    return total


def positional_tile_scores(kind: str, field, inp: tuple,
                           text_tiles: dict, pos_tiles: dict
                           ) -> tuple[jax.Array, jax.Array]:
    """(s_leaf [B, tile] f32 with the clause boost applied, m_leaf
    [B, tile] bool) for one positional clause over one doc tile —
    the shared leaf evaluator of bundle_tile_eval, the Pallas kernel
    (interpret reference), and eval_node's unfused path."""
    if positional_prefix(kind) == "bm25f":
        qt, idf, wf, pboost, _msm_c, _boost_c = inp
        raw = bm25f_tile_scores(field, qt, idf, wf, text_tiles,
                                pos_tiles)
        return raw * pboost[:, None], raw > 0.0
    qt, _wb, idf_sum, slop, pboost, _msm_c, _boost_c = inp
    t_tids, _t_imps = text_tiles[field]
    t_pos, k1ln, _lnorm = pos_tiles[field]
    freq = positional_tile_freqs(kind, qt, slop, t_tids, t_pos)
    raw = positional_impacts(freq, idf_sum, k1ln)
    return raw * pboost[:, None], freq > 0


@jax.named_scope("tile_bounds")
def bundle_tile_bounds(clauses: tuple, cl_inputs: tuple, text_cols: dict,
                       num_cols: dict, msm: jax.Array,
                       boost: jax.Array | None
                       ) -> tuple[jax.Array, jax.Array]:
    """Per-tile (can_match [B, J] bool, score bound [B, J] f32) for a
    clause bundle.

    can_match is msm-aware: a tile is matchable only when every
    must/filter clause can possibly match in it (dense: positive bound;
    range: [tile_lo, tile_hi] overlaps [lo, hi]) AND at least msm should
    clauses can. The bound sums the boost-weighted per-clause block-max
    bounds of the scoring clauses (must + should) — a monotone upper
    bound on any doc's post-boost score — and is BOUND_SLACK-inflated
    once more on top of the per-clause inflation to absorb the extra
    adds/muls of the multi-clause combine. Its operations carry the
    name `tile_bounds` in a device trace, apart from the walk's."""
    b = msm.shape[0]
    n_tiles = text_cols[bundle_primary_field(clauses)]["tile_max"].n_tiles
    bound = jnp.zeros((b, n_tiles), jnp.float32)
    possible = jnp.ones((b, n_tiles), bool)
    pos_cnt = jnp.zeros((b, n_tiles), jnp.int32)
    for (role, kind, field, _w), inp in zip(clauses, cl_inputs):
        head = positional_prefix(kind)
        if kind in _DENSE_KINDS:
            qt, wq, msm_c, boost_c = inp
            ub = dense_tile_bounds(text_cols[field]["tile_max"], qt, wq)
            p = ((ub > 0.0) | (msm_c <= 0)[:, None]) & (msm_c <= 1)[:, None]
            if role in ("must", "should"):
                bound = bound + ub * boost_c[:, None]
            if role in ("must", "filter"):
                possible = possible & p
            elif role == "should":
                pos_cnt = pos_cnt + p.astype(jnp.int32)
        elif head in ("phrase_pos", "span_pos"):
            # position-BLIND bound (the tiered pager's host mirror
            # must stay exact without fetching a single tile): a tile
            # missing ANY required term can't match a phrase/span
            # (presence gate, exact: tile_max > 0 iff the term occurs
            # there); a present tile's phrase impact is bounded by
            # Sum_i (idf_sum/idf_i) * tile_max_i — phrase freq <= the
            # pointer sweep's iteration count <= Sum_i tf_i, and the
            # saturation tf/(tf + k1ln) is concave-subadditive, so
            # idf_sum*k1p1*satur(freq) <= Sum_i idf_sum*k1p1*
            # satur(tf_i) = Sum_i wb_i * impact_i. Ordered span freq
            # counts (start, end) PAIRS and can exceed Sum tf_i, so it
            # takes the flat satur < 1 bound idf_sum * (k1 + 1)
            # instead. BOUND_SLACK absorbs the f32 rounding of either
            # chain (real margins dwarf 32 eps: satur's distance from
            # 1 is >= ~1e-4 at POS_CAP'd tfs).
            qt, wb, idf_sum, _slop, pboost, msm_c, boost_c = inp
            tm = text_cols[field]["tile_max"]
            pres = jnp.ones((b, n_tiles), bool)
            for i in range(qt.shape[1]):
                pres = pres & (tile_max_rows(tm, qt[:, i]) > 0.0) \
                    & (qt[:, i] >= 0)[:, None]
            if kind.endswith(":o"):
                ub = jnp.broadcast_to(
                    (idf_sum * jnp.float32(BM25_K1 + 1.0)
                     * jnp.float32(BOUND_SLACK))[:, None], (b, n_tiles))
            else:
                ub = dense_tile_bounds(tm, qt, wb)
            ub = jnp.where(pres, ub, 0.0) * pboost[:, None]
            p = (pres | (msm_c <= 0)[:, None]) & (msm_c <= 1)[:, None]
            if role in ("must", "should"):
                bound = bound + ub * boost_c[:, None]
            if role in ("must", "filter"):
                possible = possible & p
            elif role == "should":
                pos_cnt = pos_cnt + p.astype(jnp.int32)
        elif head == "bm25f":
            # per-term any-field presence; a present term's saturated
            # contribution idf_t * acc / (k1 + acc) is < idf_t, so the
            # tile bound is the presence-gated idf sum
            qt, idf, _wf, pboost, msm_c, boost_c = inp
            nf, nt = qt.shape[1], qt.shape[2]
            ub = jnp.zeros((b, n_tiles), jnp.float32)
            p_any = jnp.zeros((b, n_tiles), bool)
            for t in range(nt):
                pres_t = jnp.zeros((b, n_tiles), bool)
                for fi in range(nf):
                    tm = text_cols[field[fi]]["tile_max"]
                    pres_t = pres_t | (
                        (tile_max_rows(tm, qt[:, fi, t]) > 0.0)
                        & (qt[:, fi, t] >= 0)[:, None])
                ub = ub + jnp.where(pres_t, idf[:, t][:, None], 0.0)
                p_any = p_any | pres_t
            ub = ub * jnp.float32(BOUND_SLACK) * pboost[:, None]
            p = (p_any | (msm_c <= 0)[:, None]) & (msm_c <= 1)[:, None]
            if role in ("must", "should"):
                bound = bound + ub * boost_c[:, None]
            if role in ("must", "filter"):
                possible = possible & p
            elif role == "should":
                pos_cnt = pos_cnt + p.astype(jnp.int32)
        elif kind in _VEC_KINDS:
            # vector clause: the executor supplies the EXACT per-tile
            # bound (max of the similarity column, slack-inflated);
            # can-match is "some doc in the tile carries a vector"
            _col, v_exists, ub = inp
            tile = v_exists.shape[0] // n_tiles
            p = jnp.broadcast_to(
                v_exists.reshape(n_tiles, tile).any(axis=1)[None, :],
                (b, n_tiles))
            bound = bound + ub
            if role == "must":
                possible = possible & p
            else:                           # should
                pos_cnt = pos_cnt + p.astype(jnp.int32)
        elif role != "must_not":            # range mask (no bound to
            lo, hi = inp                    # prune on for exclusions)
            tl = num_cols[field]["tile_lo"]
            th = num_cols[field]["tile_hi"]
            possible = possible & ((tl[None, :] <= hi[:, None])
                                   & (th[None, :] >= lo[:, None]))
    can_match = possible & (pos_cnt >= msm[:, None])
    if boost is not None:
        bound = bound * boost[:, None]
    # combine slack, sign-guarded: dense/range bounds are nonnegative
    # (identical behavior), but a vector clause's bound can be
    # negative (dot_product on non-unit vectors) — scaling a negative
    # total up would lower it below the true tile max
    return can_match, jnp.where(bound >= 0.0,
                                bound * jnp.float32(BOUND_SLACK),
                                bound / jnp.float32(BOUND_SLACK))


def bundle_tile_bounds_np(clauses: tuple, cl_inputs: tuple,
                          text_tile_max: dict, num_extrema: dict,
                          msm: np.ndarray, boost: np.ndarray | None
                          ) -> tuple[np.ndarray, np.ndarray]:
    """HOST mirror of bundle_tile_bounds — the tiered-residency pager's
    survivor oracle (index/tiering.py): it must decide, BEFORE any tile
    is fetched, exactly which tiles the device walk could possibly
    match in. Keep op-for-op in lockstep with bundle_tile_bounds above.

    Exactness of the can_match half (the only half correctness rides
    on): per-clause ub sums nonnegative f32 products, so `ub > 0` is
    order-independent and bit-agrees with any compilation of the device
    sum — a product is positive iff both factors are (identical IEEE
    semantics host and device, including underflow-to-zero), and
    nonnegative addends cannot cancel. Range-overlap and msm tests are
    exact integer/ordered comparisons on the same build_tile_minmax
    numbers the device reads. The bound half inherits the same
    BOUND_SLACK inflation and is advisory (fetch ordering), never a
    correctness input."""
    b = msm.shape[0]
    field0 = bundle_primary_field(clauses)
    n_tiles = text_tile_max[field0].n_tiles
    bound = np.zeros((b, n_tiles), np.float32)
    possible = np.ones((b, n_tiles), bool)
    pos_cnt = np.zeros((b, n_tiles), np.int32)
    for (role, kind, field, _w), inp in zip(clauses, cl_inputs):
        head = positional_prefix(kind)
        if kind in _VEC_KINDS:
            # the vector clause's bound is a DEVICE product (the
            # similarity column matmul) — there is nothing to mirror
            # host-side, so the tiered pager must decline knn bundles
            # (executor admission does; this is the backstop)
            raise ValueError("knn_vec bundles have no host bound mirror")
        if head in ("phrase_pos", "span_pos"):
            # position-BLIND by design (see bundle_tile_bounds): the
            # presence gate reads only tile_max, which the pager holds
            # resident — no position tile is touched before the
            # survivor decision, and the exactness argument is the
            # dense one (tile_max > 0 is order-independent in f32)
            qt, wb, idf_sum, _slop, pboost, msm_c, boost_c = (
                np.asarray(x) for x in inp)
            tm = text_tile_max[field]
            safe = np.clip(qt, 0, max(len(tm.start) - 2, 0))
            pres = np.ones((b, n_tiles), bool)
            for i in range(qt.shape[1]):
                pres = pres & (tm.rows(safe[:, i]) > 0.0) \
                    & (qt[:, i] >= 0)[:, None]
            if kind.endswith(":o"):
                ub = np.broadcast_to(
                    (idf_sum.astype(np.float32)
                     * np.float32(BM25_K1 + 1.0)
                     * np.float32(BOUND_SLACK))[:, None],
                    (b, n_tiles)).astype(np.float32)
            else:
                ub = np.zeros((b, n_tiles), np.float32)
                for i in range(qt.shape[1]):
                    w = np.where(qt[:, i] >= 0, wb[:, i],
                                 np.float32(0.0)).astype(np.float32)
                    ub = ub + tm.rows(safe[:, i]) * w[:, None]
                ub = ub * np.float32(BOUND_SLACK)
            ub = np.where(pres, ub, np.float32(0.0)) \
                * pboost[:, None].astype(np.float32)
            p = (pres | (msm_c <= 0)[:, None]) & (msm_c <= 1)[:, None]
            if role in ("must", "should"):
                bound = bound + ub * boost_c[:, None].astype(np.float32)
            if role in ("must", "filter"):
                possible = possible & p
            elif role == "should":
                pos_cnt = pos_cnt + p.astype(np.int32)
            continue
        if head == "bm25f":
            qt, idf, _wf, pboost, msm_c, boost_c = (
                np.asarray(x) for x in inp)
            nf, nt = qt.shape[1], qt.shape[2]
            ub = np.zeros((b, n_tiles), np.float32)
            p_any = np.zeros((b, n_tiles), bool)
            for t in range(nt):
                pres_t = np.zeros((b, n_tiles), bool)
                for fi in range(nf):
                    tm = text_tile_max[field[fi]]
                    safe = np.clip(qt[:, fi, t], 0,
                                   max(len(tm.start) - 2, 0))
                    pres_t = pres_t | ((tm.rows(safe) > 0.0)
                                       & (qt[:, fi, t] >= 0)[:, None])
                ub = ub + np.where(pres_t, idf[:, t][:, None],
                                   np.float32(0.0))
                p_any = p_any | pres_t
            ub = (ub * np.float32(BOUND_SLACK)
                  * pboost[:, None].astype(np.float32))
            p = (p_any | (msm_c <= 0)[:, None]) & (msm_c <= 1)[:, None]
            if role in ("must", "should"):
                bound = bound + ub * boost_c[:, None].astype(np.float32)
            if role in ("must", "filter"):
                possible = possible & p
            elif role == "should":
                pos_cnt = pos_cnt + p.astype(np.int32)
            continue
        if kind in _DENSE_KINDS:
            qt, wq, msm_c, boost_c = (np.asarray(x) for x in inp)
            tm = text_tile_max[field]
            safe = np.clip(qt, 0, max(len(tm.start) - 2, 0))
            ub = np.zeros((b, n_tiles), np.float32)
            for q in range(qt.shape[1]):
                w = np.where(qt[:, q] >= 0, wq[:, q],
                             np.float32(0.0)).astype(np.float32)
                ub = ub + tm.rows(safe[:, q]) * w[:, None]
            ub = ub * np.float32(BOUND_SLACK)
            p = ((ub > 0.0) | (msm_c <= 0)[:, None]) \
                & (msm_c <= 1)[:, None]
            if role in ("must", "should"):
                bound = bound + ub * boost_c[:, None].astype(np.float32)
            if role in ("must", "filter"):
                possible = possible & p
            elif role == "should":
                pos_cnt = pos_cnt + p.astype(np.int32)
        elif role != "must_not":
            lo, hi = (np.asarray(x) for x in inp)
            tl, th = num_extrema[field]
            possible = possible & ((tl[None, :] <= hi[:, None])
                                   & (th[None, :] >= lo[:, None]))
    can_match = possible & (pos_cnt >= np.asarray(msm)[:, None])
    if boost is not None:
        bound = bound * np.asarray(boost)[:, None].astype(np.float32)
    # sign-guarded combine slack — kept op-for-op with the device
    # version above (a no-op for the nonnegative dense/range bounds
    # this mirror actually serves; knn bundles raise earlier)
    return can_match, np.where(bound >= 0.0,
                               bound * np.float32(BOUND_SLACK),
                               bound / np.float32(BOUND_SLACK)
                               ).astype(np.float32)


def bundle_tile_eval(clauses: tuple, cl_inputs: tuple, text_tiles: dict,
                     num_tiles: dict, msm: jax.Array,
                     boost: jax.Array | None, t_live: jax.Array,
                     vec_tiles: dict | None = None,
                     pos_tiles: dict | None = None
                     ) -> tuple[jax.Array, jax.Array]:
    """Evaluate a clause bundle over one doc tile -> (score [B, tile]
    post-boost, match [B, tile] incl. live). Accumulation mirrors
    eval_node's bool branch op for op (must scores, then should scores;
    where-masked adds; nested wrapper boost before the parent add; outer
    boost last) so scores stay bit-identical to the unfused path.
    `vec_tiles[ci]` = (col [B, tile], exists [tile]) — this tile's
    slice of clause ci's precomputed similarity column (same numbers
    eval_node's knn_vec leaf reads, so hybrid scores stay identical).
    `pos_tiles[field]` = (t_pos [tile, L*P], k1ln [tile], lnorm
    [tile]) — this tile's slice of the positions column family, for
    the positional clause kinds."""
    b = msm.shape[0]
    tile = t_live.shape[0]
    score = jnp.zeros((b, tile), jnp.float32)
    must_ok = jnp.ones((b, tile), bool)
    not_any = jnp.zeros((b, tile), bool)
    cnt = jnp.zeros((b, tile), jnp.int32)
    for ci, ((role, kind, field, _w), inp) in enumerate(
            zip(clauses, cl_inputs)):
        if kind in _DENSE_KINDS:
            qt, wq, msm_c, boost_c = inp
            t_tids, t_imps = text_tiles[field]
            s_leaf = _dense_tile_scores(t_tids, t_imps, qt, wq)
            m_leaf = s_leaf > 0.0
            # single-should wrapper semantics (exact: for unwrapped
            # clauses msm_c = 1 / boost_c = 1 reduce to m_leaf / s_leaf)
            m = (m_leaf | (msm_c <= 0)[:, None]) & (msm_c <= 1)[:, None]
            s = jnp.where(m_leaf, s_leaf, 0.0) * boost_c[:, None]
        elif positional_prefix(kind):
            s_leaf, m_leaf = positional_tile_scores(
                kind, field, inp, text_tiles, pos_tiles)
            msm_c, boost_c = inp[-2], inp[-1]
            m = (m_leaf | (msm_c <= 0)[:, None]) & (msm_c <= 1)[:, None]
            s = jnp.where(m_leaf, s_leaf, 0.0) * boost_c[:, None]
        elif kind in _VEC_KINDS:
            t_col, t_exists = vec_tiles[ci]
            m = jnp.broadcast_to(t_exists[None, :], (b, tile))
            s = t_col                        # boost already folded in
        else:
            lo, hi = inp
            t_vals, t_exists = num_tiles[field]
            m = ((t_vals[None, :] >= lo[:, None])
                 & (t_vals[None, :] <= hi[:, None]) & t_exists[None, :])
            s = None                         # mask-only roles
        if role == "must":
            score = score + jnp.where(m, s, 0.0)
            must_ok = must_ok & m
        elif role == "filter":
            must_ok = must_ok & m
        elif role == "must_not":
            not_any = not_any | m
        else:
            score = score + jnp.where(m, s, 0.0)
            cnt = cnt + m.astype(jnp.int32)
    match = must_ok & (~not_any) & (cnt >= msm[:, None]) & t_live[None, :]
    if boost is not None:
        score = score * boost[:, None]
    return score, match


def bundle_tile_match(clauses: tuple, cl_inputs: tuple, text_tiles: dict,
                      num_tiles: dict, msm: jax.Array, t_live: jax.Array,
                      vec_tiles: dict | None = None,
                      pos_tiles: dict | None = None) -> jax.Array:
    """Mask-only bundle_tile_eval: the match mask [B, tile] of one doc
    tile WITHOUT the weighted score accumulation — the k == 0
    (filtered / size-0 agg) pass, where the score matrix is never
    consumed.

    Exactness: a dense clause's unfused match is `score > 0`, where
    score sums (impact * weight) over the doc's matching term slots.
    Impacts of real postings are strictly positive (BM25 idf > 0,
    tf-norm > 0) and clause weights are clamped positive at bind time,
    so `score > 0` is EQUIVALENT to "some query term (qt >= 0) is
    present in a slot with positive impact" — which is what this
    membership test computes, minus the FMA work."""
    b = msm.shape[0]
    tile = t_live.shape[0]
    must_ok = jnp.ones((b, tile), bool)
    not_any = jnp.zeros((b, tile), bool)
    cnt = jnp.zeros((b, tile), jnp.int32)
    for ci, ((role, kind, field, _w), inp) in enumerate(
            zip(clauses, cl_inputs)):
        if kind in _VEC_KINDS:
            _t_col, t_exists = vec_tiles[ci]
            m = jnp.broadcast_to(t_exists[None, :], (b, tile))
            if role in ("must", "filter"):
                must_ok = must_ok & m
            elif role == "must_not":
                not_any = not_any | m
            else:
                cnt = cnt + m.astype(jnp.int32)
            continue
        head = positional_prefix(kind)
        if head == "bm25f":
            # bm25f match is `score > 0`, and a term's saturated
            # contribution is positive iff some field carries the term
            # with a positive tf (weights/idf are bind-clamped > 0) —
            # so the mask is the dense membership test OR-reduced over
            # (field, term), no position decode needed
            qt, _idf, _wf, _pboost, msm_c, _boost_c = inp
            nf, nt = qt.shape[1], qt.shape[2]
            m_leaf = jnp.zeros((b, tile), bool)
            for fi in range(nf):
                t_tids, t_imps = text_tiles[field[fi]]
                present = t_imps > 0.0
                for t in range(nt):
                    tq = qt[:, fi, t][:, None, None]
                    hit = jnp.any((t_tids[None] == tq) & present[None],
                                  axis=-1)
                    m_leaf = m_leaf | (hit
                                       & (qt[:, fi, t] >= 0)[:, None])
            m = (m_leaf | (msm_c <= 0)[:, None]) & (msm_c <= 1)[:, None]
        elif head:
            # phrase/span match requires the occurrence count — there
            # is no cheaper exact test than running the adjacency
            qt, _wb, _idf_sum, slop, _pb, msm_c, _boost_c = inp
            t_tids, _t_imps = text_tiles[field]
            t_pos, _k1ln, _lnorm = pos_tiles[field]
            freq = positional_tile_freqs(kind, qt, slop, t_tids, t_pos)
            m_leaf = freq > 0
            m = (m_leaf | (msm_c <= 0)[:, None]) & (msm_c <= 1)[:, None]
        elif kind in _DENSE_KINDS:
            qt, _wq, msm_c, _boost_c = inp
            t_tids, t_imps = text_tiles[field]
            present = t_imps > 0.0                   # [tile, L]
            m_leaf = jnp.zeros((b, tile), bool)
            for q in range(qt.shape[1]):
                tq = qt[:, q][:, None, None]         # [B, 1, 1]
                hit = jnp.any((t_tids[None] == tq) & present[None],
                              axis=-1)
                m_leaf = m_leaf | (hit & (qt[:, q] >= 0)[:, None])
            # single-should wrapper semantics (see bundle_tile_eval)
            m = (m_leaf | (msm_c <= 0)[:, None]) & (msm_c <= 1)[:, None]
        else:
            lo, hi = inp
            t_vals, t_exists = num_tiles[field]
            m = ((t_vals[None, :] >= lo[:, None])
                 & (t_vals[None, :] <= hi[:, None]) & t_exists[None, :])
        if role in ("must", "filter"):
            must_ok = must_ok & m
        elif role == "must_not":
            not_any = not_any | m
        else:
            cnt = cnt + m.astype(jnp.int32)
    return must_ok & (~not_any) & (cnt >= msm[:, None]) & t_live[None, :]


# ---------------------------------------------------------------------------
# Stepped tile loop (resident query loop, see search/resident.py)
#
# A `step` argument — (chunk_tiles, init_state, check) — reshapes the
# single fori_loop over tiles into an outer loop over CHUNKS of
# chunk_tiles tiles. `check(chunk_idx, state) -> (timed_out, state)`
# runs once per chunk (the executor wires an io_callback that polls the
# host clock against the dispatch deadline and meters injected
# straggler delay); once it reports timed_out the remaining chunks'
# tile work is skipped entirely, so a laggard step EXITS EARLY instead
# of burning the rest of its tile walk — the preemptive device-side
# timeout. With step=None the original single loop runs: the composed
# chunked loop visits tiles in the identical order, so un-timed results
# are bit-identical either way. The Pallas engine honors the SAME step
# contract (ops/pallas_scoring.fused_topk_bundle_pallas /
# match_mask_bundle_pallas): there the chunks are separate pallas_call
# invocations with the running threshold threaded through a [B, 1]
# in/out pair, and `check` runs between kernels — one contract, two
# engines, so the resident loop and the mesh swap engines freely.
# ---------------------------------------------------------------------------


def _stepped_tile_loop(n_tiles: int, body, st0, step):
    """fori(0, n_tiles, body, st0), optionally chunked with a per-chunk
    step check. Returns (state, timed_out bool scalar | None). A body
    that walks several tiles an iteration passes its iteration count
    and a chunk size in iterations."""
    if step is None:
        return jax.lax.fori_loop(0, n_tiles, body, st0), None
    chunk_tiles, ck0, check = step
    n_chunks = -(-n_tiles // chunk_tiles)

    def chunk_body(c, outer):
        st, ck, _t = outer
        timed, ck = check(c, ck)
        st = jax.lax.cond(
            timed, lambda s: s,
            lambda s: jax.lax.fori_loop(
                c * chunk_tiles,
                jnp.minimum((c + 1) * chunk_tiles, n_tiles), body, s),
            st)
        return st, ck, timed

    st, ck, timed = jax.lax.fori_loop(
        0, n_chunks, chunk_body, (st0, ck0, jnp.bool_(False)))
    # one FINAL check after the last chunk: a deadline expiring during
    # the last chunk's work (or the only chunk's, at n_chunks == 1)
    # must still report timed_out — the resident caller skips the
    # cooperative collect-boundary check on the strength of this
    # verdict, so the device must cover the whole walk, not all-but-
    # the-end of it
    final, _ck = check(n_chunks, ck)
    return st, timed | final


def match_mask_bundle_fused(text_cols: dict, num_cols: dict,
                            clauses: tuple, cl_inputs: tuple,
                            msm: jax.Array, boost: jax.Array | None,
                            live: jax.Array, emit_match: bool = True,
                            step=None):
    """Fused match-mask-only pass over a clause bundle — the k == 0
    engine (size-0 counts and filtered aggregation plans), which skips
    the score matrix AND the top-k selection entirely.

    Returns (total [B] int32, prune_stats int32 [3] = (hard_skipped,
    0, tiles_examined)) plus, when emit_match, the exact match mask
    [B, cap] bool for a downstream aggregation pass. Hard-skipping on
    the msm-aware can_match is exact: a skipped tile provably contains
    no matching doc, so its mask rows stay zero. A `step` (see
    _stepped_tile_loop) appends the timed_out scalar to the result."""
    field0 = bundle_primary_field(clauses)
    n_tiles = text_cols[field0]["tile_max"].n_tiles
    cap = live.shape[0]
    tile = cap // n_tiles
    b = msm.shape[0]
    can_match, _ub = bundle_tile_bounds(clauses, cl_inputs, text_cols,
                                        num_cols, msm, boost)
    text_fields = bundle_text_fields(clauses)
    pos_fields = bundle_pos_fields(clauses)
    num_fields = tuple(dict.fromkeys(
        f for _r, kd, f, _w in clauses if kd in RANGE_CLAUSE_KINDS))
    vec_idx = tuple(i for i, (_r, kd, _f, _w) in enumerate(clauses)
                    if kd in _VEC_KINDS)

    def body(j, st):
        lo = j * tile
        can_j = jax.lax.dynamic_slice_in_dim(can_match, j, 1, axis=1)[:, 0]

        def hard_skip(st):
            return (st[0], st[1] + jnp.array([1, 0, 1], jnp.int32)) + st[2:]

        def eval_tile(st):
            total, pruned = st[:2]
            text_tiles = {
                f: (jax.lax.dynamic_slice(
                        text_cols[f]["fwd_tids"], (lo, 0),
                        (tile, text_cols[f]["fwd_tids"].shape[1])),
                    jax.lax.dynamic_slice(
                        text_cols[f]["fwd_imps"], (lo, 0),
                        (tile, text_cols[f]["fwd_imps"].shape[1])))
                for f in text_fields}
            pos_tiles = {
                f: (jax.lax.dynamic_slice(
                        text_cols[f]["fwd_pos"], (lo, 0),
                        (tile, text_cols[f]["fwd_pos"].shape[1])),
                    jax.lax.dynamic_slice(text_cols[f]["k1ln"], (lo,),
                                          (tile,)),
                    jax.lax.dynamic_slice(text_cols[f]["lnorm"], (lo,),
                                          (tile,)))
                for f in pos_fields}
            num_tiles = {
                f: (jax.lax.dynamic_slice(num_cols[f]["values"], (lo,),
                                          (tile,)),
                    jax.lax.dynamic_slice(num_cols[f]["exists"], (lo,),
                                          (tile,)))
                for f in num_fields}
            vec_tiles = {
                i: (jax.lax.dynamic_slice(cl_inputs[i][0], (0, lo),
                                          (b, tile)),
                    jax.lax.dynamic_slice(cl_inputs[i][1], (lo,),
                                          (tile,)))
                for i in vec_idx}
            t_live = jax.lax.dynamic_slice(live, (lo,), (tile,))
            match = bundle_tile_match(clauses, cl_inputs, text_tiles,
                                      num_tiles, msm, t_live,
                                      vec_tiles=vec_tiles,
                                      pos_tiles=pos_tiles)
            total = total + match.sum(axis=-1, dtype=jnp.int32)
            pruned = pruned + jnp.array([0, 0, 1], jnp.int32)
            out = (total, pruned)
            if emit_match:
                out = out + (jax.lax.dynamic_update_slice(
                    st[2], match, (0, lo)),)
            return out

        return jax.lax.cond(jnp.any(can_j), eval_tile, hard_skip, st)

    st0 = (jnp.zeros((b,), jnp.int32), jnp.zeros((3,), jnp.int32))
    if emit_match:
        st0 = st0 + (jnp.zeros((b, cap), bool),)
    st, timed = _stepped_tile_loop(n_tiles, body, st0, step)
    out = st if emit_match else st[:2]
    return out if timed is None else out + (timed,)


# most tiles one iteration of the XLA top-k walk scores: an iteration
# costs the chip 15-20 us whatever it scores (a conditional, the slices,
# the candidate merge), so a walk of 256 one-tile steps was mostly that
WALK_STEP_MAX = 16


def walk_step_tiles(n_tiles: int, chunk_tiles: int | None = None) -> int:
    """Tiles one step of score_topk_bundle_fused scores, from shapes
    alone: the largest power of two up to min(WALK_STEP_MAX,
    n_tiles // 16) that divides n_tiles and, under a resident `step`,
    its chunk_tiles (a chunk then ends on a step). 16 at 256 tiles, 4
    at 64, 1 for packs of 16 tiles or fewer. The breaker's estimate
    (search/executor._fused_row_elems) sizes from the same number."""
    most = min(WALK_STEP_MAX, n_tiles // 16)
    g = 1
    while (2 * g <= most and n_tiles % (2 * g) == 0
           and (chunk_tiles is None or chunk_tiles % (2 * g) == 0)):
        g *= 2
    return g


def score_topk_bundle_fused(text_cols: dict, num_cols: dict, clauses: tuple,
                            cl_inputs: tuple, msm: jax.Array,
                            boost: jax.Array | None, live: jax.Array,
                            k: int, emit_match: bool = False,
                            step=None, init_topk=None, idx_offset: int = 0,
                            step_tiles: int | None = None):
    """Fused block-max-WAND score + top-k over a bool clause bundle.

    Returns (top_scores [B, k], top_idx [B, k], total [B] int32,
    prune_stats int32 [3] = (hard_skipped, thresholded, tiles_examined))
    plus, when emit_match, the exact match mask [B, cap] bool (incl.
    live) for a downstream aggregation pass — hard-skipped tiles keep
    their zeros, which is exact because a hard skip means no doc there
    can match. Entries past a query's total are -inf with undefined
    indices — the top_k_hits contract.

    Selection happens on POST-boost scores computed in eval_node's exact
    op order, so doc ids and tie order are identical to the unfused
    full-matrix path for ANY positive boosts (the PR 1 pre-boost
    selection caveat is gone). Correct pruning relies on the
    forward-index invariant that a doc's slots hold DISTINCT term ids.
    A `step` (see _stepped_tile_loop) appends the timed_out scalar to
    the result tuple.

    `init_topk` seeds the running top-k state with an EARLIER walk's
    (top_s, top_i) and `idx_offset` shifts this walk's doc indices —
    together they chain base + delta packs (streaming write path) into
    ONE selection: the base walk's k-th best becomes the delta walk's
    opening threshold (its tiles prune against it, exactly as base
    tiles prune against each other), candidates merge through the same
    running_topk_merge (existing state concatenated first, so base docs
    win ties — the (segment order, doc id) tie rule), and the merged
    result equals a per-segment top-k union truncated host-side,
    byte-for-byte. Totals/prune stats cover ONLY this walk.

    The walk takes `step_tiles` tiles a step (walk_step_tiles of the
    pack's shapes where None). Bounds stay per tile: a step is skipped
    when none of its tiles can match for any query, and the prune stats
    count tiles as a walk of one tile a step does (a skipped step's
    tiles are hard skips; a scored step's tile whose bound beats no
    query's k-th best at the step's start is thresholded). A scored
    step extracts candidates only where some match scores strictly
    above its query's k-th best: passages come in increasing order, so
    a later one that ties the k-th best loses the tie anyway, and the
    merge would leave the state as it is.
    """
    field0 = bundle_primary_field(clauses)
    n_tiles = text_cols[field0]["tile_max"].n_tiles
    cap = live.shape[0]
    tile = cap // n_tiles
    b = msm.shape[0]
    k = min(k, cap) if init_topk is None else init_topk[0].shape[1]
    g = step_tiles or walk_step_tiles(
        n_tiles, None if step is None else step[0])
    slab = g * tile
    ck = min(k, slab)
    can_match, ub = bundle_tile_bounds(clauses, cl_inputs, text_cols,
                                       num_cols, msm, boost)
    can_match = can_match.reshape(b, n_tiles // g, g)
    ub = ub.reshape(b, n_tiles // g, g)
    text_fields = bundle_text_fields(clauses)
    pos_fields = bundle_pos_fields(clauses)
    num_fields = tuple(dict.fromkeys(
        f for _r, kd, f, _w in clauses if kd in RANGE_CLAUSE_KINDS))
    vec_idx = tuple(i for i, (_r, kd, _f, _w) in enumerate(clauses)
                    if kd in _VEC_KINDS)

    def body(j, st):
        lo = j * slab
        can_j = jax.lax.dynamic_index_in_dim(can_match, j, axis=1,
                                             keepdims=False)
        ub_j = jax.lax.dynamic_index_in_dim(ub, j, axis=1, keepdims=False)

        def hard_skip(st):
            return st[:3] + (st[3] + jnp.array([g, 0, g], jnp.int32),) \
                + st[4:]

        def score_step(st):
            top_s, top_i, total, pruned = st[:4]
            text_tiles = {
                f: (jax.lax.dynamic_slice(
                        text_cols[f]["fwd_tids"], (lo, 0),
                        (slab, text_cols[f]["fwd_tids"].shape[1])),
                    jax.lax.dynamic_slice(
                        text_cols[f]["fwd_imps"], (lo, 0),
                        (slab, text_cols[f]["fwd_imps"].shape[1])))
                for f in text_fields}
            pos_tiles = {
                f: (jax.lax.dynamic_slice(
                        text_cols[f]["fwd_pos"], (lo, 0),
                        (slab, text_cols[f]["fwd_pos"].shape[1])),
                    jax.lax.dynamic_slice(text_cols[f]["k1ln"], (lo,),
                                          (slab,)),
                    jax.lax.dynamic_slice(text_cols[f]["lnorm"], (lo,),
                                          (slab,)))
                for f in pos_fields}
            num_tiles = {
                f: (jax.lax.dynamic_slice(num_cols[f]["values"], (lo,),
                                          (slab,)),
                    jax.lax.dynamic_slice(num_cols[f]["exists"], (lo,),
                                          (slab,)))
                for f in num_fields}
            vec_tiles = {
                i: (jax.lax.dynamic_slice(cl_inputs[i][0], (0, lo),
                                          (b, slab)),
                    jax.lax.dynamic_slice(cl_inputs[i][1], (lo,),
                                          (slab,)))
                for i in vec_idx}
            t_live = jax.lax.dynamic_slice(live, (lo,), (slab,))
            score, match = bundle_tile_eval(clauses, cl_inputs, text_tiles,
                                            num_tiles, msm, boost, t_live,
                                            vec_tiles=vec_tiles,
                                            pos_tiles=pos_tiles)
            total = total + match.sum(axis=-1, dtype=jnp.int32)
            kth = top_s[:, -1]
            bound_beats = jnp.any(can_j & (ub_j > kth[:, None]), axis=0)

            def merge(args):
                ts, ti = args
                cand = jnp.where(match, score, NEG_INF)
                c_s, c_loc = jax.lax.top_k(cand, ck)
                return running_topk_merge(ts, ti, c_s,
                                          c_loc + lo + idx_offset)

            beats = jnp.any(match & (score > kth[:, None]))
            top_s, top_i = jax.lax.cond(beats, merge, lambda a: a,
                                        (top_s, top_i))
            thresholded = g - bound_beats.sum(dtype=jnp.int32)
            pruned = pruned + jnp.stack(
                [jnp.int32(0), thresholded, jnp.int32(g)])
            out = (top_s, top_i, total, pruned)
            if emit_match:
                out = out + (jax.lax.dynamic_update_slice(
                    st[4], match, (0, lo)),)
            return out

        return jax.lax.cond(jnp.any(can_j), score_step, hard_skip, st)

    top_s0, top_i0 = (running_topk_init(b, k) if init_topk is None
                      else init_topk)
    st0 = (top_s0, top_i0, jnp.zeros((b,), jnp.int32),
           jnp.zeros((3,), jnp.int32))
    if emit_match:
        st0 = st0 + (jnp.zeros((b, cap), bool),)
    if step is not None:
        step = (step[0] // g,) + tuple(step[1:])
    st, timed = _stepped_tile_loop(n_tiles // g, body, st0, step)
    out = st if emit_match else st[:4]
    return out if timed is None else out + (timed,)


def score_topk_dense_fused(fwd_tids: jax.Array, fwd_imps: jax.Array,
                           tile_max: TileSummary, qt: jax.Array,
                           wq: jax.Array, live: jax.Array, k: int,
                           msm: jax.Array | None = None,
                           boost: jax.Array | None = None
                           ) -> tuple[jax.Array, jax.Array, jax.Array,
                                      jax.Array]:
    """Single-dense-clause entry (PR 1 signature), now a thin wrapper
    over the bundle engine: one should clause whose enclosing bool node
    contributes the dynamic msm/boost. Unlike PR 1, boost is applied
    BEFORE selection in eval_node's exact op order, so doc ids and ties
    match the unfused path for any boost > 0."""
    b = qt.shape[0]
    if msm is None:
        msm = jnp.ones((b,), jnp.int32)
    clauses = (("should", "terms_dense", "f", False),)
    cl_inputs = ((qt, wq, jnp.ones((b,), jnp.int32),
                  jnp.ones((b,), jnp.float32)),)
    text_cols = {"f": {"fwd_tids": fwd_tids, "fwd_imps": fwd_imps,
                       "tile_max": tile_max}}
    return score_topk_bundle_fused(text_cols, {}, clauses, cl_inputs,
                                   msm, boost, live, k)
