"""Device-side pack-build programs: sort / segment / scatter / reduce.

The heavy half of pack build (index/devbuild.py is the host driver):
postings construction over a tokenized batch is a stable sort by
(term-id, doc) followed by segment boundaries, cumulative sums and a
handful of scatters — exactly the shape that parallelizes on the mesh
("The Performance Envelope of Inverted Indexing on Modern Hardware"),
and the eager-impact layout the read path wants is what the scatters
emit directly (the BM25S observation).

Exactness contract — the reason a device-built pack can share
fingerprint-keyed caches, the autotune store and resident entries with
a host-built one: every program here performs only EXACT operations —

  * integer stable argsorts (the two-pass idiom below ≡ np.lexsort),
  * segment boundaries + integer cumulative sums,
  * scatter-set with unique target indices (pads dropped out of
    bounds), scatter-add of integers,
  * scatter-max / min-max reductions of f32 (order-free),
  * gathers.

No float arithmetic whose result could depend on association order or
on the backend's libm runs on device. The one float computation of
pack build — eager BM25 impacts — deliberately stays in the canonical
host path (`segment._flat_impacts`): XLA's exp/log differ from
numpy's in the last ulp, and the identity contract is bit-for-bit.
Consequence: the same programs are byte-identical on EVERY backend,
including the JAX_PLATFORMS=cpu fallback the tier-1 suite runs under.

Shape discipline: callers pad every input to pow2 buckets
(`batch_cap` occurrences, `term_cap`/`vocab_buckets` vocabulary,
`cap` docs, `n_slots` forward lanes) so builder shapes don't thrash
XLA — the same next_pow2 convention as the read path. Pad elements
carry sort keys that order AFTER every real element (INT32_MAX) or
scatter indices that land out of bounds (dropped by mode="drop";
always padded POSITIVE-side — jnp wraps negative indices).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# block lane width — keep in sync with index/segment.BLOCK (not
# imported: ops modules stay import-light so index can lazy-load them)
BLOCK = 128


def lexsort_by_term_doc(tid: jnp.ndarray, doc: jnp.ndarray) -> jnp.ndarray:
    """Permutation sorting occurrences by (term-id, doc), stably.

    Two-pass stable argsort ≡ np.lexsort((doc, tid)) — composing a
    stable sort on the minor key with one on the major key avoids the
    int64 fused key (tid * cap + doc), which would overflow int32 on
    non-x64 jax. Stability preserves token order within each
    (term, doc) group, which is what keeps position lists byte-equal
    to the host builder's per-doc accumulation order.
    """
    order = jnp.argsort(doc, stable=True)
    return order[jnp.argsort(tid[order], stable=True)]


@partial(jax.jit, static_argnames=("batch_cap", "vocab_buckets"))
def sort_segment_postings(tid: jnp.ndarray, doc: jnp.ndarray,
                          pos: jnp.ndarray, *, batch_cap: int,
                          vocab_buckets: int):
    """Sort one field's occurrence stream and segment it into postings.

    Inputs are [batch_cap] int32 (the static pins every shape in the
    program — one compile per pow2 bucket), padded with
    tid = doc = INT32_MAX so pads sort to the tail (they collapse into
    one trailing pseudo posting the host slices off). Returns

      pos_s  [batch_cap] positions in CSR order (== pos_data stream),
      tf     [batch_cap] occurrences per posting (position counts),
      df     [vocab_buckets] postings per term (int, exact),
      p_tid  [batch_cap] term id per posting,
      p_doc  [batch_cap] doc id per posting (== doc_ids stream).

    Postings are numbered by first occurrence in the sorted stream, so
    posting order is (term asc, doc asc) — the host CSR order.
    """
    order = lexsort_by_term_doc(tid, doc)
    tid_s = tid[order]
    doc_s = doc[order]
    pos_s = pos[order]
    idx = jnp.arange(batch_cap, dtype=jnp.int32)
    newseg = (idx == 0) | (tid_s != jnp.roll(tid_s, 1)) \
        | (doc_s != jnp.roll(doc_s, 1))
    seg = newseg.astype(jnp.int32)
    pid = jnp.cumsum(seg) - 1
    tf = jnp.zeros(batch_cap, jnp.int32).at[pid].add(
        jnp.ones_like(pid))
    # pads carry tid INT32_MAX >= vocab_buckets — dropped
    df = jnp.zeros(vocab_buckets, jnp.int32).at[tid_s].add(
        seg, mode="drop")
    # every occurrence of a posting writes the same value: exact
    p_tid = jnp.zeros(batch_cap, jnp.int32).at[pid].set(tid_s)
    p_doc = jnp.zeros(batch_cap, jnp.int32).at[pid].set(doc_s)
    return pos_s, tf, df, p_tid, p_doc


@partial(jax.jit, static_argnames=("nb_cap",))
def pack_block_lanes(slot_idx: jnp.ndarray, docs: jnp.ndarray,
                     imps: jnp.ndarray, fill_doc: jnp.ndarray, *,
                     nb_cap: int):
    """Scatter CSR postings into the flat 128-lane block arrays.

    slot_idx[i] = (block_start[tid] + rank // 128) * 128 + rank % 128
    (host-computed, unique per posting; pads = nb_cap * 128 → dropped).
    Unwritten lanes keep the host pad convention: doc = cap (fill_doc),
    impact = 0.
    """
    bd = jnp.full(nb_cap * BLOCK, fill_doc, jnp.int32)
    bd = bd.at[slot_idx].set(docs, mode="drop")
    bi = jnp.zeros(nb_cap * BLOCK, jnp.float32)
    bi = bi.at[slot_idx].set(imps, mode="drop")
    return bd, bi


@jax.jit
def forward_slots(doc_ids: jnp.ndarray) -> jnp.ndarray:
    """Per-posting forward-index slot: the posting's rank within its
    doc in CSR (term-ascending) order — the order the host builder
    fills slots in. One stable sort by doc groups each doc's postings
    (stability preserves CSR order inside the group), a running
    group-start cummax turns positions into ranks, and the inverse
    permutation carries ranks back to posting order. Pads carry
    doc = INT32_MAX and group at the tail (their slots are garbage;
    the host slices them off).
    """
    n = doc_ids.shape[0]
    order = jnp.argsort(doc_ids, stable=True)
    d_s = doc_ids[order]
    idx = jnp.arange(n, dtype=jnp.int32)
    newgrp = (idx == 0) | (d_s != jnp.roll(d_s, 1))
    start = jax.lax.cummax(jnp.where(newgrp, idx, 0))
    rank = idx - start
    return jnp.zeros(n, jnp.int32).at[order].set(rank)


@partial(jax.jit, static_argnames=("cap", "n_slots"))
def scatter_forward(docs: jnp.ndarray, slots: jnp.ndarray,
                    tids: jnp.ndarray, imps: jnp.ndarray, *,
                    cap: int, n_slots: int):
    """Scatter postings into the [cap, n_slots] forward index.

    (doc, slot) pairs are unique; pads carry doc = cap (row out of
    bounds → dropped). 2-D scatter keeps indices inside int32 even
    when cap * n_slots would overflow a flat int32 index.
    """
    ft = jnp.full((cap, n_slots), -1, jnp.int32)
    ft = ft.at[docs, slots].set(tids, mode="drop")
    fi = jnp.zeros((cap, n_slots), jnp.float32)
    fi = fi.at[docs, slots].set(imps, mode="drop")
    return ft, fi


@partial(jax.jit, static_argnames=("cap", "pos_cols"))
def scatter_positions(docs: jnp.ndarray, cols: jnp.ndarray,
                      deltas: jnp.ndarray, *, cap: int, pos_cols: int):
    """Scatter per-position int16 deltas into the [cap, pos_cols]
    positional pack (pos_cols = n_slots * P, both pow2-bucketed by the
    caller — the pad_delta_shapes convention). (doc, col) pairs are
    unique per position; pads carry doc = cap (row out of bounds →
    dropped). Integer scatter-set with unique targets: byte-identical
    to the host pack_positions fill.
    """
    fp = jnp.full((cap, pos_cols), -1, jnp.int16)
    return fp.at[docs, cols].set(deltas, mode="drop")


@partial(jax.jit, static_argnames=("entry_cap",))
def scatter_tile_max(runs: jnp.ndarray, imps: jnp.ndarray, *,
                     entry_cap: int):
    """The float half of build_tile_max as one scatter-max: out[e] =
    max impact of the postings whose (term, tile) run is entry e
    (index/segment.tile_runs numbers them). Max is order-free, so the
    result is byte-equal to the host's np.maximum.reduceat over the
    sorted postings (same value multiset per entry, zeros in the pad).
    Pads carry run = entry_cap → dropped; the host slices [:entries].
    """
    out = jnp.zeros((entry_cap,), jnp.float32)
    return out.at[runs].max(imps, mode="drop")


@partial(jax.jit, static_argnames=("n_tiles",))
def tile_minmax(vals: jnp.ndarray, exists: jnp.ndarray,
                lo_pad: jnp.ndarray, hi_pad: jnp.ndarray, *,
                n_tiles: int):
    """Per-tile min/max of a doc-value column, absent/NaN rows masked
    to the identity sentinels (exists already excludes NaN — the host
    caller masks once for both paths). Min/max reductions are
    order-free: byte-equal to the host build_tile_minmax.
    """
    vt = vals.reshape(n_tiles, -1)
    et = exists.reshape(n_tiles, -1)
    lo = jnp.where(et, vt, lo_pad).min(axis=1)
    hi = jnp.where(et, vt, hi_pad).max(axis=1)
    return lo, hi


@partial(jax.jit, static_argnames=("iters",))
def _kmeans_loop(x: jnp.ndarray, valid: jnp.ndarray,
                 cent0: jnp.ndarray, *, iters: int) -> jnp.ndarray:
    """Jitted Lloyd iterations (index/ann._kmeans promoted whole).

    Mirrors the host loop step-for-step: argmin assignment, mean
    update, then empty clusters reseeded from the farthest points
    (rank-matched: the i-th empty cluster takes the i-th farthest
    point, exactly the host's `cent[empty] = x[far[:n_empty]]`).
    Padded rows (valid == False) are parked on assignment index C
    (dropped by the scatters) and carry dmin = -inf so they are never
    picked as reseed candidates. f32 means/distances run in XLA — this
    path does NOT promise bit-equality with the numpy host k-means
    (it doesn't need to: the byte-identity contract is between
    host-built and device-built SEGMENTS, which share whichever
    k-means path is enabled), only determinism per backend.
    """
    n, _d = x.shape
    c = cent0.shape[0]
    x2 = jnp.einsum("nd,nd->n", x, x)

    def step(_i, cent):
        c2 = jnp.einsum("cd,cd->c", cent, cent)
        d = c2[None, :] - 2.0 * jnp.dot(
            x, cent.T, preferred_element_type=jnp.float32)
        assign = jnp.argmin(d, axis=1).astype(jnp.int32)
        assign = jnp.where(valid, assign, c)
        counts = jnp.zeros(c, jnp.int32).at[assign].add(
            jnp.ones_like(assign), mode="drop")
        sums = jnp.zeros_like(cent).at[assign].add(x, mode="drop")
        nonempty = counts > 0
        mean = sums / jnp.maximum(counts, 1).astype(x.dtype)[:, None]
        dmin = jnp.take_along_axis(
            d, jnp.clip(assign, 0, c - 1)[:, None], axis=1)[:, 0] + x2
        dmin = jnp.where(valid, dmin, -jnp.inf)
        far = jnp.argsort(-dmin)
        ranks = jnp.cumsum((~nonempty).astype(jnp.int32)) - 1
        cand = x[far[jnp.clip(ranks, 0, n - 1)]]
        return jnp.where(nonempty[:, None], mean, cand)

    return jax.lax.fori_loop(0, iters, step, cent0)


def kmeans_device(x: np.ndarray, n_clusters: int, seed: int,
                  iters: int = 10) -> np.ndarray:
    """Device k-means entry: host rng picks the same init sample as the
    host path (np.default_rng(seed).choice without replacement), the
    Lloyd loop runs jitted. Rows are padded to a pow2 batch so builder
    shapes don't thrash XLA (`batch` joins the compile key).
    """
    x = np.ascontiguousarray(x, dtype=np.float32)
    n = x.shape[0]
    rng = np.random.default_rng(seed)
    init = x[rng.choice(n, size=n_clusters, replace=False)].copy()
    batch = _next_pow2(n, floor=BLOCK)
    xp = np.zeros((batch, x.shape[1]), np.float32)
    xp[:n] = x
    valid = np.zeros(batch, bool)
    valid[:n] = True
    cent = _kmeans_loop(jnp.asarray(xp), jnp.asarray(valid),
                        jnp.asarray(init), iters=int(iters))
    return np.asarray(jax.device_get(cent), dtype=np.float32)


def _next_pow2(n: int, floor: int = 1) -> int:
    # mirror of index/segment.next_pow2 (kept local: ops stays
    # import-light)
    n = max(int(n), floor, 1)
    return 1 << (n - 1).bit_length()
