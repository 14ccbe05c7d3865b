"""IVF coarse quantization for dense_vector fields (pack-time build).

The exact kNN scan (ops/knn.py) tops out around 1M x 256 per device:
every query streams the whole shard's vectors through the MXU. This
module adds the coarse stage that lets vector serving go an order of
magnitude further — k-means clustering at pack build, cluster pruning
at query time — grounded in "Faster Exact Search using Document
Clustering" and "Lucene for Approximate Nearest-Neighbors Search on
Arbitrary Dense Vectors" (PAPERS.md): cluster-local extrema prune
clusters exactly the way block-max tile summaries prune WAND tiles,
and a DECLARED recall target replaces HNSW's graph-tuning side
effects.

Build contract (the `pad_delta_shapes` convention): the cluster count
and per-cluster capacity are pow2-BUCKETED, so the pack's shape
signature — and with it every fingerprint-keyed cache and compiled
program — stays epoch-constant across rebuilds of similarly-sized
segments. Per cluster the index stores:

  * centroid [D] f32 — the query-time coarse matmul input;
  * radius f32 — max distance from centroid to any member in the
    similarity's working space (unit sphere for cosine, raw space
    otherwise), from which ops/ann.cluster_bounds derives an upper
    bound on the TRANSFORMED similarity of any member: the tile_max
    analog, one bound per cluster per query;
  * cluster-sorted member ordinals [cluster_cap] int32 (pad = -1).

Query-time pruning and probing live in ops/ann.py; the shard searcher
wires them in (search/shard_searcher.py). Delta segments always serve
the exact scan — IVF is a base-generation artifact, rebuilt by
compaction like the other pack summaries. Build failure (including an
injected `site=ann:phase=build` fault) degrades the segment to the
exact scan instead of failing the refresh: the index is an
accelerator, never a correctness input.
"""

from __future__ import annotations

import os
import threading

import numpy as np

from .segment import next_pow2
from ..utils import faults

_TRUE = ("1", "true", "on", "yes")

# below this many vectors the exact scan wins outright (one small
# matmul — roughly the crossover where the exact path already switches
# to approx_max_k selection); also keeps clusters populated enough for
# the radius bound to prune meaningfully
DEFAULT_MIN_DOCS = 1 << 16
DEFAULT_RECALL = 0.95
# k-means training sample cap: IVF practice trains the coarse
# quantizer on a sample and assigns the full set in one pass
_TRAIN_CAP = 1 << 18
_KMEANS_ITERS = 10

# multiplicative slack on the transformed cluster bounds: member
# vectors are scored from their bf16-rounded device copies while the
# centroid geometry is computed in f32 — 1/64 covers the ~2^-8
# relative input rounding of both matmul operands with margin, and
# scores are nonnegative, so inflating the bound only makes pruning
# more conservative (never drops a cluster whose member could win)
ANN_BOUND_SLACK = np.float32(1.0 + 1.0 / 64.0)


# module config (node startup: Node plumbs index.ann.* through
# configure(); env vars override at read time — the tiering.py
# convention, ownership token and all)
_cfg_lock = threading.Lock()
_cfg_min_docs: int | None = None
_cfg_nprobe: int | None = None
_cfg_recall: float | None = None
_cfg_token: object | None = None


def configure(min_docs: int | None = None, nprobe: int | None = None,
              recall: float | None = None) -> object:
    """Node startup hook (process-global, last node wins). Returns an
    ownership token for reset(if_current=...)."""
    global _cfg_min_docs, _cfg_nprobe, _cfg_recall, _cfg_token
    with _cfg_lock:
        if min_docs is not None:
            _cfg_min_docs = int(min_docs)
        if nprobe is not None:
            _cfg_nprobe = int(nprobe)
        if recall is not None:
            _cfg_recall = float(recall)
        _cfg_token = object()
        return _cfg_token


def reset(if_current: object | None = None) -> None:
    global _cfg_min_docs, _cfg_nprobe, _cfg_recall, _cfg_token
    with _cfg_lock:
        if if_current is not None and if_current is not _cfg_token:
            return
        _cfg_min_docs = _cfg_nprobe = _cfg_recall = None
        _cfg_token = None


def min_docs() -> int:
    env = os.environ.get("ES_TPU_ANN_MIN_DOCS")
    if env is not None:
        return int(env)
    with _cfg_lock:
        return _cfg_min_docs if _cfg_min_docs is not None \
            else DEFAULT_MIN_DOCS


def declared_recall() -> float:
    with _cfg_lock:
        return _cfg_recall if _cfg_recall is not None else DEFAULT_RECALL


def default_nprobe(n_clusters: int, recall: float | None = None) -> int:
    """nprobe for a declared recall target, pow2-bucketed (nprobe is a
    jit-static of the probe program — the same recompile-hazard class
    as k, guarded the same way). The mapping is a documented heuristic
    (README "Vector search"): probe a recall-scaled fraction of the
    cluster count, floored at 8 — cluster sizes are sqrt(N)-ish, so a
    fraction of clusters is a fraction of the corpus scanned. The
    cluster-bound threshold prune then skips most probed clusters
    without scoring them, which is why over-probing is cheap.
    """
    with _cfg_lock:
        cfg = _cfg_nprobe
    if cfg is not None:
        return max(1, next_pow2(cfg, floor=1))
    r = declared_recall() if recall is None else float(recall)
    # fraction of clusters to probe: 1/8 at 0.95, 1/4 at 0.99+, 1/16
    # below 0.9 — empirically comfortable for sqrt(N) clusterings
    frac = 0.25 if r >= 0.99 else (0.125 if r >= 0.9 else 0.0625)
    return max(8, next_pow2(int(np.ceil(n_clusters * frac)), floor=1))


# serializes concurrent ensure_ann() installs; the k-means build itself
# runs OUTSIDE it (a lost race wastes one build, never corrupts state)
_ENSURE_LOCK = threading.Lock()


def ensure_ann(segment, field: str, similarity: str, *,
               index: str | None = None, shard: int | None = None):
    """Lazily build (once) and return `segment.ann[field]` — the
    ensure_* convention of the other pack summaries (executor
    ensure_num_tiles et al.). Returns None when the segment is below
    the exact-scan crossover, is a delta pack, or the build failed
    (injected `site=ann:phase=build` faults degrade to the exact scan
    — the index is an accelerator, never a correctness input; the
    failure is sticky per (segment, field) so a faulty build is not
    retried per search)."""
    ai = segment.ann.get(field)
    if ai is not None:
        return ai
    if getattr(segment, "delta_parent", None) is not None:
        return None
    skip = getattr(segment, "_ann_skip", None)
    if skip is not None and field in skip:
        return None
    vc = segment.vectors.get(field)
    if vc is None:
        return None
    try:
        built = build_ann(vc.values, vc.exists, similarity,
                          index=index, shard=shard)
    except Exception:
        # degrade to the exact scan, but VISIBLY: a real build bug
        # (not just an injected fault) would otherwise silently cost
        # every future search on this segment the exact-scan price
        import logging
        logging.getLogger(__name__).exception(
            "ANN build failed for [%s] on segment [%s]; serving the "
            "exact scan (sticky until rebuild)", field,
            getattr(segment, "seg_id", "?"))
        built = None
    with _ENSURE_LOCK:
        ai = segment.ann.get(field)
        if ai is not None:
            return ai          # lost the build race; first install wins
        if built is None:
            if getattr(segment, "_ann_skip", None) is None:
                segment._ann_skip = set()
            segment._ann_skip.add(field)
            return None
        # copy-on-write (the segment-dict convention): concurrent
        # searches iterate segment.ann without the lock
        segment.ann = {**segment.ann, field: built}
    return built


def ensure_ann_device(segment, field: str, similarity: str, *,
                      index: str | None = None, shard: int | None = None):
    """ensure_ann + (once) upload the IVF arrays. Returns (AnnIndex,
    device dict) or None. The upload lives on `segment._ann_device`,
    DELIBERATELY outside the segment's main device tree
    (executor.device_arrays): the ann arrays feed only the dedicated
    probe program (ops/ann.ivf_topk), and growing the main pytree would
    re-key every cached program for ordinary text queries. Bytes are
    fielddata-breaker-accounted with the standard weakref GC backstop;
    Segment.drop_device clears the attr (holds are idempotent)."""
    ai = ensure_ann(segment, field, similarity, index=index, shard=shard)
    if ai is None:
        return None
    cache = getattr(segment, "_ann_device", None)
    entry = None if cache is None else cache.get(field)
    if entry is None:
        import weakref

        import jax.numpy as jnp

        from ..utils.breaker import breaker_service
        hold = breaker_service().breaker("fielddata").hold(ai.nbytes())
        weakref.finalize(segment, hold.release)
        # counts stay host-side (they only shaped the members build);
        # the probe program consumes centroids/radii/members
        entry = {"centroids": jnp.asarray(ai.centroids),
                 "radii": jnp.asarray(ai.radii),
                 "members": jnp.asarray(ai.members),
                 "_breaker_hold": hold}
        with _ENSURE_LOCK:
            cache = getattr(segment, "_ann_device", None)
            if cache is None:
                cache = {}
                segment._ann_device = cache
            existing = cache.get(field)
            if existing is not None:
                # lost the upload race: release OUR hold now (the
                # winner's is the accounted one) instead of stranding
                # it until segment GC
                hold.release()
                entry = existing
            else:
                cache[field] = entry
    return ai, entry


class AnnIndex:
    """One field's IVF coarse index over a segment's vectors."""

    __slots__ = ("similarity", "centroids", "radii", "members", "counts")

    def __init__(self, similarity: str, centroids: np.ndarray,
                 radii: np.ndarray, members: np.ndarray,
                 counts: np.ndarray):
        self.similarity = similarity
        self.centroids = centroids      # [C, D] f32 (working space)
        self.radii = radii              # [C] f32
        self.members = members          # [C, cluster_cap] int32, pad -1
        self.counts = counts            # [C] int32

    @property
    def n_clusters(self) -> int:
        return self.centroids.shape[0]

    @property
    def cluster_cap(self) -> int:
        return self.members.shape[1]

    @property
    def dims(self) -> int:
        return self.centroids.shape[1]

    def nbytes(self) -> int:
        return (self.centroids.nbytes + self.radii.nbytes
                + self.members.nbytes + self.counts.nbytes)

    def arrays(self) -> dict[str, np.ndarray]:
        """Store round-trip payload (index/store.py `ann__<field>`)."""
        return {"centroids": self.centroids, "radii": self.radii,
                "members": self.members, "counts": self.counts}

    @classmethod
    def from_arrays(cls, similarity: str,
                    arrays: dict[str, np.ndarray]) -> "AnnIndex":
        return cls(similarity,
                   np.ascontiguousarray(arrays["centroids"],
                                        dtype=np.float32),
                   np.ascontiguousarray(arrays["radii"],
                                        dtype=np.float32),
                   np.ascontiguousarray(arrays["members"],
                                        dtype=np.int32),
                   np.ascontiguousarray(arrays["counts"],
                                        dtype=np.int32))


def _working_space(values: np.ndarray, similarity: str) -> np.ndarray:
    """Vectors in the geometry the cluster bound is argued in: the unit
    sphere for cosine (the bound is on q_hat . x_hat), raw space for
    dot_product / l2_norm (bounds via ||q|| r and ||q - c|| - r)."""
    x = values.astype(np.float32, copy=False)
    if similarity == "cosine":
        n = np.linalg.norm(x, axis=1, keepdims=True)
        return x / np.maximum(n, 1e-12)
    return x


def _kmeans(x: np.ndarray, n_clusters: int, seed: int,
            iters: int = _KMEANS_ITERS) -> np.ndarray:
    """Seeded Lloyd k-means on a training sample -> [C, D] f32
    centroids. When the device-parallel builder is enabled the WHOLE
    loop runs jitted (ops/build.kmeans_device — same init sample, same
    empty-cluster reseed rule; `_assign_full` below was already device-
    chunked), falling back here on any device error. Either path is
    deterministic per backend, and host-vs-device segment identity
    holds because both builds share whichever path is enabled."""
    from . import devbuild
    if devbuild.enabled():
        try:
            from ..ops.build import kmeans_device
            cent = kmeans_device(x, n_clusters, seed, iters=iters)
            devbuild._bump("kmeans_device")
            return cent
        except Exception as e:
            devbuild.on_fallback("kmeans", e)
    return _kmeans_host(x, n_clusters, seed, iters)


def _kmeans_host(x: np.ndarray, n_clusters: int, seed: int,
                 iters: int = _KMEANS_ITERS) -> np.ndarray:
    """Host reference Lloyd loop: empty clusters re-seed to the points
    farthest from their assigned centroid."""
    rng = np.random.default_rng(seed)
    n = x.shape[0]
    cent = x[rng.choice(n, size=n_clusters, replace=False)].copy()
    x2 = np.einsum("nd,nd->n", x, x)
    for _ in range(iters):
        # argmin_c ||x - c||^2 = argmin_c ||c||^2 - 2 x.c
        c2 = np.einsum("cd,cd->c", cent, cent)
        d = c2[None, :] - 2.0 * (x @ cent.T)          # [n, C] + const
        assign = np.argmin(d, axis=1)
        counts = np.bincount(assign, minlength=n_clusters)
        sums = np.zeros_like(cent)
        np.add.at(sums, assign, x)
        nonempty = counts > 0
        cent[nonempty] = sums[nonempty] / counts[nonempty, None]
        empty = np.nonzero(~nonempty)[0]
        if empty.size:
            # farthest points from their centroid re-seed the empties
            dmin = d[np.arange(n), assign] + x2
            far = np.argsort(-dmin)[: empty.size]
            cent[empty] = x[far]
    return cent.astype(np.float32)


def _assign_full(x: np.ndarray, cent: np.ndarray,
                 chunk: int = 1 << 17) -> tuple[np.ndarray, np.ndarray]:
    """Assign EVERY vector to its nearest centroid and measure each
    cluster's radius, chunked so the [chunk, C] distance slab stays
    bounded at 10M+ scale. Heavy half runs as jnp matmuls so a real
    accelerator does the assignment pass at device speed (CPU jax
    falls back to the host BLAS it would have used anyway)."""
    import jax
    import jax.numpy as jnp

    n, _d = x.shape
    c2 = np.einsum("cd,cd->c", cent, cent).astype(np.float32)

    @jax.jit
    def one_chunk(xc, centj, c2j):
        d = c2j[None, :] - 2.0 * jnp.dot(
            xc, centj.T, preferred_element_type=jnp.float32)
        a = jnp.argmin(d, axis=1)
        dmin = jnp.take_along_axis(d, a[:, None], axis=1)[:, 0]
        return a.astype(jnp.int32), dmin

    assign = np.empty(n, dtype=np.int32)
    dmin = np.empty(n, dtype=np.float32)
    centj = jnp.asarray(cent)
    c2j = jnp.asarray(c2)
    for lo in range(0, n, chunk):
        hi = min(lo + chunk, n)
        xc = x[lo:hi]
        if hi - lo < chunk and n > chunk:
            # pad to the chunk shape so the jitted program compiles once
            xc = np.concatenate(
                [xc, np.zeros((chunk - (hi - lo), x.shape[1]),
                              np.float32)])
        a, dm = one_chunk(jnp.asarray(xc), centj, c2j)
        assign[lo:hi] = np.asarray(a)[: hi - lo]
        dmin[lo:hi] = np.asarray(dm)[: hi - lo]
    x2 = np.einsum("nd,nd->n", x, x).astype(np.float32)
    d2 = np.maximum(dmin + x2, 0.0)       # true squared distance
    radii2 = np.zeros(cent.shape[0], dtype=np.float32)
    np.maximum.at(radii2, assign, d2)
    return assign, np.sqrt(radii2)


def build_ann(values: np.ndarray, exists: np.ndarray, similarity: str,
              *, index: str | None = None, shard: int | None = None,
              seed: int = 0) -> AnnIndex | None:
    """Build one field's IVF index at pack build, or None when the
    segment is below the exact-scan crossover (`index.ann.min_docs` /
    ES_TPU_ANN_MIN_DOCS). Raises on injected `site=ann:phase=build`
    faults — the caller (segment build) catches and degrades to the
    exact scan."""
    ords = np.nonzero(np.asarray(exists, dtype=bool))[0].astype(np.int32)
    n = int(ords.size)
    if n < min_docs():
        return None
    faults.on_dispatch("ann", index=index, shard=shard, phase="build")
    x = _working_space(np.asarray(values)[ords], similarity)
    # sqrt(N)-ish coarse stage, pow2-bucketed so the pack shape
    # signature is epoch-constant (the pad_delta_shapes convention);
    # every cluster keeps >= ~2 members on average at the floor
    c = next_pow2(int(np.sqrt(n)), floor=8)
    c = min(c, next_pow2(max(n // 2, 1), floor=1))
    train = x
    if n > _TRAIN_CAP:
        rng = np.random.default_rng(seed)
        train = x[rng.choice(n, size=_TRAIN_CAP, replace=False)]
    cent = _kmeans(train, c, seed)
    assign, radii = _assign_full(x, cent)
    # bf16 device rounding slack folded into the stored radius once
    # (see ANN_BOUND_SLACK — applied again on the transformed bound)
    counts = np.bincount(assign, minlength=c).astype(np.int32)
    ccap = next_pow2(int(counts.max()), floor=8)
    members = np.full((c, ccap), -1, dtype=np.int32)
    order = np.argsort(assign, kind="stable")
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    for ci in range(c):
        lo = int(starts[ci])
        row = order[lo: lo + int(counts[ci])]
        members[ci, : row.size] = ords[row]
    return AnnIndex(similarity, cent, radii.astype(np.float32),
                    members, counts)
