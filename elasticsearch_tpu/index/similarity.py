"""Pluggable per-field similarities baked into index-time impacts.

Reference analog: index/similarity/SimilarityService.java +
SimilarityModule.java (ES 1.x exposes Lucene's TFIDF ("default"), BM25,
DFR, IB, LMDirichlet and LMJelinekMercer similarities, configured under
`index.similarity.<name>.type` and referenced per-field via the mapping's
`similarity` property).

TPU-first design: the reference scores postings one at a time through a
Similarity object inside the Lucene hot loop (BulkScorer). Here scoring
is eager (BM25S-style): every similarity is expressed as a *vectorized
per-posting impact function* evaluated once at segment build, so the
query-time path (gather -> weight -> scatter-add, ops/scoring.py) is
identical for every similarity — swapping similarity costs nothing at
search time. The per-(term,doc) score of every supported similarity is a
function of (tf, doc_len) plus per-term/corpus constants (df, ttf,
doc_count, avg_len, total_len), which is exactly what the segment builder
has in hand when it lays out posting blocks.

Two consequences, both documented divergences:
  * changing a field's similarity requires a reindex (the reference
    recomputes at query time; we bake at index time — the mapping API
    rejects in-place similarity changes the same way it rejects analyzer
    changes);
  * the DFS query-then-fetch global-stats rescale is exact for the
    df-ratio family (BM25, classic TF/IDF) and a no-op for similarities
    whose df-dependence is non-multiplicative (DFR/IB/LM) — see
    `df_scale`.

Impacts are clamped to a tiny positive floor because `score > 0` doubles
as the match mask in the executor (ops/scoring.py score_term).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

from ..utils.settings import Settings
from ..utils.errors import IllegalArgumentError

# floor keeping matched postings strictly positive (match-mask semantics)
_IMPACT_FLOOR = 1e-6


@dataclass(frozen=True)
class FieldStats:
    """Per-term + per-field corpus statistics available at layout time.

    df: document frequency of the term; ttf: total term frequency
    (sum of tf over docs); doc_count: docs with the field; avg_len /
    total_len: average / total field length in tokens. Mirrors Lucene's
    TermStatistics + CollectionStatistics handed to
    SimilarityBase.score().
    """

    df: float
    ttf: float
    doc_count: float
    avg_len: float
    total_len: float


class Similarity:
    """Base: vectorized impact function over one term's postings."""

    name = "base"

    def impacts(self, tf: np.ndarray, dl: np.ndarray,
                st: FieldStats) -> np.ndarray:
        """Per-posting score contribution. tf, dl: float64 [n]."""
        raise NotImplementedError

    def field_impacts(self, tf: np.ndarray, dl: np.ndarray,
                      indptr: np.ndarray, df: np.ndarray, *,
                      doc_count: float, avg_len: float,
                      total_len: float) -> np.ndarray:
        """float32 [nnz] impacts of all postings of one field, which the
        pack build bakes in. tf, dl: float64 [nnz], term-major; term t's
        postings are `indptr[t]:indptr[t + 1]` and `df[t]` its document
        frequency. Here one `impacts` call a term, whose float bits are
        the similarity's as they always were; a similarity whose
        per-term constants are scalars spread over the postings
        overrides it with array code that keeps those bits."""
        T = len(indptr) - 1
        ttf = np.bincount(np.repeat(np.arange(T), np.diff(indptr)),
                          weights=tf, minlength=T)
        out = np.zeros(len(tf), dtype=np.float32)
        for t in range(T):
            s, e = int(indptr[t]), int(indptr[t + 1])
            if s == e:
                continue
            st = FieldStats(df=float(df[t]), ttf=float(ttf[t]),
                            doc_count=doc_count, avg_len=avg_len,
                            total_len=total_len)
            out[s:e] = self.impacts(tf[s:e], dl[s:e], st)
        return out

    def _spread(self, term_scalar, indptr: np.ndarray, df: np.ndarray,
                doc_count: float) -> np.ndarray:
        """float64 [nnz]: `term_scalar(df, doc_count)`, computed as the
        Python scalar it is in `impacts`, once for each distinct df, and
        repeated over each term's postings."""
        distinct, inverse = np.unique(df, return_inverse=True)
        values = np.array([term_scalar(float(d), doc_count)
                           for d in distinct.tolist()], dtype=np.float64)
        return np.repeat(values[inverse], np.diff(indptr))

    def df_scale(self, df_local: float, n_local: float,
                 df_global: float, n_global: float) -> float:
        """Multiplier turning a locally-idf'd impact into the global-stats
        score for DFS query-then-fetch (ref: dfs/AggregatedDfs consumed by
        TermWeight). 1.0 when the similarity's df-dependence is not a
        separable factor of the impact."""
        return 1.0

    def finish(self, imp: np.ndarray) -> np.ndarray:
        return np.maximum(imp, _IMPACT_FLOOR)


class BM25Similarity(Similarity):
    """Lucene BM25Similarity (the engine default; ref
    index/similarity/BM25SimilarityProvider.java)."""

    name = "BM25"

    def __init__(self, k1: float = 1.2, b: float = 0.75):
        self.k1 = float(k1)
        self.b = float(b)

    @staticmethod
    def idf(df: float, n: float) -> float:
        return math.log(1.0 + (n - df + 0.5) / (df + 0.5))

    def impacts(self, tf, dl, st):
        idf = self.idf(st.df, st.doc_count)
        k_d = self.k1 * (1.0 - self.b + self.b * dl / st.avg_len)
        return self.finish(idf * tf * (self.k1 + 1.0) / (tf + k_d))

    def field_impacts(self, tf, dl, indptr, df, *, doc_count, avg_len,
                      total_len):
        # `impacts` over all postings at once: each statement is one of
        # its operations on the same operands (a sum's and a product's
        # sides may swap, the bits do not), in place, because at millions
        # of postings a fresh temporary costs more than the arithmetic
        k_d = self.b * dl
        k_d /= avg_len
        k_d += 1.0 - self.b
        k_d *= self.k1
        k_d += tf
        imp = self._spread(self.idf, indptr, df, doc_count)
        imp *= tf
        imp *= self.k1 + 1.0
        imp /= k_d
        return np.maximum(imp, _IMPACT_FLOOR, out=imp).astype(np.float32)

    def df_scale(self, df_local, n_local, df_global, n_global):
        lo = self.idf(df_local, n_local)
        if lo <= 0 or n_global <= 0:
            return 1.0
        return self.idf(df_global, n_global) / lo


class ClassicSimilarity(Similarity):
    """Lucene TFIDF DefaultSimilarity — the reference's "default"
    similarity (ref: index/similarity/DefaultSimilarityProvider.java).

    Practical scoring function per term: sqrt(tf) * idf^2 / sqrt(dl),
    idf = 1 + ln(N / (df + 1)). queryNorm is a per-query constant
    (rank-neutral) and coord was removed in later Lucene; both omitted.
    Unlike Lucene we keep the length norm exact rather than 8-bit
    quantized."""

    name = "default"

    @staticmethod
    def idf(df: float, n: float) -> float:
        return 1.0 + math.log(max(n, 1.0) / (df + 1.0))

    def impacts(self, tf, dl, st):
        idf = self.idf(st.df, st.doc_count)
        norm = 1.0 / np.sqrt(np.maximum(dl, 1.0))
        return self.finish(np.sqrt(tf) * (idf * idf) * norm)

    def field_impacts(self, tf, dl, indptr, df, *, doc_count, avg_len,
                      total_len):
        # `impacts` over all postings at once, in its order of operations
        idf2 = self._spread(lambda d, n: self.idf(d, n) * self.idf(d, n),
                            indptr, df, doc_count)
        norm = 1.0 / np.sqrt(np.maximum(dl, 1.0))
        return self.finish(np.sqrt(tf) * idf2 * norm).astype(np.float32)

    def df_scale(self, df_local, n_local, df_global, n_global):
        lo = self.idf(df_local, n_local)
        if lo <= 0 or n_global <= 0:
            return 1.0
        r = self.idf(df_global, n_global) / lo
        return r * r


def _tfn(normalization: str, c: float, mu: float, z: float,
         tf: np.ndarray, dl: np.ndarray, st: FieldStats) -> np.ndarray:
    """DFR/IB term-frequency normalizations (Lucene NormalizationH1/H2/H3/Z;
    ref: org.apache.lucene.search.similarities.Normalization*)."""
    dl = np.maximum(dl, 1.0)
    if normalization in ("h1", "H1"):
        return tf * (st.avg_len / dl) * c
    if normalization in ("h2", "H2", "", None):
        return tf * np.log2(1.0 + c * st.avg_len / dl)
    if normalization in ("h3", "H3"):
        p = (st.ttf + 1.0) / (st.total_len + 1.0)
        return (tf + mu * p) / (dl + mu) * mu
    if normalization in ("z", "Z"):
        return tf * np.power(st.avg_len / dl, z)
    if normalization in ("no", "none"):
        return tf.astype(np.float64)
    raise IllegalArgumentError(
        f"Unsupported Normalization [{normalization}]")


class DFRSimilarity(Similarity):
    """Divergence-from-randomness (Lucene DFRSimilarity; ref
    index/similarity/DFRSimilarityProvider.java). Configured by
    basic_model (g | if | in | ine), after_effect (no | b | l) and
    normalization (no | h1 | h2 | h3 | z)."""

    name = "DFR"

    def __init__(self, basic_model: str = "g", after_effect: str = "l",
                 normalization: str = "h2", c: float = 1.0,
                 mu: float = 800.0, z: float = 0.30):
        self.basic_model = str(basic_model).lower()
        self.after_effect = str(after_effect).lower()
        self.normalization = str(normalization).lower()
        self.c, self.mu, self.z = float(c), float(mu), float(z)
        if self.basic_model not in ("g", "if", "in", "ine"):
            raise IllegalArgumentError(
                f"Unsupported BasicModel [{basic_model}]")
        if self.after_effect not in ("no", "none", "b", "l"):
            raise IllegalArgumentError(
                f"Unsupported AfterEffect [{after_effect}]")

    def _basic(self, tfn: np.ndarray, st: FieldStats) -> np.ndarray:
        n, f, df = st.doc_count, max(st.ttf, 1.0), st.df
        if self.basic_model == "g":
            lam = f / (n + f)
            return np.log2(1.0 / (lam + 1.0)) \
                + tfn * np.log2((1.0 + lam) / lam)
        if self.basic_model == "if":
            return tfn * math.log2(1.0 + (n + 1.0) / (f + 0.5))
        if self.basic_model == "in":
            return tfn * math.log2(1.0 + (n + 1.0) / (df + 0.5))
        # ine: expected df under a random distribution of F occurrences
        ne = n * (1.0 - math.pow((n - 1.0) / n, f)) if n > 1 else n
        return tfn * math.log2(1.0 + (n + 1.0) / (ne + 0.5))

    def _after(self, tfn: np.ndarray, st: FieldStats) -> np.ndarray:
        if self.after_effect == "l":
            return 1.0 / (tfn + 1.0)
        if self.after_effect == "b":
            return (st.ttf + 1.0) / (max(st.df, 1.0) * (tfn + 1.0))
        return np.ones_like(tfn)

    def impacts(self, tf, dl, st):
        tfn = _tfn(self.normalization, self.c, self.mu, self.z, tf, dl, st)
        return self.finish(self._basic(tfn, st) * self._after(tfn, st))


class IBSimilarity(Similarity):
    """Information-based similarity (Lucene IBSimilarity; ref
    index/similarity/IBSimilarityProvider.java). distribution (ll | spl),
    lambda (df | ttf), normalization as DFR."""

    name = "IB"

    def __init__(self, distribution: str = "ll", lambda_: str = "df",
                 normalization: str = "h2", c: float = 1.0,
                 mu: float = 800.0, z: float = 0.30):
        self.distribution = str(distribution).lower()
        self.lambda_kind = str(lambda_).lower()
        self.normalization = str(normalization).lower()
        self.c, self.mu, self.z = float(c), float(mu), float(z)
        if self.distribution not in ("ll", "spl"):
            raise IllegalArgumentError(
                f"Unsupported Distribution [{distribution}]")
        if self.lambda_kind not in ("df", "ttf"):
            raise IllegalArgumentError(f"Unsupported Lambda [{lambda_}]")

    def impacts(self, tf, dl, st):
        if self.lambda_kind == "df":
            lam = (st.df + 1.0) / (st.doc_count + 1.0)
        else:
            lam = (st.ttf + 1.0) / (st.doc_count + 1.0)
        lam = min(max(lam, 1e-9), 1.0 - 1e-9)
        tfn = _tfn(self.normalization, self.c, self.mu, self.z, tf, dl, st)
        if self.distribution == "ll":
            imp = -np.log(lam / (tfn + lam))
        else:  # spl: smoothed power law
            num = np.power(lam, tfn / (tfn + 1.0)) - lam
            imp = -np.log(np.maximum(num, 1e-12) / (1.0 - lam))
        return self.finish(imp)


class LMDirichletSimilarity(Similarity):
    """Language model with Dirichlet smoothing (Lucene
    LMDirichletSimilarity; ref index/similarity/
    LMDirichletSimilarityProvider.java). Scores below zero are clamped,
    as in Lucene."""

    name = "LMDirichlet"

    def __init__(self, mu: float = 2000.0):
        self.mu = float(mu)

    def impacts(self, tf, dl, st):
        p = (st.ttf + 1.0) / (st.total_len + 1.0)
        imp = np.log(1.0 + tf / (self.mu * p)) \
            + math.log(self.mu) - np.log(dl + self.mu)
        return self.finish(np.maximum(imp, 0.0))


class LMJelinekMercerSimilarity(Similarity):
    """Language model, Jelinek-Mercer smoothing (Lucene
    LMJelinekMercerSimilarity; ref index/similarity/
    LMJelinekMercerSimilarityProvider.java)."""

    name = "LMJelinekMercer"

    def __init__(self, lambda_: float = 0.1):
        if not 0.0 < float(lambda_) <= 1.0:
            raise IllegalArgumentError(
                f"lambda must be in (0..1] but was [{lambda_}]")
        self.lambda_ = float(lambda_)

    def impacts(self, tf, dl, st):
        p = (st.ttf + 1.0) / (st.total_len + 1.0)
        dl = np.maximum(dl, 1.0)
        imp = np.log1p((1.0 - self.lambda_) * (tf / dl)
                       / (self.lambda_ * p))
        return self.finish(imp)


DEFAULT_SIMILARITY = BM25Similarity()


def _build(type_name: str, s: Settings) -> Similarity:
    t = str(type_name)
    if t in ("BM25", "bm25"):
        return BM25Similarity(k1=s.get_float("k1", 1.2),
                              b=s.get_float("b", 0.75))
    if t in ("default", "classic", "tfidf", "TF/IDF"):
        return ClassicSimilarity()
    if t == "DFR":
        return DFRSimilarity(
            basic_model=s.get_str("basic_model", "g"),
            after_effect=s.get_str("after_effect", "l"),
            normalization=s.get_str("normalization", "h2"),
            c=s.get_float("normalization.h1.c",
                          s.get_float("normalization.h2.c", 1.0)),
            mu=s.get_float("normalization.h3.mu", 800.0),
            z=s.get_float("normalization.z.z", 0.30))
    if t == "IB":
        return IBSimilarity(
            distribution=s.get_str("distribution", "ll"),
            lambda_=s.get_str("lambda", "df"),
            normalization=s.get_str("normalization", "h2"),
            c=s.get_float("normalization.h1.c",
                          s.get_float("normalization.h2.c", 1.0)),
            mu=s.get_float("normalization.h3.mu", 800.0),
            z=s.get_float("normalization.z.z", 0.30))
    if t == "LMDirichlet":
        return LMDirichletSimilarity(mu=s.get_float("mu", 2000.0))
    if t == "LMJelinekMercer":
        return LMJelinekMercerSimilarity(lambda_=s.get_float("lambda", 0.1))
    raise IllegalArgumentError(f"Unknown Similarity type [{t}]")


class SimilarityService:
    """Resolves similarity names -> instances for one index.

    Ref: index/similarity/SimilarityService.java — built-ins ("default",
    "BM25", ...) plus custom entries from `index.similarity.<name>.*`
    settings. The engine-wide default here is BM25 (the reference 1.x
    default is TFIDF "default"; BM25 is both this engine's eager-impact
    native form and the modern ES default — fields wanting classic
    scoring say `"similarity": "default"`)."""

    def __init__(self, index_settings: Settings = Settings.EMPTY):
        self._custom: dict[str, Similarity] = {}
        for name, group in index_settings.groups("index.similarity").items():
            t = group.get_str("type")
            if not t:
                raise IllegalArgumentError(
                    f"Similarity [{name}] must have an associated type")
            self._custom[name] = _build(t, group)

    def get(self, name: str | None, field: str = "") -> Similarity:
        if not name:
            return DEFAULT_SIMILARITY
        if name in self._custom:
            return self._custom[name]
        try:
            return _build(name, Settings.EMPTY)
        except IllegalArgumentError:
            where = f" for field [{field}]" if field else ""
            raise IllegalArgumentError(
                f"Unknown Similarity type [{name}]{where}")

    def for_field(self, mapper_service, field: str) -> Similarity:
        fm = mapper_service.field(field)
        sim_name = getattr(fm, "similarity", None) if fm is not None else None
        # "cosine" is the dense_vector-metric default riding the shared
        # mapping attribute; text fields treat it as unset
        if sim_name in (None, "", "cosine"):
            return DEFAULT_SIMILARITY
        return self.get(sim_name, field)
