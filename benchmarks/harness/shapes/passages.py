"""The record shape `passages`: a full-text passage corpus, `_id` and one
`text` field of type `text` under the standard analyzer, as MS MARCO's
`collection.tsv` or the `pmc` track's articles have it.

No corpus can be fetched here, so a passage is drawn from the seed with
the distributions the configuration's file states under `corpus`: its
length in tokens lognormal (`length_lognormal`, cut to `length_min` ..
`length_max`), each token a word of a `vocabulary` of that many words
drawn with popularity rank^-`term_zipf`. The words are the same for every
seed (`word`): lower-case letters only, which the standard analyzer
leaves as they are.

The reference's clauses, beside none (`match_all`):

    {"field": f, "match": [words], "operator": "or" | "and", "score": "bm25"}
    {"field": f, "phrase": [words], "score": "bm25"}

A `term` query on the field is a `match` of one word. BM25 as the
program's documentation states it (`index/similarity.py`), in float64:
idf `ln(1 + (N - df + 0.5) / (df + 0.5))`, tf part `tf (k1 + 1) / (tf +
k1 (1 - b + b dl / avgdl))`, with N, df and avgdl of the document's own
shard (query_then_fetch), dl the exact token count, `k1` and `b` the
configuration's `similarity`. A `match` scores the sum over its distinct
words; a `phrase` (slop 0) scores the sum of its words' idf times the tf
part of the exact phrase frequency. Plain postings (word -> the places
it stands at, in document order) are built once, so a window of
thousands of distinct queries is compared in seconds.

Imports numpy and the benchmark's shared yardstick only; nothing of the
program and nothing it has made.
"""

from __future__ import annotations

import numpy as np

from ..corpus import Records, apportion, route_shards

GENERATOR_VERSION = 1       # bump when the docs of a seed change
FIELD = "text"
TRIES = 1000                # passages looked at for one query's words


def word(i: int) -> str:
    """The `i`-th most popular word: `z` and the number in base 26,
    two letters at the least."""
    s = ""
    while True:
        s = chr(97 + i % 26) + s
        i //= 26
        if not i:
            return "z" + s.rjust(2, "a")


def word_id(w: str) -> int:
    """A word's number; -1 where no passage can hold it."""
    if len(w) < 3 or w[0] != "z" or not all("a" <= ch <= "z" for ch in w):
        return -1
    i = 0
    for ch in w[1:]:
        i = i * 26 + ord(ch) - 97
    return i if word(i) == w else -1


def dealt(shares: dict, count: int) -> list:
    """`count` keys of `shares`, each as often as its share says, so that
    every seed gets the same set."""
    return [k for k, c in zip(shares, apportion(list(shares.values()), count))
            for _ in range(c)]


class Corpus(Records):
    """`n` passages drawn from `seed`. `tokens` holds every passage's
    word numbers one after another, `start[d]` where passage d begins,
    `dl[d]` its length."""

    def __init__(self, n: int, seed: int, n_shards: int, params: dict):
        rng = np.random.default_rng(seed)
        self.n, self.n_shards = n, n_shards
        self.k1 = float(params["similarity"]["k1"])
        self.b = float(params["similarity"]["b"])
        self.vocabulary = int(params["vocabulary"])
        mu, sigma = params["length_lognormal"]
        self.dl = np.clip(rng.lognormal(mu, sigma, size=n).astype(np.int64),
                          params["length_min"], params["length_max"])
        self.start = np.concatenate([[0], np.cumsum(self.dl)])
        w = 1.0 / (np.arange(self.vocabulary) + 1.0) ** params["term_zipf"]
        cdf = np.cumsum(w / w.sum())
        self.tokens = np.minimum(
            np.searchsorted(cdf, rng.random(int(self.start[-1]))),
            self.vocabulary - 1).astype(np.int32)
        self.cols = {}
        self.shard = route_shards(n, n_shards)
        self.n_s = np.bincount(self.shard, minlength=n_shards) \
            .astype(np.float64)
        self.avgdl = np.bincount(self.shard, weights=self.dl,
                                 minlength=n_shards) / np.maximum(self.n_s, 1)
        self._words = None
        self._places = None
        self._held = {}

    def passage(self, d: int) -> np.ndarray:
        return self.tokens[self.start[d]:self.start[d + 1]]

    def bulk_body(self, lo: int, hi: int) -> bytes:
        """`_bulk` lines of passages lo..hi-1."""
        if self._words is None:
            self._words = np.array([word(i) for i in range(self.vocabulary)],
                                   dtype=object)
        said = self._words[self.tokens[self.start[lo]:self.start[hi]]]
        at = self.start[lo:hi + 1] - self.start[lo]
        lines = []
        for j, i in enumerate(range(lo, hi)):
            lines.append('{"index":{"_id":"%d"}}' % i)
            lines.append('{"%s":"%s"}' % (
                FIELD, " ".join(said[at[j]:at[j + 1]])))
        return ("\n".join(lines) + "\n").encode()

    # -- the plain reference -------------------------------------------------

    def places(self, t: int) -> np.ndarray:
        """Where word `t` stands in `tokens`, ascending (so by document,
        then position): the plain postings, built at the first call."""
        if self._places is None:
            order = np.argsort(self.tokens, kind="stable").astype(np.int32)
            first = np.concatenate([[0], np.cumsum(np.bincount(
                self.tokens, minlength=self.vocabulary))])
            doc = np.repeat(np.arange(self.n, dtype=np.int32), self.dl)
            self._places = (order, first, doc)
        order, first, _doc = self._places
        if not 0 <= t < self.vocabulary:
            return order[:0]
        return order[first[t]:first[t + 1]]

    def counted(self, at: np.ndarray):
        """(documents, how often each) of ascending places."""
        d = self._places[2][at]
        if not len(d):
            return d, d
        edge = np.concatenate([[0], np.flatnonzero(np.diff(d)) + 1, [len(d)]])
        return d[edge[:-1]], np.diff(edge).astype(np.int32)

    def held(self, t: int):
        """(documents, tf in each) of word `t`. A popular word's are kept:
        a window's queries ask for the same few again and again."""
        if t not in self._held:
            out = self.counted(self.places(t))
            if len(out[0]) * 16 < self.n:
                return out
            self._held[t] = out
        return self._held[t]

    def idf(self, docs: np.ndarray, dtype) -> np.ndarray:
        """A word's idf in each shard, from the documents that hold it."""
        half, one = dtype(0.5), dtype(1.0)
        df = np.bincount(self.shard[docs], minlength=self.n_shards) \
            .astype(dtype)
        n_s = self.n_s.astype(dtype)
        return np.log(one + (n_s - df + half) / (df + half)).astype(dtype)

    def tf_part(self, docs: np.ndarray, tf: np.ndarray, dtype) -> np.ndarray:
        one, k1, b = dtype(1.0), dtype(self.k1), dtype(self.b)
        rel = (self.dl[docs].astype(dtype)
               / self.avgdl.astype(dtype)[self.shard[docs]]).astype(dtype)
        k = (k1 * (one - b + b * rel)).astype(dtype)
        tf = tf.astype(dtype)
        return (tf * (k1 + one) / (tf + k)).astype(dtype)

    def clause(self, c: dict, dtype=np.float64):
        """(match mask, score column) of one clause."""
        if c["field"] != FIELD or c["score"] != "bm25":
            raise ValueError(f"the passages shape knows no clause {c}")
        score = np.zeros(self.n, dtype)
        if "phrase" in c:
            ids = [word_id(w) for w in c["phrase"]]
            at = self.places(ids[0])
            # an occurrence: the words one after another in one passage
            at = at[at + len(ids) <= self.start[self._places[2][at] + 1]]
            idf = np.zeros(self.n_shards, dtype)
            for j, t in enumerate(ids):
                if j:
                    at = at[self.tokens[at + j] == t]
                idf = (idf + self.idf(self.held(t)[0], dtype)).astype(dtype)
            docs, freq = self.counted(at)
            match = np.zeros(self.n, bool)
            match[docs] = True
            score[docs] = idf[self.shard[docs]] \
                * self.tf_part(docs, freq, dtype)
            return match, score
        ids = list(dict.fromkeys(word_id(w) for w in c["match"]))
        seen = np.zeros(self.n, np.int16)
        for t in ids:
            docs, tf = self.held(t)
            seen[docs] += 1
            score[docs] = (score[docs] + self.idf(docs, dtype)[
                self.shard[docs]] * self.tf_part(docs, tf, dtype)) \
                .astype(dtype)
        match = seen == len(ids) if c["operator"] == "and" else seen > 0
        return match, np.where(match, score, dtype(0.0)).astype(dtype)

    def evaluate(self, spec: dict, dtype=np.float64):
        """(match mask, score column) of a whole query; its clauses are
        conjunctive, their scores summed."""
        if not spec["clauses"]:
            return np.ones(self.n, bool), np.ones(self.n, dtype)
        match = np.ones(self.n, bool)
        score = np.zeros(self.n, dtype)
        for c in spec["clauses"]:
            m, s = self.clause(c, dtype)
            match &= m
            score = (score + s).astype(dtype)
        return match, score

    # -- queries drawn per request -------------------------------------------

    def draw(self, op: dict, count: int, rng) -> list:
        """`count` (REST body, spec) pairs of a drawn operation. Its
        `draw` group states the `clause` (`match` or `phrase`), the
        shares of the numbers of words (`terms_shares`) and, for a
        `match`, of the operators (`operator_shares`), and `frequent`:
        the share of a match's words taken from the `frequent_ranks`
        most popular of the vocabulary. Every seed gets the same set of
        (number of words, operator, frequent words), in another order. The
        words are a seeded passage's own and differ within a query, so
        that a conjunction and a phrase have an answer."""
        d = op["draw"]
        size = d.get("size", 10)
        sizes = sorted(int(m) for m in dealt(d["terms_shares"], count))
        kinds, rich = [], []
        for m in sorted(set(sizes)):
            c = sizes.count(m)
            if d["clause"] == "phrase":
                kinds += [None] * c
                rich += [0] * c
            else:
                kinds += dealt(d["operator_shares"], c)
                rich += np.diff(np.floor(np.arange(c + 1) * m * d["frequent"]
                                         + 1e-9)).astype(int).tolist()
        turn = rng.permutation(count)
        out = []
        for m, f, kind in ((sizes[i], rich[i], kinds[i]) for i in turn):
            words = [word(int(t)) for t in (
                self._phrase(m, rng) if kind is None
                else self._words_of_a_passage(m, f, d["frequent_ranks"],
                                              rng))]
            if kind is None:
                body = {"query": {"match_phrase": {FIELD: " ".join(words)}},
                        "size": size}
                clause = {"field": FIELD, "phrase": words, "score": "bm25"}
            else:
                body = {"query": {"match": {FIELD: {
                    "query": " ".join(words), "operator": kind}}},
                    "size": size}
                clause = {"field": FIELD, "match": words, "operator": kind,
                          "score": "bm25"}
            out.append((body, {"clauses": [clause], "size": size}))
        return out

    def _phrase(self, m: int, rng) -> np.ndarray:
        """`m` words that stand one after another in a passage, no two
        the same."""
        for _ in range(TRIES):
            p = self.passage(int(rng.integers(self.n)))
            if len(p) < m:
                continue
            at = int(rng.integers(len(p) - m + 1))
            if len(set(p[at:at + m].tolist())) == m:
                return p[at:at + m]
        raise ValueError(f"no passage with {m} different words in a row")

    def _words_of_a_passage(self, m: int, f: int, ranks: int,
                            rng) -> np.ndarray:
        """`m` different words of one passage, `f` of them among the
        `ranks` most popular of the vocabulary, in the passage's order."""
        for _ in range(TRIES):
            p = self.passage(int(rng.integers(self.n)))
            _, first = np.unique(p, return_index=True)
            own = p[np.sort(first)]
            often, seldom = own[own < ranks], own[own >= ranks]
            if len(often) >= f and len(seldom) >= m - f:
                took = np.concatenate([rng.permutation(often)[:f],
                                       rng.permutation(seldom)[:m - f]])
                return own[np.isin(own, took)]
        raise ValueError(f"no passage with {f} frequent and {m - f} other "
                         f"words")
