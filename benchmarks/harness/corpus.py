"""The yardstick: the corpus, the plain numpy reference over it, its
low-precision control and the comparison that decides `correct`.

A configuration names its record shape (`record_shape`; absent:
`http_logs`), and `shape` finds the shape's module by that name: this
file for `http_logs`, `harness/shapes/<name>.py` for any other. A shape
module has `GENERATOR_VERSION` and a `Corpus(n, seed, n_shards, params)`
(a `Records`) with `bulk_body`, `evaluate` and, where its mixes draw
queries, `draw`. What every shape shares is written once, here:
`route_shards`, `Records.top`, `.buckets` and `.warm_bodies`, `answer_from`,
`digest`, `Reference.compare` with its seven numbers, `fold`, `judge`
and the bfloat16 control.

The `http_logs` shape is the record of the `http_logs` track of
elastic/rally-tracks (web-server log lines of the 1998 World Cup site):
`@timestamp`, `clientip`, `request` (text with a `.raw` keyword),
integer `status`, integer `size`. The track's 31 GB of documents are
not here (no network), so the values are drawn from the seed with the
distributions the configuration's file states under `corpus`.

An operation of a traffic mix comes with a `spec`, a small declarative
form of its query that the reference reads (see `Reference`):

    {"clauses": [{"field": f, "eq": v | "gte": a, "lt": b, "score": "idf" | "one"}],
     "size": k, "sort": {"field": f, "order": "asc" | "desc"},
     "histogram": {"name": agg, "field": f, "interval_ms": n}}

Clauses are conjunctive (`bool.must`); no clause is `match_all`.
Elasticsearch's query_then_fetch scores a document with the statistics
of its own shard, so the reference routes ids the way the configuration
states (DJB2 of the `_id`, as Elasticsearch 2.0's DjbHashFunction) and
keeps per-shard counts.

Imports numpy only; nothing of the program and nothing it has made.
"""

from __future__ import annotations

import calendar
import importlib
import json
import sys
import time

import numpy as np

GENERATOR_VERSION = 2       # bump when the docs of a seed change
METHODS = ("GET", "HEAD", "POST")
PROTOCOLS = ("HTTP/1.0", "HTTP/1.1")
SECTIONS = ("images", "news", "teams", "venues", "history", "competition",
            "playing", "individuals", "member", "tickets")
EXTENSIONS = (".gif", ".html", ".jpg", ".htm")


def parse_iso(s: str) -> int:
    """`1998-05-01T00:00:00Z` -> epoch milliseconds."""
    return 1000 * calendar.timegm(time.strptime(s, "%Y-%m-%dT%H:%M:%SZ"))


def urls(n: int, root_rank: int) -> list[str]:
    """`n` distinct paths in the order of their popularity. The site's
    root, which the track's `term` operation asks for, stands at
    `root_rank`. The same for every seed."""
    out = []
    for i in range(n):
        lang = ("english", "french")[i % 3 == 2]
        sec = SECTIONS[(i // 3) % len(SECTIONS)]
        out.append(f"/{lang}/{sec}/{sec[:4]}_{i}{EXTENSIONS[i % 4]}")
    out[root_rank] = "/"
    return out


def route_shards(n_docs: int, n_shards: int) -> np.ndarray:
    """Shard of each doc whose `_id` is its decimal number: DJB2 over
    the id's characters, as a signed 32-bit int, floor-mod shards."""
    if n_shards == 1:
        return np.zeros(n_docs, np.int64)
    h = np.full(n_docs, 5381, np.int64)
    ids = np.arange(n_docs, dtype=np.int64)
    ndig = np.where(ids == 0, 1,
                    np.floor(np.log10(np.maximum(ids, 1))).astype(np.int64)
                    + 1)
    for pos in range(int(ndig.max())):
        # pos-th character from the left, for the ids that have one
        active = ndig > pos
        digit = (ids // 10 ** np.maximum(ndig - 1 - pos, 0)) % 10
        nh = (h * 33 + 48 + digit) & 0xFFFFFFFF
        h = np.where(active, nh, h)
    h = np.where(h >= 1 << 31, h - (1 << 32), h)
    return np.mod(h, n_shards)


def apportion(weights, count: int) -> np.ndarray:
    """`count` things shared out in proportion to `weights`, whole
    numbers by largest remainders: the same for every seed."""
    want = np.asarray(weights, float) * count / np.sum(weights)
    have = np.floor(want).astype(int)
    have[np.argsort(have - want, kind="stable")[:count - have.sum()]] += 1
    return have


def shape(config: dict):
    """The module of the configuration's record shape. Any other than
    `http_logs` is `harness/shapes/<name>.py`, found with `benchmarks/`
    on the import path, where every entry of the harness puts it."""
    name = config.get("record_shape", "http_logs")
    if name == "http_logs":
        return sys.modules[__name__]
    return importlib.import_module("harness.shapes." + name)


def corpus_of(config: dict, docs: int, seed: int):
    """The configuration's corpus at `docs` documents, from its shape's
    generator. The shape gets the `corpus` group with the configuration's
    `similarity` beside it (a text shape scores with its `k1` and `b`)."""
    return shape(config).Corpus(
        docs, seed, config["number_of_shards"],
        dict(config["corpus"], similarity=config["similarity"]))


class Records:
    """What every shape's corpus shares: `n` documents whose `_id` is
    their number, `shard` (each document's shard), `cols` (one value a
    document for each field a query can sort or bucket on), and the
    choice of the best hits. A shape adds `bulk_body`, `evaluate` and,
    where its mixes draw queries, `draw`."""

    n: int
    shard: np.ndarray
    cols: dict

    def top(self, spec: dict, match: np.ndarray, score: np.ndarray):
        """The `size` best docs: by the sort field where the query has
        one, else by score descending; ties shard ascending, then
        document ascending, the order the configuration states."""
        k = spec["size"]
        cand = np.flatnonzero(match)
        if not k or not len(cand):
            return cand[:0]
        if spec.get("sort"):
            key = self.cols[spec["sort"]["field"]][cand].astype(np.float64)
            if spec["sort"]["order"] == "desc":
                key = -key
        else:
            key = -score[cand].astype(np.float64)
        if len(cand) > k:
            # everything that ties with the k-th best stays in
            kth = np.partition(key, k - 1)[k - 1]
            cand, key = cand[key <= kth], key[key <= kth]
        return cand[np.lexsort((cand, self.shard[cand], key))][:k]

    def buckets(self, spec: dict, match: np.ndarray) -> dict:
        h = spec["histogram"]
        slot = self.cols[h["field"]][match] // h["interval_ms"]
        keys, counts = np.unique(slot, return_counts=True)
        return {int(k) * h["interval_ms"]: int(c)
                for k, c in zip(keys, counts)}

    def warm_bodies(self, op: dict) -> list:
        """The bodies that warm an operation up, one for each plan shape
        its requests can have: a fixed body itself; every planned query
        of a drawn operation, since the program pads a plan to a power of
        two of its terms' posting blocks, so each query may be a plan
        shape of its own, and the harness knows no rule of the program's
        to tell which are."""
        return op["bodies"] if "draw" in op else [op["body"]]


class Corpus(Records):
    """`n` log lines drawn from `seed` with the parameters of the
    configuration's `corpus` group. Columns, one value a document:
    `@timestamp` (epoch ms), `status`, `size`, `request.raw` (an integer
    key of the request line; `request_key` gives a line's key)."""

    def __init__(self, n: int, seed: int, n_shards: int, params: dict):
        rng = np.random.default_rng(seed)
        self.n, self.n_shards = n, n_shards
        self.urls = urls(params["urls"], params["root_rank"])
        w = 1.0 / (np.arange(len(self.urls)) + 1.0) ** params["url_zipf"]
        url = rng.choice(len(self.urls), size=n, p=w / w.sum())
        method = rng.choice(len(METHODS), size=n, p=params["method_shares"])
        proto = rng.choice(len(PROTOCOLS), size=n,
                           p=params["protocol_shares"])
        codes = np.array([int(c) for c in params["status_shares"]])
        status = codes[rng.choice(len(codes), size=n, p=np.array(
            list(params["status_shares"].values())))]
        mu, sigma = params["size_lognormal"]
        size = np.minimum(rng.lognormal(mu, sigma, size=n),
                          params["size_max"]).astype(np.int64)
        size[status == 304] = 0         # not modified: no body is sent
        lo, hi = (parse_iso(s) // 1000 for s in params["span"])
        self.ip = rng.integers(0, params["clients"], size=n)
        self.cols = {
            "@timestamp": 1000 * rng.integers(lo, hi, size=n),
            "status": status.astype(np.int64),
            "size": size,
            "request.raw": (url * len(METHODS) + method) * len(PROTOCOLS)
            + proto,
        }
        self.shard = route_shards(n, n_shards)
        self.n_s = np.bincount(self.shard, minlength=n_shards) \
            .astype(np.float64)

    def request_line(self, key: int) -> str:
        um, p = divmod(int(key), len(PROTOCOLS))
        u, m = divmod(um, len(METHODS))
        return f"{METHODS[m]} {self.urls[u]} {PROTOCOLS[p]}"

    def request_key(self, line: str) -> int:
        """The key of a request line; -1 where no document can have it."""
        try:
            m, u, p = line.split(" ")
            return (self.urls.index(u) * len(METHODS) + METHODS.index(m)) \
                * len(PROTOCOLS) + PROTOCOLS.index(p)
        except ValueError:
            return -1

    def bulk_body(self, lo: int, hi: int) -> bytes:
        """`_bulk` lines of docs lo..hi-1. The track's documents carry
        `@timestamp` as epoch seconds; the program reads any number as
        milliseconds, so the same instant goes as an ISO-8601 string,
        which the mapping's `strict_date_optional_time` also admits."""
        ts = self.cols["@timestamp"][lo:hi] // 1000
        stamp = [time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime(t))
                 for t in ts.tolist()]
        ip = self.ip[lo:hi]
        lines = []
        for j, i in enumerate(range(lo, hi)):
            c = int(ip[j])
            lines.append('{"index":{"_id":"%d"}}' % i)
            lines.append(
                '{"@timestamp":"%s","clientip":"%d.%d.%d.0","request":"%s",'
                '"status":%d,"size":%d}' % (
                    stamp[j], 1 + c % 223, (c // 223) % 256,
                    (c // 57088) % 256,
                    self.request_line(self.cols["request.raw"][i]),
                    self.cols["status"][i], self.cols["size"][i]))
        return ("\n".join(lines) + "\n").encode()

    # -- the plain reference -------------------------------------------------

    def clause(self, c: dict, dtype=np.float64):
        """(match mask, score column) of one clause. `score` `one` is the
        constant 1 of a range or a numeric match; `idf` is BM25 of a
        keyword term (tf 1, no norms, so the tf part is 1): the idf of
        the document's own shard."""
        col = self.cols[c["field"]]

        def value(v):
            if c["field"] == "request.raw":
                return self.request_key(v)
            return parse_iso(v) if isinstance(v, str) and "T" in v else int(v)

        if "eq" in c:
            m = col == value(c["eq"])
        else:
            m = np.ones(self.n, bool)
            if "gte" in c:
                m &= col >= value(c["gte"])
            if "lt" in c:
                m &= col < value(c["lt"])
        if c["score"] == "one":
            return m, m.astype(dtype)
        half, one = dtype(0.5), dtype(1.0)
        df = np.bincount(self.shard[m], minlength=self.n_shards).astype(dtype)
        n_s = self.n_s.astype(dtype)
        idf = np.log(one + (n_s - df + half) / (df + half)).astype(dtype)
        return m, np.where(m, idf[self.shard], dtype(0.0)).astype(dtype)

    def evaluate(self, spec: dict, dtype=np.float64):
        """(match mask, score column) of a whole query."""
        if not spec["clauses"]:
            return np.ones(self.n, bool), np.ones(self.n, dtype)
        match = np.ones(self.n, bool)
        score = np.zeros(self.n, dtype)
        for c in spec["clauses"]:
            m, s = self.clause(c, dtype)
            match &= m
            score = (score + s).astype(dtype)
        return match, score


def answer_from(corpus: Corpus, spec: dict, dtype) -> dict:
    """A whole answer computed in `dtype`, in the digest's form: what
    the low-precision control puts in the program's place."""
    match, score = corpus.evaluate(spec, dtype)
    best = corpus.top(spec, match, score)
    out = {"total": int(match.sum()), "ids": [int(d) for d in best],
           "scores": [float(score[d]) for d in best]}
    if spec.get("sort"):
        col = corpus.cols[spec["sort"]["field"]]
        out["sorts"] = [[int(col[d])] for d in best]
    if spec.get("histogram"):
        out["buckets"] = {spec["histogram"]["name"]:
                          corpus.buckets(spec, match)}
    return out


def digest(resp: dict) -> dict:
    """What of a search response is compared: total, ids, scores, sort
    values and the non-empty buckets of each aggregation."""
    hits = resp["hits"]["hits"]
    out = {"total": resp["hits"]["total"],
           "ids": [int(h["_id"]) for h in hits],
           "scores": [h.get("_score") for h in hits]}
    if any("sort" in h for h in hits):
        out["sorts"] = [h.get("sort") for h in hits]
    aggs = resp.get("aggregations")
    if aggs:
        out["buckets"] = {name: {int(b["key"]): b["doc_count"]
                                 for b in a["buckets"] if b["doc_count"]}
                          for name, a in aggs.items()}
    return out


COMPARED = ("total_wrong", "hits_wrong", "buckets_wrong", "order_wrong",
            "sort_wrong", "score_gap", "rank_gap")


class Reference:
    """The float64 reference's answers, each worked out once: requests
    of one query have the same answer. The newest `keep` are held (a
    match mask and a score a document each), which is every one of a mix
    of fixed bodies; a window of drawn queries is compared query by
    query (`run.py:compare_all`)."""

    def __init__(self, corpus: Records, keep: int = 16):
        self.corpus, self.keep = corpus, keep
        self._known: dict[str, tuple] = {}

    def of(self, spec: dict) -> tuple:
        key = json.dumps(spec, sort_keys=True)
        if key not in self._known:
            if len(self._known) >= self.keep:
                del self._known[next(iter(self._known))]
            match, score = self.corpus.evaluate(spec)
            self._known[key] = (match, score,
                                self.corpus.top(spec, match, score))
        return self._known[key]

    def compare(self, spec: dict, got: dict) -> dict:
        """One served answer against the reference. Returns the numbers
        compared:

        - `total_wrong`, `hits_wrong`, `buckets_wrong`, `order_wrong`,
          `sort_wrong`: 0 or 1 (exact comparisons, limit 0).
          `order_wrong` is the tie rule on the served answer itself: hits
          with equal keys come shard ascending, then document ascending.
          `sort_wrong`: the served sort values are not the reference's
          best, or not those of the documents served with them.
        - `score_gap`: the largest |served score - reference score of
          that doc| over the hits, relative to max(1, |reference|).
        - `rank_gap`: how far the reference score of the doc served at
          rank r lies below the reference's r-th best score, relative to
          max(1, that score). A doc that does not match the query reads 1.

        A query sorted by a field is compared by its sort values; its
        scores are not (Elasticsearch gives none there).
        """
        c = self.corpus
        match, score, best = self.of(spec)
        out = dict.fromkeys(COMPARED, 0)
        out["score_gap"] = out["rank_gap"] = 0.0
        out["total_wrong"] = int(got["total"] != int(match.sum()))
        ids = got["ids"]
        if len(ids) != len(best) or len(set(ids)) != len(ids):
            out["hits_wrong"] = 1
        inside = [0 <= d < c.n and bool(match[d]) for d in ids]
        sort = spec.get("sort")
        if sort:
            col = c.cols[sort["field"]]
            served = got.get("sorts") or []
            keys = [s[0] if isinstance(s, list) and len(s) == 1 else None
                    for s in served]
            if keys != [int(col[d]) for d in best] or keys != [
                    int(col[d]) if ok else None
                    for d, ok in zip(ids, inside)]:
                out["sort_wrong"] = 1
            falls = (lambda a, b: a < b) if sort["order"] == "desc" \
                else (lambda a, b: a > b)
        else:
            keys = got["scores"]
            if "sorts" in got:
                out["sort_wrong"] = 1
            falls = lambda a, b: a < b      # noqa: E731
        for r, d in enumerate(ids):
            if not inside[r] or r >= len(best) or keys[r] is None:
                out["rank_gap"] = 1.0
                continue
            if not sort:
                ref_d, ref_r = float(score[d]), float(score[best[r]])
                out["score_gap"] = max(out["score_gap"], abs(
                    keys[r] - ref_d) / max(1.0, abs(ref_d)))
                out["rank_gap"] = max(out["rank_gap"],
                                      (ref_r - ref_d) / max(1.0, abs(ref_r)))
            if r and inside[r - 1] and keys[r - 1] is not None:
                a = ids[r - 1]
                if falls(keys[r - 1], keys[r]) or (
                        keys[r - 1] == keys[r]
                        and (c.shard[a], a) > (c.shard[d], d)):
                    out["order_wrong"] = 1
        if spec.get("histogram"):
            ref_b = {spec["histogram"]["name"]: c.buckets(spec, match)}
            # a digest that came through JSON has its keys as strings
            got_b = {name: {int(k): v for k, v in b.items()}
                     for name, b in (got.get("buckets") or {}).items()}
            out["buckets_wrong"] = int(got_b != ref_b)
        elif got.get("buckets"):
            out["buckets_wrong"] = 1
        return out


def fold(readings: list[dict]) -> dict:
    """A run's numbers: the counts summed, the gaps at their widest."""
    out = {}
    for name in COMPARED:
        vals = [r[name] for r in readings]
        out[name] = (max(vals, default=0.0) if name.endswith("_gap")
                     else int(sum(vals)))
    return out


def judge(numbers: dict, limits: dict) -> bool:
    """Whether every number compared is within the configuration's
    limit for it."""
    return all(numbers[k] <= limits[k] for k in COMPARED)


def bfloat16():
    """The control's precision: the nearest below the float32 that the
    configurations state."""
    import ml_dtypes
    return ml_dtypes.bfloat16
