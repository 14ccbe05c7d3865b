"""Benchmarks for all five BASELINE.json configs, TPU vs CPU baselines.

Prints ONE JSON line PER METRIC (5 lines):

  {"metric": "http_logs_bm25_qps",          "value": ..., "unit": "qps",
   "vs_baseline": ..., "p50_ms": ..., "p99_ms": ...}
  {"metric": "msmarco_bool_bm25_qps",       ...}
  {"metric": "nyc_taxis_terms_agg_ms_per_query",  "unit": "ms", ...}
  {"metric": "nyc_taxis_date_histogram_ms_per_query", ...}
  {"metric": "msmarco_knn_rescore_qps",     ...}

`vs_baseline` is always "x times faster than the CPU baseline":
tpu_qps / cpu_qps for throughput metrics, cpu_ms / tpu_ms for latency
metrics. Baselines are numpy implementations of the SAME algorithmic
family (eager-impact BM25, bincount aggs, exact-matmul kNN) with pinned
seeds, so the ratio isolates the hardware/XLA win and cannot drift run
to run the way a wall-clock-resampled baseline does.

On a TPU backend, configs [0] (http_logs match) and [1] (msmarco bool
must/should) additionally A/B the autotuned fused block-max score+top-k
path against the plain unfused XLA path ("fused_qps" / "xla_qps"
fields). On every backend they gate fused results on doc-id identity
with the unfused path, and EVERY executor workload reports a "fused"
block: admission rate with per-reason rejections, block-prune rate, and
the autotuner's backend choices.

Reference paths these mirror (BASELINE.md):
- BM25 + top-k: search/query/QueryPhase.java:92-168
- terms/date_histogram: bucket/terms/GlobalOrdinalsStringTermsAggregator
  .java:101-116, bucket/histogram/HistogramAggregator.java
- kNN+rescore: BASELINE.json configs[4]
"""

from __future__ import annotations

import json
import os
import random
import sys
import threading
import time

import numpy as np

N_DOCS = int(os.environ.get("BENCH_DOCS", 100_000))
BATCH = int(os.environ.get("BENCH_BATCH", 1024))
N_BATCHES = int(os.environ.get("BENCH_BATCHES", 8))
# HBM-resident analytics scale: Rally's nyc_taxis is ~165M rows; at 20M
# the corpus no longer fits CPU caches (where numpy bincount shines)
# while the TPU column scan barely notices — the scale the hardware
# comparison is honest at. CPU baselines run at the SAME row count.
TAXI_ROWS = int(os.environ.get("BENCH_TAXI_ROWS", 20_000_000))
TAXI_CARD = int(os.environ.get("BENCH_TAXI_CARD", 10_000))
AGG_REPS = int(os.environ.get("BENCH_AGG_REPS", 30))
# HBM-resident vector scale (msmarco-v2 is 138M passages; 50k fits in
# CPU cache). 1M x 256 = 0.5GB bf16 on device; the CPU baseline runs at
# the same scale.
KNN_DOCS = int(os.environ.get("BENCH_KNN_DOCS", 1_000_000))
KNN_DIM = int(os.environ.get("BENCH_KNN_DIM", 256))
KNN_BATCH = int(os.environ.get("BENCH_KNN_BATCH", 256))
TOP_K = 10

COMMON_WORDS = ["images", "french", "english", "venues", "tickets", "news",
                "sport", "history", "results", "teams", "athletes", "medal",
                "schedule", "village", "torch", "ceremony", "host", "city",
                "official", "site", "main", "index", "home", "photos",
                "stories", "accueil", "francais", "anglais", "cgi", "bin"]
METHODS = ["get", "post", "head"]
EXTS = ["html", "gif", "jpg", "cgi", "htm"]
VOCAB_SIZE = int(os.environ.get("BENCH_VOCAB", 4000))


def log(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr)


def pcts(lat_ms: list[float]) -> tuple[float, float]:
    a = np.sort(np.asarray(lat_ms))
    return (float(np.percentile(a, 50)), float(np.percentile(a, 99)))


def throughput_and_latency(batches, dispatch, collect):
    """Two passes over `batches`:

    1. pipelined serving — dispatch EVERY batch async, then collect
       (host bind/dispatch overlaps in-flight device compute; what a
       served QPS number should measure), timed as a whole;
    2. per-batch round trips for p50/p99 latency.

    Returns (total_s, lat_ms list).
    """
    # best of two pipelined passes: the shared device has
    # visible run-to-run contention; the faster pass is the truer
    # hardware number
    totals = []
    for _ in range(2):
        t_all = time.time()
        pending = [dispatch(b) for b in batches]
        for tok in pending:
            collect(tok)
        totals.append(time.time() - t_all)
    total_s = min(totals)
    lat = []
    for b in batches:
        t_b = time.time()
        collect(dispatch(b))
        lat.append((time.time() - t_b) * 1000.0)
    return total_s, lat


def best_time(fn) -> float:
    """min elapsed of two runs — the same best-of-2 discipline the
    pipelined device pass uses, so host contention strips from BOTH
    sides of every vs_baseline ratio."""
    ts = []
    for _ in range(2):
        t0 = time.time()
        fn()
        ts.append(time.time() - t0)
    return min(ts)


def _vocab() -> list[str]:
    return COMMON_WORDS + [f"p{i:05d}" for i in range(VOCAB_SIZE)]


def _fused_reset():
    from elasticsearch_tpu.search import executor as ex
    ex._fused_stats.reset()


def _fused_block() -> dict:
    """Per-workload fused-scoring report: admission rate (with
    per-reason rejections — WHY a plan fell back, and which fused-
    admitted shapes the PALLAS kernel could not serve), block-prune
    rate, the autotuner's backend choices, and the loss audit (shapes
    where the Pallas candidate lost to XLA by >10% — the ROADMAP item-3
    regression signal, gated in _loss_audit_gate). Callers
    _fused_reset() at workload start so the numbers are
    workload-scoped."""
    from elasticsearch_tpu.search import executor as ex
    stats = ex.fused_scoring_stats()
    return {"admission_rate": round(stats["admission"]["rate"], 4),
            "rejected": stats["admission"]["rejected"],
            "pallas_rejected": stats["admission"]["pallas_rejected"],
            "prune_rate": round(stats["prune_rate"], 4),
            "backend_choices": stats["backend_choices"],
            "loss_audit": stats["loss_audit"]}


def _loss_audit_gate(label: str) -> None:
    """HARD gate on real-TPU runs: no fused plan shape where the Pallas
    kernel was admitted as a candidate but lost to XLA by >10% in the
    autotuner's best-of-N. Off-TPU the kernel is never timed, so the
    audit is vacuously clean and the gate is a no-op."""
    import jax
    from elasticsearch_tpu.search import executor as ex
    if jax.default_backend() != "tpu":
        return
    audit = ex.fused_scoring_stats()["loss_audit"]
    if audit["count"]:
        raise AssertionError(
            f"autotuner loss-audit failed ({label}): pallas lost to "
            f"xla by >10% on {audit['count']} shape(s): "
            f"{audit['shapes']}")


def _with_fused_disabled(fn):
    """Run fn with ES_TPU_FUSED=0, restoring the prior env."""
    prior = os.environ.get("ES_TPU_FUSED")
    os.environ["ES_TPU_FUSED"] = "0"
    try:
        return fn()
    finally:
        if prior is None:
            os.environ.pop("ES_TPU_FUSED", None)
        else:
            os.environ["ES_TPU_FUSED"] = prior


def _fused_identity_gate(dispatch_sample, label: str,
                         top_k: int = TOP_K) -> dict | None:
    """Fused-vs-unfused gate over EVERY signature group of a sample
    batch: totals and doc ids must be identical, scores within 1e-5
    (ids are the acceptance contract; scores stay tolerant to FMA-
    contraction ulps across backends). Returns the workload-scoped
    fused report (captured BEFORE the unfused rerun records its own
    'disabled' rejections), or None when fusion is env-disabled.
    Raises when vacuous — nothing was admitted, so the gate proved
    nothing."""
    from elasticsearch_tpu.search import executor as ex
    from elasticsearch_tpu.search.executor import collect_segment_result
    if not ex.fused_enabled():
        return None

    def _collected():
        return [collect_segment_result(o, l, n_)
                for o, l, n_ in dispatch_sample()]

    res_f = _collected()
    fused_report = _fused_block()
    res_u = _with_fused_disabled(_collected)
    for (hits_f, _af), (hits_u, _au) in zip(res_f, res_u):
        ts_f, _tkf, ti_f, tt_f, _tmf = hits_f
        ts_u, _tku, ti_u, tt_u, _tmu = hits_u
        if not (tt_f == tt_u).all():
            raise AssertionError(f"fused/unfused total mismatch ({label})")
        for qi in range(ts_f.shape[0]):
            n_check = min(int(tt_u[qi]), top_k)
            if not (ti_f[qi][:n_check] == ti_u[qi][:n_check]).all():
                raise AssertionError(
                    f"fused/unfused doc-id mismatch ({label})")
            if not np.allclose(ts_f[qi][:n_check], ts_u[qi][:n_check],
                               atol=1e-5, rtol=1e-5):
                raise AssertionError(
                    f"fused/unfused score mismatch ({label})")
    stats = ex.fused_scoring_stats()
    if stats["dispatches"] <= 0:
        raise AssertionError(
            f"fused path was never admitted ({label}); the "
            "fused/unfused identity gate is vacuous")
    _loss_audit_gate(label)
    return fused_report


def _fused_tpu_ab(out: dict, measured_run, n_done: int) -> None:
    """TPU-only A/B: re-measure the workload with fusion AND the Pallas
    kernels disabled (the BENCH_r05 unfused-XLA lineage) and report
    fused_qps / xla_qps. One definition for every workload — the env
    save/restore + cache-clear choreography must not fork per bench."""
    import jax
    from elasticsearch_tpu.search import executor as ex
    from elasticsearch_tpu.ops import pallas_scoring as ps
    if jax.default_backend() != "tpu" or not ex.fused_enabled():
        return
    out["fused_qps"] = out["value"]
    prior_f = os.environ.get("ES_TPU_FUSED")
    prior_p = os.environ.get("ES_TPU_PALLAS")
    os.environ["ES_TPU_FUSED"] = "0"
    os.environ["ES_TPU_PALLAS"] = "0"
    ps.pallas_enabled.cache_clear()
    ex._segment_program_packed.clear_cache()
    try:
        measured_run()   # recompile + warm the unfused path
        other_s, _ = measured_run()
        out["xla_qps"] = round(n_done / other_s, 1)
    finally:
        for var, prior in (("ES_TPU_FUSED", prior_f),
                           ("ES_TPU_PALLAS", prior_p)):
            if prior is None:
                os.environ.pop(var, None)
            else:
                os.environ[var] = prior
        ps.pallas_enabled.cache_clear()
        ex._segment_program_packed.clear_cache()


def _zipf_weights(n: int) -> list[float]:
    w = [1.0 / (i + 3) ** 0.9 for i in range(n)]
    total = sum(w)
    return [x / total for x in w]


def make_corpus(n: int, seed: int = 42):
    rng = random.Random(seed)
    vocab = _vocab()
    weights = _zipf_weights(len(vocab))

    def pick():
        return rng.choices(vocab, weights=weights)[0]

    zipf_paths = [[pick() for _ in range(rng.randint(2, 5))]
                  + [rng.choice(EXTS)] for _ in range(max(n // 25, 400))]
    docs = []
    for i in range(n):
        p = zipf_paths[min(int(rng.paretovariate(1.2)) - 1,
                           len(zipf_paths) - 1)]
        msg = " ".join([rng.choice(METHODS)] + p
                       + [str(rng.choice([200, 200, 200, 404, 304]))])
        docs.append((str(i), {"message": msg,
                              "size": rng.randint(100, 100_000),
                              "status": str(rng.choice(
                                  [200, 200, 200, 404, 500]))}))
    return docs


def make_queries(n: int, seed: int = 7, k_max: int = 3):
    rng = random.Random(seed)
    vocab = _vocab()
    head = vocab[: max(len(vocab) // 8, 30)]
    weights = _zipf_weights(len(head))
    return [" ".join(rng.choices(head, weights=weights,
                                 k=rng.randint(1, k_max)))
            for _ in range(n)]


# ---------------------------------------------------------------------------
# CPU baseline: CSR eager-impact scorer (BM25S-style)
# ---------------------------------------------------------------------------


class CpuBM25:
    def __init__(self, seg, field: str = "message"):
        pf = seg.text[field]
        self.term_index = pf.term_index
        self.indptr = pf.indptr
        self.doc_ids = pf.doc_ids
        from elasticsearch_tpu.index.segment import BM25_K1, BM25_B, bm25_idf
        idf = bm25_idf(pf.df.astype(np.float64), pf.doc_count)
        k_d = BM25_K1 * (1 - BM25_B + BM25_B * pf.doc_len / pf.avg_len)
        imps = np.empty_like(pf.tfs, dtype=np.float32)
        for t in range(len(pf.terms)):
            s, e = int(pf.indptr[t]), int(pf.indptr[t + 1])
            tf = pf.tfs[s:e].astype(np.float64)
            imps[s:e] = idf[t] * tf * (BM25_K1 + 1.0) / (
                tf + k_d[pf.doc_ids[s:e]])
        self.imps = imps
        self.n = seg.capacity

    def _scores(self, qterms: list[str]) -> np.ndarray:
        scores = np.zeros(self.n, dtype=np.float32)
        for t in qterms:
            tid = self.term_index.get(t, -1)
            if tid < 0:
                continue
            s, e = int(self.indptr[tid]), int(self.indptr[tid + 1])
            if e - s < 2048:
                scores[self.doc_ids[s:e]] += self.imps[s:e]
            else:
                scores += np.bincount(self.doc_ids[s:e],
                                      weights=self.imps[s:e],
                                      minlength=self.n).astype(np.float32)
        return scores

    def search(self, qterms: list[str], k: int):
        scores = self._scores(qterms)
        idx = np.argpartition(scores, -k)[-k:]
        order = idx[np.argsort(-scores[idx], kind="stable")]
        return order, scores[order]

    def search_bool(self, must: list[str], should: list[str], k: int):
        """bool must (required, scored) + should (optional, scored)."""
        scores = self._scores(must + should)
        for t in must:
            tid = self.term_index.get(t, -1)
            mask = np.zeros(self.n, dtype=bool)
            if tid >= 0:
                s, e = int(self.indptr[tid]), int(self.indptr[tid + 1])
                mask[self.doc_ids[s:e]] = True
            scores = np.where(mask, scores, 0.0)
        idx = np.argpartition(scores, -k)[-k:]
        order = idx[np.argsort(-scores[idx], kind="stable")]
        return order, scores[order]


def build_segment(docs, mapping):
    from elasticsearch_tpu.index.mapping import MapperService
    from elasticsearch_tpu.index.segment import SegmentBuilder
    svc = MapperService(mapping=mapping)
    builder = SegmentBuilder()
    for did, d in docs:
        builder.add(svc.parse(did, d))
    seg = builder.build("bench")
    live = np.zeros(seg.capacity, dtype=bool)
    live[: seg.num_docs] = True
    return svc, seg, live


# ---------------------------------------------------------------------------
# config[0]: http_logs match BM25 QPS (+ pallas A/B on TPU)
# ---------------------------------------------------------------------------


def bench_http_logs() -> dict:
    import jax
    from elasticsearch_tpu.search.query_dsl import QueryParser
    from elasticsearch_tpu.search.executor import (
        QueryBinder, execute_segment_async, collect_segment_result)

    _fused_reset()
    t0 = time.time()
    docs = make_corpus(N_DOCS)
    svc, seg, live = build_segment(docs, {"properties": {
        "message": {"type": "text"},
        "size": {"type": "long"},
        "status": {"type": "keyword"}}})
    log(f"http_logs: {N_DOCS} docs, {len(seg.text['message'].terms)} "
        f"terms, built in {time.time()-t0:.1f}s")

    queries = make_queries(BATCH * (N_BATCHES + 2))
    parser = QueryParser(svc)
    binder = QueryBinder(seg, svc)

    def dispatch_batch(batch_queries):
        bounds = [binder.bind(parser.parse({"bool": {"should": [
            {"match": {"message": q}}], "minimum_should_match": 1}}))
            for q in batch_queries]
        sig_groups = {}
        for b in bounds:
            sig_groups.setdefault(b.signature(), []).append(b)
        return [execute_segment_async(seg, live, group, TOP_K)
                for group in sig_groups.values()]

    batches = [queries[(i + 2) * BATCH: (i + 3) * BATCH]
               for i in range(N_BATCHES)]

    def collect_all(outs):
        for out, lay, n in outs:
            collect_segment_result(out, lay, n)

    def measured_run():
        return throughput_and_latency(batches, dispatch_batch, collect_all)

    t0 = time.time()
    measured_run()  # warmup incl. compiles
    log(f"http_logs warmup (incl. compiles): {time.time()-t0:.1f}s")
    total_s, lat = measured_run()
    n_done = sum(len(b) for b in batches)
    qps = n_done / total_s
    p50, p99 = pcts(lat)

    # CPU baseline (pinned seed corpus/queries -> stable denominator)
    cpu = CpuBM25(seg)
    analyzer = svc.analysis.analyzer("standard")
    cpu_queries = queries[2 * BATCH: 2 * BATCH + 128]
    cpu_qps = len(cpu_queries) / best_time(
        lambda: [cpu.search(analyzer.analyze(q), TOP_K)
                 for q in cpu_queries])

    # matched-recall gate on a sample
    sample = batches[0][:8]
    out0, lay0, n0 = dispatch_batch(sample)[0]
    (ts, _tk, ti, tt, _tm), _aggs = collect_segment_result(out0, lay0, n0)
    for qi, q in enumerate(sample):
        cpu_ids, cpu_scores = cpu.search(analyzer.analyze(q), TOP_K)
        n_check = min(int(tt[qi]), TOP_K)
        if not np.allclose(ts[qi][:n_check], cpu_scores[:n_check],
                           rtol=1e-4):
            raise AssertionError(f"score mismatch for {q!r}")
        if n_check >= 2 and cpu_scores[0] - cpu_scores[1] > 1e-3 * abs(
                cpu_scores[0]) and int(ti[qi][0]) != int(cpu_ids[0]):
            raise AssertionError(f"top-doc mismatch for {q!r}")

    out = {"metric": "http_logs_bm25_qps", "value": round(qps, 1),
           "unit": "qps", "vs_baseline": round(qps / cpu_qps, 2),
           "p50_ms": round(p50, 1), "p99_ms": round(p99, 1)}

    # fused-vs-unfused identity gate (any backend) + workload report
    fused_report = _fused_identity_gate(
        lambda: dispatch_batch(sample), "http_logs")
    if fused_report is not None:
        out["fused"] = fused_report

    # fused-autotuned vs plain unfused XLA A/B (TPU only: the round-5
    # xla_qps lineage this PR's acceptance bar is measured against)
    from elasticsearch_tpu.search import executor as ex
    if jax.default_backend() == "tpu" and not ex.fused_enabled():
        # fusion disabled for the measured run: no fused number to A/B
        # against. The unfused run still uses the Pallas kernels unless
        # those were ALSO disabled — label the lineage accordingly
        from elasticsearch_tpu.ops import pallas_scoring as ps
        out["xla_qps" if not ps.pallas_enabled() else "pallas_qps"] = \
            out["value"]
    else:
        _fused_tpu_ab(out, measured_run, n_done)
    return out


# ---------------------------------------------------------------------------
# config[1]: msmarco-style bool must/should multi-term BM25 QPS
# ---------------------------------------------------------------------------


def bench_bool_msmarco() -> dict:
    import jax
    from elasticsearch_tpu.search.query_dsl import QueryParser
    from elasticsearch_tpu.search.executor import (
        QueryBinder, execute_segment_async, collect_segment_result)

    _fused_reset()
    n = max(N_DOCS // 2, 10_000)
    rng = random.Random(11)
    vocab = _vocab()
    weights = _zipf_weights(len(vocab))
    t0 = time.time()
    docs = []
    for i in range(n):
        # passage-like docs: 20-60 tokens
        words = rng.choices(vocab, weights=weights,
                            k=rng.randint(20, 60))
        docs.append((str(i), {"passage": " ".join(words)}))
    svc, seg, live = build_segment(docs, {"properties": {
        "passage": {"type": "text"}}})
    log(f"msmarco: {n} passages, {len(seg.text['passage'].terms)} terms, "
        f"built in {time.time()-t0:.1f}s")

    rngq = random.Random(13)
    head = vocab[: max(len(vocab) // 8, 30)]
    wts = _zipf_weights(len(head))
    pairs = []
    for _ in range(BATCH // 2 * (N_BATCHES + 1)):
        must = rngq.choices(head, weights=wts, k=1)
        should = rngq.choices(head, weights=wts, k=rngq.randint(2, 4))
        pairs.append((must, should))

    parser = QueryParser(svc)
    binder = QueryBinder(seg, svc)

    def body(must, should):
        return {"bool": {
            "must": [{"match": {"passage": t}} for t in must],
            "should": [{"match": {"passage": t}} for t in should]}}

    def dispatch(batch):
        bounds = [binder.bind(parser.parse(body(m, s_)))
                  for m, s_ in batch]
        groups = {}
        for b in bounds:
            groups.setdefault(b.signature(), []).append(b)
        return [execute_segment_async(seg, live, g, TOP_K)
                for g in groups.values()]

    bsz = BATCH // 2
    batches = [pairs[(i + 1) * bsz: (i + 2) * bsz]
               for i in range(N_BATCHES)]

    def collect_all(outs):
        for out, lay, n_ in outs:
            collect_segment_result(out, lay, n_)

    def run():
        return throughput_and_latency(batches, dispatch, collect_all)

    t0 = time.time()
    run()
    log(f"msmarco warmup: {time.time()-t0:.1f}s")
    total_s, lat = run()
    n_done = sum(len(b) for b in batches)
    qps = n_done / total_s
    p50, p99 = pcts(lat)

    cpu = CpuBM25(seg, "passage")
    analyzer = svc.analysis.analyzer("standard")
    cpu_pairs = pairs[:96]
    cpu_qps = len(cpu_pairs) / best_time(
        lambda: [cpu.search_bool(
            [w for t in m for w in analyzer.analyze(t)],
            [w for t in s_ for w in analyzer.analyze(t)], TOP_K)
            for m, s_ in cpu_pairs])
    out = {"metric": "msmarco_bool_bm25_qps", "value": round(qps, 1),
           "unit": "qps", "vs_baseline": round(qps / cpu_qps, 2),
           "p50_ms": round(p50, 1), "p99_ms": round(p99, 1)}

    # fused-vs-unfused identity gate (any backend): the block-max-WAND
    # bool engine must return the SAME doc ids and totals as the
    # unfused full-matrix path — checked over every signature group of
    # a sample batch — plus the workload fused report
    fused_report = _fused_identity_gate(
        lambda: dispatch(batches[0][:16]), "msmarco_bool")
    if fused_report is not None:
        out["fused"] = fused_report

    # fused-autotuned vs plain unfused XLA A/B (TPU only) — the
    # msmarco_bool acceptance bar is measured against BENCH_r05's
    # unfused lineage
    _fused_tpu_ab(out, run, n_done)
    return out


def _with_positional_disabled(fn):
    """Run fn with ES_TPU_POSITIONAL=0 (phrase/span/BM25F served by the
    host oracle, search/phrase.py), restoring the prior env."""
    prior = os.environ.get("ES_TPU_POSITIONAL")
    os.environ["ES_TPU_POSITIONAL"] = "0"
    try:
        return fn()
    finally:
        if prior is None:
            os.environ.pop("ES_TPU_POSITIONAL", None)
        else:
            os.environ["ES_TPU_POSITIONAL"] = prior


def bench_phrase_heavy() -> dict:
    """Positional scoring on device (ISSUE 20): an msmarco-shaped
    workload where every query carries a positional clause — exact and
    sloppy phrases, ordered/unordered span_near, and multi_match
    cross_fields (BM25F) over title+passage. A/B is ES_TPU_POSITIONAL:
    on = phrase/span/BM25F evaluated per tile inside the fused bundle
    engines against the fwd_pos column family; off = the host oracle
    loops (search/phrase.py). The A/B is identity-gated per query, the
    run hard-fails if the device positional path was never dispatched,
    and on TPU the fused p50 must come in at <= 0.5x the host oracle's.
    """
    import jax
    from elasticsearch_tpu.search.query_dsl import QueryParser
    from elasticsearch_tpu.search.executor import (
        QueryBinder, execute_segment_async, collect_segment_result)
    from elasticsearch_tpu.search import executor as ex

    _fused_reset()
    n = max(N_DOCS // 2, 10_000)
    rng = random.Random(17)
    vocab = _vocab()
    weights = _zipf_weights(len(vocab))
    t0 = time.time()
    docs, texts = [], []
    for i in range(n):
        words = rng.choices(vocab, weights=weights,
                            k=rng.randint(20, 60))
        title = rng.choices(vocab, weights=weights,
                            k=rng.randint(3, 8))
        texts.append(words)
        docs.append((str(i), {"title": " ".join(title),
                              "passage": " ".join(words)}))
    svc, seg, live = build_segment(docs, {"properties": {
        "title": {"type": "text"}, "passage": {"type": "text"}}})
    pf = seg.text["passage"]
    log(f"phrase_heavy: {n} passages, pos_width={pf.pos_width}, "
        f"built in {time.time()-t0:.1f}s")

    # queries sampled from real passages so phrases actually land:
    # 40% match_phrase (exact + sloppy), 30% span_near, 30% BM25F
    rngq = random.Random(19)
    bodies = []
    for _ in range(BATCH // 2 * (N_BATCHES + 1)):
        src = texts[rngq.randrange(len(texts))]
        j = rngq.randrange(len(src) - 3)
        r = rngq.random()
        if r < 0.4:
            ln = 3 if rngq.random() < 0.3 else 2
            bodies.append({"match_phrase": {"passage": {
                "query": " ".join(src[j:j + ln]),
                "slop": rngq.choice([0, 0, 1, 2])}}})
        elif r < 0.7:
            bodies.append({"span_near": {"clauses": [
                {"span_term": {"passage": src[j]}},
                {"span_term": {"passage": src[j + 2]}}],
                "slop": rngq.choice([2, 3, 4]),
                "in_order": rngq.random() < 0.5}})
        else:
            bodies.append({"multi_match": {
                "query": " ".join(src[j:j + 2]),
                "type": "cross_fields",
                "fields": ["title^2", "passage"]}})

    parser = QueryParser(svc)
    binder = QueryBinder(seg, svc)

    def dispatch(batch):
        bounds = [binder.bind(parser.parse(b)) for b in batch]
        groups = {}
        for b in bounds:
            groups.setdefault(b.signature(), []).append(b)
        return [execute_segment_async(seg, live, g, TOP_K)
                for g in groups.values()]

    bsz = BATCH // 2
    batches = [bodies[(i + 1) * bsz: (i + 2) * bsz]
               for i in range(N_BATCHES)]

    def collect_all(outs):
        for out_, lay, n_ in outs:
            collect_segment_result(out_, lay, n_)

    def run():
        return throughput_and_latency(batches, dispatch, collect_all)

    t0 = time.time()
    run()
    log(f"phrase_heavy warmup: {time.time()-t0:.1f}s")
    total_s, lat = run()
    n_done = sum(len(b) for b in batches)
    p50, p99 = pcts(lat)

    # hard gate: the workload must actually exercise the device
    # positional path — a silent all-host-fallback bench would report a
    # meaningless A/B
    stats = ex.fused_scoring_stats()
    if stats["positional"]["dispatches"] <= 0:
        raise AssertionError(
            "phrase_heavy: zero fused positional dispatches — every "
            "query fell back to the host oracle "
            f"(fallbacks={stats['admission']['positional_fallbacks']})")
    pos_report = {
        "dispatches": stats["positional"]["dispatches"],
        "tiles": stats["positional"]["tiles"],
        "prune_rate": round(stats["positional"]["prune_rate"], 4),
        "admitted": stats["admission"]["positional_admitted"],
        "fallbacks": stats["admission"]["positional_fallbacks"]}

    # per-query identity gate vs the host oracle (grouping differs
    # between the two binders, so compare one query at a time)
    def _per_query(sample):
        out_ = []
        for b in sample:
            res = execute_segment_async(
                seg, live, [binder.bind(parser.parse(b))], TOP_K)
            out_.append(collect_segment_result(*res))
        return out_

    sample = batches[0][:24]
    res_f = _per_query(sample)
    res_h = _with_positional_disabled(lambda: _per_query(sample))
    for qi, ((hits_f, _af), (hits_h, _ah)) in enumerate(zip(res_f, res_h)):
        ts_f, _tkf, ti_f, tt_f, _tmf = hits_f
        ts_h, _tkh, ti_h, tt_h, _tmh = hits_h
        if not (tt_f == tt_h).all():
            raise AssertionError(
                f"phrase_heavy: device/host total mismatch on "
                f"{sample[qi]}")
        n_check = min(int(tt_h[0]), TOP_K)
        if not (ti_f[0][:n_check] == ti_h[0][:n_check]).all() or \
                not (ts_f[0][:n_check] == ts_h[0][:n_check]).all():
            raise AssertionError(
                f"phrase_heavy: device/host hit mismatch on "
                f"{sample[qi]}")

    # host-oracle A/B: the same measured run with ES_TPU_POSITIONAL=0
    def _host_run():
        _with_positional_disabled(run)              # warm the host path
        other_s, lat_h = _with_positional_disabled(run)
        return pcts(lat_h)[0]

    host_p50 = _host_run()
    out = {"metric": "phrase_heavy_p50_ms", "value": round(p50, 1),
           "unit": "ms", "vs_baseline": round(host_p50 / p50, 2),
           "p50_ms": round(p50, 1), "p99_ms": round(p99, 1),
           "qps": round(n_done / total_s, 1),
           "host_oracle_p50_ms": round(host_p50, 1),
           "positional": pos_report}
    # acceptance bar (TPU only — on CPU the "device" path is XLA
    # emulation and the bar says nothing): fused p50 <= 0.5x host
    if jax.default_backend() == "tpu" and p50 > 0.5 * host_p50:
        raise AssertionError(
            f"phrase_heavy: fused p50 {p50:.1f}ms > 0.5x host oracle "
            f"{host_p50:.1f}ms — the device positional path must at "
            "least halve phrase-heavy latency")
    _loss_audit_gate("phrase_heavy")
    return out


# ---------------------------------------------------------------------------
# unbatched traffic: serial vs coalesced vs pipelined msearch dispatch
# ---------------------------------------------------------------------------


DISPATCH_DOCS = int(os.environ.get("BENCH_DISPATCH_DOCS", 12_000))
DISPATCH_N = int(os.environ.get("BENCH_DISPATCH_N", 8))


def _strip_timing(resp: dict) -> str:
    return json.dumps({k: v for k, v in resp.items()
                       if k not in ("took", "status")},
                      sort_keys=True, default=str)


def bench_unbatched_traffic(round_trip_ms: float) -> dict:
    """The single-query latency gap scenario: N concurrent single-query
    msearch items vs the serial per-request loop. Coalesced = N
    identical-shape queries (ONE batched dispatch through the scheduler);
    pipelined = N heterogeneous shapes (back-to-back async dispatches,
    overlapped round trips). Identity-gated: the msearch items must be
    byte-identical (minus took/status) to the serial responses. Records
    the nodes_stats()["dispatch"] counters alongside."""
    from elasticsearch_tpu.node import Node

    N = DISPATCH_N
    t0 = time.time()
    docs = make_corpus(DISPATCH_DOCS)
    node = Node({"index.number_of_shards": 1})
    node.create_index("http_logs", mappings={"properties": {
        "message": {"type": "text"},
        "size": {"type": "long"},
        "status": {"type": "keyword"}}})
    for did, d in docs:
        node.index_doc("http_logs", did, d)
    node.refresh("http_logs")
    log(f"unbatched_traffic: {DISPATCH_DOCS} docs ingested in "
        f"{time.time()-t0:.1f}s")

    rng = random.Random(29)
    head = _vocab()[: 400]
    # identical-shape items: one single-term match each -> same plan
    # signature, ONE batched device dispatch for all N
    co_items = [("http_logs",
                 {"query": {"match": {"message": rng.choice(head)}},
                  "size": TOP_K}) for _ in range(N)]
    # heterogeneous shapes: i+1 should-terms -> N distinct plans, no
    # coalescing possible; the scheduler must PIPELINE their dispatches
    pipe_items = [("http_logs",
                   {"query": {"bool": {"should": [
                       {"match": {"message": rng.choice(head)}}
                       for _ in range(i + 1)],
                       "minimum_should_match": 1}},
                    "size": TOP_K}) for i in range(N)]

    def serial(items):
        return [node.search(i, dict(b)) for i, b in items]

    def batched(items):
        return node.msearch([(i, dict(b)) for i, b in items])["responses"]

    def p50_of(fn, items, reps):
        lat = []
        for _ in range(reps):
            t = time.time()
            fn(items)
            lat.append((time.time() - t) * 1000.0)
        return float(np.percentile(np.asarray(lat), 50))

    reps = max(AGG_REPS // 3, 5)
    out = {"metric": "unbatched_traffic_msearch_p50_ms", "unit": "ms",
           "n_queries": N, "docs": DISPATCH_DOCS}
    for label, items in (("coalesced", co_items), ("pipelined",
                                                   pipe_items)):
        # identity gate FIRST (doubles as compile warmup for both paths)
        want = serial(items)
        got = batched(items)
        for w, g in zip(want, got):
            if _strip_timing(w) != _strip_timing(g):
                raise AssertionError(
                    f"serial/{label} msearch responses differ")
        serial_p50 = p50_of(serial, items, reps)
        msearch_p50 = p50_of(batched, items, reps)
        out[f"serial_{label}_p50_ms"] = round(serial_p50, 2)
        out[f"{label}_p50_ms"] = round(msearch_p50, 2)
        out[f"{label}_speedup"] = round(serial_p50 / msearch_p50, 2) \
            if msearch_p50 > 0 else float("inf")
        # acceptance gate: with a real per-dispatch round trip, N
        # coalesced/pipelined single queries must cost <= 0.5x the
        # serial loop. On a local backend with no per-dispatch round trip (CPU CI) the flat
        # overhead the scheduler amortizes is near zero, so the ratio
        # is reported but not gated.
        if round_trip_ms > 5.0 and msearch_p50 > 0.5 * serial_p50:
            raise AssertionError(
                f"{label} msearch p50 {msearch_p50:.1f}ms > 0.5x serial "
                f"{serial_p50:.1f}ms")
    out["value"] = out["coalesced_p50_ms"]
    out["vs_baseline"] = out["coalesced_speedup"]
    ds = node.nodes_stats()["nodes"][node.name]["dispatch"]
    out["dispatch"] = {"queries": ds["queries"],
                       "coalesced_queries": ds["coalesced_queries"],
                       "batches_dispatched": ds["batches_dispatched"],
                       "pipeline_depth": ds["pipeline_depth"],
                       "window_hit_rate": round(
                           ds["window"]["hit_rate"], 4)}
    node.close()
    return out


def bench_overload_mixed_tenant(round_trip_ms: float) -> dict:
    """Traffic control plane under overload (search/traffic.py): a
    quota'd bulk tenant floods msearch from background threads while an
    unconfigured interactive tenant streams lone queries.

    Gates (per-dispatch round trip; reported-only on local CI with no per-dispatch round trip):
      * interactive p99 under the flood <= 2x its unloaded p99 — the
        priority lanes + admission shed protect the interactive class;
      * the bulk tenant is THROTTLED, never errored: shed items are
        structured 429s carrying retry_after, zero 5xx, and some items
        still make real progress;
      * the hot-query leg's repeat p50 <= 0.1x the device-dispatch p50
        — a warm generation-keyed cache hit skips the device entirely.
    """
    from elasticsearch_tpu.node import Node

    t0 = time.time()
    docs = make_corpus(DISPATCH_DOCS)
    node = Node({
        "index.number_of_shards": 1,
        # the bulk tenant: token-bucket quota + the bulk drain lane
        "search.traffic.tenant.bulk.rate": 200,
        "search.traffic.tenant.bulk.burst": 50,
        "search.traffic.tenant.bulk.lane": "bulk",
    })
    try:
        return _overload_mixed_tenant_body(node, docs, t0, round_trip_ms)
    finally:
        # close in finally: an assertion gate raising must not leak the
        # node's pools/scheduler into later scenarios (PR 9's
        # bench_concurrent_index_search lesson)
        node.close()


def _overload_mixed_tenant_body(node, docs, t0, round_trip_ms: float) -> dict:
    node.create_index("http_logs", mappings={"properties": {
        "message": {"type": "text"},
        "size": {"type": "long"},
        "status": {"type": "keyword"}}},
        settings={"index": {"cache": {"query": {
            "enable": True, "include_hits": True}}}})
    for did, d in docs:
        node.index_doc("http_logs", did, d)
    node.refresh("http_logs")
    log(f"overload_mixed_tenant: {DISPATCH_DOCS} docs ingested in "
        f"{time.time()-t0:.1f}s")

    rng = random.Random(31)
    head = _vocab()[: 400]

    def lone_body():
        # query_cache=False: the interactive leg measures REAL device
        # latency under load, not cache hits (the cache leg is below)
        return {"query": {"match": {"message": rng.choice(head)}},
                "size": TOP_K, "query_cache": False}

    inter_bodies = [lone_body() for _ in range(40)]
    flood_items = [("http_logs", lone_body()) for _ in range(8)]

    def interactive_leg():
        lat = []
        for b in inter_bodies:
            t = time.time()
            node.search("http_logs", dict(b))
            lat.append((time.time() - t) * 1000.0)
        return lat

    interactive_leg()                       # compile/warm both paths
    unloaded = interactive_leg()
    unloaded_p50, unloaded_p99 = pcts(unloaded)

    # -- the storm: background bulk msearch flood + interactive stream
    stop = threading.Event()
    flood_counts = {200: 0, 429: 0, "other": 0, "retry_after_missing": 0}
    counts_mx = threading.Lock()   # += from 3 threads is not atomic

    def flood():
        while not stop.is_set():
            resp = node.msearch(
                [(i, dict(b)) for i, b in flood_items], tenant="bulk")
            with counts_mx:
                for item in resp["responses"]:
                    s = item.get("status", 200)
                    if s == 200:
                        flood_counts[200] += 1
                    elif s == 429:
                        flood_counts[429] += 1
                        if not item.get("retry_after"):
                            flood_counts["retry_after_missing"] += 1
                    else:
                        flood_counts["other"] += 1
            # minimal client pacing: a zero-sleep spin measures GIL
            # starvation of the shed path itself (thousands of py
            # exception allocations/s), not the lanes under load
            time.sleep(0.001)

    threads = [threading.Thread(target=flood) for _ in range(3)]
    for th in threads:
        th.start()
    try:
        # warmup UNDER load first: coalescing with flood batches pads
        # to larger pow2 buckets than the unloaded leg ever exercised,
        # and the one-time XLA compile for a fresh bucket would
        # otherwise land in the measured p99 as a fake starvation spike
        interactive_leg()
        loaded = interactive_leg()
    finally:
        stop.set()
        for th in threads:
            th.join()
    loaded_p50, loaded_p99 = pcts(loaded)

    if flood_counts["other"]:
        raise AssertionError(
            f"bulk flood surfaced non-429 errors: {flood_counts}")
    if flood_counts[429] == 0:
        raise AssertionError("flood never tripped admission control")
    if flood_counts["retry_after_missing"]:
        raise AssertionError(
            f"{flood_counts['retry_after_missing']} shed items lacked "
            f"retry_after")
    if flood_counts[200] == 0:
        raise AssertionError("bulk tenant was starved outright, not "
                             "throttled")
    if round_trip_ms > 5.0 and loaded_p99 > 2.0 * unloaded_p99:
        raise AssertionError(
            f"interactive p99 {loaded_p99:.1f}ms > 2x unloaded "
            f"{unloaded_p99:.1f}ms under bulk flood")

    # -- hot-query leg: the generation-keyed device-skip cache
    hot = {"query": {"match": {"message": head[0]}}, "size": TOP_K}
    distinct = [{"query": {"match": {"message": w}}, "size": TOP_K}
                for w in head[100:100 + 20]]
    miss_lat = []
    for b in distinct:                      # all first-times: device
        t = time.time()
        node.search("http_logs", dict(b))
        miss_lat.append((time.time() - t) * 1000.0)
    node.search("http_logs", dict(hot))     # prime the entry
    hit_lat = []
    for _ in range(20):                     # all repeats: cache
        t = time.time()
        node.search("http_logs", dict(hot))
        hit_lat.append((time.time() - t) * 1000.0)
    miss_p50, _ = pcts(miss_lat)
    hit_p50, _ = pcts(hit_lat)
    if round_trip_ms > 5.0 and hit_p50 > 0.1 * miss_p50:
        raise AssertionError(
            f"hot repeat p50 {hit_p50:.2f}ms > 0.1x device-dispatch "
            f"p50 {miss_p50:.2f}ms — cache hit still paid a dispatch")

    ds = node.nodes_stats()["nodes"][node.name]["dispatch"]
    traffic = ds["traffic"]
    out = {"metric": "overload_mixed_tenant_p99_ms", "unit": "ms",
           "value": round(loaded_p99, 2),
           "unloaded_p50_ms": round(unloaded_p50, 2),
           "unloaded_p99_ms": round(unloaded_p99, 2),
           "loaded_p50_ms": round(loaded_p50, 2),
           "loaded_p99_ms": round(loaded_p99, 2),
           "p99_degradation": round(loaded_p99 / unloaded_p99, 2)
           if unloaded_p99 > 0 else float("inf"),
           "vs_baseline": round(unloaded_p99 / loaded_p99, 2)
           if loaded_p99 > 0 else float("inf"),
           "bulk_admitted": flood_counts[200],
           "bulk_rejected_429": flood_counts[429],
           "bulk_5xx": flood_counts["other"],
           "hot_query_hit_p50_ms": round(hit_p50, 3),
           "device_dispatch_p50_ms": round(miss_p50, 2),
           "cache_hit_rate": round(
               traffic["query_cache"]["hit_rate"], 4),
           "lane_depth_high_water": {
               lane: s["depth_high_water"]
               for lane, s in traffic["lanes"].items()},
           "adaptive_window_ms": traffic["window"]["last_window_ms"]}
    return out


def bench_lone_query(round_trip_ms: float) -> dict:
    """The LONE-query scenario the dispatch scheduler cannot help: a
    single request with no concurrent traffic pays one full synchronous
    dispatch on the cold path. The resident query loop
    (ES_TPU_RESIDENT_LOOP, search/resident.py) serves it from a pinned
    AOT executable with a donated, async-staged param feed instead.
    Identity-gated (resident responses must be byte-identical to cold,
    minus took); with a per-dispatch round trip the resident p50 must come in at
    <= 0.6x the cold-dispatch p50. Reports the
    nodes_stats()["dispatch"]["resident"] counters alongside."""
    from elasticsearch_tpu.node import Node
    from elasticsearch_tpu.search import resident as resident_mod

    t0 = time.time()
    docs = make_corpus(DISPATCH_DOCS)
    node = Node({"index.number_of_shards": 1})
    node.create_index("http_logs", mappings={"properties": {
        "message": {"type": "text"},
        "size": {"type": "long"},
        "status": {"type": "keyword"}}})
    for did, d in docs:
        node.index_doc("http_logs", did, d)
    node.refresh("http_logs")
    log(f"lone_query: {DISPATCH_DOCS} docs ingested in "
        f"{time.time()-t0:.1f}s")

    rng = random.Random(37)
    head = _vocab()[: 400]
    bodies = [{"query": {"match": {"message": rng.choice(head)}},
               "size": TOP_K} for _ in range(16)]
    reps = max(AGG_REPS // 3, 5)

    def p50_run():
        lat = []
        for _ in range(reps):
            for b in bodies:
                t = time.time()
                node.search("http_logs", dict(b))
                lat.append((time.time() - t) * 1000.0)
        return float(np.percentile(np.asarray(lat), 50))

    had = os.environ.pop("ES_TPU_RESIDENT_LOOP", None)
    try:
        for b in bodies:                  # cold warmup (compile + tune)
            node.search("http_logs", dict(b))
        cold_resps = [node.search("http_logs", dict(b)) for b in bodies]
        cold_p50 = p50_run()

        os.environ["ES_TPU_RESIDENT_LOOP"] = "1"
        for b in bodies:                  # resident warmup (AOT compile)
            node.search("http_logs", dict(b))
        res_resps = [node.search("http_logs", dict(b)) for b in bodies]
        for c, r in zip(cold_resps, res_resps):
            if _strip_timing(c) != _strip_timing(r):
                raise AssertionError("resident/cold responses differ")
        res_p50 = p50_run()
    finally:
        if had is None:
            os.environ.pop("ES_TPU_RESIDENT_LOOP", None)
        else:
            os.environ["ES_TPU_RESIDENT_LOOP"] = had

    # acceptance gate: with a real per-dispatch round trip, the pinned
    # entry + staged feed must shed at least 40% of the lone-query
    # latency. On a local backend with no per-dispatch round trip (CPU CI) the flat
    # overhead being shed is near zero, so the ratio is reported only.
    if round_trip_ms > 5.0 and res_p50 > 0.6 * cold_p50:
        raise AssertionError(
            f"resident lone-query p50 {res_p50:.1f}ms > 0.6x cold "
            f"{cold_p50:.1f}ms")
    rs = node.nodes_stats()["nodes"][node.name]["dispatch"]["resident"]
    # which engine the pinned entries actually run: pallas-tuned packs
    # are now served resident instead of falling back to cold dispatch,
    # and the loss audit must stay clean on the shapes this workload
    # tuned
    engines = {}
    for e in rs["entries"]:
        engines[e["backend"]] = engines.get(e["backend"], 0) + 1
    _loss_audit_gate("lone_query")
    node.close()
    return {"metric": "lone_query_p50_ms", "unit": "ms",
            "value": round(res_p50, 2),
            "cold_p50_ms": round(cold_p50, 2),
            "vs_baseline": round(res_p50 / cold_p50, 2)
            if cold_p50 > 0 else 1.0,
            "resident": {
                "resident_hits": rs["resident_hits"],
                "cold_dispatches": rs["cold_dispatches"],
                "evictions": rs["evictions"],
                "preempted_by_deadline": rs["preempted_by_deadline"],
                "staged_feed_overlap_ms":
                    rs["staged_feed_overlap_ms"]["high_water"],
                "entry_count": rs["entry_count"],
                "entry_engines": engines,
                "residency_bytes": rs["residency_bytes"]},
            "docs": DISPATCH_DOCS}


def bench_concurrent_index_search(round_trip_ms: float) -> dict:
    """Sustained writes + searches — the production shape the streaming
    write path (ROADMAP item 1, index.streaming.delta) exists for: a
    writer thread indexes + refreshes continuously while the read path
    serves a fused query mix. Before the delta pack, every refresh
    minted a fresh fingerprint and cold-started autotune choices,
    resident executables, and compiled programs; with it a refresh is
    an epoch bump, so the concurrent search p50 is gated at <= 1.5x the
    read-only p50 with a per-dispatch round trip. Identity-gated against a
    FULL-REBUILD ORACLE (the same final doc set indexed into a fresh
    engine and refreshed once — base + one delta, which is exactly what
    the generation pack converges to). Reports the refresh_reuses /
    compaction_evictions counters; gated so the storm never mints a
    fresh base fingerprint (a new NON-pack autotune key without a
    compaction) and a same-bucket epoch bump re-tunes ZERO keys —
    first-tune-per-delta-bucket pack keys are the documented, counted
    exception."""
    import threading
    from elasticsearch_tpu.node import Node
    from elasticsearch_tpu.search import executor as executor_mod

    t0 = time.time()
    n_docs = DISPATCH_DOCS
    docs = make_corpus(n_docs)
    mappings = {"properties": {
        "message": {"type": "text"},
        "size": {"type": "long"},
        "status": {"type": "keyword"}}}
    had = os.environ.get("ES_TPU_RESIDENT_LOOP")
    os.environ["ES_TPU_RESIDENT_LOOP"] = "1"
    node = Node({"index.number_of_shards": 1})
    try:
        node.create_index(
            "stream", settings={"index.streaming.delta": True,
                                # threshold compaction stays off for
                                # the storm: impacts are EAGER per
                                # segment, so a mid-storm fold changes
                                # which field stats scored the writer
                                # docs and no single-delta oracle can
                                # reproduce it (compaction byte-
                                # identity has its own gate in
                                # tests/test_streaming_writes.py);
                                # this scenario measures the refresh
                                # storm, where the oracle is exact
                                "index.delta.min_compact_docs": 1 << 30},
            mappings=mappings)
        for did, d in docs:
            node.index_doc("stream", did, d)
        node.refresh("stream")
        node.indices["stream"].shard(0).compact()  # seed a real base
        log(f"concurrent_index_search: {n_docs} docs ingested in "
            f"{time.time()-t0:.1f}s")

        rng = random.Random(53)
        head = _vocab()[: 400]
        bodies = [{"query": {"match": {"message": rng.choice(head)}},
                   "size": TOP_K} for _ in range(16)]
        reps = max(AGG_REPS // 3, 5)

        def p50_run():
            lat = []
            for _ in range(reps):
                for b in bodies:
                    t = time.time()
                    node.search("stream", dict(b))
                    lat.append((time.time() - t) * 1000.0)
            return float(np.percentile(np.asarray(lat), 50))

        for b in bodies:                 # warm: tune + pin residents
            node.search("stream", dict(b))
        read_only_p50 = p50_run()
        keys_before = set(executor_mod._autotune_choices)

        # -- writer storm: index + refresh while the searches run -----
        stop = threading.Event()
        written: list[int] = [0]
        writer_errors: list[BaseException] = []
        vocab = _vocab()

        def writer():
            try:
                i = 0
                wrng = random.Random(7)
                last_refresh = time.time()
                while not stop.is_set():
                    did = f"w{i}"
                    node.index_doc("stream", did, {
                        "message": " ".join(wrng.choice(vocab)
                                            for _ in range(8)),
                        "size": wrng.randint(10, 50_000),
                        "status": wrng.choice(["200", "404", "500"])})
                    i += 1
                    # ES-shaped refresh cadence (index.refresh_interval
                    # is time-based, default 1s; 200ms keeps several
                    # epoch bumps inside the measurement window)
                    if time.time() - last_refresh >= 0.2:
                        node.refresh("stream")
                        last_refresh = time.time()
                    written[0] = i
            except BaseException as e:  # noqa: BLE001 — a dead writer
                writer_errors.append(e)  # must fail the gate, not
                                         # silently idle the storm
        wt = threading.Thread(target=writer, daemon=True)
        wt.start()
        try:
            concurrent_p50 = p50_run()
        finally:
            stop.set()
            wt.join(timeout=10.0)
        if writer_errors:
            raise AssertionError(
                "concurrent_index_search: the writer storm died: "
                f"{writer_errors[0]!r}")
        if written[0] == 0:
            raise AssertionError(
                "concurrent_index_search: writer made no progress — "
                "the gates would be vacuous")
        node.refresh("stream")
        new_keys = set(executor_mod._autotune_choices) - keys_before
        rs = node.nodes_stats()["nodes"][node.name]["dispatch"]["resident"]
        streaming = node.indices["stream"].shard(0).segment_stats().get(
            "streaming", {})

        # identity gate vs the full-rebuild oracle: the SAME final doc
        # set in a fresh delta-mode engine, one refresh (base + one
        # delta — the state the generation pack converges to)
        final_resps = [node.search("stream", dict(b)) for b in bodies]
        oracle = Node({"index.number_of_shards": 1})
        try:
            oracle.create_index(
                "stream", settings={"index.streaming.delta": True,
                                    "index.delta.min_compact_docs": 1 << 30},
                mappings=mappings)
            for did, d in docs:
                oracle.index_doc("stream", did, d)
            oracle.refresh("stream")
            oracle.indices["stream"].shard(0).compact()
            eng = node.indices["stream"].shard(0)
            for did, _ver, src in eng.snapshot_docs():
                # writer docs in their original visibility order
                # (snapshot order preserves it through any mid-storm
                # compaction)
                if did.startswith("w"):
                    oracle.index_doc("stream", did, src)
            oracle.refresh("stream")
            oracle_resps = [oracle.search("stream", dict(b)) for b in bodies]
            for a, b in zip(final_resps, oracle_resps):
                if _strip_timing(a) != _strip_timing(b):
                    raise AssertionError(
                        "concurrent_index_search: delta-pack response "
                        "diverged from the full-rebuild oracle")
        finally:
            oracle.close()

        # the refresh storm must not re-key the surviving generation.
        # The FIRST search over a never-before-seen (base, delta
        # bucket) pack necessarily tunes that pack key once — and again
        # when the growing delta crosses a pow2 capacity bucket; both
        # are the documented re-key events, not regressions. What a
        # refresh must NEVER do is mint a fresh base fingerprint: that
        # shows up here as a new NON-pack autotune key (single-segment
        # keys are fingerprint-tuples, pack keys start with "pack") —
        # and with threshold compaction disabled for the storm, there
        # is no legitimate source of one.
        base_rekeys = [k for k in new_keys
                       if not (isinstance(k, tuple) and k
                               and k[0] == "pack")]
        if base_rekeys:
            raise AssertionError(
                f"refresh storm re-tuned {len(base_rekeys)} non-pack "
                f"autotune keys (generation keying regressed): "
                f"{sorted(map(repr, base_rekeys))[:3]}")
        # direct acceptance check: an epoch bump whose delta stays in
        # its pow2 bucket performs ZERO autotune re-tunes
        eng = node.indices["stream"].shard(0)
        d0 = eng._delta_seg
        if d0 is not None and d0.num_docs + 4 < d0.capacity:
            cap0, tunes_mid = d0.capacity, len(executor_mod._autotune_choices)
            for j in range(3):
                node.index_doc("stream", f"zb{j}", {
                    "message": "epoch bump probe", "size": 1,
                    "status": "200"})
            node.refresh("stream")
            d1 = eng._delta_seg
            if d1 is not None and d1.capacity == cap0:
                for b in bodies:
                    node.search("stream", dict(b))
                bump_tunes = (len(executor_mod._autotune_choices)
                              - tunes_mid)
                if bump_tunes:
                    raise AssertionError(
                        f"a same-bucket epoch bump re-tuned "
                        f"{bump_tunes} autotune keys (generation "
                        "keying regressed)")
        if round_trip_ms > 5.0 and concurrent_p50 > 1.5 * read_only_p50:
            raise AssertionError(
                f"concurrent search p50 {concurrent_p50:.1f}ms > 1.5x "
                f"read-only {read_only_p50:.1f}ms")
    finally:
        if had is None:
            os.environ.pop("ES_TPU_RESIDENT_LOOP", None)
        else:
            os.environ["ES_TPU_RESIDENT_LOOP"] = had
        node.close()
    return {"metric": "concurrent_index_search_p50_ms", "unit": "ms",
            "value": round(concurrent_p50, 2),
            "read_only_p50_ms": round(read_only_p50, 2),
            "vs_baseline": (round(concurrent_p50 / read_only_p50, 2)
                            if read_only_p50 > 0 else 1.0),
            "docs_written_during_run": written[0],
            "new_pack_bucket_tunes": len(new_keys) - len(base_rekeys),
            "base_rekeys_during_storm": len(base_rekeys),
            "resident": {
                "refresh_reuses": rs["refresh_reuses"],
                "compaction_evictions": rs["compaction_evictions"],
                "evictions": rs["evictions"],
                "resident_hits": rs["resident_hits"],
                "cold_dispatches": rs["cold_dispatches"]},
            "streaming": streaming}


def bench_crash_recovery() -> dict:
    """Recovery wall time after a write storm (ISSUE 15): ingest the
    dispatch-scale corpus into a path-backed node (periodic flushes +
    an unflushed translog tail — the abrupt-shutdown shape Engine.close
    leaves, since close never flushes), then time a cold reopen:
    commit load + translog replay + searcher publication. The CLEAN
    path is gated: zero corruptions detected, zero commit fallbacks,
    zero truncated translog bytes, zero contained shards — recovery
    salvage machinery must be provably idle when nothing is wrong."""
    import shutil
    import tempfile
    from elasticsearch_tpu.node import Node

    n_docs = DISPATCH_DOCS
    docs = make_corpus(n_docs)
    data_path = tempfile.mkdtemp(prefix="bench_crash_recovery_")
    mappings = {"properties": {
        "message": {"type": "text"},
        "size": {"type": "long"},
        "status": {"type": "keyword"}}}
    t0 = time.time()
    node = Node({"path.data": data_path, "node.name": "crash-bench",
                 "index.number_of_shards": 1})
    node2 = None
    try:
        # async durability for the storm half: the leg measures
        # RECOVERY, and per-op fsync would make ingest dominate the
        # wall clock without changing what recovery replays (the ops
        # are flushed to the file either way; fsync cadence only
        # matters under power loss, which tests/test_durability.py
        # covers deterministically)
        node.create_index("wal", mappings=mappings, settings={
            "index.translog.durability": "async"})
        flush_every = max(n_docs // 4, 1)
        for i, (did, d) in enumerate(docs):
            node.index_doc("wal", did, d)
            if (i + 1) % flush_every == 0 and (i + 1) < n_docs:
                node.flush("wal")
        # the last ~quarter stays translog-only: recovery must replay
        node.close()
        log(f"crash_recovery: {n_docs} docs ingested in "
            f"{time.time() - t0:.1f}s; reopening")
        t1 = time.time()
        node2 = Node({"path.data": data_path,
                      "node.name": "crash-bench"})
        node2.refresh("wal")
        recovery_ms = (time.time() - t1) * 1000.0
        r = node2.search("wal", {"query": {"match_all": {}},
                                 "size": 0})
        if r["hits"]["total"] != n_docs:
            raise AssertionError(
                f"crash_recovery: {r['hits']['total']} of {n_docs} "
                "docs survived a clean-shutdown recovery")
        dur = node2.nodes_stats()["nodes"]["crash-bench"][
            "indices"]["durability"]
        for key in ("corruptions_detected", "commits_fell_back",
                    "translog_truncated_bytes", "segments_salvaged",
                    "shards_failed_corrupt"):
            if dur[key] != 0:
                raise AssertionError(
                    f"crash_recovery: salvage counter [{key}]="
                    f"{dur[key]} on the CLEAN path (expected 0)")
        if not node2.verify_integrity()["clean"]:
            raise AssertionError(
                "crash_recovery: store verify unclean after recovery")
        return {"metric": "crash_recovery_ms",
                "value": round(recovery_ms, 1), "unit": "ms",
                "vs_baseline": 1.0,
                "docs": n_docs,
                "docs_per_s_recovered": round(
                    n_docs / (recovery_ms / 1000.0), 1),
                "durability_counters": dur,
                "note": "cold reopen after a write storm: commit load "
                        "+ translog replay + refresh; salvage "
                        "counters gated to zero on the clean path"}
    finally:
        if node2 is not None:
            node2.close()
        shutil.rmtree(data_path, ignore_errors=True)


def bench_oversubscribed_corpus(round_trip_ms: float) -> dict:
    """Beyond-HBM packs (index/tiering.py): the SAME corpus served
    fully resident vs through tiered tile residency with the HBM
    budget shrunk (via ES_TPU_TIERED_BUDGET_BYTES) until the pack is
    ~6x the budget — a CI-sized stand-in for a corpus that genuinely
    cannot fit the device. The workload is the HIGH-PRUNE-RATE shape
    tiering exists for: selective head terms whose postings live in a
    few tiles, so the bound computation over the resident summaries
    filters most fetches (prune_skipped_fetches must come out nonzero
    — proving pruning filters I/O, not just FLOPs). Gates: responses
    byte-identical to the fully-resident run, and with a per-dispatch round trip
    the tiered p50 must hold at <= 2x fully resident. Reports the
    tiering counters (hits/misses/evictions/prune-skipped/overlap)."""
    from elasticsearch_tpu.node import Node
    from elasticsearch_tpu.index import tiering as tiering_mod

    def build_node():
        node = Node({"index.number_of_shards": 1})
        node.create_index("logs", mappings={"properties": {
            "message": {"type": "text"},
            "size": {"type": "long"},
            "status": {"type": "keyword"}}})
        for did, d in docs:
            node.index_doc("logs", did, d)
        node.refresh("logs")
        return node

    t0 = time.time()
    docs = make_corpus(DISPATCH_DOCS)
    rng = random.Random(71)
    head = _vocab()[: 400]
    bodies = [{"query": {"match": {"message": rng.choice(head)}},
               "size": TOP_K} for _ in range(16)]
    reps = max(AGG_REPS // 3, 5)

    def p50_run(node):
        lat = []
        for _ in range(reps):
            for b in bodies:
                t = time.time()
                node.search("logs", dict(b))
                lat.append((time.time() - t) * 1000.0)
        return float(np.percentile(np.asarray(lat), 50))

    had = {k: os.environ.pop(k, None)
           for k in ("ES_TPU_TIERED_PACK", "ES_TPU_TIERED_BUDGET_BYTES")}
    node = tiered_node = None
    try:
        # -- fully resident reference ---------------------------------
        node = build_node()
        log(f"oversubscribed_corpus: {DISPATCH_DOCS} docs ingested in "
            f"{time.time()-t0:.1f}s")
        for b in bodies:                  # compile + tune warmup
            node.search("logs", dict(b))
        resident_resps = [node.search("logs", dict(b)) for b in bodies]
        resident_p50 = p50_run(node)
        # size the budget off the REAL pack: forward index + columns
        seg = node.indices["logs"].shard(0).segments[0]
        fwd_bytes = sum(pf.fwd_tids.nbytes + pf.fwd_imps.nbytes
                        for pf in seg.text.values()
                        if pf.fwd_tids is not None)
        pack_bytes = seg.nbytes() + fwd_bytes
        node.close()
        node = None

        # -- tiered run: corpus ~6x the budget ------------------------
        tiering_mod.reset()
        os.environ["ES_TPU_TIERED_PACK"] = "1"
        os.environ["ES_TPU_TIERED_BUDGET_BYTES"] = str(
            max(pack_bytes // 6, 1))
        tiered_node = build_node()
        for b in bodies:                  # compile warmup (chunk progs)
            tiered_node.search("logs", dict(b))
        tiered_resps = [tiered_node.search("logs", dict(b))
                        for b in bodies]
        for r_ref, r_t in zip(resident_resps, tiered_resps):
            if _strip_timing(r_ref) != _strip_timing(r_t):
                raise AssertionError(
                    "tiered/fully-resident responses differ")
        tiered_p50 = p50_run(tiered_node)
        snap = tiering_mod.stats_snapshot()
        if snap["tiered_dispatches"] == 0:
            raise AssertionError(
                "oversubscribed corpus never took the tiered path — "
                "the gate would be vacuous")
        if snap["prune_skipped_fetches"] == 0:
            raise AssertionError(
                "no prune-skipped fetches: pruning filtered zero I/O "
                "on a high-prune-rate workload")
        if round_trip_ms > 5.0 and tiered_p50 > 2.0 * resident_p50:
            raise AssertionError(
                f"tiered p50 {tiered_p50:.1f}ms exceeds 2x fully-"
                f"resident {resident_p50:.1f}ms")
    finally:
        for n in (node, tiered_node):
            if n is not None:
                n.close()
        for k, v in had.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        tiering_mod.reset()
    return {"metric": "oversubscribed_corpus_p50_ms",
            "value": round(tiered_p50, 2), "unit": "ms",
            "vs_baseline": round(tiered_p50 / resident_p50, 2)
            if resident_p50 > 0 else 1.0,
            "fully_resident_p50_ms": round(resident_p50, 2),
            "pack_bytes": int(pack_bytes),
            "budget_bytes": int(max(pack_bytes // 6, 1)),
            "oversubscription": 6.0,
            "tiering": {k: snap[k] for k in (
                "tile_hits", "tile_misses", "tile_evictions",
                "prune_skipped_fetches", "tiered_dispatches",
                "resident_bytes", "summary_bytes",
                "prefetch_overlap_ms")},
            "docs": DISPATCH_DOCS}


def bench_degraded_search(round_trip_ms: float) -> dict:
    """Partial-failure scenario: p50 + result-completeness of a
    multi-shard search with one injected dead shard and one injected
    slow shard (utils/faults.py), vs the healthy baseline. Gates that a
    DEAD shard degrades gracefully — the search must not retry-loop or
    stall, so its p50 may exceed healthy by at most one failover round
    trip (round_trip_ms) plus noise margin. The slow-shard leg reports the
    deadline path (`timed_out: true`, laggard failed) un-gated."""
    from elasticsearch_tpu.node import Node
    from elasticsearch_tpu.utils import faults

    t0 = time.time()
    docs = make_corpus(DISPATCH_DOCS)
    node = Node({"index.number_of_shards": 3})
    node.create_index("http_logs", mappings={"properties": {
        "message": {"type": "text"},
        "size": {"type": "long"},
        "status": {"type": "keyword"}}})
    for did, d in docs:
        node.index_doc("http_logs", did, d)
    node.refresh("http_logs")
    log(f"degraded_search: {DISPATCH_DOCS} docs / 3 shards ingested in "
        f"{time.time()-t0:.1f}s")

    rng = random.Random(31)
    head = _vocab()[: 400]
    bodies = [{"query": {"match": {"message": rng.choice(head)}},
               "size": TOP_K} for _ in range(40)]
    reps = max(AGG_REPS // 3, 5)

    def p50_run():
        lat = []
        for _ in range(reps):
            t = time.time()
            for b in bodies:
                node.search("http_logs", dict(b))
            lat.append((time.time() - t) * 1000.0 / len(bodies))
        return float(np.percentile(np.asarray(lat), 50))

    for b in bodies:                      # compile warmup
        node.search("http_logs", dict(b))
    healthy_p50 = p50_run()
    healthy_total = sum(node.search("http_logs", dict(b))["hits"]["total"]
                        for b in bodies)

    try:
        faults.configure("shard_error:shard=1:index=http_logs")
        dead_p50 = p50_run()
        dead_resps = [node.search("http_logs", dict(b)) for b in bodies]
    finally:
        faults.clear()
    assert all(r["_shards"]["failed"] == 1 for r in dead_resps)
    dead_total = sum(r["hits"]["total"] for r in dead_resps)
    completeness = dead_total / healthy_total if healthy_total else 1.0

    # slow-shard leg: straggler + deadline -> timed_out partials
    try:
        faults.configure("shard_delay:ms=50:shard=2:index=http_logs")
        slow = [node.search("http_logs", dict(b, timeout="20ms"))
                for b in bodies[:10]]
    finally:
        faults.clear()
    timed_out_frac = sum(r["timed_out"] for r in slow) / len(slow)

    # acceptance gate: one dead shard may add at most one failover
    # round trip (the isolation retry re-dispatches the failed job
    # once) on top of healthy p50, plus a noise margin
    limit = healthy_p50 + round_trip_ms + max(0.5 * healthy_p50, 10.0)
    if dead_p50 > limit:
        raise AssertionError(
            f"degraded p50 {dead_p50:.1f}ms exceeds healthy "
            f"{healthy_p50:.1f}ms + one round trip ({limit:.1f}ms)")

    ds = node.nodes_stats()["nodes"][node.name]["dispatch"]
    eviction = bench_eviction_leg(round_trip_ms)
    node.close()
    return {"metric": "degraded_search_p50_ms",
            "value": round(dead_p50, 2), "unit": "ms",
            "vs_baseline": round(dead_p50 / healthy_p50, 2)
            if healthy_p50 > 0 else 1.0,
            "healthy_p50_ms": round(healthy_p50, 2),
            "completeness": round(completeness, 4),
            "timed_out_frac": round(timed_out_frac, 2),
            "failover": ds["failover"],
            "eviction": eviction, "docs": DISPATCH_DOCS}


def bench_eviction_leg(round_trip_ms: float) -> dict:
    """Elastic-mesh leg of the degraded scenario: one replica row
    PERMANENTLY dead (`device_dead` injection). Before eviction every
    search pays a failover round trip; the health tracker evicts the
    row, a background repack re-shards onto the survivors while the old
    pack keeps serving, and the searcher swap removes the tax. Gates
    (per-dispatch round trip): after eviction settles, p50 must return to
    within 1.1x the healthy mesh p50; results are byte-identical to
    healthy across the WHOLE lifecycle (dying, during-repack, settled,
    re-expanded); re-expansion restores full replication; counters
    prove each stage ran."""
    import jax
    if len(jax.devices()) < 4:
        return {"skipped": f"needs >= 4 devices for a 2x2 mesh, "
                           f"have {len(jax.devices())}"}
    from elasticsearch_tpu.node import Node
    from elasticsearch_tpu.parallel.mesh import build_mesh
    from elasticsearch_tpu.parallel.repack import ElasticMeshSearcher
    from elasticsearch_tpu.utils import faults

    docs = make_corpus(DISPATCH_DOCS)
    node = Node({"node.name": "bench-evict"})
    node.create_index("ev_logs",
                      settings={"index.number_of_shards": 2},
                      mappings={"properties": {
                          "message": {"type": "text"},
                          "size": {"type": "long"},
                          "status": {"type": "keyword"}}})
    for did, d in docs:
        node.index_doc("ev_logs", did, d)
    node.refresh("ev_logs")

    rng = random.Random(37)
    head = _vocab()[: 400]
    bodies = [{"query": {"match": {"message": rng.choice(head)}},
               "size": TOP_K} for _ in range(20)]
    reps = max(AGG_REPS // 5, 4)

    es = ElasticMeshSearcher(node, "ev_logs", build_mesh(2, 2),
                             failure_threshold=3, probe_interval_ms=50)

    def strip(r):
        return json.dumps({k: v for k, v in r.items() if k != "took"},
                          sort_keys=True, default=str)

    def p50_run():
        lat = []
        for _ in range(reps):
            t = time.time()
            for b in bodies:
                es.search(dict(b))
            lat.append((time.time() - t) * 1000.0 / len(bodies))
        return float(np.percentile(np.asarray(lat), 50))

    for b in bodies:                      # compile warmup
        es.search(dict(b))
    healthy = [strip(es.search(dict(b))) for b in bodies]
    healthy_p50 = p50_run()

    from elasticsearch_tpu.search import dispatch as _dm
    try:
        return _run_eviction_leg(es, node, bodies, healthy, healthy_p50,
                                 strip, p50_run, round_trip_ms, _dm)
    finally:
        # gates may raise mid-lifecycle: the searcher's breaker hold
        # and the node must never leak into the rest of the bench run
        faults.clear()
        es.close()
        node.close()


def _run_eviction_leg(es, node, bodies, healthy, healthy_p50, strip,
                      p50_run, round_trip_ms, _dm) -> dict:
    from elasticsearch_tpu.utils import faults
    try:
        faults.configure("device_dead:replica=0:site=mesh")
        # dying phase: every search succeeds (failover tax) until the
        # threshold evicts; then searches keep succeeding DURING the
        # background repack — identity asserted throughout, the loop
        # only stops once the swap lands (n_replicas drops to 1)
        during = 0
        rounds = 0
        while es.n_replicas == 2 and rounds < 200:
            for b, w in zip(bodies, healthy):
                if strip(es.search(dict(b))) != w:
                    raise AssertionError(
                        "response diverged during eviction/repack")
                during += 1
            rounds += 1
        if not es.await_settled(60.0):
            raise AssertionError("eviction did not settle")
        if es.n_replicas != 1:
            raise AssertionError("dead row was not evicted")
        for b, w in zip(bodies, healthy):      # post-swap warmup + identity
            if strip(es.search(dict(b))) != w:
                raise AssertionError("response diverged across the swap")
        retries_before = _dm.failover_stats.retries.count
        settled_p50 = p50_run()
        tax_retries = _dm.failover_stats.retries.count - retries_before
    finally:
        faults.clear()

    # no per-search failover tax after the swap
    if tax_retries != 0:
        raise AssertionError(
            f"{tax_retries} failover retries after eviction settled")
    # latency gate with a per-dispatch round trip (flat round trips dominate there);
    # reported-only on local CI with no per-dispatch round trip where noise swamps the ratio
    if round_trip_ms > 5.0 and settled_p50 > 1.1 * healthy_p50:
        raise AssertionError(
            f"settled degraded p50 {settled_p50:.1f}ms > 1.1x healthy "
            f"mesh p50 {healthy_p50:.1f}ms")

    # re-expansion: the injected death is lifted -> probe -> full mesh
    es.probe_now()
    if not es.await_settled(60.0):
        raise AssertionError("re-expansion did not settle")
    if es.n_replicas != 2:
        raise AssertionError("re-expansion did not restore replication")
    for b, w in zip(bodies, healthy):
        if strip(es.search(dict(b))) != w:
            raise AssertionError("response diverged after re-expansion")

    ev = _dm.eviction_stats.snapshot()
    if not (ev["rows_dead"] >= 1 and ev["repacks"] >= 2
            and ev["swaps"] >= 2 and ev["re_expansions"] >= 1):
        raise AssertionError(f"lifecycle counters incomplete: {ev}")
    log(f"eviction: healthy {healthy_p50:.2f}ms settled "
        f"{settled_p50:.2f}ms during-repack searches {during}")
    return {"healthy_mesh_p50_ms": round(healthy_p50, 2),
            "settled_p50_ms": round(settled_p50, 2),
            "vs_healthy": round(settled_p50 / healthy_p50, 2)
            if healthy_p50 > 0 else 1.0,
            "searches_during_lifecycle": during,
            "counters": ev}


# ---------------------------------------------------------------------------
# nyc_taxis corpus for configs [2] and [3]
# ---------------------------------------------------------------------------


TAXI_BASE = 1420070400  # 2015-01-01, the nyc_taxis epoch


def build_taxis():
    """20M-row columnar load (build_columnar: the bulk ingestion path —
    a doc-by-doc parse would take ~10 minutes at this scale)."""
    t0 = time.time()
    from elasticsearch_tpu.index.mapping import MapperService
    from elasticsearch_tpu.index.segment import build_columnar
    rng = np.random.default_rng(5)
    zones = rng.integers(0, TAXI_CARD, size=TAXI_ROWS).astype(np.int32)
    ts = (TAXI_BASE + rng.integers(0, 365 * 86400, size=TAXI_ROWS))
    fare = np.round(rng.gamma(2.5, 6.0, size=TAXI_ROWS), 2)
    terms = [f"z{i:05d}" for i in range(TAXI_CARD)]
    seg = build_columnar(
        "taxis", TAXI_ROWS,
        keywords={"zone": (terms, zones)},
        numerics={"ts": ("date", ts.astype(np.int64) * 1000),
                  "fare": ("double", fare)})
    svc = MapperService(mapping={"properties": {
        "zone": {"type": "keyword"},
        "ts": {"type": "date"},
        "fare": {"type": "double"}}})
    live = np.zeros(seg.capacity, dtype=bool)
    live[:TAXI_ROWS] = True
    log(f"nyc_taxis: {TAXI_ROWS} rows, zone card={TAXI_CARD}, "
        f"built in {time.time()-t0:.1f}s")
    return svc, seg, live, zones, ts, fare


def _reader(svc, seg, live):
    from elasticsearch_tpu.search.shard_searcher import ShardReader
    return ShardReader("taxis", [seg], {seg.seg_id: live}, svc)


def taxi_windows(n: int, seed: int = 17) -> list[tuple[int, int]]:
    """Randomized 30-65 day pickup-time windows (the Rally autohisto/
    date-range pattern): every query in a batch scans the corpus under a
    DIFFERENT filter, so no caching/dedup can stand in for the scan."""
    rng = random.Random(seed)
    out = []
    for _ in range(n):
        lo = TAXI_BASE + rng.randrange(0, 300 * 86400)
        hi = lo + rng.randrange(30, 65) * 86400
        out.append((lo, hi))
    return out


def measure_dispatch_round_trip_ms() -> float:
    """Flat per-dispatch round trip: the p50 of a trivial jitted
    program + device_get. This is serving-stack overhead,
    not compute — reported separately so device compute is legible."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x + 1.0)
    x = jnp.zeros(8, jnp.float32)
    jax.device_get(f(x))
    lat = []
    for _ in range(15):
        t0 = time.time()
        jax.device_get(f(x))
        lat.append((time.time() - t0) * 1000.0)
    return float(np.percentile(lat, 50))


def _agg_lat(reader, body_fn, windows, batch: int
             ) -> tuple[float, float, float]:
    """(single p50, single p99, batched per-query ms) over VARYING
    windows. The batched figure divides one B-wide msearch (ONE device
    program — the deployment shape) by B; the single-query p50 carries
    the per-dispatch round trip (~65ms) on top of the compute."""
    reader.search(body_fn(*windows[0]))  # compile single
    lat = []
    for i in range(AGG_REPS):
        w = windows[i % len(windows)]
        t0 = time.time()
        reader.search(body_fn(*w))
        lat.append((time.time() - t0) * 1000.0)
    p50, p99 = pcts(lat)
    bodies = [body_fn(*w) for w in windows[:batch]]
    reader.msearch([dict(b) for b in bodies])  # compile batched program
    blat = []
    for _ in range(max(AGG_REPS // 10, 2)):
        t0 = time.time()
        reader.msearch([dict(b) for b in bodies])
        blat.append((time.time() - t0) * 1000.0 / batch)
    return p50, p99, float(np.min(blat))


def _terms_body(lo: int, hi: int) -> dict:
    return {"size": 0,
            "query": {"range": {"ts": {"gte": lo * 1000,
                                       "lt": hi * 1000}}},
            "aggs": {"zones": {"terms": {"field": "zone", "size": 10}}}}


def bench_terms_agg(reader, zones, ts, round_trip_ms: float) -> dict:
    _fused_reset()
    windows = taxi_windows(256)
    p50, p99, batched_ms = _agg_lat(reader, _terms_body, windows,
                                    batch=256)
    # correctness: exact filtered top-10 counts vs numpy on 2 windows
    for lo, hi in windows[:2]:
        r = reader.search(_terms_body(lo, hi))
        m = (ts >= lo) & (ts < hi)
        counts = np.bincount(zones[m], minlength=TAXI_CARD)
        top = np.argsort(-counts, kind="stable")[:10]
        got = {b["key"]: b["doc_count"]
               for b in r["aggregations"]["zones"]["buckets"]}
        want = {f"z{int(z):05d}": int(counts[z]) for z in top}
        if sorted(got.values()) != sorted(want.values()):
            raise AssertionError(f"terms agg mismatch: {got} vs {want}")
        if r["hits"]["total"] != int(m.sum()):
            raise AssertionError("terms agg total mismatch")

    # CPU baseline: SAME filtered scan at the SAME row count
    cpu_windows = windows[:4]

    def _cpu():
        for lo, hi in cpu_windows:
            m = (ts >= lo) & (ts < hi)
            c = np.bincount(zones[m], minlength=TAXI_CARD)
            np.argpartition(-c, 10)[:10]
    cpu_ms = best_time(_cpu) * 1000.0 / len(cpu_windows)
    return {"metric": "nyc_taxis_terms_agg_ms_per_query",
            "value": round(batched_ms, 3), "unit": "ms",
            "vs_baseline": round(cpu_ms / batched_ms, 2),
            "p50_ms": round(p50, 2), "p99_ms": round(p99, 2),
            "single_query_p50_ms": round(p50, 2),
            "single_device_p50_ms": round(max(p50 - round_trip_ms, 0.0), 2),
            "batch": 256, "cpu_ms": round(cpu_ms, 3),
            "rows": TAXI_ROWS,
            "query": "randomized 30-65d ts range filter",
            "fused": _fused_block()}


def _hist_body(lo: int, hi: int) -> dict:
    return {"size": 0,
            "query": {"range": {"ts": {"gte": lo * 1000,
                                       "lt": hi * 1000}}},
            "aggs": {"per_week": {
                "date_histogram": {"field": "ts", "interval": "week"},
                "aggs": {"avg_fare": {"avg": {"field": "fare"}},
                         "total": {"sum": {"field": "fare"}}}}}}


def bench_date_histogram(reader, ts, fare, round_trip_ms: float) -> dict:
    _fused_reset()
    windows = taxi_windows(256, seed=23)
    p50, p99, batched_ms = _agg_lat(reader, _hist_body, windows,
                                    batch=256)
    # correctness: exact per-bucket counts + sum tolerance on 2 windows
    week = 7 * 86400
    for lo, hi in windows[:2]:
        r = reader.search(_hist_body(lo, hi))
        m = (ts >= lo) & (ts < hi)
        origin = (ts.min() // week) * week
        wk = (ts[m] - origin) // week
        counts = np.bincount(wk)
        nz = np.nonzero(counts)[0]
        got = {b["key"]: b["doc_count"]
               for b in r["aggregations"]["per_week"]["buckets"]
               if b["doc_count"]}
        want = {int(origin + w * week) * 1000: int(counts[w]) for w in nz}
        if got != want:
            raise AssertionError(
                f"date_histogram counts mismatch ({len(got)} vs "
                f"{len(want)} buckets)")
        total_got = sum(b["total"]["value"]
                        for b in r["aggregations"]["per_week"]["buckets"])
        if not np.isclose(total_got, float(fare[m].sum()), rtol=1e-3):
            raise AssertionError(
                f"date_histogram sum mismatch: {total_got} "
                f"vs {fare[m].sum()}")

    cpu_windows = windows[:4]

    def _cpu():
        for lo, hi in cpu_windows:
            m = (ts >= lo) & (ts < hi)
            wk = (ts[m] - TAXI_BASE) // week
            counts = np.bincount(wk, minlength=54)
            sums = np.bincount(wk, weights=fare[m], minlength=54)
            sums / np.maximum(counts, 1)
    cpu_ms = best_time(_cpu) * 1000.0 / len(cpu_windows)
    return {"metric": "nyc_taxis_date_histogram_ms_per_query",
            "value": round(batched_ms, 3), "unit": "ms",
            "vs_baseline": round(cpu_ms / batched_ms, 2),
            "p50_ms": round(p50, 2), "p99_ms": round(p99, 2),
            "single_query_p50_ms": round(p50, 2),
            "single_device_p50_ms": round(max(p50 - round_trip_ms, 0.0), 2),
            "batch": 256, "cpu_ms": round(cpu_ms, 3),
            "rows": TAXI_ROWS,
            "query": "randomized 30-65d ts range filter",
            "fused": _fused_block()}


# ---------------------------------------------------------------------------
# config[4]: dense_vector kNN + BM25 rescore
# ---------------------------------------------------------------------------


def bench_knn() -> dict:
    import functools
    import jax
    import jax.numpy as jnp
    from elasticsearch_tpu.ops.knn import knn_topk

    rng = np.random.default_rng(23)
    t0 = time.time()
    emb = rng.standard_normal((KNN_DOCS, KNN_DIM),
                              dtype=np.float32)
    bm25 = rng.gamma(2.0, 2.0, size=KNN_DOCS).astype(np.float32)
    queries = rng.standard_normal(
        (KNN_BATCH * 4, KNN_DIM)).astype(np.float32)
    norms = np.linalg.norm(emb, axis=1).astype(np.float32)
    dev_emb = jnp.asarray(emb, dtype=jnp.bfloat16)  # MXU-native storage
    dev_norms = jnp.asarray(norms)
    dev_exists = jnp.ones(KNN_DOCS, bool)
    dev_live = jnp.ones(KNN_DOCS, bool)
    dev_bm25 = jnp.asarray(bm25)
    log(f"knn: {KNN_DOCS} x {KNN_DIM} vectors in {time.time()-t0:.1f}s")

    @functools.partial(jax.jit, static_argnames=("k", "window"))
    def knn_rescore(qv, v, nrm, b25, k: int, window: int):
        # retrieve `window` candidates by cosine (approx_max_k at 0.99
        # recall — the HNSW-stage analog), rescore EXACTLY with BM25 sum
        # in the same program (the ES hybrid rule: combined = knn_score
        # + rescore query). Corpus arrays ride as arguments: a 0.5GB
        # closure constant would be baked into the uploaded HLO.
        scores, idx = knn_topk(v, nrm, dev_exists, dev_live,
                               qv, similarity="cosine", k=window,
                               approx_recall=0.99)
        combined = scores + b25[idx]
        order = jnp.argsort(-combined, axis=1)[:, :k]
        return (jnp.take_along_axis(combined, order, axis=1),
                jnp.take_along_axis(idx, order, axis=1))

    batches = [queries[i * KNN_BATCH: (i + 1) * KNN_BATCH]
               for i in range(4)]

    def run():
        return throughput_and_latency(
            batches,
            lambda b: knn_rescore(jnp.asarray(b), dev_emb, dev_norms,
                                  dev_bm25, TOP_K, 100),
            jax.block_until_ready)

    run()
    total_s, lat = run()
    qps = len(queries) / total_s
    p50, p99 = pcts(lat)

    # CPU baseline at the SAME scale: exact-window retrieve + rescore
    qn = queries[:32]

    def _cpu():
        qnorm = np.linalg.norm(qn, axis=1, keepdims=True)
        s_ = (1.0 + (qn @ emb.T) / (qnorm * norms[None, :] + 1e-9)) / 2.0
        for row in range(qn.shape[0]):
            cand = np.argpartition(-s_[row], 100)[:100]
            comb = s_[row][cand] + bm25[cand]
            cand[np.argsort(-comb)[:TOP_K]]
    cpu_qps = qn.shape[0] / best_time(_cpu)

    # matched-recall gate: measured recall@10 of the (approx retrieve +
    # exact rescore) pipeline against the exact CPU pipeline, averaged
    # over 32 queries — the methodology HNSW itself is judged by
    qnorm = np.linalg.norm(qn, axis=1, keepdims=True)
    sims = (1.0 + (qn @ emb.T) / (qnorm * norms[None, :] + 1e-9)) / 2.0
    s, i_dev = knn_rescore(jnp.asarray(qn), dev_emb, dev_norms,
                           dev_bm25, TOP_K, 100)
    i_dev = np.asarray(i_dev)
    hits = 0
    for row in range(qn.shape[0]):
        cand = np.argpartition(-sims[row], 100)[:100]
        exact_ids = cand[np.argsort(-(sims[row][cand]
                                      + bm25[cand]))][:TOP_K]
        hits += len(set(map(int, exact_ids))
                    & set(map(int, i_dev[row][:TOP_K])))
    recall = hits / (qn.shape[0] * TOP_K)
    if recall < 0.85:
        raise AssertionError(f"knn recall@10 too low: {recall:.3f}")
    return {"metric": "msmarco_knn_rescore_qps", "value": round(qps, 1),
            "unit": "qps", "vs_baseline": round(qps / cpu_qps, 2),
            "p50_ms": round(p50, 1), "p99_ms": round(p99, 1),
            "recall_at_10": round(recall, 3), "docs": KNN_DOCS,
            "dim": KNN_DIM}


def bench_knn_10m() -> dict:
    """IVF cluster-pruned ANN at 10M x 256 (ROADMAP item 1): recall@10
    >= 0.95 HARD GATE against the exact device scan, qps vs the exact
    path reported, cluster-prune counters proving the bound-vs-
    threshold skip fires. On the CPU CI backend the leg runs a scaled
    proxy (BENCH_KNN10M_DOCS/_DIM) — the gate applies at every scale;
    the 10M x 256 numbers come from the TPU run."""
    import functools
    import jax
    import jax.numpy as jnp
    from elasticsearch_tpu.index.ann import build_ann, default_nprobe
    from elasticsearch_tpu.ops.ann import ivf_topk
    from elasticsearch_tpu.ops.knn import knn_topk

    on_tpu = jax.default_backend() == "tpu"
    n_docs = int(os.environ.get("BENCH_KNN10M_DOCS",
                                10_000_000 if on_tpu else 100_000))
    dim = int(os.environ.get("BENCH_KNN10M_DIM",
                             256 if on_tpu else 64))
    n_q = 64
    rng = np.random.default_rng(31)
    t0 = time.time()
    # embedding-shaped corpus: vectors concentrate around semantic
    # centers (what gives IVF coarse quantization its bite); built in
    # chunks so the 10M x 256 slab streams instead of peaking 2x
    n_centers = 1024
    centers = rng.standard_normal((n_centers, dim)).astype(np.float32)
    emb = np.empty((n_docs, dim), dtype=np.float32)
    for lo in range(0, n_docs, 1 << 20):
        hi = min(lo + (1 << 20), n_docs)
        emb[lo:hi] = centers[rng.integers(0, n_centers, hi - lo)] \
            + rng.standard_normal((hi - lo, dim)).astype(np.float32) * 0.2
    norms = np.linalg.norm(emb, axis=1).astype(np.float32)
    exists = np.ones(n_docs, bool)
    log(f"knn_10m: {n_docs} x {dim} corpus in {time.time()-t0:.1f}s")

    t0 = time.time()
    prior_min = os.environ.get("ES_TPU_ANN_MIN_DOCS")
    os.environ["ES_TPU_ANN_MIN_DOCS"] = "1"
    try:
        ai = build_ann(emb, exists, "cosine", seed=7)
    finally:
        if prior_min is None:
            os.environ.pop("ES_TPU_ANN_MIN_DOCS", None)
        else:
            os.environ["ES_TPU_ANN_MIN_DOCS"] = prior_min
    assert ai is not None
    build_s = time.time() - t0
    nprobe = default_nprobe(ai.n_clusters)
    log(f"knn_10m: C={ai.n_clusters} ccap={ai.cluster_cap} "
        f"nprobe={nprobe} built in {build_s:.1f}s")

    dev = dict(vectors=jnp.asarray(emb, dtype=jnp.bfloat16),
               norms=jnp.asarray(norms), exists=jnp.asarray(exists),
               live=jnp.asarray(np.ones(n_docs, bool)),
               members=jnp.asarray(ai.members),
               centroids=jnp.asarray(ai.centroids),
               radii=jnp.asarray(ai.radii))
    # queries near members (the embedding-retrieval shape)
    queries = emb[rng.integers(0, n_docs, n_q)] \
        + rng.standard_normal((n_q, dim)).astype(np.float32) * 0.1
    qd = jnp.asarray(queries)

    def ivf(q):
        return ivf_topk(dev["vectors"], dev["norms"], dev["exists"],
                        dev["live"], dev["members"],
                        dev["centroids"], dev["radii"], q,
                        similarity="cosine", k=TOP_K, nprobe=nprobe)

    def exact(q):
        return knn_topk(dev["vectors"], dev["norms"], dev["exists"],
                        dev["live"], q, similarity="cosine", k=TOP_K)

    jax.block_until_ready(ivf(qd))          # compile
    jax.block_until_ready(exact(qd))
    ivf_s = best_time(lambda: jax.block_until_ready(ivf(qd)))
    exact_s = best_time(lambda: jax.block_until_ready(exact(qd)))
    ivf_qps = n_q / ivf_s
    exact_qps = n_q / exact_s

    s_a, i_a, stats = ivf(qd)
    s_e, _i_e = exact(qd)
    s_a, s_e = np.asarray(s_a), np.asarray(s_e)
    stats = np.asarray(stats)
    # SCORE-based recall@10 against the exact scan (ids are arbitrary
    # among bf16 score ties): a hit counts when it reaches the exact
    # k-th best
    hits = sum(int((s_a[r] >= s_e[r][-1] - 1e-6).sum())
               for r in range(n_q))
    recall = min(hits / (n_q * TOP_K), 1.0)
    if recall < 0.95:
        raise AssertionError(f"knn_10m recall@10 too low: {recall:.3f}")
    if int(stats[1]) <= 0:
        raise AssertionError("knn_10m: cluster-prune skip counter is "
                             "zero — the bound-vs-threshold prune "
                             "never fired")
    return {"metric": "knn_10m_qps", "value": round(ivf_qps, 1),
            "unit": "qps", "vs_baseline": round(ivf_qps / exact_qps, 2),
            "exact_qps": round(exact_qps, 1),
            "recall_at_10": round(recall, 3),
            "p50_ms": round(ivf_s / n_q * 1000, 3),
            "docs": n_docs, "dim": dim,
            "n_clusters": ai.n_clusters, "nprobe": nprobe,
            "build_s": round(build_s, 1),
            "clusters": {"probed": int(stats[0]),
                         "pruned": int(stats[1]),
                         "scored": int(stats[2])}}


def bench_hybrid_knn() -> dict:
    """Hybrid BM25+kNN msmarco leg: the knn bundle clause (one fused
    device dispatch per search) with the IDENTITY GATE — every fused
    response must be byte-identical to the unfused (sequential-math)
    oracle run of the same bodies."""
    from elasticsearch_tpu.search.shard_searcher import ShardReader
    from elasticsearch_tpu.search import executor as ex

    _fused_reset()
    n = max(N_DOCS // 4, 5_000)
    dim = 128
    rng = random.Random(17)
    nrng = np.random.default_rng(17)
    vocab = _vocab()
    weights = _zipf_weights(len(vocab))
    emb = nrng.standard_normal((n, dim)).astype(np.float32)
    t0 = time.time()
    docs = []
    for i in range(n):
        words = rng.choices(vocab, weights=weights,
                            k=rng.randint(20, 60))
        docs.append((str(i), {"passage": " ".join(words),
                              "emb": [float(x) for x in emb[i]]}))
    svc, seg, live = build_segment(docs, {"properties": {
        "passage": {"type": "text"},
        "emb": {"type": "dense_vector", "dims": dim,
                "similarity": "cosine"}}})
    reader = ShardReader("msmarco", [seg], {seg.seg_id: live}, svc)
    log(f"hybrid_knn: {n} passages x {dim}d in {time.time()-t0:.1f}s")

    rngq = random.Random(19)
    head = vocab[: max(len(vocab) // 8, 30)]
    wts = _zipf_weights(len(head))
    bodies = []
    for i in range(BATCH):
        terms = rngq.choices(head, weights=wts, k=2)
        qv = emb[rngq.randrange(n)] + nrng.standard_normal(
            dim).astype(np.float32) * 0.1
        bodies.append({"knn": {"field": "emb",
                               "query_vector": [float(x) for x in qv],
                               "k": TOP_K},
                       "query": {"match": {"passage": " ".join(terms)}},
                       "size": TOP_K})

    def run():
        t0 = time.time()
        out = reader.msearch([dict(b) for b in bodies])
        return time.time() - t0, out

    run()                                    # compile
    total_s, fused_out = run()
    qps = len(bodies) / total_s
    adm = ex.fused_scoring_stats()["admission"]
    if adm["admitted"] <= 0 or adm["knn"].get("query_rewrite", 0) <= 0:
        raise AssertionError(f"hybrid_knn: bundle never admitted {adm}")

    # identity gate vs the unfused sequential oracle
    os.environ["ES_TPU_FUSED"] = "0"
    try:
        oracle = reader.msearch([dict(b) for b in bodies])
    finally:
        os.environ.pop("ES_TPU_FUSED", None)
    for a, b in zip(fused_out, oracle):
        a, b = dict(a), dict(b)
        a["took"] = b["took"] = 0
        if json.dumps(a, sort_keys=True) != json.dumps(b, sort_keys=True):
            raise AssertionError("hybrid_knn: fused response diverged "
                                 "from the sequential oracle")
    return {"metric": "hybrid_bm25_knn_msmarco_qps",
            "value": round(qps, 1), "unit": "qps", "vs_baseline": 1.0,
            "identity": "fused == sequential oracle (byte)",
            "docs": n, "dim": dim, "batch": len(bodies),
            "admission": {"admitted": adm["admitted"],
                          "knn": adm["knn"],
                          "pallas_rejected": adm["pallas_rejected"]}}


# ---------------------------------------------------------------------------
# device-parallel index build (ROADMAP item 1): bulk ingest A/B,
# compaction under the write storm, ANN build wall-time
# ---------------------------------------------------------------------------

INGEST_DOCS = int(os.environ.get("BENCH_INGEST_DOCS", 20_000))


def _parse_corpus(docs, mapping):
    from elasticsearch_tpu.index.mapping import MapperService
    svc = MapperService(mapping=mapping)
    return [svc.parse(did, d) for did, d in docs]


def bench_bulk_ingest() -> dict:
    """Device vs host pack build A/B over the http_logs-shaped corpus,
    with the PACK-IDENTITY GATE: the device-built segment must carry
    the host builder's exact fingerprint (eager impacts, layouts,
    extrema bit-for-bit) — same-bytes-or-fallback is the device
    builder's whole contract (index/devbuild.py). With a per-dispatch round trip
    the A/B is additionally gated at >= 2x host docs/sec."""
    import jax
    from elasticsearch_tpu.index.segment import SegmentBuilder
    from elasticsearch_tpu.index import devbuild

    on_tpu = jax.default_backend() == "tpu"
    t0 = time.time()
    docs = make_corpus(INGEST_DOCS)
    mapping = {"properties": {"message": {"type": "text"},
                              "size": {"type": "long"},
                              "status": {"type": "keyword"}}}
    parsed = _parse_corpus(docs, mapping)
    log(f"bulk_ingest: {INGEST_DOCS} docs parsed in {time.time()-t0:.1f}s")

    builder = SegmentBuilder()
    for pd in parsed:
        builder.add(pd)

    # build() reads accumulated state without consuming it, so one
    # builder serves every A/B rep; the host pass stays pure-host
    # (no device pack dispatch) by never entering enable_scope
    host_s = best_time(lambda: builder.build("ab"))
    seg_host = builder.build("ab")

    devbuild.build_segment(builder, "ab")        # compile warm-up
    devbuild.reset_stats()
    dev_s = best_time(lambda: devbuild.build_segment(builder, "ab"))
    seg_dev = devbuild.build_segment(builder, "ab")
    if devbuild.stats()["builds_fallback"]:
        raise AssertionError("bulk_ingest: device build fell back to "
                             f"host: {devbuild.stats()}")
    if seg_dev.fingerprint() != seg_host.fingerprint():
        raise AssertionError(
            "bulk_ingest: device pack diverged from host pack "
            f"({seg_dev.fingerprint()} != {seg_host.fingerprint()})")

    dev_dps = INGEST_DOCS / dev_s
    host_dps = INGEST_DOCS / host_s
    speedup = dev_dps / host_dps
    if on_tpu and speedup < 2.0:
        raise AssertionError("bulk_ingest: device build "
                             f"{speedup:.2f}x host — gate is 2x on "
                             "per-dispatch round trip")
    return {"metric": "bulk_ingest_docs_per_s", "value": round(dev_dps, 1),
            "unit": "docs/s", "vs_baseline": round(speedup, 2),
            "host_docs_per_s": round(host_dps, 1),
            "identity": "device pack == host pack (fingerprint)",
            "docs": INGEST_DOCS}


def bench_compaction_storm() -> dict:
    """Compaction wall-time under the PR 9 write storm shape: delta
    segments accumulate across refresh epochs, then one fold produces
    the new base. Device vs host A/B on the SAME delta stack, gated on
    the folded base's fingerprint matching across the two paths."""
    from elasticsearch_tpu.node import Node
    from elasticsearch_tpu.index import devbuild

    n_rounds = int(os.environ.get("BENCH_STORM_ROUNDS", 6))
    per_round = max(INGEST_DOCS // (n_rounds * 4), 256)
    mappings = {"properties": {"message": {"type": "text"},
                               "size": {"type": "long"},
                               "status": {"type": "keyword"}}}

    def storm(device: bool):
        node = Node({"index.number_of_shards": 1})
        node.create_index(
            "storm", settings={"index.streaming.delta": True,
                               "index.build.device": device,
                               # fold exactly once, under the timer
                               "index.delta.min_compact_docs": 1 << 30},
            mappings=mappings)
        docs = make_corpus(n_rounds * per_round, seed=91)
        for r in range(n_rounds):
            for did, d in docs[r * per_round: (r + 1) * per_round]:
                node.index_doc("storm", did, d)
            node.refresh("storm")
        eng = node.indices["storm"].shard(0)
        t0 = time.time()
        with devbuild.enable_scope(device):
            eng._compact_now()
        wall = time.time() - t0
        fps = sorted(s.fingerprint() for s in eng.segments)
        node.close()
        return wall, fps

    host_s, host_fps = storm(device=False)
    dev_s, dev_fps = storm(device=True)
    if dev_fps != host_fps:
        raise AssertionError("compaction_storm: device fold diverged "
                             "from host fold")
    return {"metric": "compaction_storm_wall_ms",
            "value": round(dev_s * 1000, 1), "unit": "ms",
            "vs_baseline": round(host_s / max(dev_s, 1e-9), 2),
            "host_wall_ms": round(host_s * 1000, 1),
            "identity": "device fold == host fold (fingerprint)",
            "docs": n_rounds * per_round, "deltas": n_rounds}


def bench_ann_build() -> dict:
    """IVF k-means build wall-time, device vs host Lloyd iterations.
    1M+ x 256 vectors on TPU; env-scaled proxy on the CPU CI backend
    (the device path compiles and runs everywhere — only the speedup
    claim needs a per-dispatch round trip)."""
    import jax
    from elasticsearch_tpu.index.ann import build_ann
    from elasticsearch_tpu.index import devbuild

    on_tpu = jax.default_backend() == "tpu"
    n_docs = int(os.environ.get("BENCH_ANN_BUILD_DOCS",
                                1_000_000 if on_tpu else 50_000))
    dim = int(os.environ.get("BENCH_ANN_BUILD_DIM",
                             256 if on_tpu else 64))
    rng = np.random.default_rng(29)
    n_centers = 512
    centers = rng.standard_normal((n_centers, dim)).astype(np.float32)
    emb = np.empty((n_docs, dim), dtype=np.float32)
    for lo in range(0, n_docs, 1 << 20):
        hi = min(lo + (1 << 20), n_docs)
        emb[lo:hi] = centers[rng.integers(0, n_centers, hi - lo)] \
            + rng.standard_normal((hi - lo, dim)).astype(np.float32) * 0.2
    exists = np.ones(n_docs, bool)

    prior_min = os.environ.get("ES_TPU_ANN_MIN_DOCS")
    os.environ["ES_TPU_ANN_MIN_DOCS"] = "1"
    try:
        def run(device: bool):
            with devbuild.enable_scope(device):
                t0 = time.time()
                ai = build_ann(emb, exists, "cosine", seed=7)
                return time.time() - t0, ai
        run(device=True)                         # compile warm-up
        dev_s, ai_dev = run(device=True)
        host_s, ai_host = run(device=False)
    finally:
        if prior_min is None:
            os.environ.pop("ES_TPU_ANN_MIN_DOCS", None)
        else:
            os.environ["ES_TPU_ANN_MIN_DOCS"] = prior_min
    assert ai_dev is not None and ai_host is not None
    if ai_dev.n_clusters != ai_host.n_clusters:
        raise AssertionError("ann_build: cluster counts diverged")
    return {"metric": "ann_build_wall_s", "value": round(dev_s, 2),
            "unit": "s", "vs_baseline": round(host_s / max(dev_s, 1e-9), 2),
            "host_wall_s": round(host_s, 2),
            "docs": n_docs, "dim": dim,
            "n_clusters": ai_dev.n_clusters}


def bench_host_replace_recovery() -> dict:
    """Live-join recovery wall time (ISSUE 19): a 3-host scoped-session
    replica pod loses a member to a hard kill, the survivors quorum-
    evict it, and the metric is the wall time for a REPLACEMENT to join
    the live pod — hello/identity handshake, quorum admit, epoch
    rebuild — until every member (joiner included) serves again.
    Identity-gated: responses must be byte-identical across the whole
    kill -> evict -> replace arc on every driver (the replica-layout
    contract). CPU-runnable: scoped sessions are per-host device
    runtimes, so one process can play all three hosts over a LocalHub.
    The full-SPMD variant (global jax.distributed mesh, DCN admit) is
    hardware-gated — it needs a real multi-process pod (see
    tests/test_membership_procs.py for the real-OS-process arc)."""
    import jax
    from elasticsearch_tpu.cluster.transport import LocalHub
    from elasticsearch_tpu.index.mapping import MapperService
    from elasticsearch_tpu.index.segment import SegmentBuilder
    from elasticsearch_tpu.parallel.multihost import MultiHostIndex
    from elasticsearch_tpu.search.dispatch import membership_stats
    from elasticsearch_tpu.utils import faults
    from elasticsearch_tpu.utils.settings import Settings

    hosts = ["h0", "h1", "h2"]
    n_docs = 2000
    svc = MapperService(mapping={"properties": {
        "status": {"type": "keyword"},
        "size": {"type": "long"}}})

    def segs():
        b = SegmentBuilder()
        for i in range(n_docs):
            b.add(svc.parse(str(i), {
                "status": ["200", "404", "500"][i % 3], "size": i}))
        return [b.build("s0")]

    settings = Settings({
        "mesh.ping_interval": "-1", "mesh.ping_timeout": "500ms",
        "mesh.ping_retries": 3, "mesh.exec_backoff": "10ms"})
    hub = LocalHub()
    tr = {h: hub.create_transport(h, n_threads=6) for h in hosts}
    pod: dict[str, MultiHostIndex] = {}

    def mk(me, join=False):
        pod[me] = MultiHostIndex(
            tr[me], me, hosts, segs(), svc, {h: 1 for h in hosts},
            settings=settings, layout="replica", session="scoped",
            membership="quorum", join=join)

    threads = [threading.Thread(target=mk, args=(h,))
               for h in hosts[1:]]
    [t.start() for t in threads]
    mk(hosts[0])
    [t.join(timeout=120) for t in threads]
    body = {"query": {"term": {"status": "500"}}, "size": 10}
    try:
        a, b = pod["h0"], pod["h1"]
        base = _strip_timing(a.search(body))
        before = membership_stats.replacements.count

        # hard-kill h2; survivors evict it on heartbeats
        faults.configure("host_dead:host=h2")
        for _ in range(4):
            a.heartbeat_now()
        if not a.await_settled(60) or a.members != ("h0", "h1"):
            raise AssertionError(
                f"host_replace: eviction did not settle "
                f"({a.members}; {a.decisions})")
        if _strip_timing(a.search(body)) != base:
            raise AssertionError(
                "host_replace: survivor bytes drifted after eviction")

        # replacement joins the LIVE pod — this is the measured arc
        faults.clear()
        pod["h2"].close()
        tr["h2"].close()
        tr["h2"] = hub.create_transport("h2", n_threads=6)
        t0 = time.time()
        mk("h2", join=True)
        if not (a.await_settled(60) and b.await_settled(60)):
            raise AssertionError("host_replace: join did not settle")
        for h in hosts:
            if pod[h].members != ("h0", "h1", "h2"):
                raise AssertionError(
                    f"host_replace: [{h}] members {pod[h].members}")
            if _strip_timing(pod[h].search(body)) != base:
                raise AssertionError(
                    f"host_replace: [{h}] bytes drifted after join")
        recovery_ms = (time.time() - t0) * 1000.0
        if membership_stats.replacements.count != before + 1:
            raise AssertionError("host_replace: replacement not "
                                 "counted as a replacement")
        return {"metric": "host_replace_recovery_ms",
                "value": round(recovery_ms, 1), "unit": "ms",
                "vs_baseline": 1.0,
                "note": "replacement process joins a live scoped-"
                        "session pod (zero survivor restarts): "
                        "hello/identity handshake + quorum admit + "
                        "epoch rebuild until all 3 members serve "
                        "byte-identically; full-SPMD global-mesh "
                        f"variant hardware-gated (backend="
                        f"{jax.default_backend()})"}
    finally:
        faults.clear()
        for idx in pod.values():
            idx.close()
        for t in tr.values():
            t.close()


def main():
    import jax
    from elasticsearch_tpu.utils.compile_cache import configure_compile_cache
    log(f"compile cache: {configure_compile_cache()}")
    log(f"devices={jax.devices()} backend={jax.default_backend()}")
    results = [bench_http_logs(), bench_bool_msmarco(),
               bench_phrase_heavy()]
    round_trip_ms = measure_dispatch_round_trip_ms()
    log(f"per-dispatch round trip p50: {round_trip_ms:.1f} ms")
    unbatched = bench_unbatched_traffic(round_trip_ms)
    svc, seg, live, zones, ts, fare = build_taxis()
    reader = _reader(svc, seg, live)
    results.append({"metric": "dispatch_round_trip_ms",
                    "value": round(round_trip_ms, 2), "unit": "ms",
                    "vs_baseline": 1.0,
                    "note": "flat per-dispatch round trip "
                            "(serving stack, not compute); "
                            "subtracted in single_device_p50_ms"})
    results.append(unbatched)
    results.append(bench_overload_mixed_tenant(round_trip_ms))
    results.append(bench_lone_query(round_trip_ms))
    results.append(bench_concurrent_index_search(round_trip_ms))
    results.append(bench_crash_recovery())
    results.append(bench_oversubscribed_corpus(round_trip_ms))
    results.append(bench_degraded_search(round_trip_ms))
    results.append(bench_terms_agg(reader, zones, ts, round_trip_ms))
    results.append(bench_date_histogram(reader, ts, fare, round_trip_ms))
    results.append(bench_knn())
    results.append(bench_knn_10m())
    results.append(bench_hybrid_knn())
    results.append(bench_bulk_ingest())
    results.append(bench_compaction_storm())
    results.append(bench_ann_build())
    results.append(bench_host_replace_recovery())
    for r in results:
        print(json.dumps(r))


if __name__ == "__main__":
    main()
