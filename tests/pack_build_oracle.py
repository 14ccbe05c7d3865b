"""The text pack build as loops: one statement a token, a posting, a
block or a term, as `index/segment.py` had it until PR 35 made the
build array code. Kept as the oracle of `test_pack_build_arrays.py`:
the array build has to give these loops' bytes. Nothing of the program
calls this file; it shares with `segment.py` only the constants, the
dataclasses and the three helpers the issue left as they were
(`tile_summary`, `pack_positions`, `next_pow2`)."""

from __future__ import annotations

import numpy as np

from elasticsearch_tpu.index.mapping import TEXT
from elasticsearch_tpu.index.segment import (
    BLOCK, MAX_FWD_SLOTS, PostingsField, next_pow2, pack_positions,
    tile_summary,
)
from elasticsearch_tpu.index.similarity import DEFAULT_SIMILARITY, FieldStats


def build_text(builder) -> dict[str, PostingsField]:
    """`SegmentBuilder.build`'s text part: every text field of the
    builder's documents, in the order `build` gives them."""
    n = len(builder.docs)
    cap = next_pow2(n, floor=BLOCK)
    text_postings: dict[str, dict[str, list[tuple[int, list[int]]]]] = {}
    text_doclen: dict[str, np.ndarray] = {}
    for d, doc in enumerate(builder.docs):
        doc_tokens: dict[str, list[str]] = {}
        for pf in doc.fields:
            if pf.type == TEXT:
                doc_tokens.setdefault(pf.name, []).extend(pf.tokens or [])
        for fname, toks in doc_tokens.items():
            postings = text_postings.setdefault(fname, {})
            if fname not in text_doclen:
                text_doclen[fname] = np.zeros(cap, dtype=np.float32)
            text_doclen[fname][d] += float(len(toks))
            pos_local: dict[str, list[int]] = {}
            for i, tok in enumerate(toks):
                pos_local.setdefault(tok, []).append(i)
            for term, positions in pos_local.items():
                postings.setdefault(term, []).append((d, positions))
    return {
        name: build_postings(name, postings, text_doclen[name], n, cap,
                             builder._sim_for(name))
        for name, postings in text_postings.items()
    }


def build_postings(name: str, postings: dict, doc_len: np.ndarray,
                   n_docs: int, cap: int, sim=None) -> PostingsField:
    terms = sorted(postings)
    term_index = {t: i for i, t in enumerate(terms)}
    df = np.array([len(postings[t]) for t in terms], dtype=np.int32)
    indptr = np.zeros(len(terms) + 1, dtype=np.int64)
    np.cumsum(df, out=indptr[1:])
    nnz = int(indptr[-1])
    doc_ids = np.empty(nnz, dtype=np.int32)
    tfs = np.empty(nnz, dtype=np.float32)
    pos_chunks: list[list[int]] = []
    for i, t in enumerate(terms):
        plist = postings[t]  # already in doc order (docs added in order)
        s = indptr[i]
        for j, (d, positions) in enumerate(plist):
            doc_ids[s + j] = d
            tfs[s + j] = len(positions)
            pos_chunks.append(positions)
    pos_indptr = np.zeros(nnz + 1, dtype=np.int64)
    np.cumsum([len(c) for c in pos_chunks], out=pos_indptr[1:])
    pos_data = (np.concatenate([np.asarray(c, dtype=np.int32)
                                for c in pos_chunks])
                if pos_chunks else np.empty(0, dtype=np.int32))

    doc_count = int(np.count_nonzero(doc_len[:n_docs])) or n_docs
    total_len = float(doc_len.sum())
    avg_len = (total_len / doc_count) if doc_count else 1.0

    pf = PostingsField(
        name=name, terms=terms, term_index=term_index, df=df,
        indptr=indptr, doc_ids=doc_ids, tfs=tfs,
        doc_len=doc_len, doc_count=doc_count, avg_len=max(avg_len, 1e-9),
        pos_data=pos_data, pos_indptr=pos_indptr,
    )
    pack_layout(pf, cap, flat_impacts(pf, sim))
    return pf


def flat_impacts(pf: PostingsField, sim=None) -> np.ndarray:
    """Per-posting impacts in CSR order, one `sim.impacts` call a term."""
    if sim is None:
        sim = DEFAULT_SIMILARITY
    T = len(pf.terms)
    total_len = float(pf.doc_len.sum())
    ttf_all = np.zeros(T, dtype=np.float64)
    np.add.at(ttf_all,
              np.repeat(np.arange(T), np.diff(pf.indptr)),
              pf.tfs.astype(np.float64))
    out = np.zeros(len(pf.doc_ids), dtype=np.float32)
    for t in range(T):
        s, e = int(pf.indptr[t]), int(pf.indptr[t + 1])
        if s == e:
            continue
        docs = pf.doc_ids[s:e]
        tf = pf.tfs[s:e].astype(np.float64)
        st = FieldStats(df=float(pf.df[t]), ttf=float(ttf_all[t]),
                        doc_count=float(pf.doc_count),
                        avg_len=float(pf.avg_len), total_len=total_len)
        out[s:e] = sim.impacts(tf, pf.doc_len[docs].astype(np.float64), st)
    return out


def extract_flat_impacts(pf: PostingsField) -> np.ndarray:
    """`pack_layout`'s block fill run backwards."""
    nnz = len(pf.doc_ids)
    out = np.empty(nnz, dtype=np.float32)
    T = len(pf.terms)
    for t in range(T):
        s, e = int(pf.indptr[t]), int(pf.indptr[t + 1])
        b0 = int(pf.block_start[t])
        for off in range(0, e - s, BLOCK):
            blk = b0 + off // BLOCK
            ln = min(BLOCK, e - s - off)
            out[s + off: s + off + ln] = pf.block_imps[blk, :ln]
    return out


def pack_layout(pf: PostingsField, cap: int, imps: np.ndarray) -> None:
    """128-lane blocks, forward index, block-max summary and positional
    pack from CSR postings and their impacts, a term and a block at a
    time."""
    T = len(pf.terms)
    n_blocks_per_term = (np.diff(pf.indptr) + BLOCK - 1) // BLOCK
    block_start = np.zeros(T + 1, dtype=np.int32)
    np.cumsum(n_blocks_per_term, out=block_start[1:])
    nb = int(block_start[-1])
    nb_pad = next_pow2(nb, floor=1)
    block_docs = np.full((nb_pad, BLOCK), cap, dtype=np.int32)
    block_imps = np.zeros((nb_pad, BLOCK), dtype=np.float32)
    for t in range(T):
        s, e = int(pf.indptr[t]), int(pf.indptr[t + 1])
        docs = pf.doc_ids[s:e]
        imp = imps[s:e]
        b0 = int(block_start[t])
        for off in range(0, e - s, BLOCK):
            blk = b0 + off // BLOCK
            ln = min(BLOCK, e - s - off)
            block_docs[blk, :ln] = docs[off:off + ln]
            block_imps[blk, :ln] = imp[off:off + ln]
    pf.block_docs = block_docs
    pf.block_imps = block_imps
    pf.block_start = block_start

    lengths = np.zeros(cap, dtype=np.int64)
    np.add.at(lengths, pf.doc_ids, 1)
    L = next_pow2(int(lengths.max(initial=1)), floor=8)
    if L > MAX_FWD_SLOTS:
        pf.fwd_tids = None
        pf.fwd_imps = None
        return
    fwd_tids = np.full((cap, L), -1, dtype=np.int32)
    fwd_imps = np.zeros((cap, L), dtype=np.float32)
    slot = np.zeros(cap, dtype=np.int64)
    for t in range(T):
        s, e = int(pf.indptr[t]), int(pf.indptr[t + 1])
        docs = pf.doc_ids[s:e]
        b0 = int(block_start[t])
        for off in range(0, e - s, BLOCK):
            blk = b0 + off // BLOCK
            ln = min(BLOCK, e - s - off)
            d_slice = docs[off:off + ln]
            j = slot[d_slice]
            fwd_tids[d_slice, j] = t
            fwd_imps[d_slice, j] = block_imps[blk, :ln]
            slot[d_slice] = j + 1
    pf.fwd_tids = fwd_tids
    pf.fwd_imps = fwd_imps
    pf.tile_max = tile_summary(
        np.repeat(np.arange(T, dtype=np.int64), np.diff(pf.indptr)),
        pf.doc_ids, imps, T, cap)
    pack_positions(pf, cap)
