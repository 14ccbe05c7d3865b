"""The text pack build is array code (PR 35): `SegmentBuilder.build`'s
text part, `_build_postings`, `_flat_impacts`, `_pack_layout_host` and
`extract_flat_impacts` run no statement a posting, a block or a term.
The bytes they give are the bytes of the loops they replaced, which
`pack_build_oracle.py` keeps: every case here builds the same documents
both ways and compares every array of every `PostingsField`, dtype and
all."""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

import pack_build_oracle as oracle
from elasticsearch_tpu.index.mapping import (
    KEYWORD, TEXT, ParsedDocument, ParsedField,
)
from elasticsearch_tpu.index.segment import (
    BLOCK, MAX_FWD_SLOTS, PostingsField, SegmentBuilder, concat_segments,
    extract_flat_impacts, next_pow2, pad_delta_shapes,
)
from elasticsearch_tpu.index.similarity import (
    BM25Similarity, ClassicSimilarity, DFRSimilarity, IBSimilarity,
    LMDirichletSimilarity, LMJelinekMercerSimilarity,
)
from elasticsearch_tpu.index.store import Store

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import corpus as C  # noqa: E402  (numpy only)
from harness.shapes import passages  # noqa: E402

ARRAYS = ("df", "indptr", "doc_ids", "tfs", "doc_len", "pos_data",
          "pos_indptr", "block_docs", "block_imps", "block_start",
          "fwd_tids", "fwd_imps", "fwd_pos", "lnorm", "k1ln")
SCALARS = ("name", "terms", "term_index", "doc_count", "avg_len",
           "pos_width")


def same_field(got: PostingsField, want: PostingsField) -> None:
    for key in SCALARS:
        assert getattr(got, key) == getattr(want, key), key
    for key in ARRAYS:
        a, b = getattr(got, key), getattr(want, key)
        assert (a is None) == (b is None), key
        if a is not None:
            assert a.dtype == b.dtype, (key, a.dtype, b.dtype)
            assert a.shape == b.shape, (key, a.shape, b.shape)
            assert np.array_equal(a, b), key
    assert (got.tile_max is None) == (want.tile_max is None)
    if got.tile_max is not None:
        assert got.tile_max.grid == want.tile_max.grid
        for key in ("start", "tiles", "vals"):
            a, b = getattr(got.tile_max, key), getattr(want.tile_max, key)
            assert a.dtype == b.dtype and np.array_equal(a, b), key


def same_text(got: dict, want: dict) -> None:
    assert list(got) == list(want)       # the fields and their order
    for name in want:
        same_field(got[name], want[name])


def text_doc(doc_id: str, *values, field: str = "body") -> ParsedDocument:
    """One document whose `field` is given once for each of `values`
    (a list of tokens, or None for a value that analysed to nothing)."""
    return ParsedDocument(doc_id=doc_id, source=b"{}", fields=[
        ParsedField(name=field, type=TEXT, tokens=v) for v in values])


def builder_of(docs, similarity=None) -> SegmentBuilder:
    b = SegmentBuilder(similarity=(lambda _f: similarity)
                       if similarity is not None else None)
    for doc in docs:
        b.add(doc)
    return b


def drawn_docs(n: int, seed: int, vocabulary: int = 400,
               longest: int = 40) -> list[ParsedDocument]:
    """`n` documents of one text field, words drawn rank^-1 so that a
    few terms fill many blocks and most have one posting."""
    rng = np.random.default_rng(seed)
    w = 1.0 / (np.arange(vocabulary) + 1.0)
    cdf = np.cumsum(w / w.sum())
    docs = []
    for d in range(n):
        ids = np.searchsorted(cdf, rng.random(int(rng.integers(1, longest))))
        docs.append(text_doc(str(d), [f"w{i}" for i in ids.tolist()]))
    return docs


def passages_docs() -> list[ParsedDocument]:
    """The text cell's record shape at its rehearsal size."""
    with open(os.path.join(BENCH, "configs",
                           "msmarco-passage-1shard.json")) as f:
        config = json.load(f)
    corpus = C.corpus_of(config, 4096, 2147483693)
    words = np.array([passages.word(i) for i in range(corpus.vocabulary)],
                     dtype=object)
    return [text_doc(str(d), list(words[corpus.passage(d)]), field="text")
            for d in range(corpus.n)]


def two_values_docs() -> list[ParsedDocument]:
    # the second value's positions run on from the first's, and a term
    # of both values is one posting
    return [text_doc("0", ["a", "b", "a"], ["b", "c"]),
            text_doc("1", ["c"], None, ["c", "a"]),
            text_doc("2", ["d", "d", "d", "d"])]


def empty_docs() -> list[ParsedDocument]:
    # a document without the field, one whose field has no token, a
    # field that no document gives a token (it still gets its
    # PostingsField and zero lengths), and fields that interleave
    return [
        ParsedDocument(doc_id="0", source=b"{}", fields=[
            ParsedField(name="tag", type=KEYWORD, value="x")]),
        ParsedDocument(doc_id="1", source=b"{}", fields=[
            ParsedField(name="body", type=TEXT, tokens=[]),
            ParsedField(name="void", type=TEXT, tokens=None)]),
        ParsedDocument(doc_id="2", source=b"{}", fields=[
            ParsedField(name="title", type=TEXT, tokens=["t", "u"]),
            ParsedField(name="body", type=TEXT, tokens=["a", "b", "a"]),
            ParsedField(name="title", type=TEXT, tokens=["t"])]),
        text_doc("3", ["b"]),
    ]


def nested_docs() -> list[ParsedDocument]:
    docs = []
    for d in range(40):
        doc = text_doc(str(d), [f"p{d % 7}", "shared", f"p{d % 3}"])
        for c in range(d % 3):
            doc.nested.append(("comments", [
                ParsedField(name="comments.text", type=TEXT,
                            tokens=["shared", f"c{c}", f"p{d % 5}"]),
                ParsedField(name="body", type=TEXT, tokens=[f"n{c}"]),
            ], b"{}"))
        docs.append(doc)
    return docs


def wide_docs() -> list[ParsedDocument]:
    # one document of more distinct terms than the forward index has
    # slots: the field keeps its blocks and gets no forward index
    wide = [f"u{i:04d}" for i in range(MAX_FWD_SLOTS + 1)]
    return drawn_docs(60, 5) + [text_doc("wide", wide + wide[:9])]


def block_edge_docs() -> list[ParsedDocument]:
    # terms of exactly 127, 128, 129, 256 and 257 postings
    counts = {"k127": BLOCK - 1, "k128": BLOCK, "k129": BLOCK + 1,
              "k256": 2 * BLOCK, "k257": 2 * BLOCK + 1}
    return [text_doc(str(d), [t for t, c in counts.items() if d < c]
                     + [f"own{d}"] + ["k256"] * (d % 2 and d < 2 * BLOCK))
            for d in range(2 * BLOCK + 1)]


BUILDS = {
    "passages_4096": (passages_docs, None),
    "two_values_of_a_field": (two_values_docs, None),
    "repeated_terms": (lambda: drawn_docs(300, 1, vocabulary=12), None),
    "no_token": (empty_docs, None),
    "nested": (nested_docs, None),
    "wider_than_the_forward_index": (wide_docs, None),
    "blocks_of_128_129_256": (block_edge_docs, None),
    "bm25_k1_b": (lambda: drawn_docs(500, 2), BM25Similarity(k1=0.9, b=0.4)),
    "classic": (lambda: drawn_docs(500, 3), ClassicSimilarity()),
    "dfr": (lambda: drawn_docs(500, 4), DFRSimilarity()),
    "ib": (lambda: drawn_docs(200, 6), IBSimilarity()),
    "lm_dirichlet": (lambda: drawn_docs(500, 4), LMDirichletSimilarity()),
    "lm_jelinek_mercer": (lambda: drawn_docs(200, 7),
                          LMJelinekMercerSimilarity()),
}


@pytest.mark.parametrize("case", list(BUILDS))
def test_the_array_build_gives_the_loops_bytes(case):
    docs, similarity = BUILDS[case]
    builder = builder_of(docs(), similarity)
    seg = builder.build("s0")
    want = oracle.build_text(builder)
    assert want, case
    same_text(seg.text, want)
    for name, pf in seg.text.items():
        flat = extract_flat_impacts(pf)
        assert flat.dtype == np.float32
        assert np.array_equal(flat, oracle.extract_flat_impacts(pf)), name
        assert np.array_equal(flat, oracle.flat_impacts(
            pf, builder._sim_for(name))), name


def test_the_cases_reach_what_they_name():
    """The shapes the cases are there for do occur in them."""
    seg = builder_of(wide_docs()).build("s0")
    assert seg.text["body"].fwd_tids is None
    assert seg.text["body"].block_docs is not None
    pf = builder_of(block_edge_docs()).build("s0").text["body"]
    for term, df in (("k127", 127), ("k128", 128), ("k129", 129),
                     ("k256", 256), ("k257", 257)):
        assert pf.df[pf.lookup(term)] == df
    assert pf.tfs[pf.indptr[pf.lookup("k256")] + 1] == 2.0
    text = builder_of(empty_docs()).build("s0").text
    assert list(text) == ["body", "void", "title"]
    assert text["void"].terms == [] and not text["void"].doc_len.any()
    assert text["void"].block_docs.shape == (1, BLOCK)
    pf = builder_of(two_values_docs()).build("s0").text["body"]
    j = int(pf.indptr[pf.lookup("c")])
    assert pf.doc_ids[j] == 0 and pf.tfs[j] == 1.0
    assert pf.pos_data[pf.pos_indptr[j]] == 4     # 3 tokens came before
    assert pf.doc_len[:3].tolist() == [5.0, 3.0, 4.0]
    nested = builder_of(nested_docs()).build("s0")
    assert nested.parent_of is not None
    assert nested.num_docs > 40 and "comments.text" in nested.text


@pytest.mark.parametrize("case", ["passages_4096", "no_token", "nested",
                                  "wider_than_the_forward_index"])
def test_a_reopened_segment_is_the_one_that_was_flushed(case, tmp_path):
    """Reopening runs `_layout_blocks` over the stored CSR: the same
    arrays as the refresh made, and as the loops make."""
    docs, similarity = BUILDS[case]
    builder = builder_of(docs(), similarity)
    seg = builder.build("s0")
    store = Store(str(tmp_path))
    store.save_segment(seg)
    loaded, _live = store.load_segment("s0")
    same_text(loaded.text, seg.text)
    same_text(loaded.text, oracle.build_text(builder))


@pytest.mark.parametrize("dead", [False, True])
def test_concat_keeps_the_impacts_and_packs_them_as_the_loops_do(dead):
    """`concat_segments` feeds the impacts it read back from its
    sources' blocks through `_pack_layout`: every posting keeps the
    impact its own segment gave it, laid out as the loops lay it out."""
    segs = [builder_of(drawn_docs(300, 11)).build("a"),
            builder_of(drawn_docs(200, 12)).build("b"),
            builder_of(block_edge_docs()).build("c")]
    masks = None
    if dead:
        masks = {}
        for s in segs:
            live = np.ones(s.capacity, dtype=bool)
            live[1:s.num_docs:3] = False
            masks[s.seg_id] = live
    merged = concat_segments(segs, "m", live_masks=masks)
    got = merged.text["body"]
    kept = {}
    base = 0
    for s in segs:
        pf = s.text["body"]
        live = (np.ones(s.num_docs, dtype=bool) if masks is None
                else masks[s.seg_id][:s.num_docs])
        new_row = base + np.cumsum(live) - 1
        flat = oracle.extract_flat_impacts(pf)
        for t, term in enumerate(pf.terms):
            for j in range(int(pf.indptr[t]), int(pf.indptr[t + 1])):
                d = int(pf.doc_ids[j])
                if live[d]:
                    kept[term, int(new_row[d])] = flat[j]
        base += int(live.sum())
    assert len(kept) == len(got.doc_ids)
    imps = np.array([kept[got.terms[t], int(got.doc_ids[j])]
                     for t in range(len(got.terms))
                     for j in range(int(got.indptr[t]),
                                    int(got.indptr[t + 1]))],
                    dtype=np.float32)
    want = PostingsField(
        name=got.name, terms=got.terms, term_index=got.term_index,
        df=got.df, indptr=got.indptr, doc_ids=got.doc_ids, tfs=got.tfs,
        doc_len=got.doc_len, doc_count=got.doc_count, avg_len=got.avg_len,
        pos_data=got.pos_data, pos_indptr=got.pos_indptr)
    oracle.pack_layout(want, merged.capacity, imps)
    same_field(got, want)
    assert np.array_equal(extract_flat_impacts(got), imps)


def test_impacts_are_read_back_from_a_padded_delta():
    """A delta's `block_start` is padded past its terms
    (`pad_delta_shapes`); reading the impacts back takes the terms'
    entries only."""
    builder = builder_of(drawn_docs(150, 21, vocabulary=37))
    seg = pad_delta_shapes(builder.build("d"))
    pf = seg.text["body"]
    assert len(pf.block_start) > len(pf.terms) + 1
    assert np.array_equal(extract_flat_impacts(pf),
                          oracle.flat_impacts(pf))
    assert seg.capacity == next_pow2(150, floor=BLOCK)
