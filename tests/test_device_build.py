"""Device-parallel index build (index/devbuild.py) byte-identity matrix.

The device builder's whole contract is SAME BYTES OR FALLBACK: a
device-built segment must carry the host builder's exact fingerprint —
eager impacts bit-for-bit, identical block/forward/tile layouts,
identical numeric extrema and doc values — across fresh builds, delta
packs, deletes, compaction folds, and restarts. Every test here runs
the host path as the oracle and diffs the device path against it.
"""

import dataclasses
import os

import numpy as np
import pytest

from elasticsearch_tpu.index import devbuild
from elasticsearch_tpu.index.mapping import MapperService, ParsedField
from elasticsearch_tpu.index.segment import (
    SegmentBuilder, build_tile_minmax, concat_segments,
)
from elasticsearch_tpu.utils import faults

MAPPING = {"properties": {
    "body": {"type": "text"},
    "title": {"type": "text"},
    "tag": {"type": "keyword"},
    "tags": {"type": "keyword"},
    "n": {"type": "long"},
    "price": {"type": "double"},
    "ts": {"type": "date"},
    "ok": {"type": "boolean"},
    "emb": {"type": "dense_vector", "dims": 8},
}}

WORDS = ["alpha", "beta", "gamma", "delta", "epsilon", "zeta", "eta",
         "theta", "iota", "kappa"]


def _doc(rng, i):
    d = {"body": " ".join(rng.choice(WORDS,
                                     size=int(rng.integers(1, 15)))),
         "tag": str(rng.choice(WORDS[:4])),
         "n": int(rng.integers(-50, 50)),
         "price": float(np.round(rng.gamma(2.0, 5.0), 3)),
         "ts": int(1420070400_000 + rng.integers(0, 10**9) * 1000),
         "ok": bool(rng.integers(0, 2))}
    if i % 3 == 0:                      # second text field, sparse
        d["title"] = " ".join(rng.choice(WORDS, size=2))
    if i % 4 == 0:                      # multi-valued keyword
        d["tags"] = [str(w) for w in rng.choice(WORDS, size=3)]
    if i % 5 != 0:                      # vector with gaps
        d["emb"] = [float(x) for x in rng.normal(size=8)]
    if i % 7 == 0:                      # empty text field value
        d["body"] = ""
    return d


def _builder(n=80, seed=0, svc=None):
    svc = svc or MapperService(mapping=MAPPING)
    rng = np.random.default_rng(seed)
    b = SegmentBuilder()
    for i in range(n):
        b.add(svc.parse(f"d{i}", _doc(rng, i)))
    return b, svc


def _np_eq(a, b):
    if dataclasses.is_dataclass(a) and dataclasses.is_dataclass(b):
        # a column's own record (the block-max TileSummary): field by
        # field, the same bytes
        return type(a) is type(b) and all(
            _np_eq(getattr(a, f.name), getattr(b, f.name))
            for f in dataclasses.fields(a))
    if isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
        if not (isinstance(a, np.ndarray) and isinstance(b, np.ndarray)):
            return False
        if a.dtype != b.dtype or a.shape != b.shape:
            return False
        if a.dtype.kind == "f":
            return np.array_equal(a, b, equal_nan=True)
        return np.array_equal(a, b)
    return a == b


def _assert_columns_equal(ca, cb, label):
    for f in dataclasses.fields(ca):
        va, vb = getattr(ca, f.name), getattr(cb, f.name)
        assert _np_eq(va, vb), f"{label}.{f.name} diverged"


def assert_segments_identical(host, dev):
    assert host.fingerprint() == dev.fingerprint()
    assert host.cache_key() == dev.cache_key()
    assert host.num_docs == dev.num_docs
    assert host.capacity == dev.capacity
    assert host.ids == dev.ids
    assert host.id_map == dev.id_map
    assert np.array_equal(host.versions, dev.versions)
    for group in ("text", "keywords", "numerics", "vectors", "geos"):
        ga, gb = getattr(host, group), getattr(dev, group)
        assert sorted(ga) == sorted(gb), f"{group} field sets diverged"
        for name in ga:
            _assert_columns_equal(ga[name], gb[name], f"{group}.{name}")


# ---------------------------------------------------------------------------
# fresh builds
# ---------------------------------------------------------------------------


def test_mixed_field_build_identity():
    bh, svc = _builder(seed=1)
    bd, _ = _builder(seed=1, svc=svc)
    host = bh.build("s")
    before = devbuild.stats()
    dev = devbuild.build_segment(bd, "s")
    after = devbuild.stats()
    assert after["builds_device"] == before["builds_device"] + 1
    assert after["builds_fallback"] == before["builds_fallback"]
    assert after["docs_device"] >= before["docs_device"] + 80
    assert_segments_identical(host, dev)
    # eager impacts specifically must be byte-equal (the contract the
    # compaction identity chain leans on)
    for name in host.text:
        assert host.text[name].block_imps.tobytes() == \
            dev.text[name].block_imps.tobytes()


def test_env_toggle_routes_host_builder(monkeypatch):
    monkeypatch.setenv("ES_TPU_DEVICE_BUILD", "1")
    assert devbuild.enabled()
    bh, svc = _builder(n=40, seed=2)
    bd, _ = _builder(n=40, seed=2, svc=svc)
    monkeypatch.delenv("ES_TPU_DEVICE_BUILD")
    host = bh.build("s")                 # env off: pure host oracle
    monkeypatch.setenv("ES_TPU_DEVICE_BUILD", "1")
    before = devbuild.stats()["pack_layout_device"]
    dev = bd.build("s")                  # env on: device pack layout
    assert devbuild.stats()["pack_layout_device"] > before
    assert_segments_identical(host, dev)


def test_empty_and_degenerate_fields_identity():
    svc = MapperService(mapping=MAPPING)

    def mk():
        b = SegmentBuilder()
        b.add(svc.parse("a", {"body": "", "n": 1}))
        b.add(svc.parse("b", {"tag": "x"}))
        b.add(svc.parse("c", {"body": "alpha alpha alpha"}))
        return b
    host = mk().build("s")
    dev = devbuild.build_segment(mk(), "s")
    assert_segments_identical(host, dev)


# ---------------------------------------------------------------------------
# delta packs, deletes, compaction
# ---------------------------------------------------------------------------


def _service(tmp_path, device, subdir):
    from elasticsearch_tpu.index.index_service import IndexService
    from elasticsearch_tpu.utils.settings import Settings
    root = tmp_path / subdir
    root.mkdir(parents=True, exist_ok=True)
    return IndexService("ix", Settings({
        "index.streaming.delta": True,
        "index.build.device": device,
        "index.delta.min_compact_docs": 1 << 30}),
        mapping=MAPPING, data_path=str(root))


def _fps(svc):
    return sorted(s.fingerprint()
                  for eng in svc.shards.values() for s in eng.segments)


def _keys(svc):
    return sorted(s.cache_key()
                  for eng in svc.shards.values() for s in eng.segments)


def test_delta_and_compaction_identity(tmp_path):
    rng_docs = [(f"d{i}", _doc(np.random.default_rng(100 + i), i))
                for i in range(60)]
    svcs = [_service(tmp_path, dev, f"dev{dev}") for dev in (False, True)]
    try:
        for svc in svcs:
            for did, d in rng_docs[:40]:
                svc.index_doc(did, d)
            svc.refresh()                       # base via builder
            for eng in svc.shards.values():
                eng.compact()
            for did, d in rng_docs[40:]:        # delta pack on top
                svc.index_doc(did, d)
            svc.refresh()
        host_svc, dev_svc = svcs
        assert _fps(host_svc) == _fps(dev_svc)
        assert _keys(host_svc) == _keys(dev_svc)   # delta cache keys too
        for svc in svcs:                        # deletes, then the fold
            for did in ("d3", "d41", "d17"):
                svc.delete_doc(did)
            svc.refresh()
            for eng in svc.shards.values():
                eng.compact()
        assert _fps(host_svc) == _fps(dev_svc)
    finally:
        for svc in svcs:
            svc.close()


def test_restart_roundtrip_identity(tmp_path):
    docs = [(f"d{i}", _doc(np.random.default_rng(200 + i), i))
            for i in range(30)]
    fps = {}
    for dev in (False, True):
        svc = _service(tmp_path, dev, f"rt{dev}")
        for did, d in docs:
            svc.index_doc(did, d)
        svc.refresh()
        svc.flush()
        svc.close()
        svc = _service(tmp_path, dev, f"rt{dev}")   # reopen from disk
        fps[dev] = _fps(svc)
        assert svc.doc_count() == 30
        svc.close()
    assert fps[False] == fps[True]


def test_concat_identity_under_deletes():
    svc = MapperService(mapping=MAPPING)
    segs = {}
    for tag in ("host", "dev"):
        b1, _ = _builder(n=50, seed=5, svc=svc)
        b2, _ = _builder(n=30, seed=6, svc=svc)
        segs[tag] = (b1.build("a"), b2.build("b"))
    assert segs["host"][0].fingerprint() == segs["dev"][0].fingerprint()
    live_a = np.ones(50, bool)
    live_a[[2, 9, 31]] = False
    live_b = np.ones(30, bool)
    live_b[11] = False
    masks = {"a": live_a, "b": live_b}
    host = concat_segments(segs["host"], "m", live_masks=masks)
    with devbuild.enable_scope():
        dev = concat_segments(segs["dev"], "m", live_masks=masks)
    assert_segments_identical(host, dev)
    for name in host.text:
        assert host.text[name].block_imps.tobytes() == \
            dev.text[name].block_imps.tobytes()


# ---------------------------------------------------------------------------
# numeric tile extrema
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_tile_minmax_identity(dtype):
    cap = 4096
    rng = np.random.default_rng(7)
    exists = rng.random(cap) < 0.8
    if dtype is np.float32:
        vals = rng.normal(size=cap).astype(np.float32)
        vals[rng.random(cap) < 0.05] = np.nan      # NaN poison guard
        vals[rng.random(cap) < 0.02] = np.inf
    else:
        vals = rng.integers(-1000, 1000, cap).astype(np.int32)
    host = build_tile_minmax(vals, exists, cap)
    before = devbuild.stats()["tile_minmax_device"]
    with devbuild.enable_scope():
        dev = build_tile_minmax(vals, exists, cap)
    assert devbuild.stats()["tile_minmax_device"] == before + 1
    assert host is not None and dev is not None
    for a, b in zip(host, dev):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# IVF build (device k-means)
# ---------------------------------------------------------------------------


def test_ann_build_identity_fixed_seed(monkeypatch):
    from elasticsearch_tpu.index.ann import ensure_ann
    monkeypatch.setenv("ES_TPU_ANN_MIN_DOCS", "1")
    monkeypatch.setenv("ES_TPU_DEVICE_BUILD", "1")
    svc = MapperService(mapping=MAPPING)
    segs = []
    for _ in range(2):                   # host-built vs device-built pack
        b, _ = _builder(n=600, seed=9, svc=svc)
        segs.append(b.build("s") if len(segs) == 0
                    else devbuild.build_segment(b, "s"))
    ais = [ensure_ann(s, "emb", "cosine") for s in segs]
    assert ais[0] is not None and ais[1] is not None
    np.testing.assert_array_equal(ais[0].centroids, ais[1].centroids)
    np.testing.assert_array_equal(ais[0].members, ais[1].members)
    np.testing.assert_array_equal(ais[0].radii, ais[1].radii)
    assert ais[0].n_clusters == ais[1].n_clusters


# ---------------------------------------------------------------------------
# fault-injected device errors: host fallback, identity, no breaker leak
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("phase", ["build", "pack"])
def test_fault_fallback_identity_no_leak(phase):
    from elasticsearch_tpu.utils.breaker import breaker_service
    bh, svc = _builder(n=40, seed=11)
    bd, _ = _builder(n=40, seed=11, svc=svc)
    host = bh.build("s")
    brk = breaker_service().breaker("fielddata")
    used_before = brk.used
    faults.configure(f"shard_error:site=build:phase={phase}")
    try:
        before = devbuild.stats()
        if phase == "build":
            dev = devbuild.build_segment(bd, "s")
        else:
            with devbuild.enable_scope():
                dev = bd.build("s")
        after = devbuild.stats()
    finally:
        faults.clear()
    assert after["builds_fallback"] > before["builds_fallback"]
    assert_segments_identical(host, dev)
    assert brk.used == used_before      # mid-build error must not leak


# ---------------------------------------------------------------------------
# deletes-only compaction short-circuit + ANN carry-over
# ---------------------------------------------------------------------------


def test_compact_skip_when_only_deletes(tmp_path):
    svc = _service(tmp_path, False, "skip")
    try:
        for i in range(20):
            svc.index_doc(f"d{i}", _doc(np.random.default_rng(i), i))
        svc.refresh()
        for eng in svc.shards.values():
            eng.compact()                       # real base
        svc.delete_doc("d4")                    # deletes-only window
        before = devbuild.stats()["build_skipped"]
        skipped = 0
        for eng in svc.shards.values():
            if eng.segments and not eng.compact():
                skipped += 1
        assert skipped > 0
        assert devbuild.stats()["build_skipped"] >= before + skipped
        assert svc.doc_count() == 19            # delete still applied
    finally:
        svc.close()


def test_concat_carries_ann_when_vectors_unchanged(monkeypatch):
    from elasticsearch_tpu.index.ann import ensure_ann
    monkeypatch.setenv("ES_TPU_ANN_MIN_DOCS", "1")
    svc = MapperService(mapping=MAPPING)
    b, _ = _builder(n=300, seed=13, svc=svc)
    seg = b.build("a")
    ai = ensure_ann(seg, "emb", "cosine")
    assert ai is not None
    before = devbuild.stats()["build_skipped"]
    merged = concat_segments([seg], "m")
    assert merged.ann.get("emb") is ai          # transplanted, not rebuilt
    assert devbuild.stats()["build_skipped"] == before + 1
    # a delete invalidates the row numbering: no carry-over
    live = np.ones(seg.num_docs, bool)
    live[5] = False
    merged2 = concat_segments([seg], "m2", live_masks={"a": live})
    assert merged2.ann.get("emb") is None


# ---------------------------------------------------------------------------
# engine stats surface
# ---------------------------------------------------------------------------


def test_engine_surfaces_build_stats(tmp_path):
    svc = _service(tmp_path, True, "stats")
    try:
        for i in range(25):
            svc.index_doc(f"d{i}", _doc(np.random.default_rng(i), i))
        svc.refresh()
        assert svc.op_stats.build_total >= 1
        assert svc.op_stats.build_docs >= 25
        assert svc.op_stats.build_device_total >= 1
    finally:
        svc.close()


def test_node_stats_expose_device_build():
    from elasticsearch_tpu.node import Node
    node = Node({"index.number_of_shards": 1})
    try:
        node.create_index("ix", settings={"index.build.device": True},
                          mappings=MAPPING)
        for i in range(10):
            node.index_doc("ix", f"d{i}",
                           _doc(np.random.default_rng(i), i))
        node.refresh("ix")
        ns = node.nodes_stats()["nodes"][node.name]
        db = ns["indexing"]["device_build"]
        assert db["builds_device"] >= 1
        assert "docs_per_s" in db
        idx = node.indices_stats()["_all"]["total"]["indexing"]
        assert idx["build_total"] >= 1
        assert idx["device_build_total"] >= 1
        assert idx["build_docs"] >= 10
        assert idx["build_docs_per_s"] >= 0.0
    finally:
        node.close()
