"""A `_bulk` request's index / create items go to each shard as one batch
(Node.bulk -> IndexService.index_many -> Engine.index_many ->
DocumentMapper.parse_many -> Translog.add_many). The batch is the single
document's algorithm at another size, so the two have to agree on
everything a client, a searcher or a recovery can see."""

import copy
import dataclasses
import glob
import itertools
import json
import os
import types
import urllib.request

import numpy as np
import pytest

from elasticsearch_tpu.index import translog as translog_mod
from elasticsearch_tpu.native import tokenizer as native_tokenizer
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.rest.server import RestServer
from elasticsearch_tpu.utils import faults, profiler
from elasticsearch_tpu.utils.errors import (ElasticsearchTpuError,
                                            PowerLossError)

INDEX = "logs"

# the mapping of benchmarks/configs/http_logs-*.json
MAPPING = {
    "dynamic": "strict",
    "properties": {
        "@timestamp": {"type": "date",
                       "format": "strict_date_optional_time||epoch_second"},
        "message": {"type": "keyword", "index": False, "doc_values": False},
        "clientip": {"type": "ip"},
        "request": {"type": "text", "fields": {
            "raw": {"type": "keyword", "ignore_above": 256}}},
        "status": {"type": "integer"},
        "size": {"type": "integer"},
        "geoip": {"properties": {
            "country_name": {"type": "keyword"},
            "city_name": {"type": "keyword"},
            "location": {"type": "geo_point"}}},
    },
}


def log_doc(i: int) -> dict:
    """A document of the benchmark's corpus (harness/corpus.py)."""
    return {"@timestamp": "1998-05-%02dT%02d:%02d:%02dZ" % (
                1 + i % 28, i % 24, (7 * i) % 60, (13 * i) % 60),
            "clientip": "%d.%d.%d.0" % (1 + i % 223, (3 * i) % 256, i % 7),
            "request": "%s /english/images/imag_%d.gif HTTP/1.%d" % (
                ("GET", "HEAD", "POST")[i % 3], i % 50, i % 2),
            "status": (200, 304, 404)[i % 3], "size": 100 + 37 * i}


def write(action: str, doc_id, doc: dict, **meta) -> tuple[str, dict]:
    return action, {"_index": INDEX, "_id": doc_id, "_type": None,
                    "_routing": None, "doc": doc, **meta}


def index(i, **meta):
    return write("index", str(i), log_doc(i), **meta)


def delete(i):
    return "delete", {"_index": INDEX, "_id": str(i), "_type": None,
                      "_routing": None}


# name -> the requests of the case, each a list of (action, payload)
CASES = {
    "cell_documents": [[index(i) for i in range(lo, lo + 120)]
                       for lo in (0, 120)],
    "same_id_twice": [[index(1), index(2), write("index", "1", log_doc(7)),
                       index(3), write("index", "1", log_doc(8))]],
    "create_of_existing_id": [
        [index(1), index(2)],
        [write("create", "1", log_doc(5)), write("create", "3", log_doc(3)),
         write("create", "3", log_doc(6)), index(4)]],
    "failures_mid_run": [[
        index(1),
        write("index", "2", {**log_doc(2), "no_such_field": 1}),
        index(3),
        write("index", "4", {**log_doc(4), "@timestamp": "the day after"}),
        write("index", "5", {**log_doc(5), "status": "two hundred"}),
        index(6)]],
    "delete_and_update_between_runs": [[
        index(1), index(2), index(3), delete(2), delete(9),
        index(2), index(4),
        ("update", {"_index": INDEX, "_id": "1", "_type": None,
                    "_routing": None, "doc": {"doc": {"size": 5}}}),
        index(1), index(5)]],
    "explicit_routing": [[index(i, _routing="r%d" % (i % 3))
                          for i in range(12)] + [index(20), index(21)]],
    "auto_generated_id": [[write("index", None, log_doc(1)), index(2),
                           write("create", None, log_doc(3))]],
    "batch_of_one": [[index(1)], [index(1)], [index(2)]],
}


def new_node(path, shards: int, durability: str) -> Node:
    node = Node({"node.name": "t0", "path.data": str(path)})
    if INDEX not in node.indices:
        node.create_index(INDEX, settings={
            "index.number_of_shards": shards,
            "index.number_of_replicas": 0,
            "index.translog.durability": durability}, mappings=MAPPING)
        assert node.indices[INDEX].mappers.mapper.dynamic == "strict"
        assert {e.translog.durability for e in
                node.indices[INDEX].shards.values()} == {durability}
    assert len(node.indices[INDEX].shards) == shards
    return node


@pytest.fixture()
def fixed_ids(monkeypatch):
    """Auto-generated ids from a counter, restarted for each side of a
    comparison."""
    import uuid
    state = {"n": itertools.count()}

    def restart():
        state["n"] = itertools.count()
    monkeypatch.setattr(uuid, "uuid4", lambda: types.SimpleNamespace(
        hex="%032x" % next(state["n"])))
    return restart


def one_at_a_time(node: Node, ops) -> list[dict]:
    """The same operations through the single-document APIs, answered
    as `_bulk` answers them."""
    items = []
    for action, p in ops:
        try:
            if action in ("index", "create"):
                r = node.index_doc(p["_index"], p["_id"],
                                   copy.deepcopy(p["doc"]),
                                   routing=p["_routing"], doc_type=p["_type"],
                                   op_type=action)
                r["status"] = 201 if r["created"] else 200
            elif action == "delete":
                r = node.delete_doc(p["_index"], p["_id"],
                                    doc_type=p["_type"],
                                    routing=p["_routing"])
                r["status"] = 200 if r.get("found") else 404
            else:
                r = node.update_doc(p["_index"], p["_id"],
                                    copy.deepcopy(p["doc"]),
                                    doc_type=p["_type"],
                                    routing=p["_routing"])
                r["status"] = 200
            items.append({action: r})
        except ElasticsearchTpuError as e:
            items.append({action: {"error": e.to_dict(),
                                   "status": e.status}})
    return items


def assert_same(a, b, where: str = "") -> None:
    """Deep equality over dataclasses, containers and numpy arrays; a
    segment's own name is a process-wide counter, not content."""
    assert type(a) is type(b), where
    if dataclasses.is_dataclass(a):
        for f in dataclasses.fields(a):
            if f.name != "seg_id":
                assert_same(getattr(a, f.name), getattr(b, f.name),
                            f"{where}.{f.name}")
    elif isinstance(a, np.ndarray):
        assert a.dtype == b.dtype and a.shape == b.shape, where
        assert np.array_equal(a, b, equal_nan=a.dtype.kind == "f"), where
    elif isinstance(a, dict):
        assert list(a) == list(b), where
        for k in a:
            assert_same(a[k], b[k], f"{where}[{k!r}]")
    elif isinstance(a, (list, tuple)):
        assert len(a) == len(b), where
        for i, (x, y) in enumerate(zip(a, b)):
            assert_same(x, y, f"{where}[{i}]")
    else:
        assert a == b, where


def translog_bytes(node: Node) -> dict[int, bytes]:
    out = {}
    for sid, eng in node.indices[INDEX].shards.items():
        files = sorted(glob.glob(os.path.join(eng.translog.dir, "*.log")))
        out[sid] = b"".join(open(f, "rb").read() for f in files)
    return out


def visible(node: Node, ids) -> dict:
    """_version and _source of each id through realtime get."""
    out = {}
    for doc_id in ids:
        for routing in (None, "r0", "r1", "r2"):
            try:
                r = node.get_doc(INDEX, doc_id, routing=routing)
                out[doc_id, routing] = (r["_version"], bytes(r["_source"]))
            except ElasticsearchTpuError as e:
                out[doc_id, routing] = type(e).__name__
    return out


@pytest.mark.parametrize("durability", ["request", "async"])
@pytest.mark.parametrize("shards", [1, 5])
@pytest.mark.parametrize("case", list(CASES))
def test_bulk_is_the_single_document_path_at_another_size(
        tmp_path, fixed_ids, case, shards, durability):
    requests = CASES[case]
    single = new_node(tmp_path / "single", shards, durability)
    batched = new_node(tmp_path / "batched", shards, durability)
    before = batched.nodes_stats()["nodes"]
    before = next(iter(before.values()))["indices"]["indexing"]
    try:
        fixed_ids()
        items_single = [one_at_a_time(single, ops) for ops in requests]
        fixed_ids()
        responses = [batched.bulk(copy.deepcopy(ops)) for ops in requests]
        assert [r["items"] for r in responses] == items_single
        for r in responses:
            assert r["errors"] == any(
                "error" in next(iter(it.values())) for it in r["items"])

        ids = {next(iter(it.values())).get("_id", p["_id"])
               for ops, items in zip(requests, items_single)
               for (_a, p), it in zip(ops, items)} - {None}
        assert visible(batched, ids) == visible(single, ids)
        assert translog_bytes(batched) == translog_bytes(single)

        single.refresh(INDEX)
        batched.refresh(INDEX)
        for sid, eng in single.indices[INDEX].shards.items():
            other = batched.indices[INDEX].shards[sid]
            assert_same(other.segments, eng.segments, f"shard {sid}")
            assert_same([other.live[s.seg_id] for s in other.segments],
                        [eng.live[s.seg_id] for s in eng.segments],
                        f"shard {sid} live")
        count = single.count(INDEX)["count"]
        assert batched.count(INDEX)["count"] == count

        # the counters: every index op of a request that reached its
        # shard beside another is a batch doc
        after = next(iter(batched.nodes_stats()["nodes"].values()))[
            "indices"]["indexing"]
        written = sum("error" not in next(iter(it.values()))
                      for items in items_single for it in items
                      if next(iter(it)) in ("index", "create"))
        updates = sum(next(iter(it)) == "update" and "error" not in
                      it["update"] for items in items_single for it in items)
        assert after["index_total"] - before["index_total"] == \
            written + updates
        if case == "cell_documents" and shards == 1:
            assert after["bulk_batch_docs"] == after["index_total"] == 240
            assert after["bulk_batches"] == 2
        if case == "batch_of_one":
            assert after["bulk_batch_docs"] == after["bulk_batches"] == 0
    finally:
        single.close()
        batched.close()

    # nothing was flushed: both reopen from the translog alone
    single = new_node(tmp_path / "single", shards, durability)
    batched = new_node(tmp_path / "batched", shards, durability)
    try:
        assert batched.count(INDEX)["count"] == count
        assert single.count(INDEX)["count"] == count
        assert visible(batched, ids) == visible(single, ids)
    finally:
        single.close()
        batched.close()


def test_rest_bulk_answers_item_for_item(tmp_path):
    """Through the REST handler: ndjson in, one item an action out, in
    request order, each failure its own."""
    node = new_node(tmp_path / "rest", 5, "request")
    server = RestServer(node, "127.0.0.1", 0).start()
    try:
        lines = []
        for i in range(40):
            doc = log_doc(i)
            if i == 17:
                doc["no_such_field"] = True
            if i == 23:
                doc["@timestamp"] = "23 o'clock"
            lines += [json.dumps({"index" if i % 4 else "create":
                                  {"_id": str(i % 30)}}), json.dumps(doc)]
        lines.insert(50, json.dumps({"delete": {"_id": "3"}}))
        req = urllib.request.Request(
            f"http://{server.host}:{server.port}/{INDEX}/_bulk",
            data=("\n".join(lines) + "\n").encode(), method="POST")
        with urllib.request.urlopen(req) as resp:
            r = json.loads(resp.read())
        assert r["errors"] is True and len(r["items"]) == 41
        for n, it in enumerate(r["items"]):
            i = n if n < 25 else n - 1      # the delete sits at item 25
            (action, body), = it.items()
            if n == 25:
                assert action == "delete" and body["status"] == 200
            elif i in (17, 23):
                assert body["status"] == 400, it
                assert ("no_such_field" if i == 17 else "23 o'clock") \
                    in body["error"]["reason"], it
            elif i >= 30 and action == "create" and i % 30 not in (17, 23):
                assert body["status"] == 409, it    # the id is live
            elif i == 33:       # the id the delete took away: created anew
                assert (body["status"], body["_version"]) == (201, 1), it
            else:
                assert body["_id"] == str(i % 30)
                assert body["status"] == (200 if i >= 30 else 201), it
                assert body["_version"] == (2 if i >= 30 else 1), it
    finally:
        server.stop()
        node.close()


def test_one_request_is_one_tokenizer_call_and_one_translog_flush(
        tmp_path, monkeypatch):
    """5,000 items to one shard: one native `analyze_batch` call for the
    one text field, one translog write and flush, each batch timer
    counted once."""
    node = new_node(tmp_path / "count", 1, "async")
    eng = node.indices[INDEX].shards[0]
    assert eng.translog._wal is None or eng.translog._fh is None
    calls = {"analyze_batch": 0, "texts": 0, "flush": 0, "fsync": 0}

    real_analyze = native_tokenizer.NativeStandardAnalyzer.analyze_batch

    def analyze_batch(self, texts):
        calls["analyze_batch"] += 1
        calls["texts"] += len(texts)
        return real_analyze(self, texts)
    monkeypatch.setattr(native_tokenizer.NativeStandardAnalyzer,
                        "analyze_batch", analyze_batch)

    tl = eng.translog
    if tl._fh is not None:      # no native library: the python file
        real = tl._fh

        class Counting:
            def write(self, b):
                return real.write(b)

            def flush(self):
                calls["flush"] += 1
                return real.flush()

            def __getattr__(self, name):
                return getattr(real, name)
        tl._fh = Counting()
    else:
        lib = tl._lib

        class CountingLib:
            def est_wal_write(self, *a):
                calls["flush"] += 1
                return lib.est_wal_write(*a)

            def __getattr__(self, name):
                return getattr(lib, name)
        tl._lib = CountingLib()
    real_sync = translog_mod.Translog.sync
    monkeypatch.setattr(
        translog_mod.Translog, "sync",
        lambda self: (calls.__setitem__("fsync", calls["fsync"] + 1),
                      real_sync(self))[1])

    timers = ("bulk_route", "bulk_parse", "bulk_apply", "bulk_translog")
    before = profiler.phase_stats()
    try:
        r = node.bulk([index(i) for i in range(5000)])
        assert not r["errors"] and len(r["items"]) == 5000
        assert calls == {"analyze_batch": 1, "texts": 5000, "flush": 1,
                         "fsync": 0}
        after = profiler.phase_stats()
        for name in timers:
            assert after[name]["count"] - before.get(
                name, {"count": 0})["count"] == 1, name
        stats = next(iter(node.nodes_stats()["nodes"].values()))
        assert stats["indices"]["indexing"]["bulk_batches"] == 1
        assert stats["indices"]["indexing"]["bulk_batch_docs"] == 5000
        assert node.count(INDEX)["count"] == 0      # not refreshed yet
        node.refresh(INDEX)
        assert node.count(INDEX)["count"] == 5000
    finally:
        node.close()


def test_request_durability_fsyncs_once_before_the_acknowledgement(
        tmp_path, monkeypatch):
    node = new_node(tmp_path / "sync", 1, "request")
    fsyncs = []
    real_sync = translog_mod.Translog.sync

    def sync(self):
        fsyncs.append(self._size_in_gen)
        return real_sync(self)
    monkeypatch.setattr(translog_mod.Translog, "sync", sync)
    try:
        r = node.bulk([index(i) for i in range(300)])
        assert not r["errors"]
        tl = node.indices[INDEX].shards[0].translog
        # one fsync, after every record of the batch was written
        assert fsyncs == [tl._size_in_gen] and tl._synced_size == fsyncs[0]
    finally:
        node.close()


def died(node: Node) -> None:
    """The process is dead: no close, no flush; its lock on the data
    path goes with it."""
    node._node_lock_fh.close()
    node._node_lock_fh = None


def surviving_ids(path, shards: int, durability: str) -> list[str]:
    node = new_node(path, shards, durability)
    try:
        node.refresh(INDEX)
        r = node.search(INDEX, {"query": {"match_all": {}}, "size": 1000,
                                "fields": []})
        assert all(s.failed is None
                   for s in node.indices[INDEX].shards.values())
        return sorted((h["_id"] for h in r["hits"]["hits"]), key=int)
    finally:
        node.close()


@pytest.mark.parametrize("phase", ["append", "fsync"])
def test_crash_before_the_fsync_acknowledges_nothing(tmp_path, phase):
    """`request` durability, power lost (page cache dropped) while the
    batch is on its way to disk: the bulk call never returns, so no item
    was acknowledged, and recovery finds a prefix of the batch or
    nothing of it, on top of everything acknowledged before."""
    path = tmp_path / "crash"
    node = new_node(path, 1, "request")
    assert not node.bulk([index(i) for i in range(50)])["errors"]
    faults.configure(f"crash_point:site=translog:phase={phase}:unsynced=drop")
    try:
        with pytest.raises(PowerLossError):
            node.bulk([index(i) for i in range(50, 100)])
    finally:
        faults.clear()
    died(node)
    survivors = surviving_ids(path, 1, "request")
    assert survivors[:50] == [str(i) for i in range(50)]
    assert survivors == [str(i) for i in range(len(survivors))]
    assert len(survivors) < 100


def test_torn_batch_replays_a_prefix(tmp_path):
    """A death in the middle of the batch's write (no page cache lost):
    the records before the tear are whole and replay, the torn one is
    truncated away."""
    path = tmp_path / "torn"
    node = new_node(path, 1, "async")
    assert not node.bulk([index(i) for i in range(20)])["errors"]
    # rate < 1: the tear lands on whichever record the seeded draw picks
    faults.configure("crash_point:site=translog:phase=append:rate=0.2",
                     seed=3)
    try:
        with pytest.raises(PowerLossError):
            node.bulk([index(i) for i in range(20, 60)])
    finally:
        faults.clear()
    died(node)
    survivors = surviving_ids(path, 1, "async")
    assert survivors == [str(i) for i in range(len(survivors))]
    assert 20 < len(survivors) < 60


@pytest.mark.parametrize("durability", ["request", "async"])
def test_after_the_acknowledgement_every_item_replays(tmp_path, durability):
    """The bulk call has returned: a process death now (and, under
    `request`, a power loss) loses no item of the batch."""
    path = tmp_path / "acked"
    node = new_node(path, 5, durability)
    assert not node.bulk([index(i) for i in range(200)])["errors"]
    if durability == "request":
        for eng in node.indices[INDEX].shards.values():
            eng.translog._drop_unsynced()       # the power goes
    died(node)
    survivors = surviving_ids(path, 5, durability)
    assert survivors == [str(i) for i in range(200)]
