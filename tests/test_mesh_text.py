"""A text index whose shards lie one a device, served through `Node` and REST.

The `msmarco-passage-4shard` deployment of `benchmarks/configs/` at its
`rehearsal_docs` (4,096 seeded passages) in an index of 4 shards over 4
of `conftest.py`'s virtual devices (`index.routing.allocation.devices`
4), loaded, flushed and reopened on its commit as the benchmark does it.
Every plan shape of the `dev-queries` mix (each operator of its
`operator_shares` with each number of words of its `terms_shares`, drawn
as the mix draws them, so that rare words are absent from some shards)
is sent alone (`_search`) and four at once (`_msearch`, one coalesced
round), and every answer is compared with the benchmark's own plain
reference (`benchmarks/harness/shapes/passages.py`: float64 numpy BM25,
DJB2 routing, each shard's own statistics) under the configuration's
limits, and with the same index placed on one device. Each round is one
program over the four devices that runs the fused tile walk on each
(`launches.fused_xla`), and its tiles are counted for each shard row
(`fused_scoring.mesh_tiles`) as they are in all (`fused_scoring.tiles`),
with the most a row scored in each round (`fused_scoring.mesh_rounds`).
Then what the mesh reader keeps (`search/bound_plans.py`): one entry a
body, a group of kept bodies assembled without a parse or a bind, the
boosts checked for every group, and what empties or bypasses the kept
entries.
"""

import http.client
import json
import os
import sys

import numpy as np
import pytest

from elasticsearch_tpu.node import Node
from elasticsearch_tpu.parallel import mesh_reader
from elasticsearch_tpu.rest.server import RestServer

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import corpus as C  # noqa: E402  (numpy only)

SEED = 2147483917
WIDTH = 4               # queries drawn for each case, and an _msearch's


def _json(*parts: str) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


CONFIG = _json("configs", "msmarco-passage-4shard.json")
DRAW = _json("traffic", "dev-queries.json")["operations"][0]["draw"]
INDEX = CONFIG["index"]
SHARDS = CONFIG["number_of_shards"]
DOCS = CONFIG["rehearsal_docs"]
CASES = [(op, int(m)) for op in sorted(DRAW["operator_shares"])
         for m in sorted(DRAW["terms_shares"], key=int)]


class Served:
    """The configuration loaded over REST, flushed, and reopened on its
    commit; `devices` None leaves the placement setting out (every shard
    on the first device)."""

    def __init__(self, data_path: str, devices: int | None = SHARDS):
        settings = dict(CONFIG["index_settings"])
        if devices is None:
            settings.pop(mesh_reader.DEVICES_SETTING)
        self.corpus = C.corpus_of(CONFIG, DOCS, SEED)
        self.data_path = data_path
        self.start()
        self.call("PUT", f"/{INDEX}", {"settings": settings,
                                       "mappings": CONFIG["mappings"]})
        for lo in range(0, DOCS, CONFIG["bulk_size"]):
            r = self.call("POST", f"/{INDEX}/_bulk", self.corpus.bulk_body(
                lo, min(lo + CONFIG["bulk_size"], DOCS)))
            assert not r["errors"]
        for verb in ("_refresh", "_flush"):
            self.call("POST", f"/{INDEX}/{verb}")
        self.stop()
        self.start()
        assert self.call("GET", f"/{INDEX}/_count")["count"] == DOCS

    def start(self) -> None:
        self.node = Node({"node.name": "mesh-text-0",
                          "path.data": self.data_path})
        self.server = RestServer(self.node, "127.0.0.1", 0).start()
        self.conn = http.client.HTTPConnection(
            self.server.host, self.server.port, timeout=300)

    def stop(self) -> None:
        self.conn.close()
        self.server.stop()
        self.node.close()

    def call(self, method: str, path: str, body=None):
        data = body if isinstance(body, bytes) or body is None \
            else json.dumps(body).encode()
        self.conn.request(method, path, body=data,
                          headers={"Content-Type": "application/json"})
        r = self.conn.getresponse()
        out = json.loads(r.read())
        assert r.status == 200, out
        return out

    def search(self, bodies: list[dict], together: bool) -> list[dict]:
        """Each body alone, or all in one `_msearch`."""
        if not together:
            return [self.call("POST", f"/{INDEX}/_search", b)
                    for b in bodies]
        head = json.dumps({"index": INDEX})
        lines = "".join(f"{head}\n{json.dumps(b)}\n" for b in bodies)
        return self.call("POST", "/_msearch", lines.encode())["responses"]

    def stats(self) -> dict:
        return next(iter(self.call("GET", "/_nodes/stats")["nodes"].values()))


@pytest.fixture(scope="module")
def placed(tmp_path_factory):
    sv = Served(str(tmp_path_factory.mktemp("mesh-text")))
    yield sv
    sv.stop()


@pytest.fixture(scope="module")
def one_device(tmp_path_factory):
    sv = Served(str(tmp_path_factory.mktemp("one-text")), devices=None)
    yield sv
    sv.stop()


def drawn(corpus, operator: str, terms: int) -> list:
    """`WIDTH` (body, spec) pairs as the mix draws them, all of one
    operator and number of words."""
    op = {"draw": dict(DRAW, operator_shares={operator: 1.0},
                       terms_shares={str(terms): 1.0})}
    return corpus.draw(op, WIDTH, np.random.default_rng([SEED, terms]))


def _absent_from_a_shard(corpus, words: list[str]) -> bool:
    from harness.shapes.passages import word_id
    return any(np.bincount(corpus.shard[corpus.held(word_id(w))[0]],
                           minlength=SHARDS).min() == 0 for w in words)


def _rows_moved(before: dict, after: dict) -> dict:
    """The rows whose tile counters moved, by how much (the counters
    are the process's: rows of other indexes stand still)."""
    zero = {"examined": 0.0, "hard_skipped": 0.0, "thresholded": 0.0}
    moved = {r: {k: v - before.get(r, zero)[k] for k, v in t.items()}
             for r, t in after.items()}
    return {r: t for r, t in moved.items() if any(t.values())}


@pytest.mark.parametrize("together", [False, True], ids=["search", "msearch"])
@pytest.mark.parametrize("operator,terms", CASES)
def test_every_plan_shape_is_the_reference_s_and_the_one_device_s(
        placed, one_device, operator, terms, together):
    pairs = drawn(placed.corpus, operator, terms)
    assert all(len(s["clauses"][0]["match"]) == terms for _b, s in pairs)
    assert any(_absent_from_a_shard(placed.corpus, s["clauses"][0]["match"])
               for _b, s in pairs)
    bodies = [b for b, _s in pairs]
    before = placed.stats()
    got = placed.search(bodies, together)
    after = placed.stats()
    want = one_device.search(bodies, together=True)
    ref = C.Reference(placed.corpus)
    for r, w, (_b, spec) in zip(got, want, pairs):
        assert r["_shards"] == {"total": SHARDS, "successful": SHARDS,
                                "failed": 0} and not r["timed_out"]
        digest = C.digest(r)
        numbers = ref.compare(spec, digest)
        assert C.judge(numbers, CONFIG["limits"]), (numbers, digest)
        numbers = ref.compare(spec, C.digest(w))
        assert C.judge(numbers, CONFIG["limits"]), numbers
        assert [h["_id"] for h in r["hits"]["hits"]] \
            == [h["_id"] for h in w["hits"]["hits"]]
        assert r["hits"]["total"] == w["hits"]["total"]

    d0, d1 = before["dispatch"], after["dispatch"]
    launches = sum(d1["launches"].values()) - sum(d0["launches"].values())
    assert d1["mesh"]["searches"] - d0["mesh"]["searches"] == WIDTH
    assert d1["mesh"]["fallbacks"] == d0["mesh"]["fallbacks"]
    # every launch was one program over the four devices that ran the
    # fused walk; four bodies of one plan shape sent together are one
    assert d1["launches"]["fused_xla"] - d0["launches"]["fused_xla"] \
        == launches == (1 if together else WIDTH)
    assert d1["queries"] - d0["queries"] == SHARDS * WIDTH

    f0, f1 = before["fused_scoring"], after["fused_scoring"]
    assert f1["admission"]["rejected"] == f0["admission"]["rejected"]
    assert f1["admission"]["admitted"] - f0["admission"]["admitted"] \
        == launches
    rows = _rows_moved(f0["mesh_tiles"], f1["mesh_tiles"])
    assert sorted(rows) == [str(r) for r in range(SHARDS)]
    for key in ("examined", "hard_skipped", "thresholded"):
        assert sum(t[key] for t in rows.values()) \
            == f1["tiles"][key] - f0["tiles"][key]
    # each chip walked its own shard's tiles
    assert all(t["examined"] > 0 for t in rows.values())
    r0, r1 = f0["mesh_rounds"], f1["mesh_rounds"]
    assert r1["launches"] - r0["launches"] == launches
    slowest = r1["slowest_scored"] - r0["slowest_scored"]
    scored = [t["examined"] - t["hard_skipped"] for t in rows.values()]
    if launches == 1:
        assert slowest == max(scored)
    assert max(scored) <= slowest <= sum(scored)


def test_the_one_device_index_counts_its_rows_too(one_device):
    """Four shards on one device are one program over four rows
    (`jax.vmap`): each row's tiles are counted under its shard."""
    pairs = drawn(one_device.corpus, "or", 5)
    before = one_device.stats()
    one_device.search([b for b, _s in pairs], together=True)
    after = one_device.stats()
    rows = _rows_moved(before["fused_scoring"]["mesh_tiles"],
                       after["fused_scoring"]["mesh_tiles"])
    moved = after["fused_scoring"]["tiles"]["examined"] \
        - before["fused_scoring"]["tiles"]["examined"]
    assert sorted(rows) == [str(r) for r in range(SHARDS)]
    assert sum(t["examined"] for t in rows.values()) == moved > 0
    assert after["fused_scoring"]["mesh_rounds"]["launches"] \
        - before["fused_scoring"]["mesh_rounds"]["launches"] == 1


# -- what the mesh reader keeps: its bodies ---------------------------------

def _searcher(sv):
    """The mesh searcher of the index's current views."""
    svc = sv.node.indices[INDEX]
    return svc.placement.reader(
        [eng.acquire_searcher() for _sid, eng in sorted(svc.shards.items())]
    ).searchers[0]


def _fresh(searcher):
    """A searcher over the same pack that has kept nothing (it shares
    the compiled programs)."""
    fresh = searcher.over(searcher.packed)
    assert fresh is not None and len(fresh._plans) == 0
    return fresh


def _answer(r: dict) -> dict:
    return {k: v for k, v in r.items() if k != "took"}


def _boosted(body: dict, boost: float) -> dict:
    """The body's `match` with a boost: another body of the same plan
    shape (a boost is a parameter of the program, not its structure)."""
    (field, spec), = body["query"]["match"].items()
    return dict(body, query={"match": {field: dict(spec, boost=boost)}})


class Calls:
    """Outermost calls of the parse and of the bind on any thread (a
    bool query's parse and bind recurse into their clauses)."""

    def __init__(self, monkeypatch):
        import threading
        from elasticsearch_tpu.search.executor import QueryBinder
        from elasticsearch_tpu.search.query_dsl import QueryParser
        self.parse = self.bind = 0
        local = threading.local()

        def counting(name, fn):
            def call(*a, **kw):
                if getattr(local, name, False):
                    return fn(*a, **kw)
                setattr(local, name, True)
                setattr(self, name, getattr(self, name) + 1)
                try:
                    return fn(*a, **kw)
                finally:
                    setattr(local, name, False)
            return call

        monkeypatch.setattr(QueryParser, "parse",
                            counting("parse", QueryParser.parse))
        monkeypatch.setattr(QueryBinder, "bind",
                            counting("bind", QueryBinder.bind))


def _moved(before: dict) -> dict:
    from elasticsearch_tpu.search import bound_plans
    after = bound_plans.counts()
    return {k: after[k] - before[k]
            for k in ("hits", "misses", "bypassed", "assembled")}


def test_a_group_of_bodies_served_alone_is_assembled_without_a_bind(
        placed, monkeypatch):
    from elasticsearch_tpu.search import bound_plans
    searcher = _fresh(_searcher(placed))
    bodies = [b for b, _s in drawn(placed.corpus, "or", 5)]
    for b in bodies:
        searcher.search(b)
    calls = Calls(monkeypatch)
    before = bound_plans.counts()
    got = searcher.msearch(bodies)
    assert (calls.parse, calls.bind) == (0, 0)
    assert _moved(before) == {"hits": 0, "misses": 1, "bypassed": 0,
                              "assembled": 1}
    # the group is kept on its first body, and counted as an entry
    assert bound_plans.counts()["entries"] - before["entries"] == 1
    assert len(searcher._plans) == len(bodies)
    # the same group again launches from what its first body led; in
    # another order it is assembled again
    before = bound_plans.counts()
    assert [_answer(r) for r in searcher.msearch(bodies)] \
        == [_answer(r) for r in got]
    assert _moved(before) == {"hits": 1, "misses": 0, "bypassed": 0,
                              "assembled": 0}
    before = bound_plans.counts()
    searcher.msearch(bodies[::-1])
    assert _moved(before) == {"hits": 0, "misses": 1, "bypassed": 0,
                              "assembled": 1}
    assert (calls.parse, calls.bind) == (0, 0)
    monkeypatch.undo()
    want = _fresh(searcher).msearch(bodies)
    assert [_answer(r) for r in got] == [_answer(r) for r in want]
    assert all(r["hits"]["hits"] for r in got)


@pytest.mark.parametrize("n", [2, 4])
def test_bodies_served_alone_are_kept_once_each(placed, n):
    searcher = _fresh(_searcher(placed))
    body = drawn(placed.corpus, "and", 3)[0][0]
    bodies = [_boosted(body, 1.0 + i) for i in range(n)]
    for b in bodies:
        searcher.search(b)
    # one entry a body, its group of one with it
    assert len(searcher._plans) == n
    from elasticsearch_tpu.search import bound_plans
    before = bound_plans.counts()
    for b in bodies:
        searcher.search(b)
    assert _moved(before) == {"hits": n, "misses": 0, "bypassed": 0,
                              "assembled": 0}


@pytest.mark.parametrize("kept", [1, 3])
def test_a_group_of_kept_and_new_bodies_binds_only_the_new(
        placed, monkeypatch, kept):
    from elasticsearch_tpu.search import bound_plans
    searcher = _fresh(_searcher(placed))
    body = drawn(placed.corpus, "or", 4)[0][0]
    bodies = [_boosted(body, 1.0 + i) for i in range(4)]
    for b in bodies[:kept]:
        searcher.search(b)
    calls = Calls(monkeypatch)
    before = bound_plans.counts()
    got = searcher.msearch(bodies)
    new = len(bodies) - kept
    views = len(searcher.packed.bind_views)
    assert (calls.parse, calls.bind) == (new, new * views)
    assert _moved(before) == {"hits": 0, "misses": 1, "bypassed": 0,
                              "assembled": 0}
    assert len(searcher._plans) == len(bodies)
    monkeypatch.undo()
    want = _fresh(searcher).msearch(bodies)
    assert [_answer(r) for r in got] == [_answer(r) for r in want]


def test_a_body_of_no_positive_boost_keeps_its_group_off_the_fused_walk(
        placed):
    searcher = _fresh(_searcher(placed))
    body = drawn(placed.corpus, "or", 3)[0][0]
    bodies = [_boosted(body, 2.0), _boosted(body, 0.0)]
    searcher.search(bodies[0])          # alone: admitted, and kept
    before = placed.stats()
    got = searcher.msearch(bodies)
    after = placed.stats()
    d0, d1 = before["dispatch"]["launches"], after["dispatch"]["launches"]
    assert d1.get("fused_xla", 0) == d0.get("fused_xla", 0)
    assert d1["unfused"] - d0.get("unfused", 0) == 1
    a0 = before["fused_scoring"]["admission"]["rejected"]
    a1 = after["fused_scoring"]["admission"]["rejected"]
    assert a1["nonpositive_boost"] - a0.get("nonpositive_boost", 0) == 1
    want = _fresh(searcher).msearch(bodies)
    assert [_answer(r) for r in got] == [_answer(r) for r in want]
    # the kept body alone is still the fused walk's
    before = placed.stats()["dispatch"]["launches"]
    searcher.search(bodies[0])
    assert placed.stats()["dispatch"]["launches"]["fused_xla"] \
        - before["fused_xla"] == 1


def test_a_stepped_plan_is_not_kept(placed):
    import time
    from elasticsearch_tpu.search import bound_plans
    searcher = _fresh(_searcher(placed))
    body = drawn(placed.corpus, "or", 6)[0][0]
    before = bound_plans.counts()
    stepped = searcher.msearch([body], deadline=time.monotonic() + 300)
    assert _moved(before) == {"hits": 0, "misses": 0, "bypassed": 1,
                              "assembled": 0}
    bb = searcher._plans.get(bound_plans.body_key(body))
    assert bb is not None and bb.alone is None
    before = bound_plans.counts()
    plain = searcher.msearch([body])
    # the body was kept, its group of one was not: assembled now
    assert _moved(before) == {"hits": 0, "misses": 1, "bypassed": 0,
                              "assembled": 1}
    assert _answer(stepped[0]) == _answer(plain[0])


def test_a_switch_of_the_executor_empties_what_the_mesh_reader_kept(
        placed, monkeypatch):
    """A body kept with the fused engines on is not launched from what
    was kept once they are off (`plan_switches`, in the stamp)."""
    from elasticsearch_tpu.search import bound_plans
    searcher = _fresh(_searcher(placed))
    body = drawn(placed.corpus, "or", 4)[0][0]
    searcher.search(body)
    monkeypatch.setenv("ES_TPU_FUSED", "0")
    before = bound_plans.counts()
    launched = placed.stats()["dispatch"]["launches"]
    got = searcher.search(body)
    after = placed.stats()["dispatch"]["launches"]
    assert _moved(before) == {"hits": 0, "misses": 1, "bypassed": 0,
                              "assembled": 0}
    assert after.get("fused_xla", 0) == launched.get("fused_xla", 0)
    assert after["unfused"] - launched.get("unfused", 0) == 1
    assert _answer(got) == _answer(_fresh(searcher).search(body))


def test_callers_on_several_threads_share_what_is_kept(placed, monkeypatch):
    """More threads than cores on one searcher, groups of kept and new
    bodies and room for six entries: every answer is a lone caller's,
    and the searcher never holds more than its capacity."""
    import os
    import sys
    import threading
    from elasticsearch_tpu.search import bound_plans
    searcher = _fresh(_searcher(placed))
    body = drawn(placed.corpus, "or", 3)[0][0]
    bodies = [_boosted(body, 1.0 + i) for i in range(5)]
    alone = [_answer(searcher.search(b)) for b in bodies]
    monkeypatch.setattr(bound_plans, "CAPACITY", 6)
    wrong, errors, over = [], [], []

    def work(seed: int) -> None:
        try:
            for i in range(6):
                idx = [(seed + i + j) % len(bodies) for j in range(1 + i % 3)]
                got = searcher.msearch([bodies[j] for j in idx])
                if [_answer(r) for r in got] != [alone[j] for j in idx]:
                    wrong.append(idx)
                if len(searcher._plans) > 6:
                    over.append(idx)
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(2 * (os.cpu_count() or 4))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert not errors and not wrong and not over


def test_a_mapping_change_empties_what_was_kept(placed):
    """Last in the module: the mapping it gains stays."""
    from elasticsearch_tpu.search import bound_plans
    searcher = _searcher(placed)
    body = drawn(placed.corpus, "and", 5)[0][0]
    first = placed.search([body], together=False)[0]
    before = bound_plans.counts()
    placed.search([body], together=False)
    assert _moved(before)["hits"] == 1
    assert len(searcher._plans) > 2
    placed.call("PUT", f"/{INDEX}/_mapping",
                {"properties": {"kept_plans_probe": {"type": "keyword"}}})
    before = bound_plans.counts()
    again = placed.search([body], together=False)[0]
    assert _moved(before) == {"hits": 0, "misses": 1, "bypassed": 0,
                              "assembled": 0}
    assert _searcher(placed) is searcher
    assert len(searcher._plans) == 1
    assert _answer(again) == _answer(first)
