"""The fixtures' operations served through REST at 4,096 passages, on one
shard and on five (CPU), every answer compared with the `passages`
reference by `Reference.compare`: one case an operation and shard count.
The load goes the way `harness/served.py` always does it (`PUT`, `_bulk`,
`_refresh`, `_flush`, close, reopen on the commit), in this process.

ISSUE 32 asked for these cases in tier-1 (`tests/test_passages_reference.py`);
a benchmark PR adds no file outside `benchmarks/`, so they stand here
until a later PR moves them (PERF.md, section 7)."""

import numpy as np
import pytest

import run as R
from harness import corpus as C
from harness import loadgen as G
from harness import served as S
from harness.shapes import passages as P

from test_passages import CONFIGS, LIMITS, MIX

SEED = 2147483693
CASES = ["match-or", "match-and", "phrase", "term", "default"]


@pytest.fixture(scope="module", params=[1, 5])
def served(request):
    config = CONFIGS[request.param]
    corpus = C.corpus_of(config, config["rehearsal_docs"], SEED)
    with S.Served(config, SEED, corpus.n, keep_data=False,
                  log=lambda *parts: None) as sv:
        sv.store(corpus)
        sv.open(corpus)
        yield sv, G.plan_queries(MIX, corpus, SEED, 2.0)


def admitted(sv) -> int:
    return sv.node_stats()["fused_scoring"]["admission"]["admitted"]


@pytest.mark.parametrize("case", CASES)
def test_every_answer_of_an_operation_is_the_references(served, case):
    sv, mix = served
    op = next(o for o in mix["operations"]
              if o["name"] == case.split("-")[0])
    pairs = list(zip(op.get("bodies") or [op["body"]],
                     op.get("specs") or [op["spec"]]))
    if "-" in case:
        pairs = [(b, s) for b, s in pairs
                 if s["clauses"][0]["operator"] == case.split("-")[1]]
    assert pairs
    ref = C.Reference(sv.corpus)
    before = admitted(sv)
    readings = []
    for body, spec in pairs:
        st, resp = sv.http.call("POST", f"/{sv.index}/_search", body)
        assert st == 200 and resp["_shards"]["failed"] == 0, resp
        assert resp["_shards"]["total"] == sv.config["number_of_shards"]
        readings.append(ref.compare(spec, C.digest(resp)))
    folded = C.fold(readings)
    assert C.judge(folded, LIMITS), folded
    if case.startswith("match"):
        assert admitted(sv) > before    # the fused engines took the plan


def test_the_standard_analyzer_leaves_the_words_as_they_are(served):
    sv, _mix = served
    words = [P.word(i) for i in (0, 25, 26, 40, 676, 17575, 262143)]
    st, r = sv.http.call("POST", f"/{sv.index}/_analyze",
                         {"field": "text", "text": " ".join(words)})
    assert st == 200, r
    assert [t["token"] for t in r["tokens"]] == words
    assert [t["position"] for t in r["tokens"]] == list(range(len(words)))


def test_the_index_has_the_configurations_name(served):
    sv, _mix = served
    assert sv.index == "passages"
    st, r = sv.http.call("GET", "/passages/_count")
    assert st == 200 and r["count"] == sv.corpus.n
    assert np.all(sv.corpus.dl >= 8)
    assert R.read_json(R.HERE, "configs", "http_logs-1shard.json") \
        .get("index") is None       # absent: `logs`
