"""What decides `correct`, at a size a test run can hold (4,096 docs,
CPU): the float32 reference passes, the bfloat16 control fails, an
altered answer is caught, and a run whose timed path alters an answer
where it is produced reads `correct: false`."""

import json

import numpy as np
import pytest

import run as R
from harness import corpus as C

CELLS = ["http_logs-1shard.track-searches", "http_logs-5shard.track-searches"]
CONFIG = R.read_json(R.HERE, "configs", "http_logs-1shard.json")
LIMITS = CONFIG["limits"]
MIX = R.read_json(R.HERE, "traffic", "track-searches.json")
SPECS = {op["name"]: op["spec"] for op in MIX["operations"]}
# seeds on which the control's one idf does not fall on a bfloat16 value
SEEDS = [2147483693, 2147483711, 3000000019]


def within(numbers: dict) -> bool:
    return all(numbers[k] <= LIMITS[k] for k in C.COMPARED)


@pytest.fixture(scope="module", params=[1, 5])
def corpus(request):
    return C.Corpus(4096, SEEDS[0], request.param, CONFIG["corpus"])


def answers(corpus, dtype):
    ref = C.Reference(corpus)
    return C.fold([ref.compare(s, C.answer_from(corpus, s, dtype))
                   for s in SPECS.values()])


def test_routing_is_djb2_of_the_id():
    def djb(s):
        h = 5381
        for ch in s:
            h = (h * 33 + ord(ch)) & 0xFFFFFFFF
        return (h - (1 << 32) if h >= 1 << 31 else h) % 5
    got = C.route_shards(12000, 5)
    assert all(got[i] == djb(str(i)) for i in range(0, 12000, 13))


def test_the_corpus_has_what_the_operations_ask_for():
    """At the configurations' own size no operation comes back empty
    (a 400 on the first of May is 1 line in 23,000)."""
    corpus = C.Corpus(CONFIG["docs"], SEEDS[1], 5, CONFIG["corpus"])
    ref = C.Reference(corpus)
    for name, spec in SPECS.items():
        match, _score, best = ref.of(spec)
        assert match.sum() > 0, name
        assert len(best) == min(spec["size"], match.sum()), name
    assert corpus.request_line(corpus.request_key("GET / HTTP/1.0")) \
        == "GET / HTTP/1.0"
    assert corpus.request_key("GET /nowhere HTTP/1.0") == -1


def test_float32_reference_passes(corpus):
    folded = answers(corpus, np.float32)
    assert within(folded), folded
    assert folded["score_gap"] < 2e-7


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("shards", [1, 5])
def test_bfloat16_control_fails(seed, shards):
    """The control: the reference in the program's place, computed in
    the nearest precision below the float32 the configuration states."""
    folded = answers(C.Corpus(4096, seed, shards, CONFIG["corpus"]),
                     C.bfloat16())
    assert not within(folded), folded
    assert folded["score_gap"] > 3 * LIMITS["score_gap"]
    # precision does not touch what is exact
    assert folded["total_wrong"] == folded["buckets_wrong"] \
        == folded["sort_wrong"] == 0


@pytest.mark.parametrize("fault,op,number", [
    ("total", "term", "total_wrong"),
    ("stranger", "status-200s-in-range", "rank_gap"),
    ("bucket", "hourly_agg", "buckets_wrong"),
    ("drop", "default", "hits_wrong"),
    ("score", "term", "score_gap"),
    ("swap", "desc_sort_size", "order_wrong"),
    ("sort_value", "asc_sort_size", "sort_wrong"),
    ("sort_doc", "desc_sort_size", "sort_wrong")])
def test_an_altered_answer_is_caught(corpus, fault, op, number):
    spec = SPECS[op]
    ref = C.Reference(corpus)
    a = C.answer_from(corpus, spec, np.float32)
    assert within(ref.compare(spec, a))
    if fault == "total":
        a["total"] += 1
    elif fault == "stranger":
        match = ref.of(spec)[0]
        a["ids"][0] = int(np.flatnonzero(~match)[0])
    elif fault == "bucket":
        k = next(iter(a["buckets"]["by_hour"]))
        a["buckets"]["by_hour"][k] += 1
    elif fault == "drop":
        a["ids"].pop()
        a["scores"].pop()
    elif fault == "score":
        a["scores"][0] *= 1.001
    elif fault == "swap":
        for key in ("ids", "scores", "sorts"):
            a[key][0], a[key][-1] = a[key][-1], a[key][0]
    elif fault == "sort_value":
        a["sorts"][3] = [a["sorts"][3][0] + 1000]
    elif fault == "sort_doc":
        # a document that is not among the largest, under a best value
        a["ids"][0] = int(np.argmin(corpus.cols["size"]))
    assert ref.compare(spec, a)[number] > LIMITS[number]


@pytest.fixture
def cell(request, monkeypatch):
    """A cell by name. Both are in BENCHMARK.json (the 5-shard cell
    since PR 28); a name that is not has its entry laid over what the
    harness reads (`test_passages.py` does the same with a configuration
    and a mix of its own)."""
    name = request.param
    plain = R.read_json

    def read(*parts):
        out = plain(*parts)
        if parts[-1] == "BENCHMARK.json" and name not in [
                w["name"] for w in out["workloads"]]:
            config, traffic = name.split(".")
            out["workloads"].append({"name": name, "config": config,
                                     "traffic": traffic, "chips": 1,
                                     "why": "rehearsal"})
        return out

    monkeypatch.setattr(R, "read_json", read)
    return name


def last_line(capsys) -> dict:
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.mark.parametrize("cell", CELLS, indirect=True)
def test_a_rehearsed_run_is_correct_and_names_no_device_metric(cell, capsys):
    assert R.main(["--workload", cell, "--seed", "2147483711",
                   "--seconds", "2", "--trace", "1", "--rehearse", "1"]) == 0
    line = last_line(capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    bench = R.read_json(R.ROOT, "BENCHMARK.json")
    source = {m["name"]: m["source"] for m in bench["per_layer"]}
    assert line["metrics"], "the counters are still read"
    assert all(source[m] == "program_counter" for m in line["metrics"])
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert list(line)[-1] == "compared"


def test_without_a_chip_a_run_prints_no_result(capsys):
    """Not a rehearsal, and JAX finds no TPU here: another exit code
    than 0 and no result line."""
    assert R.main(["--workload", CELLS[0], "--seed", "5", "--seconds", "2",
                   "--trace", "0"]) == 2
    assert not [ln for ln in capsys.readouterr().out.splitlines()
                if ln.startswith("{")]


@pytest.mark.parametrize("cell", CELLS, indirect=True)
def test_a_run_whose_timed_path_alters_an_answer_is_not_correct(
        cell, capsys, monkeypatch):
    """Skips the harness's look for a chip (`--rehearse 1`) and drives
    the rest of a run with the coordinator's reduce, where the answer is
    produced, adding one to every seventh total. (The index is loaded by
    a process of its own, which the fault does not reach.)"""
    import elasticsearch_tpu.node as node_mod
    plain = node_mod.merge_shard_results
    calls = [0]

    def altered(*a, **kw):
        out = plain(*a, **kw)
        calls[0] += 1
        if calls[0] % 7 == 0:
            out["hits"]["total"] += 1
        return out

    monkeypatch.setattr(node_mod, "merge_shard_results", altered)
    assert R.main(["--workload", cell, "--seed", "2147483711",
                   "--seconds", "2", "--trace", "0", "--rehearse", "1"]) == 0
    line = last_line(capsys)
    assert line["correct"] is False
    assert line["compared"]["total_wrong"]["value"] > 0
