"""The record shape `passages`, its drawn queries and the harness that
takes them as files only, at a size a test run can hold (4,096 passages,
CPU). The fixtures under `fixtures/` are a configuration of the shape
(one shard and five) and a mix with a drawn `match` (or and and), a
drawn `phrase`, a fixed `term` and a `match_all`; no entry of
`BENCHMARK.json` names them, the tests lay theirs over it."""

import ast
import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import run as R
from harness import corpus as C
from harness import loadgen as G
from harness.readers import spec_of
from harness.shapes import passages as P
from harness.workbytes import search_bytes

FIXTURES = os.path.join(R.HERE, "tests", "fixtures")
MIX = R.read_json(FIXTURES, "traffic", "passages-searches.json")
CONFIGS = {n: R.read_json(FIXTURES, "configs", f"passages-{n}shard.json")
           for n in (1, 5)}
LIMITS = CONFIGS[1]["limits"]
SEEDS = [2147483693, 2147483711, 3000000019]
CELLS = {n: f"passages-{n}shard.passages-searches" for n in (1, 5)}
ROOFLINE_CELLS = ["http_logs-1shard.track-searches",
                  "http_logs-5shard.track-searches"]


def entries(bench: dict, prefix: str) -> dict:
    """`BENCHMARK.json` with the fixtures' two configurations and two
    cells added: entries only, as a later PR adds them."""
    for n in (1, 5):
        bench["configs"].append({
            "name": f"passages-{n}shard", "source": CONFIGS[n]["source"][:200],
            "file": f"{prefix}/configs/passages-{n}shard.json",
            "reduced": [], "why": "fixture"})
        bench["workloads"].append({
            "name": CELLS[n], "config": f"passages-{n}shard",
            "traffic": "passages-searches", "chips": 1, "why": "fixture"})
    return bench


@pytest.fixture
def laid_over(monkeypatch):
    """The fixtures laid over what the harness reads, in this process:
    their entries in `BENCHMARK.json`, their mix where `traffic/` has
    none of that name."""
    plain = R.read_json

    def read(*parts):
        path = os.path.join(*parts)
        if not os.path.exists(path):
            path = path.replace(R.HERE, FIXTURES, 1)
        out = plain(path)
        if parts[-1] == "BENCHMARK.json":
            entries(out, "benchmarks/tests/fixtures")
        return out

    monkeypatch.setattr(R, "read_json", read)


def corpus_at(shards: int, seed: int = SEEDS[1], docs: int = 4096):
    return C.corpus_of(CONFIGS[shards], docs, seed)


def planned(corpus, seed: int = SEEDS[1], seconds: float = 2.0) -> dict:
    return G.plan_queries(MIX, corpus, seed, seconds)


def specs_of(mix: dict) -> list:
    return [s for op in mix["operations"]
            for s in op.get("specs") or [op["spec"]]]


def within(numbers: dict) -> bool:
    return C.judge(numbers, LIMITS)


def last_line(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


# -- the shape and its reference ---------------------------------------------

def test_a_configuration_names_its_shape_and_its_index():
    assert C.shape(CONFIGS[1]) is P
    assert C.shape({"name": "x"}) is C          # absent: http_logs
    assert C.shape(R.read_json(R.HERE, "configs", "http_logs-5shard.json")) \
        is C
    assert C.GENERATOR_VERSION == 2     # a stored http_logs index stays good


def test_the_reference_imports_numpy_and_the_yardstick_only():
    allowed = {"__future__", "calendar", "importlib", "json", "sys", "time",
               "numpy", "ml_dtypes"}
    for path in [os.path.join(R.HERE, "harness", "corpus.py"),
                 os.path.join(R.HERE, "harness", "shapes", "passages.py")]:
        with open(path) as f:
            tree = ast.parse(f.read())
        found = []
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                found += [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                # a relative import stays inside benchmarks/harness
                found += [] if node.level else [node.module]
                assert node.level == 0 or node.module == "corpus", path
        assert "numpy" in found
        assert {name.split(".")[0] for name in found} <= allowed, (path,
                                                                  found)


def test_words_are_the_same_for_every_seed_and_have_one_number_each():
    assert [P.word(i) for i in (0, 25, 26, 40, 676)] == [
        "zaa", "zaz", "zba", "zbo", "zbaa"]
    assert all(P.word_id(P.word(i)) == i for i in range(0, 262144, 97))
    assert P.word_id("the") == P.word_id("zaba") == P.word_id("z1a") == -1


def test_passages_have_the_stated_lengths_and_popular_words():
    c = corpus_at(1, docs=16384)
    p = CONFIGS[1]["corpus"]
    assert c.dl.min() >= p["length_min"] and c.dl.max() <= p["length_max"]
    assert 50 < c.dl.mean() < 62            # MS MARCO: about 56
    share = np.bincount(c.tokens, minlength=8)[:8] / len(c.tokens)
    assert 0.06 < share[0] < 0.09 and np.all(np.diff(share) < 0)
    body = c.bulk_body(3, 5).decode().splitlines()
    assert body[0] == '{"index":{"_id":"3"}}' and len(body) == 4
    assert json.loads(body[1])["text"].split() == [
        P.word(int(t)) for t in c.passage(3)]
    other = corpus_at(1, seed=SEEDS[0], docs=16384)
    assert not np.array_equal(other.tokens[:100], c.tokens[:100])


def naive(c, spec: dict):
    """The clause by a loop over the passages: what the postings and the
    vectors have to equal."""
    cl = spec["clauses"][0]
    words = [P.word_id(w) for w in cl.get("match") or cl["phrase"]]
    score = np.zeros(c.n)
    match = np.zeros(c.n, bool)
    df = np.zeros((len(words), c.n_shards))
    tf = np.zeros((len(words), c.n))
    for d in range(c.n):
        p = c.passage(d).tolist()
        if "phrase" in cl:
            tf[0, d] = sum(p[i:i + len(words)] == words
                           for i in range(len(p)))
            for j, t in enumerate(words):
                df[j, c.shard[d]] += t in p
        else:
            for j, t in enumerate(words):
                tf[j, d] = p.count(t)
                df[j, c.shard[d]] += t in p
    idf = np.log(1 + (c.n_s - df + 0.5) / (df + 0.5))
    k = c.k1 * (1 - c.b + c.b * c.dl / c.avgdl[c.shard])
    if "phrase" in cl:
        match = tf[0] > 0
        score = idf.sum(0)[c.shard] * tf[0] * (c.k1 + 1) / (tf[0] + k)
    else:
        held = (tf > 0).sum(0)
        match = held == len(words) if cl["operator"] == "and" else held > 0
        score = (idf[:, c.shard] * tf * (c.k1 + 1) / (tf + k)).sum(0)
    return match, np.where(match, score, 0.0)


@pytest.mark.parametrize("shards", [1, 5])
def test_the_postings_give_what_a_loop_over_the_passages_gives(shards):
    c = corpus_at(shards, docs=512)
    mix = planned(c, seconds=1.0)
    for spec in specs_of(mix):
        if not spec["clauses"]:
            continue
        match, score = c.evaluate(spec)
        want_match, want_score = naive(c, spec)
        assert np.array_equal(match, want_match), spec
        assert np.allclose(score, want_score, rtol=1e-12, atol=0), spec


def test_every_seed_draws_the_same_kinds_of_queries_and_each_has_an_answer():
    def kinds(mix):
        return sorted((op["name"], c.get("operator", "phrase"),
                       len(c.get("match") or c["phrase"]))
                      for op in mix["operations"] if "draw" in op
                      for s in op["specs"] for c in s["clauses"])

    def frequent(mix):
        return sum(P.word_id(w) < 64 for op in mix["operations"][:1]
                   for s in op["specs"] for w in s["clauses"][0]["match"])
    a, b = corpus_at(1, SEEDS[0]), corpus_at(1, SEEDS[1])
    mix_a, mix_b = planned(a, SEEDS[0], 10.0), planned(b, SEEDS[1], 10.0)
    assert kinds(mix_a) == kinds(mix_b)
    assert frequent(mix_a) == frequent(mix_b) > 0
    assert mix_a["operations"][0]["bodies"] != mix_b["operations"][0]["bodies"]
    assert planned(a, SEEDS[0], 10.0) == mix_a     # the same for a seed
    ref = C.Reference(a)
    for op in mix_a["operations"][:2]:
        for body, spec in zip(op["bodies"], op["specs"]):
            words = (spec["clauses"][0].get("match")
                     or spec["clauses"][0]["phrase"])
            assert len(set(words)) == len(words)
            assert ref.of(spec)[0].sum() > 0, spec
            query = body["query"]
            said = query["match_phrase"]["text"] if op["name"] == "phrase" \
                else query["match"]["text"]["query"]
            assert said == " ".join(words)


def test_head_queries_repeat_and_every_query_is_sent():
    order = G.repeats(1020, 400, 1.0, np.random.default_rng(1))
    counts = np.bincount(order)
    assert len(order) == 1020 and len(counts) == 400 and counts.min() == 1
    assert counts[0] == counts.max() > 50 and counts[0] > 2 * counts[3]
    other = G.repeats(1020, 400, 1.0, np.random.default_rng(2))
    assert other != order and sorted(other) == sorted(order)
    assert sorted(G.repeats(5, 5, 1.0, np.random.default_rng(1))) \
        == [0, 1, 2, 3, 4]


def test_drawing_queries_moves_no_request_of_a_fixed_mix():
    """The streams there were (the order of the operations and the due
    times) are not touched: for three seeds, what the parent of PR 32
    dealt and the documents it made (`recorded_requests.json`)."""
    recorded = R.read_json(R.HERE, "tests", "recorded_requests.json")
    mix = R.read_json(R.HERE, "traffic", "track-searches.json")
    for seed, want in recorded.items():
        for n in (1, 5):
            config = R.read_json(R.HERE, "configs",
                                 f"http_logs-{n}shard.json")
            body = C.corpus_of(config, 4096, int(seed)).bulk_body(0, 4096)
            assert hashlib.sha256(body).hexdigest() == want[f"bulk_{n}shard"]
        for scale in (1.0, 0.25):
            scaled = dict(mix, rate_scale=scale)
            assert G.plan_queries(scaled, None, int(seed), 51.0) == scaled
            rng = np.random.default_rng([int(seed), 11])
            dealt = G.deal_operations(scaled, 51.0, rng)
            due = G.due_times(len(dealt), 51.0, rng)
            assert len(dealt) == want[f"requests_{scale}"]
            assert hashlib.sha256(np.asarray(dealt, np.int64).tobytes()) \
                .hexdigest() == want[f"dealt_{scale}"]
            assert hashlib.sha256(np.asarray(due, np.float64).tobytes()) \
                .hexdigest() == want[f"due_{scale}"]


# -- correct, its control and its faults -------------------------------------

def answers(corpus, mix: dict, dtype) -> dict:
    ref = C.Reference(corpus)
    return C.fold([ref.compare(s, C.answer_from(corpus, s, dtype))
                   for s in specs_of(mix)])


@pytest.mark.parametrize("shards", [1, 5])
def test_float32_reference_passes(shards):
    c = corpus_at(shards)
    folded = answers(c, planned(c), np.float32)
    assert within(folded), folded


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("shards", [1, 5])
def test_bfloat16_control_fails(seed, shards):
    c = corpus_at(shards, seed)
    folded = answers(c, planned(c, seed), C.bfloat16())
    assert not within(folded), folded
    assert folded["score_gap"] > 100 * LIMITS["score_gap"]
    assert folded["total_wrong"] == 0     # precision does not touch a match


@pytest.mark.parametrize("fault,op,number", [
    ("total", "match", "total_wrong"),
    ("stranger", "phrase", "rank_gap"),
    ("drop", "term", "hits_wrong"),
    ("score", "match", "score_gap"),
    ("swap", "match", "order_wrong")])
def test_an_altered_answer_is_caught(fault, op, number):
    c = corpus_at(5)
    mix = planned(c)
    spec = next(o for o in mix["operations"] if o["name"] == op)
    spec = spec.get("specs", [spec.get("spec")])[0]
    ref = C.Reference(c)
    a = C.answer_from(c, spec, np.float32)
    assert within(ref.compare(spec, a))
    if fault == "total":
        a["total"] += 1
    elif fault == "stranger":
        a["ids"][0] = int(np.flatnonzero(~ref.of(spec)[0])[0])
    elif fault == "drop":
        a["ids"].pop()
        a["scores"].pop()
    elif fault == "score":
        a["scores"][0] *= 1.001
    elif fault == "swap":
        for key in ("ids", "scores"):
            a[key][0], a[key][-1] = a[key][-1], a[key][0]
    assert ref.compare(spec, a)[number] > LIMITS[number]


def test_each_answer_is_compared_with_its_own_query():
    """Two requests of one drawn operation, the answers swapped: each is
    right for the other's query and wrong for its own."""
    c = corpus_at(1)
    mix = planned(c)
    specs = mix["operations"][0]["specs"]
    reqs = [{"op": "match", "ok": True, "query": q,
             "digest": C.answer_from(c, specs[q], np.float32)}
            for q in (0, 1)]
    assert spec_of(mix)(reqs[1]) is specs[1]
    assert within(R.compare_all(c, mix, reqs))
    reqs[0]["digest"], reqs[1]["digest"] = reqs[1]["digest"], \
        reqs[0]["digest"]
    assert not within(R.compare_all(c, mix, reqs))


# -- the harness takes the shape as files only -------------------------------

def test_the_roofline_of_column_scans_is_read_where_its_list_says(laid_over):
    bench = R.read_json(R.ROOT, "BENCHMARK.json")
    for cell in ROOFLINE_CELLS:
        assert "column_scan_roofline" in [
            m["name"] for m in R.cell_metrics(bench, cell)[1]]
    layer = [m["name"] for m in R.cell_metrics(bench, CELLS[1])[1]]
    assert "column_scan_roofline" not in layer
    assert "fused_admission_pct" in layer and "device_ms_per_search" in layer
    listed = [m for m in bench["per_layer"] if "workloads" in m]
    assert [(m["name"], m["workloads"]) for m in listed] == [
        ("column_scan_roofline", ROOFLINE_CELLS)]


def test_the_bytes_function_says_that_it_counts_no_postings():
    spec = MIX["operations"][2]["spec"]
    with pytest.raises(ValueError, match="postings, which it does not count"):
        search_bytes(CONFIGS[1]["mappings"], spec, 4096)
    assert search_bytes(CONFIGS[1]["mappings"],
                        MIX["operations"][3]["spec"], 4096) == 0


def test_a_later_pr_adds_a_text_cell_as_files_and_entries_only(tmp_path):
    """A copy of `benchmarks/` with the fixtures put where a later PR
    puts a configuration and a mix, and their entries in a copy of
    `BENCHMARK.json`: no file that was there is edited, and the command
    runs the cell end to end."""
    tree = tmp_path / "benchmarks"
    shutil.copytree(R.HERE, tree, ignore=shutil.ignore_patterns(
        ".data", "__pycache__", ".pytest_cache"))
    for kind in ("configs", "traffic"):
        for name in os.listdir(os.path.join(FIXTURES, kind)):
            shutil.copy(os.path.join(FIXTURES, kind, name), tree / kind)
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(entries(R.read_json(R.ROOT, "BENCHMARK.json"),
                          "benchmarks"), f)
    done = subprocess.run(
        [sys.executable, str(tree / "run.py"), "--workload", CELLS[1],
         "--seed", "3000000019", "--seconds", "2", "--trace", "1",
         "--rehearse", "1"], capture_output=True, text=True, timeout=900,
        env=dict(os.environ, PYTHONPATH=R.ROOT, JAX_PLATFORMS="cpu",
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache")))
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 64
    assert line["metrics"]["fused_admission_pct"]["value"] > 50
    assert "compilations inside the window: 0" in done.stdout


def test_a_rehearsed_five_shard_run_is_correct(laid_over, capsys):
    assert R.main(["--workload", CELLS[5], "--seed", "2147483711",
                   "--seconds", "2", "--trace", "1", "--rehearse", "1"]) == 0
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"]["shard_jobs_per_search"]["value"] == 5.0
    assert line["metrics"]["fused_admission_pct"]["value"] > 50
    assert 0 < line["metrics"]["bound_plan_hit_pct"]["value"] < 100
    assert list(line)[-1] == "compared"
    assert "compilations inside the window: 0" in out


def test_a_run_whose_timed_path_alters_an_answer_is_not_correct(
        laid_over, capsys, monkeypatch):
    """As `test_correct.py` has it for `http_logs`: the coordinator's
    reduce, where the answer is produced, drops the last hit of every
    seventh answer."""
    import elasticsearch_tpu.node as node_mod
    plain = node_mod.merge_shard_results
    calls = [0]

    def altered(*a, **kw):
        out = plain(*a, **kw)
        calls[0] += 1
        if calls[0] % 7 == 0 and out["hits"]["hits"]:
            out["hits"]["hits"].pop()
        return out

    monkeypatch.setattr(node_mod, "merge_shard_results", altered)
    assert R.main(["--workload", CELLS[1], "--seed", "2147483711",
                   "--seconds", "2", "--trace", "0", "--rehearse", "1"]) == 0
    line = last_line(capsys)
    assert line["correct"] is False
    assert line["compared"]["hits_wrong"]["value"] > 0
