"""The readers of the program's phase timers and launch counters
(`harness/phases.py`) on a hand-made run, on a program that has none,
and in the rehearsal's result line."""

import json

import pytest

import run as R

SPANS = ("rest_parse", "resolve", "bind", "dispatch", "collect", "unpack",
         "fetch", "reduce", "finish", "respond")
WAITS = ("pool_wait", "scheduler_wait")
# seconds each phase adds per search in the hand-made window
PER_SEARCH = {"rest_parse": 0.0003, "resolve": 0.0001, "bind": 0.0005,
              "dispatch": 0.0002, "collect": 0.0004, "unpack": 0.00005,
              "fetch": 0.0006, "reduce": 0.00007, "finish": 0.00003,
              "respond": 0.0007, "pool_wait": 0.0004,
              "scheduler_wait": 0.00002}
SEARCHES = 40
CLIENT_S = 0.004        # first byte sent to last received, each request
WINDOW_S = 2.0


def phases(searches: int, extra: dict | None = None) -> dict:
    out = {name: {"count": searches, "sum": searches * s,
                  "mean": s} for name, s in PER_SEARCH.items()}
    whole = sum(PER_SEARCH.values()) / 0.95
    out["request"] = {"count": searches, "sum": searches * whole,
                      "mean": whole}
    out.update(extra or {})
    return out


def hand_made(with_phases: bool = True) -> R.Run:
    """A window of 40 answered searches and one refused, after a
    warm-up of 10 that both snapshots hold."""
    requests = [{"ok": True, "due": i * 0.05, "sent": i * 0.05,
                 "done": i * 0.05 + CLIENT_S} for i in range(SEARCHES)]
    requests.append({"ok": False, "due": 1.99, "sent": 1.99, "done": 2.0})
    before = {"batches_dispatched": 10}
    after = {"batches_dispatched": 10 + SEARCHES}
    if with_phases:
        before["phases"] = phases(10)
        before["launches"] = {"unfused": 10, "fused_xla": 0,
                              "fused_pallas": 0, "resident": 0,
                              "tiered": 0}
        # a phase the warm-up never entered appears in the second
        # snapshot only
        after["phases"] = phases(10 + SEARCHES, {"tiered_dispatch": {
            "count": 4, "sum": 0.004, "mean": 0.001}})
        after["launches"] = {"unfused": 10 + 30, "fused_xla": 4,
                             "fused_pallas": 0, "resident": 0,
                             "tiered": 4}
    return R.Run(requests=requests, window_s=WINDOW_S,
                 stats_before={"dispatch": before, "fused_scoring": {}},
                 stats_after={"dispatch": after, "fused_scoring": {}})


def ms(*names: str) -> float:
    return 1e3 * sum(PER_SEARCH[n] for n in names)


EXPECTED = {
    "rest_parse_ms": ms("rest_parse"),
    "rest_respond_ms": ms("respond"),
    "pool_wait_ms": ms("pool_wait"),
    "coordinate_ms": ms("resolve", "reduce", "finish"),
    "searches_in_flight":
        SEARCHES * sum(PER_SEARCH.values()) / 0.95 / WINDOW_S,
    "span_coverage_pct":
        100.0 * (SEARCHES * sum(PER_SEARCH.values()) + 0.004)
        / (SEARCHES * CLIENT_S),
    "scheduler_wait_ms": ms("scheduler_wait"),
    "fetch_ms": ms("unpack", "fetch"),
    "bind_ms": ms("bind"),
    "launch_ms": ms("dispatch") + 1e3 * 0.004 / SEARCHES,
    "device_wait_ms": ms("collect"),
    "device_launches_per_search": 38 / SEARCHES,
}


def test_the_twelve_are_the_ones_benchmark_json_added():
    bench = R.read_json(R.ROOT, "BENCHMARK.json")
    # found by name: later PRs append their own entries after these
    assert [m["name"] for m in bench["per_layer"]
            if m["name"] in EXPECTED] == list(EXPECTED)
    assert set(SPANS) | set(WAITS) == set(PER_SEARCH)


@pytest.mark.parametrize("name", list(EXPECTED))
def test_a_reader_takes_the_windows_delta(name):
    got = R.load_reader(name).read(hand_made())
    assert got == pytest.approx(EXPECTED[name], rel=1e-9)


@pytest.mark.parametrize("name", list(EXPECTED))
def test_a_reader_finds_nothing_in_a_program_without_the_timers(name):
    """The parent of the PR that brought them has no `phases` and no
    `launches` in the section: None, and no exception."""
    assert R.load_reader(name).read(hand_made(with_phases=False)) is None


def test_the_rehearsal_prints_the_launch_count_and_none_of_the_times(capsys):
    assert R.main(["--workload", "http_logs-1shard.track-searches",
                   "--seed", "2147483711", "--seconds", "2", "--trace", "1",
                   "--rehearse", "1"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    launches = line["metrics"]["device_launches_per_search"]
    assert launches["unit"] == "launches" and 0 < launches["value"] <= 1.0
    assert not set(EXPECTED) - {"device_launches_per_search"} \
        & set(line["metrics"])
