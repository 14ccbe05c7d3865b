"""The fused walk's bytes function against a hand count, and its two
readers on a hand-made run and on a program without the counters."""

import pytest

import run as R
from harness import walkbytes as W
from harness import walk_readers as WR

CELL = "msmarco-passage-1shard.dev-queries"
CONFIG = R.read_json(R.HERE, "configs", "msmarco-passage-1shard.json")
PEAKS = R.read_json(R.HERE, "peaks.json")["TPU v5 lite"]


def test_the_grid_and_the_width_come_from_the_shapes():
    assert W.tile_grid(262_144) == (1024, 256)
    assert W.tile_grid(52_429) == (1024, 64)       # a fifth of the cell
    assert W.tile_grid(1_105_228) == (1024, 2048)  # the published shard
    assert W.tile_grid(300) == (512, 1)
    assert W.forward_slots(256) == 256
    assert W.forward_slots(210) == 256
    assert W.forward_slots(64) == 64
    assert W.forward_slots(3) == 8
    assert W.forward_slots(1000) == 256


def test_hand_count():
    # one tile: 1,024 passages x 256 slots x (4 + 4) bytes = 2 MiB
    assert W.walk_bytes(1, 1024, 256) == 2_097_152
    # a walk that skips nothing at the cell's size reads the whole
    # forward index: 256 tiles, 512 MiB
    assert W.walk_bytes(256, 1024, 256) == 536_870_912
    assert W.walk_bytes(0.5, 1024, 8) == 32_768
    # six terms' windows of 256 (tile, max) pairs
    assert W.bounds_bytes(6, 256) == 12_288
    assert W.walk_ops(1, 1024, 256, 6) == 3 * 6 * 262_144


def hand_made(tiles_before=None, tiles_after=None, scoring_s=0.05) -> R.Run:
    """A window of 100 answered searches of three words each, 25 of them
    in the traced span, on one shard of the cell's size."""
    requests = [{"ok": True, "op": "match", "query": 0, "due": i * 0.1,
                 "sent": i * 0.1, "done": i * 0.1 + 0.004}
                for i in range(100)]
    spec = {"clauses": [{"field": "text", "match": ["zaa", "zab", "zac"],
                         "operator": "or", "score": "bm25"}], "size": 10}
    before = {"tiles": tiles_before} if tiles_before else {}
    after = {"tiles": tiles_after} if tiles_after else {}
    return R.Run(
        requests=requests, window_s=10.0, docs=262_144, config=CONFIG,
        mix={"operations": [{"name": "match", "specs": [spec]}]},
        stats_before={"fused_scoring": before, "dispatch": {}},
        stats_after={"fused_scoring": after, "dispatch": {}},
        trace={"scoring_s": scoring_s, "window_s": 2.5, "busy_s": 0.06},
        traced=(7.45, 10.0), peaks=PEAKS)


BEFORE = {"examined": 1000.0, "hard_skipped": 100.0, "thresholded": 50.0}
AFTER = {"examined": 26_600.0, "hard_skipped": 5_220.0,
         "thresholded": 1_330.0}


def test_the_readers_take_the_windows_delta():
    run = hand_made(BEFORE, AFTER)
    assert len(WR._traced(run)) == 25
    # (5,120 + 1,280) of 25,600
    assert WR.tile_prune_pct(run) == pytest.approx(25.0)
    # 20,480 tiles scored in the window, a quarter of them by the traced
    # searches: 5,120 x 2 MiB, and 75 terms' windows of 256 pairs
    least = 5_120 * 2_097_152 + 75 * 256 * 8
    assert WR.walk_least_bytes(run) == pytest.approx(least)
    assert WR.fused_walk_roofline(run) == pytest.approx(
        100.0 * least / 819e9 / 0.05)
    assert 0 < WR.fused_walk_roofline(run) < 100
    for name in ("tile_prune_pct", "fused_walk_roofline"):
        assert R.load_reader(name).read(run) == getattr(WR, name)(run)


@pytest.mark.parametrize("before,after", [
    (None, None),                   # a program without the counters
    (BEFORE, BEFORE),               # no fused walk ran in the window
    (BEFORE, dict(BEFORE, examined=1200.0, hard_skipped=300.0))])
def test_a_reader_finds_nothing_where_nothing_was_scored(before, after):
    run = hand_made(before, after)
    assert WR.fused_walk_roofline(run) is None
    if after == before:
        assert WR.tile_prune_pct(run) is None


def test_nothing_without_a_trace():
    run = hand_made(BEFORE, AFTER)
    run.trace = None
    assert WR.fused_walk_roofline(run) is None
    assert WR.tile_prune_pct(run) == pytest.approx(25.0)


def test_the_two_are_read_in_the_new_cell_only():
    bench = R.read_json(R.ROOT, "BENCHMARK.json")
    for cell in (w["name"] for w in bench["workloads"]):
        names = {m["name"] for m in R.cell_metrics(bench, cell)[1]}
        assert ({"tile_prune_pct", "fused_walk_roofline"} <= names) \
            == (cell == CELL)
        assert ("column_scan_roofline" in names) == (cell != CELL)
        assert "fused_admission_pct" in names
