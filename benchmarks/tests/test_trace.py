"""The trace reduction: on a hand-made trace whose numbers can be
counted on paper, and on a small trace recorded on the chip."""

import json
import os

import pytest

from harness import trace as T

MS = 1_000_000


def hand_made() -> dict:
    # window 0..100 ms on one chip. Program A runs 10..30 (ops 10..20 and
    # 20..30), program B 50..60 (one op 52..58, a nested op 53..55 that
    # the union must not count twice). A second chip runs nothing.
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit__segment_program_packed(11)", 10 * MS, 20 * MS],
                ["jit_other(7)", 50 * MS, 10 * MS]]},
            {"name": "XLA Ops", "events": [
                ["fusion.1", 10 * MS, 10 * MS], ["fusion.2", 20 * MS, 10 * MS],
                ["copy.3", 52 * MS, 6 * MS], ["fusion.1", 53 * MS, 2 * MS]]}]},
        {"name": "/device:TPU:1", "lines": []},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [["bench:window", 0, 100 * MS]]},
            {"name": "pool-search-0", "events": [
                ["query_phase:collect", 28 * MS, 30 * MS],
                ["query_phase:dispatch", 5 * MS, 4 * MS]]}]}]}


def test_busy_union_idle_and_programs():
    r = T.reduce_trace(hand_made())
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.026)       # 20 + 6, nested once
    assert r["scoring_s"] == pytest.approx(0.020)
    assert r["scoring_runs"] == 1
    assert r["programs"]["other"]["seconds"] == pytest.approx(0.010)
    assert r["longest_gap_s"] == pytest.approx(0.042)  # 58..100


def test_breakdown_names_ops_and_gaps():
    b = T.reduce_trace(hand_made())["breakdown"]
    ops = dict(b["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.012)
    assert ops["copy.3"] == pytest.approx(0.006)
    gaps = dict(b["idle_gaps"])
    # 0..10: dispatch covers 4 of it; 30..52: collect covers 30..52;
    # 58..100: collect covers no more than 0 -> "no span"
    assert gaps["query_phase:dispatch"] == pytest.approx(0.010)
    assert gaps["query_phase:collect"] == pytest.approx(0.022)
    assert gaps["no span"] == pytest.approx(0.042)
    assert sum(gaps.values()) == pytest.approx(0.100 - 0.026)


def test_window_clips_events():
    tr = hand_made()
    tr["planes"][2]["lines"][0]["events"] = [["bench:window", 15 * MS, 40 * MS]]
    r = T.reduce_trace(tr)
    assert r["window_s"] == pytest.approx(0.040)
    assert r["busy_s"] == pytest.approx(0.018)       # 15..30, 52..55
    assert r["scoring_s"] == pytest.approx(0.015)


def test_no_device_plane_reads_nothing():
    tr = {"planes": [p for p in hand_made()["planes"]
                     if p["name"] == "/host:CPU"]}
    assert T.reduce_trace(tr) is None


def test_program_name():
    assert T.program_name("jit__segment_program_packed(123)") \
        == "_segment_program_packed"
    assert T.program_name("copy.1") == "copy.1"


def test_recorded_trace():
    """A stretch of cell 1's traced window as the v5e recorded it (my
    chip run, PR 24), cut to the events the reduction reads."""
    path = os.path.join(os.path.dirname(__file__), "recorded_trace.json")
    with open(path) as f:
        rec = json.load(f)
    r = T.reduce_trace(rec["trace"])
    for key, want in rec["expect"].items():
        assert r[key] == pytest.approx(want, rel=1e-9), key
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["scoring_s"] <= r["busy_s"] * 1.01
    assert len(r["breakdown"]["device_ops"]) <= 10
    assert abs(sum(s for _n, s in r["breakdown"]["idle_gaps"])
               - (r["window_s"] - r["busy_s"])) < 1e-6
