"""chip_smoke.py cannot rot between chip runs, and cannot say yes
without a chip: the whole script runs in-process on the CPU at a tiny
--docs (forced Pallas in interpret mode) — load / answer / compare must
pass, the device assertions must be REPORTED as failed, and the verdict
must be `"ok": false` with a non-zero exit."""

import json

import chip_smoke


def test_cpu_rehearsal_passes_every_phase_but_the_device(capsys):
    # the `fused_scoring` counters are process-wide: what an earlier test
    # of this worker left there must not reach the smoke's verdicts
    from elasticsearch_tpu.search.executor import _fused_stats
    _fused_stats.record_positional("a_stranger_s_fallback")
    _fused_stats.record_pallas_reject("kernel_unavailable")
    rc = chip_smoke.main(["--docs", "4096", "--seed", "3"])
    lines = capsys.readouterr().out.strip().splitlines()
    verdict = json.loads(lines[-1])
    assert rc == 1
    assert verdict["ok"] is False
    assert verdict["device"]["platform"] == "cpu"
    assert set(verdict) == {"ok", "device"}
    assert not any('"ok": true' in ln for ln in lines)

    passed = [ln for ln in lines if ln.startswith("[pass]")]
    failed = [ln for ln in lines if ln.startswith("[FAIL]")]
    # serve / load / answer / compare: nothing failed
    assert all(ln.startswith("[FAIL] device:") for ln in failed), failed
    for name in ("logs: no bulk item reported an error",
                 "logs: count == --docs",
                 "pallas: every response 200",
                 "xla: every response 200",
                 "auto: every response 200",
                 "compare: xla == pallas",
                 "compare: xla == auto",
                 "compare: pallas == numpy reference",
                 "compare: xla == numpy reference",
                 "compare: auto == numpy reference",
                 "compare: the workload matches something",
                 "device: the phrase query ran fused"):
        assert any(name in ln for ln in passed), name
    # the device assertions are reported as failed, never skipped
    for name in ("no backend choice is 'pallas-unavailable'",
                 "no plan rejected 'kernel_unavailable'",
                 "the unset pass timed both engines",
                 "the programs ran on a tpu"):
        assert any(name in ln for ln in failed), name


def test_no_accelerator_and_no_rehearsal_prints_no_result(capsys):
    rc = chip_smoke.main([])
    captured = capsys.readouterr()
    assert rc == 1
    assert captured.out == ""
    assert "no TPU" in captured.err


def test_counters_are_read_as_what_they_moved_by():
    before = {"dispatches": 7, "prune_rate": 0.5, "backend_choices": {
        "old": {"backend": "xla", "reason": "timed"},
        "retuned": {"backend": "xla", "reason": "static"}},
        "admission": {"admitted": 3, "rate": 0.3, "positional_admitted": 1,
                      "pallas_rejected": {"kernel_unavailable": 2},
                      "positional_fallbacks": {"slop": 1}}}
    after = {"dispatches": 12, "prune_rate": 0.4, "backend_choices": {
        "old": {"backend": "xla", "reason": "timed"},
        "retuned": {"backend": "pallas", "reason": "timed"},
        "new": {"backend": "pallas", "reason": "forced"}},
        "admission": {"admitted": 9, "rate": 0.6, "positional_admitted": 2,
                      "pallas_rejected": {"kernel_unavailable": 2,
                                          "positional_mosaic": 1},
                      "positional_fallbacks": {"slop": 1}}}
    assert chip_smoke.moved(before, after) == {
        "dispatches": 5,
        "backend_choices": {
            "retuned": {"backend": "pallas", "reason": "timed"},
            "new": {"backend": "pallas", "reason": "forced"}},
        "admission": {"admitted": 6, "positional_admitted": 1,
                      "pallas_rejected": {"positional_mosaic": 1},
                      "positional_fallbacks": {}}}
