"""``bench/knee_sweep.py``: the sustain rule, the knee it picks, and the
sweep that runs each rate in a process of its own."""

from __future__ import annotations

import json
import shutil
import subprocess

from bench import knee_sweep
from bench.tests.smoke import REPO

CELL = "smollm-135m.chat-steady"


def _window(rate, queue_end=1, first=25.0, last=26.0):
    return {"rate_rps": rate, "queue_start": 0, "queue_end": queue_end,
            "no_first_token": 0, "ttft_first_third_ms": first,
            "ttft_last_third_ms": last, "ttft_p95_ms": 50.0,
            "itl_p95_ms": 22.0, "served_tokens_per_s": 1e4,
            "live_kv_pct": 25.0}


def test_sustained_rule():
    assert knee_sweep.sustained(_window(8))
    assert knee_sweep.sustained(_window(8, queue_end=knee_sweep.SLACK))
    assert not knee_sweep.sustained(_window(8, queue_end=knee_sweep.SLACK
                                            + 1))
    assert knee_sweep.sustained(_window(8, first=30.0, last=110.0))
    assert not knee_sweep.sustained(_window(8, first=30.0, last=110.1))


def test_knee_lies_below_the_first_rate_not_sustained():
    rows = [dict(_window(r), sustained=ok)
            for r, ok in ((8, True), (10, True), (12, False), (14, True))]
    assert knee_sweep.knee_of(rows) == 10
    assert knee_sweep.knee_of([dict(_window(8), sustained=False)]) is None


def _fake_children(verdicts, calls):
    """``subprocess.run`` standing in for the per-rate processes: the
    window each rate reports is sustained as ``verdicts`` says."""

    def run(cmd, **_kw):
        rate = float(cmd[cmd.index("--one-rate") + 1])
        calls.append(rate)
        s = _window(rate) if verdicts[rate] else _window(rate, queue_end=40)
        out = "generator: ...\n" + knee_sweep.LINE + json.dumps(s) + "\n"
        return subprocess.CompletedProcess(cmd, 0, stdout=out)

    return run


def test_sweep_stops_after_two_failures_and_writes_the_knee(
        tmp_path, monkeypatch):
    (tmp_path / "bench").mkdir()
    shutil.copytree(REPO / "bench/workloads", tmp_path / "bench/workloads")
    monkeypatch.setattr(knee_sweep, "ROOT", tmp_path)
    calls = []
    verdicts = {8: True, 10: True, 12: False, 14: False, 16: True}
    monkeypatch.setattr(knee_sweep.subprocess, "run",
                        _fake_children(verdicts, calls))
    table = tmp_path / "knee.md"
    rc = knee_sweep.main(["--workload", CELL, "--rates", "16", "8", "12",
                          "10", "14", "--seconds", "20", "--write",
                          "--table", str(table)])
    assert rc == 0
    assert calls == [8, 10, 12, 14]          # 16 is never run
    mix = json.loads((tmp_path / f"bench/workloads/{CELL}.json")
                     .read_text())["mix"]
    assert mix["knee_rps"] == 10
    assert mix["rate_rps"] == round(10 * mix["rate_of_knee"], 3)
    assert table.read_text().startswith(f"knee of {CELL}: 10.0 req/s")


def test_a_failed_rate_process_ends_the_sweep(tmp_path, monkeypatch):
    monkeypatch.setattr(knee_sweep, "ROOT", tmp_path)
    monkeypatch.setattr(
        knee_sweep.subprocess, "run",
        lambda cmd, **_kw: subprocess.CompletedProcess(cmd, 2, stdout=""))
    assert knee_sweep.main(["--workload", CELL, "--rates", "8"]) == 1
