"""BENCHMARK.json keeps to the benchmark's contract, and a cell, a
configuration or a per-layer metric is added with new files only."""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

from bench import harness
from bench.tests import smoke
from bench.tests.smoke import REPO

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    names = [x["name"] for sec in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in SPEC[sec]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_configs_and_cells_have_their_files():
    for c in SPEC["configs"]:
        doc = json.loads((REPO / c["file"]).read_text())
        assert set(c["reduced"]) <= set(doc), c["name"]
        assert c["file"].startswith("bench/")
    for w in SPEC["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        doc = json.loads((REPO / f"bench/workloads/{w['name']}.json")
                         .read_text())
        assert doc["config"] == w["config"]
        assert doc["traffic"] == w["traffic"]
        assert (REPO / f"bench/drivers/{doc['driver']}.py").is_file()


def test_rate_follows_knee():
    """A mix that records its knee offers ``rate_of_knee`` x knee, as
    ``bench/knee_sweep.py --write`` stores it."""
    mixes = [json.loads(p.read_text())["mix"]
             for p in sorted((REPO / "bench/workloads").glob("*.json"))]
    kneed = [m for m in mixes if "knee_rps" in m]
    assert kneed
    for mix in kneed:
        assert mix["rate_rps"] == round(
            mix["rate_of_knee"] * mix["knee_rps"], 3), mix


def test_metrics_declare_what_benchmark_json_says():
    for m in SPEC["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        bound = m["bound"]
        assert 0.01 <= bound <= 0.25
    layers = set()
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        reader = harness.load_metric(REPO, m["name"])
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == \
            (m["layer"], m["unit"], m["moves"]), m["name"]
        assert m["moves"] in e2e and UNIT.match(m["unit"])
        layers.add(m["layer"])
    perf = (REPO / "PERF.md").read_text()
    for layer in layers:
        assert f"`{layer}`" in perf, f"layer {layer!r} not named in PERF.md"


def test_every_cell_reports_setup_another_metric_and_a_layer():
    for w in SPEC["workloads"]:
        cell = harness.load_cell(w["name"], REPO)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2, w["name"]
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert m["moves"] in names, (w["name"], m["name"])


def test_adding_a_cell_needs_only_new_files(tmp_path):
    """The throwaway smoke cell (a configuration and a workload file)
    runs in a copy of the benchmark where every existing file under
    bench/ is unchanged."""
    root = smoke.make_tree(tmp_path)
    for path in (REPO / "bench").rglob("*"):
        rel = path.relative_to(REPO)
        if path.is_file() and "__pycache__" not in rel.parts \
                and "tests" not in rel.parts:
            assert (root / rel).read_bytes() == path.read_bytes(), rel
    rc, result, out, err = smoke.run_cell(root, "smollm-smoke.smoke-chat",
                                          seconds=1.0)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True
    assert set(result) >= {"correct", "attempted", "failed",
                           "metrics", "device"}
    assert {"ttft_p95_ms", "itl_p95_ms", "setup_s"} <= set(result["metrics"])
    assert list(result)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check ")


def _bare_run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         SPEC["workloads"][0]["name"], "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_chip_no_result():
    p = _bare_run(REPO)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


def test_benchmark_alone_has_no_result(tmp_path):
    """A checkout that holds only BENCHMARK.json and bench/ (no program)
    exits non-zero and prints no result."""
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _bare_run(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
