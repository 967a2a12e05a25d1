"""The chip benchmark's harness finds every cell, configuration, traffic
mix and metric by name, takes a cell that is added as files alone, and
refuses malformed files; BENCHMARK.json keeps to its shape; and without
a TPU a run prints no result."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import pytest

import tiny
from benchmarks.chip import harness

BENCH = json.loads((tiny.REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_loads(name):
    cell = harness.load_cell(name)
    assert cell.chips in (1, 4)
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    assert harness.reference_module(cell).param_shapes(cell.config)
    for m in cell.end_to_end + cell.per_layer:
        assert callable(harness.metric_reader(m["name"]))


def test_a_cell_added_as_files_loads(tmp_path):
    root = tiny.checkout(tmp_path)
    cell = harness.load_cell(tiny.CELL, root)
    assert cell.config["model"]["hidden_size"] == 64
    assert cell.traffic["seq_len"] == 32
    assert harness.reference_module(cell).dims(cell.config)["layers"] == 2
    # metrics with a workloads list apply to their cells only
    assert {m["name"] for m in cell.per_layer} == {
        m["name"] for m in BENCH["per_layer"]
        if tiny.CELL in m.get("workloads", [tiny.CELL])}


def _broken(tmp_path, edit):
    root = tiny.checkout(tmp_path)
    edit(root / "benchmarks" / "chip")
    return root


@pytest.mark.parametrize("edit, message", [
    (lambda d: (d / "workloads" / f"{tiny.CELL}.json").write_text(
        json.dumps({**tiny.WORKLOAD, "driver": "pipeline"})), "driver"),
    (lambda d: (d / "workloads" / f"{tiny.CELL}.json").write_text(
        json.dumps({**tiny.WORKLOAD, "check_steps": 1})), "check_steps"),
    (lambda d: (d / "workloads" / f"{tiny.CELL}.json").write_text(
        json.dumps({k: v for k, v in tiny.WORKLOAD.items()
                    if k != "optimizer"})), "optimizer"),
    (lambda d: (d / "workloads" / f"{tiny.CELL}.json").write_text(
        json.dumps({**tiny.WORKLOAD, "limits": {"loss_gap": 1}})),
     "grad_gap"),
    (lambda d: (d / "configs" / "tiny.json").write_text("{not json"),
     "tiny.json"),
    (lambda d: (d / "traffic" / "tiny.json").unlink(), "not found"),
    (lambda d: (d / "metrics" / "setup_s.py").unlink(), "setup_s"),
    (lambda d: (d / "metrics" / "setup_s.py").write_text("X = 1\n"),
     "read"),
])
def test_malformed_files_are_refused(tmp_path, edit, message):
    root = _broken(tmp_path, edit)
    with pytest.raises(harness.HarnessError, match=message):
        harness.load_cell(tiny.CELL, root)


def test_unknown_cell_and_configuration_are_refused(tmp_path):
    root = tiny.checkout(tmp_path)
    with pytest.raises(harness.HarnessError, match="no cell"):
        harness.load_cell("no-such-cell", root)
    with pytest.raises(harness.HarnessError, match="one chip"):
        harness.load_cell("tiny.c4", tiny.checkout(tmp_path / "c4",
                                                   name="tiny.c4", chips=4))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"][-1]["config"] = "absent"
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(harness.HarnessError, match="absent"):
        harness.load_cell(tiny.CELL, root)


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (tiny.REPO / p).is_dir() and ".." not in p
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert NAME.match(c["name"])
        assert (tiny.REPO / c["file"]).is_file()
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert NAME.match(m["name"]) and m["moves"] in e2e
        assert m["layer"] and "bound" not in m
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        assert len(w["why"]) <= 200


def test_without_a_tpu_a_run_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(tiny.REPO / "benchmarks/chip/run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
