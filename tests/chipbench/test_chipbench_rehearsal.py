"""Every cell rehearsed on the CPU at a tiny size, through the harness.

The harness refuses to run without an accelerator, so the test steers its
device check (and keeps JAX's persistent cache off); everything else --
data from the seed, set-up, the window, the comparison with the plain
reference, the metric readers and the result line -- runs as on the chip.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench_steer import (BENCH, CELLS, REPO, run_cell,  # noqa: E402
                             steer)
from chipbench import harness  # noqa: E402


@pytest.fixture
def steered(monkeypatch, tmp_path):
    steer(monkeypatch, tmp_path)


def expected_metrics(name, traced):
    if not traced:
        return {m["name"] for m in BENCH["end_to_end"]
                if name in m.get("workloads", [name])}
    return {m["name"] for m in BENCH["per_layer"]
            if name in m["workloads"]}


@pytest.mark.parametrize("traced", [False, True], ids=["trace0", "trace1"])
@pytest.mark.parametrize("name", CELLS)
def test_cell_rehearses_on_cpu(name, traced, steered, capsys):
    res, err = run_cell(name, traced, capsys)
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    keys += ["breakdown"] if traced else []
    assert sorted(res) == sorted(keys + ["checks"])
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == expected_metrics(name, traced)
    units = {m["name"]: m["unit"]
             for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    for k, v in res["metrics"].items():
        assert v["unit"] == units[k] and v["value"] == v["value"]
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    if traced:
        assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
        assert len(res["breakdown"]["device_ops"]) <= 10
    # the numbers compared are the last lines of standard error
    last = err.strip().splitlines()[-len(res["checks"]):]
    assert all(line.startswith("[chipbench] check ") for line in last)


def test_harness_finds_a_cell_file_it_has_no_list_of(tmp_path, monkeypatch):
    """A new cell is a cell file and a BENCHMARK.json entry: no code."""
    root = tmp_path / "checkout"
    shutil.copytree(REPO / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": "road-sssp-batch-q32", "config": "road-analytics",
        "traffic": "sssp-uniform-q32", "chips": 1,
        "why": "a cell added as data only"})
    bench["end_to_end"][0]["workloads"].append("road-sssp-batch-q32")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = json.loads((REPO / "chipbench/cells/road-sssp-batch.json")
                      .read_text())
    cell.update(traffic="sssp-uniform-q32")
    cell["params"]["queries_per_batch"] = 32
    (root / "chipbench/cells/road-sssp-batch-q32.json").write_text(
        json.dumps(cell))
    monkeypatch.setattr(harness, "ROOT", str(root))
    monkeypatch.setattr(harness, "HERE", str(root / "chipbench"))
    found = harness.load_cell("road-sssp-batch-q32")
    assert found.params["queries_per_batch"] == 32
    assert found.driver.__name__ == "chipbench.traffic.batch"
    assert [m["name"] for m in found.end_to_end] == ["batch_queries_per_s",
                                                     "setup_s"]


def test_no_accelerator_means_no_result(capsys):
    """Unsteered, on a machine without a chip, the run prints nothing on
    standard output and exits with another code than 0."""
    rc = harness.main(["--workload", CELLS[0], "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc == harness.NO_ACCELERATOR and out == ""
    assert "no accelerator" in err


def test_benchmark_files_alone_give_no_result(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's paths (no
    program) exits with another code than 0 and prints no result."""
    for p in BENCH["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        BENCH["command"] + ["--workload", CELLS[0], "--seed", "1",
                            "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and proc.stdout == ""


@pytest.mark.parametrize("name", CELLS)
def test_calibrate_runs_the_cell_at_each_point(name, steered, capsys):
    """The calibration sweep is runs of the cell itself, one per value of
    the swept key, with the fixed keys overridden as well."""
    from chipbench import calibrate
    cfg = harness.load_cell(name).config
    if "capacity" in cfg:
        vary, fix = "offered_per_s=20,40", ["capacity=2"]
    else:
        vary, fix = "grid_side=10,12", []
    rc = calibrate.main(["--workload", name, "--vary", vary, "--seed", "7",
                         "--seconds", "0.3"]
                        + [a for f in fix for a in ("--fix", f)])
    out, err = capsys.readouterr()
    assert rc == 0, err
    lines = [json.loads(line) for line in out.strip().splitlines()]
    key, values = vary.split("=")
    assert [p[key] for p in lines] == [json.loads(v)
                                       for v in values.split(",")]
    for p in lines:
        assert p["result"]["correct"] is True
        assert all(p[k] == json.loads(v)
                   for k, v in (f.split("=") for f in fix))
