"""The harness finds every file of a cell by name, picks up a cell that
only adds files, and refuses to run without a TPU."""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

from perfbench import harness
from perfbench.tests.cells import MIXES, small_cell

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_found_by_name(name):
    cell = harness.load_cell(name)
    assert cell.chips == 1
    assert harness.deployment_class(cell.config) is not None
    assert cell.mix["loop"] in ("open", "closed")
    assert set(cell.config["limits"]) >= {"missing", "metric_err", "cdf_gap",
                                          "decision_err"}
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.find_reader(m["name"]))


def test_benchmark_json_is_well_formed():
    names = set()
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert m["name"] not in names
        names.add(m["name"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("device_trace", "host_clock")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(CELLS)
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).exists()
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert not any(k.endswith(("_dim", "_rank")) for k in c["reduced"])
    for w in BENCH["workloads"]:
        mix = ROOT / "perfbench" / "mixes" / f"{w['config']}.{w['traffic']}.json"
        assert mix.exists(), mix


def test_a_cell_that_only_adds_files_is_picked_up(tmp_path):
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": "prescored-k100.burst", "config": "prescored-k100",
        "traffic": "burst", "chips": 1, "why": "added by a test"})
    bench["per_layer"].append({
        "name": "calls.burst", "unit": "calls", "better": "higher",
        "source": "host_clock", "layer": "intake and batching loop",
        "moves": bench["end_to_end"][0]["name"],
        "workloads": ["prescored-k100.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    mix = json.loads((ROOT / "perfbench/mixes/prescored-k100.online.json")
                     .read_text())
    mix["rate"] = 1234
    (tmp_path / "perfbench/mixes/prescored-k100.burst.json").write_text(
        json.dumps(mix))
    (tmp_path / "perfbench/metrics/calls.py").write_text(
        "def read(ctx):\n    return len(ctx.window.batches)\n")
    cell = harness.load_cell("prescored-k100.burst", root=tmp_path)
    assert cell.mix["rate"] == 1234
    assert [m["name"] for m in cell.per_layer] == ["calls.burst"]
    reader = harness.find_reader("calls.burst",
                                 bench_dir=tmp_path / "perfbench")
    assert reader(type("Ctx", (), {"window": type("W", (), {
        "batches": [3, 4]})})) == 2


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "prescored-k100.online", "--seed", str(2 ** 33 + 1), "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert '"metrics"' not in proc.stdout
    assert "TPU" in proc.stderr


@pytest.mark.parametrize("config,traffic", MIXES)
def test_cell_runs_and_is_correct_on_cpu(config, traffic):
    cell = small_cell(config, traffic)
    res = harness.run(cell.name, 2 ** 32 + 7, 1.0, False,
                      time.perf_counter(), need_tpu=False, cell=cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
