"""The harness on the CPU: every cell end to end at a tiny size, a cell added
by files alone, and the refusal to run without a TPU."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.lib import harness
from bench.lib.cell import load_cell
from bench.tests.tiny import KEPT_CELLS, ROOT, run_tiny, tiny_root

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"] + KEPT_CELLS]
E2E = {m["name"] for m in BENCHMARK["end_to_end"]}


@pytest.mark.parametrize("workload", CELLS)
def test_tiny_cell_is_correct(tmp_path, workload):
    r = run_tiny(tiny_root(tmp_path), workload)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == E2E
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert list(r)[-1] == "checks"


def test_per_layer_metrics_follow_their_workloads(tmp_path):
    root = tiny_root(tmp_path)
    resident = [m.name for m in load_cell(root, "sc2-resident-long")
                .per_layer]
    assert resident == [m["name"] for m in BENCHMARK["per_layer"]]
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["per_layer"].append({"name": "switch_gbps", "unit": "GB/s",
                            "better": "higher", "source": "program_span",
                            "layer": "real engine transfer",
                            "moves": "requests_per_s",
                            "workloads": ["sc2-swap-short"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    assert "switch_gbps" in [m.name for m in load_cell(
        root, "sc2-swap-short").per_layer]
    assert "switch_gbps" not in [m.name for m in load_cell(
        root, "sc2-resident-long").per_layer]


def test_cell_added_by_files_alone(tmp_path):
    root = tiny_root(tmp_path)
    cfg = json.loads((root / "bench/configs/starcoder2_3b-coe.json")
                     .read_text())
    cfg["catalog"] = {"domain_experts": 2, "pool_experts": 2}
    (root / "bench/configs/new-model.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/new-mix.json").write_text(json.dumps(
        {"clients": 4, "prompt_len": 16, "domain_weights": [0.5, 0.5],
         "check_requests": 8}))
    (root / "bench/metrics/completed_count.py").write_text(
        "def read(run):\n    return float(len(run.window.completed))\n")
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "new-model", "source": "a test",
                          "file": "bench/configs/new-model.json",
                          "reduced": [], "why": "a test"})
    bm["workloads"].append({"name": "new-cell", "config": "new-model",
                            "traffic": "new-mix", "chips": 1,
                            "why": "a test"})
    bm["end_to_end"].append({"name": "completed_count", "unit": "count",
                             "better": "higher", "bound": 0.1,
                             "source": "host_clock",
                             "workloads": ["new-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))

    cell = load_cell(root, "new-cell")
    assert cell.config["name"] == "new-model"
    assert cell.traffic["clients"] == 4
    assert "completed_count" in [m.name for m in cell.end_to_end]
    assert "completed_count" not in [
        m.name for m in load_cell(root, "sc2-swap-short").end_to_end]
    r = run_tiny(root, "new-cell")
    assert r["correct"], r["checks"]
    assert r["metrics"]["completed_count"]["value"] > 0


def test_unknown_workload_is_refused(tmp_path):
    with pytest.raises(SystemExit):
        load_cell(tiny_root(tmp_path), "no-such-cell")


def test_no_tpu_is_refused():
    with pytest.raises(harness.NoChip):
        harness.devices(1, require_tpu=True)


def test_run_exits_nonzero_without_tpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0],
         "--seed", str(2 ** 31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")
    assert harness.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12


def test_run_exits_nonzero_in_a_directory_of_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0],
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(env, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
