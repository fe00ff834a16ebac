"""Tiny copies of the benchmark's cells, for runs on the CPU.

``tiny_root`` writes a checkout-shaped directory: ``BENCHMARK.json`` with
the kept cells below added, the metric readers as they are, and every
configuration cut to a few-kilobyte model of the same family (and its
traffic to short prompts and a few clients). The harness then runs there exactly as it runs
a real cell, without the chip.
"""
from __future__ import annotations

import json
import shutil
import time
from pathlib import Path

from bench.lib import harness
from bench.lib.cell import load_cell

ROOT = Path(__file__).resolve().parents[2]
TINY = {"hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
        "vocab_size": 256}
# Tiny runs on the CPU over 128 served stages read a widest logit error of
# 0.035 to 0.073 (bfloat16 rounding) and the float8 control 0.51 to 0.99,
# on four seeds of each of two configurations.
TINY_LIMIT = 0.15


# Cells whose files the benchmark keeps for a later benchmark PR (their
# chip runs spread too widely to be bounded; PERF.md): the tiny copies run
# them, so the switching path stays exercised.
KEPT_CONFIGS = [{"name": "phi4_mini_3_8b-coe", "source": "see its file",
                 "file": "bench/configs/phi4_mini_3_8b-coe.json",
                 "reduced": [], "why": "kept"}]
KEPT_CELLS = [
    {"name": "sc2-swap-short", "config": "starcoder2_3b-coe",
     "traffic": "swap-short", "chips": 1, "why": "kept"},
    {"name": "phi4-mixed-long", "config": "phi4_mini_3_8b-coe",
     "traffic": "mixed-long", "chips": 1, "why": "kept"}]


def tiny_root(tmp: Path, prompt_len: int = 16, clients: int = 16,
              check_requests: int = 64) -> Path:
    tmp = Path(tmp)
    for sub in ("metrics", "traffic"):
        shutil.copytree(ROOT / "bench" / sub, tmp / "bench" / sub)
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = {c["name"] for c in bm["configs"]}
    bm["configs"] += [c for c in KEPT_CONFIGS if c["name"] not in names]
    names = {w["name"] for w in bm["workloads"]}
    bm["workloads"] += [w for w in KEPT_CELLS if w["name"] not in names]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bm))
    for c in bm["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        cfg.update(TINY)
        cfg["check"] = {"logit_err": TINY_LIMIT}
        (tmp / c["file"]).parent.mkdir(parents=True, exist_ok=True)
        (tmp / c["file"]).write_text(json.dumps(cfg))
    for path in (tmp / "bench" / "traffic").glob("*.json"):
        t = json.loads(path.read_text())
        t.update(prompt_len=prompt_len, clients=clients,
                 check_requests=check_requests)
        path.write_text(json.dumps(t))
    return tmp


def run_tiny(root: Path, workload: str, seed: int = 2 ** 31 + 7,
             seconds: float = 1.5, control: bool = False) -> dict:
    cell = load_cell(root, workload)
    return harness.run(cell, seed, seconds, False, time.perf_counter(),
                       require_tpu=False, control=control)
