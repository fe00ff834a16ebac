"""Readings that the correctness limits are set from, on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> ...
                             [--control-seeds <k>]

One process, one run of the cell per seed (the compiled programs are reused
from seed to seed). For every seed it prints the numbers compared for the
program's served logits and tokens, the lower readings; for
the first ``--control-seeds`` seeds it also prints the control's: the
reference computed with float8 weights, put in the program's place on the
same sample, the upper reading. Each line is a JSON object; the lines are also
written to ``chiprun_out/control_<cell>.jsonl``. The benchmark's own runs
never compute the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib.cell import load_cell  # noqa: E402
from bench.lib.harness import run  # noqa: E402


def main(argv) -> int:
    p = argparse.ArgumentParser(prog="bench/control.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--control-seeds", type=int, default=3)
    args = p.parse_args(argv)
    cell = load_cell(ROOT, args.workload)
    out = ROOT / "chiprun_out" / f"control_{args.workload}.jsonl"
    out.parent.mkdir(exist_ok=True)
    t = T_START
    for i, seed in enumerate(args.seeds):
        r = run(cell, seed, args.seconds, False, t,
                control=i < args.control_seeds)
        line = {"workload": args.workload, "seed": seed,
                "correct": r["correct"],
                "program": {k: v["value"] for k, v in r["checks"].items()},
                "control": r.get("control"),
                "metrics": {k: v["value"] for k, v in r["metrics"].items()}}
        print(json.dumps(line), flush=True)
        with out.open("a") as f:
            f.write(json.dumps(line) + "\n")
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
