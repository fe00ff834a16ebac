"""Benchmark aggregator (deliverable d): one harness per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--quick|--smoke] \
      [--only fig13,fig15,...] [--suite memory]

The suite registry below (``SUITES``) is the single source of truth for the
available keys: the ``--suite`` help text and docs/benchmarks.md are
generated from / checked against it, never hand-listed. One line per suite:

  key -> (runner, what it measures)

``--suite`` is an alias of ``--only``; ``--smoke`` runs the smallest
workload a suite supports (CI regression gate — suites without a dedicated
smoke size fall back to their quick size). See docs/benchmarks.md for the
per-suite BENCH_*.json schemas and the headline-number trajectory.
"""
from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import time

from benchmarks import (bench_ablation, bench_batch_latency, bench_decode,
                        bench_executors, bench_fleet, bench_hetero,
                        bench_memory, bench_memory_alloc, bench_online,
                        bench_overhead, bench_placement, bench_simperf,
                        bench_throughput, bench_kernels)
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import log as obslog

log = obslog.get_logger("bench")


def _lint(quick: bool = False):
    """Invariant-analyzer cost + status (the CI `config` job summary row):
    wall time and violation count of `python -m repro.analysis --strict src`
    so lint cost stays visible as the tree grows."""
    from repro.analysis import run_checks
    t0 = time.perf_counter()
    report = run_checks(["src"])
    wall_s = time.perf_counter() - t0
    return {"files": report.files,
            "violations": len(report.violations),
            "stale_registry_entries": len(report.warnings),
            "clean": report.ok(strict=True),
            "wall_s": round(wall_s, 3)}


# key -> (runner, one-line description). ``--suite`` help and the docs table
# are derived from this dict — add new suites HERE only.
SUITES_INFO = {
    "fig13_14": (bench_throughput.run,
                 "paper Fig. 13 throughput + Fig. 14 switches"),
    "fig15_16": (bench_ablation.run, "paper Fig. 15/16 ablation breakdown"),
    "fig17": (bench_executors.run, "paper Fig. 17 executor-count sweep"),
    "fig18": (bench_memory_alloc.run,
              "paper Fig. 18 decay-window memory allocation"),
    "fig19": (bench_overhead.run,
              "paper Fig. 19 scheduling/management overhead"),
    "fig5_12": (bench_batch_latency.run,
                "paper Fig. 5/12 batch-latency linearity"),
    "kernels": (bench_kernels.run, "Pallas kernels vs oracles"),
    "online": (bench_online.run,
               "online gateway throughput/p99 at fixed offered load"),
    "memory": (bench_memory.run,
               "tiered-memory hierarchy: policy x prefetch, contention, "
               "promotion, prefetch-trigger traffic delta"),
    "fleet": (bench_fleet.run, "devices x links x replication sweep"),
    "placement": (bench_placement.run,
                  "cost-model placement search vs greedy sweep + peer-link "
                  "replica materialization"),
    "simperf": (bench_simperf.run,
                "simulator wall-clock performance: fast path vs naive "
                "reference at 4-128 devices + search-proposal rates"),
    "hetero": (bench_hetero.run,
               "heterogeneous CPU co-execution on/off across memory-"
               "pressure sweeps: stall time, switches, throughput"),
    "decode": (bench_decode.run,
               "token-level decode: stage vs continuous batching, KV-aware "
               "vs weight-only eviction under memory pressure"),
    "lint": (_lint,
             "invariant analyzer wall time + zero-violation status over "
             "src/ (repro.analysis --strict)"),
}

SUITES = {key: runner for key, (runner, _) in SUITES_INFO.items()}


def suite_out_paths() -> dict:
    """Suite key -> the BENCH_*.json its module emits (None: no artifact)."""
    return {key: getattr(inspect.getmodule(fn), "OUT_PATH", None)
            for key, fn in SUITES.items()}


def validate_registry():
    """Every suite that emits a BENCH_*.json artifact must name it after
    its registered key — the suites used to hard-code their paths
    independently of this registry, so a renamed key silently orphaned the
    artifact docs/CI consume. Raises on any mismatch."""
    problems = [
        f"suite {key!r} writes {out!r}, expected 'BENCH_{key}.json'"
        for key, out in suite_out_paths().items()
        if out is not None and out != f"BENCH_{key}.json"]
    if problems:
        raise RuntimeError(
            "suite registry / artifact filename mismatch: "
            + "; ".join(problems)
            + " — rename OUT_PATH or the SUITES_INFO key so docs and CI "
              "find the artifact")


def suite_help() -> str:
    """``--suite`` help text, generated from the registry."""
    return "comma-separated suite keys: " + ", ".join(SUITES)


def _profiled(key: str, fn, kwargs: dict):
    """Run one suite under cProfile: dump ``BENCH_<key>.prof`` (pstats
    format — load with ``pstats.Stats`` or snakeviz) and log the top-10
    cumulative-time functions so a hot-path regression is visible in the
    CI log without downloading the artifact."""
    import cProfile
    import pstats

    prof = cProfile.Profile()
    prof.enable()
    try:
        res = fn(**kwargs)
    finally:
        prof.disable()
    path = f"BENCH_{key}.prof"
    prof.dump_stats(path)
    stats = pstats.Stats(prof)
    rows = sorted(stats.stats.items(),
                  key=lambda kv: kv[1][3], reverse=True)  # ct = cumulative
    top = []
    for (fname, line, func), (cc, nc, tt, ct, _) in rows:
        if func.startswith("<") and fname == "~":
            continue                      # builtins: noise at the top level
        short = f"{os.path.basename(fname)}:{line}({func})"
        top.append(f"{short} {ct:.3f}s/{nc}x")
        if len(top) == 10:
            break
    log.info(f"[{key}] profile -> {path}; top cumulative: " + "; ".join(top))
    return res


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="smallest workloads (implies --quick where a suite "
                         "has no dedicated smoke size) — the CI bench gate")
    ap.add_argument("--only", "--suite", dest="only", default=None,
                    help=suite_help())
    ap.add_argument("--profile", action="store_true",
                    help="run each suite under cProfile: dumps "
                         "BENCH_<key>.prof and logs the top-10 "
                         "cumulative-time functions")
    ap.add_argument("--out", default="bench_results.json")
    group = ap.add_mutually_exclusive_group()
    group.add_argument("--quiet", action="store_true",
                       help="warnings/errors only (suppresses per-suite "
                            "result dumps)")
    group.add_argument("--verbose", action="store_true",
                       help="debug-level progress")
    args = ap.parse_args(argv)

    obslog.set_level(obslog.level_from_flags(quiet=args.quiet,
                                             verbose=args.verbose))
    enable_compile_cache()
    validate_registry()
    keys = args.only.split(",") if args.only else list(SUITES)
    unknown = [k for k in keys if k not in SUITES]
    if unknown:
        ap.error(f"unknown suite keys {unknown}; {suite_help()}")
    results, failures = {}, 0
    for key in keys:
        t0 = time.perf_counter()
        mode = "(smoke)" if args.smoke else "(quick)" if args.quick else ""
        log.info(f"\n=== {key} {mode} ===")
        try:
            fn = SUITES[key]
            kwargs = {"quick": args.quick or args.smoke}
            if args.smoke and "smoke" in inspect.signature(fn).parameters:
                kwargs["smoke"] = True
            if args.profile:
                res = _profiled(key, fn, kwargs)
            else:
                res = fn(**kwargs)
            results[key] = res
            log.info(json.dumps(res, indent=1, default=str))
        except Exception as e:  # noqa: BLE001 — report and continue
            failures += 1
            results[key] = {"error": f"{type(e).__name__}: {e}"}
            import traceback
            traceback.print_exc()
        log.info(f"[{key}] {time.perf_counter() - t0:.1f}s")
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1, default=str)
    log.info(f"\n{len(keys) - failures}/{len(keys)} suites ok -> {args.out}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
