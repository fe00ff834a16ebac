"""One run of one cell: set-up, the measured window, the trace, the
comparison with the reference, and the result line.

Order matters. Set-up ends where the window opens. The device's peak memory
is read once the window has closed and before the reference runs (a
process's peak never falls again), and the reference runs after the
program's pool and host tier are freed. With ``trace`` the profiler records
the window, and the per-layer metrics are reported; without it, the
end-to-end metrics are.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import List, Optional

import jax

from bench.lib import check, serve
from bench.lib import trace as tr
from bench.lib.cell import Cell, family_modules, load_cell
from bench.lib.traffic import Traffic

ROOT = Path(__file__).resolve().parents[2]
PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(SystemExit):
    """The run found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class RunRecord:
    """What the metric readers read (``bench/metrics/<name>.py``)."""
    setup_s: float
    window: serve.Window
    counters: serve.Counters
    dims: object                    # the reference's Dims of the config
    prompt_len: int
    expert_bytes: int
    peaks: Optional[dict]           # None where no chip was required
    trace: Optional[tr.Summary]

    def executes(self) -> List[tuple]:
        """Model steps that ran in the window (the loop's whole run)."""
        w = self.window
        return [e for e in self.counters.executes
                if w.t_open <= e[0] and e[1] <= w.t_end]

    def in_window(self, stamps) -> List[float]:
        return [t for t in stamps
                if self.window.t_open <= t <= self.window.t_end]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def devices(chips: int, require_tpu: bool):
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"bench: JAX's default device is {devs[0].platform} "
                     f"({devs[0].device_kind}), not a TPU; the benchmark "
                     "never falls back to the CPU")
    if len(devs) < chips:
        raise NoChip(f"bench: the cell asks for {chips} chips, JAX finds "
                     f"{len(devs)}")
    return devs[:chips]


def peaks_for(kind: str) -> dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS.name}")
    return table[kind]


def memory(devs) -> dict:
    stats = [d.memory_stats() or {} for d in devs]
    return {"peak_bytes_in_use": max(s.get("peak_bytes_in_use", 0)
                                     for s in stats),
            "bytes_limit": min(s.get("bytes_limit", 0) for s in stats)}


def host_ram_bytes() -> int:
    with open("/proc/meminfo") as f:
        for line in f:
            if line.startswith("MemTotal:"):
                return int(line.split()[1]) * 1024
    return 0


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, *, require_tpu: bool = True,
        control: bool = False) -> dict:
    """One run; returns the result line's object. ``control`` also reads
    the control's gaps (``bench/control.py``; the benchmark's runs never
    do)."""
    devs = devices(cell.chips, require_tpu)
    peaks = None
    if require_tpu:
        peaks = peaks_for(devs[0].device_kind)
        from repro.launch.compile_cache import enable_compile_cache
        log(f"compile cache: {enable_compile_cache()}")
        # every program of the run, however quick to compile, is cached, so
        # that only a checkout's first run compiles
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"host RAM total: {host_ram_bytes()} bytes")

    counters_compiles: List[float] = []

    def on_event(event, _secs, **_kw):
        if event == COMPILE_EVENT:
            counters_compiles.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        return _run(cell, seed, seconds, trace, t_start, devs, peaks,
                    counters_compiles, control)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


def _run(cell, seed, seconds, trace, t_start, devs, peaks, compiles,
         control):
    program, reference = family_modules(cell.config)
    dm = reference.dims(cell.config)
    traffic = Traffic(cell.traffic, cell.config["catalog"]["domain_experts"],
                      dm.vocab, seed)
    s = serve.set_up(cell.config, traffic, seed, program, reference,
                     annotate=trace, log=log)
    s.counters.compiles = compiles
    log(f"memory after set-up: {memory(devs)}")
    setup_s = time.perf_counter() - t_start

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=tr.profile_options())
    try:
        w = serve.run_window(s, traffic, seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    mem = memory(devs)
    log(f"memory after the window: {mem}")
    summary = None
    if trace:
        summary = tr.summarize(tr.load(tr.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)

    record = RunRecord(setup_s=setup_s, window=w,
                       counters=s.counters, dims=dm,
                       prompt_len=traffic.prompt_len,
                       expert_bytes=s.expert_bytes, peaks=peaks,
                       trace=summary)
    domains = s.domains
    serve.release(s)
    del s
    gc.collect()

    t0 = time.perf_counter()
    stages = check.sample_stages(w.completed, domains,
                                 int(cell.traffic["check_requests"]), seed)
    ref = check.reference_logits(reference, cell.config, seed, stages)
    log(f"reference: {len(stages)} served stages in "
        f"{time.perf_counter() - t0:.1f} s")
    checks = check.checks(cell.config, stages, ref,
                          check.chain_errors(w.completed))
    correct = check.passed(checks)
    failed = check.failed_stages(stages, ref, checks) \
        + checks["chain_errors"]["value"]

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = m.read(record)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}
        elif not trace:         # no request completed: no result stands
            correct = False

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": mem["peak_bytes_in_use"]}
    result = {"correct": correct, "attempted": w.issued, "failed": failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": tr.top_ops(summary),
                               "idle_gaps": [list(g)
                                             for g in summary.idle_gaps]}
    if control:
        ctrl = check.reference_logits(reference, cell.config, seed, stages,
                                      precision="float8_e4m3fn")
        result["control"] = {
            "logit_err": check.logit_err(ref, ctrl),
            "logit_gap": float(check.token_gaps(
                ref, [int(c.argmax()) for c in ctrl]).max())}
    log(f"window: {len(w.completed)} completed of {w.issued} issued, "
        f"{len(record.in_window(record.counters.loads))} loads, "
        f"{len(record.executes())} model steps")
    for name, c in checks.items():
        log(f"check {name}: {c['value']} {c['pass']} {c['limit']}")
    result["checks"] = checks
    return result


def main(argv, t_start: float) -> int:
    p = argparse.ArgumentParser(prog="bench/run.py",
                                description="Run one benchmark cell once.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    cell = load_cell(ROOT, args.workload)
    result = run(cell, args.seed, args.seconds, bool(args.trace), t_start)
    print(json.dumps(result), flush=True)
    return 0
