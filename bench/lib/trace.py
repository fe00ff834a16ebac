"""From a profiler trace to device busy time, idle gaps and kernel time.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
lists: the device operations of each chip (the "XLA Ops" line of every
``/device:TPU:n`` plane) and the host spans that the benchmark opened
(``bench.*`` TraceAnnotations). Everything after that is arithmetic on
intervals in nanoseconds, and is tested on a small recorded trace.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

import jax

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"

Interval = Tuple[str, float, float]          # (name, start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    devices: List[List[Interval]]            # one list of ops per chip
    spans: List[Interval]                    # host spans, any thread


@dataclasses.dataclass
class Summary:
    window_s: float                          # the traced window's length
    busy_s: float                            # union of op time, chip mean
    ops: Dict[str, Tuple[int, float]]        # name -> (count, self seconds)
    idle_gaps: List[Tuple[str, float]]       # host span -> idle seconds


def profile_options():
    """No Python tracer: only the device and the benchmark's own spans."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, "
                           f"found {len(files)}")
    return files[0]


def op_name(event_name: str) -> str:
    """The HLO instruction's name ("fusion.12") out of an op event's name,
    which is the whole instruction ("%fusion.12 = bf16[...] fusion(...)")."""
    m = re.match(r"%?([^\s=]+)", event_name)
    return m.group(1) if m else event_name


def load(path: str) -> Trace:
    data = jax.profiler.ProfileData.from_file(path)
    devices, spans = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE) \
                and plane.name[len(DEVICE_PLANE):].isdigit():
            ops = [(op_name(e.name), e.start_ns, e.end_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            spans += [(e.name, e.start_ns, e.end_ns)
                      for line in plane.lines for e in line.events
                      if e.name.startswith(SPAN_PREFIX)]
    return Trace(devices=devices, spans=spans)


def merged(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    """The union of ``intervals`` clipped to [lo, hi], as sorted disjoint
    (start, end) pairs."""
    out: List[List[float]] = []
    for _, s, e in sorted(intervals, key=lambda iv: iv[1]):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: List[Tuple[float, float]], lo: float, hi: float):
    """The idle (start, end) pairs of [lo, hi] around ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def innermost(spans: List[Interval], times: List[float]) -> List[Optional[str]]:
    """For each of the sorted ``times``, the shortest host span that holds
    it (None where no span does). One sweep over spans sorted by start."""
    spans = sorted(spans, key=lambda sp: sp[1])
    out, active, i = [], [], 0
    for t in times:
        while i < len(spans) and spans[i][1] <= t:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[2] > t]
        best = min(active, key=lambda sp: sp[2] - sp[1], default=None)
        out.append(best[0] if best else None)
    return out


def self_times(ops: List[Interval], lo: float, hi: float):
    """(name, seconds) of each op inside [lo, hi], less the time of the ops
    nested in it (a ``while`` op holds its body's ops on the same line)."""
    clipped = sorted(((n, max(s, lo), min(e, hi)) for n, s, e in ops
                      if min(e, hi) > max(s, lo)),
                     key=lambda iv: (iv[1], -iv[2]))
    own = [e - s for _, s, e in clipped]
    stack: List[int] = []
    for i, (_, s, e) in enumerate(clipped):
        while stack and clipped[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= clipped[stack[-1]][2]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(clipped[i][0], own[i] / 1e9) for i in range(len(clipped))]


def summarize(trace: Trace, top: int = 10) -> Summary:
    windows = [(s, e) for n, s, e in trace.spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, "
                           f"found {len(windows)}")
    if not trace.devices:
        raise RuntimeError("the trace holds no TPU device plane")
    lo, hi = windows[0]
    inner = [sp for sp in trace.spans if sp[0] != WINDOW_SPAN]
    busy_s, ops, idle = 0.0, {}, {}
    for dev in trace.devices:
        busy = merged(dev, lo, hi)
        busy_s += sum(e - s for s, e in busy) / 1e9
        for name, secs in self_times(dev, lo, hi):
            n, t = ops.get(name, (0, 0.0))
            ops[name] = (n + 1, t + secs)
        idle_pairs = gaps(busy, lo, hi)
        owners = innermost(inner, [(s + e) / 2 for s, e in idle_pairs])
        for (s, e), who in zip(idle_pairs, owners):
            who = who or "no span"
            idle[who] = idle.get(who, 0.0) + (e - s) / 1e9 / len(
                trace.devices)
    ranked = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return Summary(window_s=(hi - lo) / 1e9,
                   busy_s=busy_s / len(trace.devices), ops=ops,
                   idle_gaps=ranked)


def top_ops(summary: Summary, top: int = 10):
    ranked = sorted(summary.ops.items(), key=lambda kv: -kv[1][1])[:top]
    return [[name, secs] for name, (_, secs) in ranked]
