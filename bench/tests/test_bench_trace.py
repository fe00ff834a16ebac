"""The reduction from a profiler trace to busy time, idle gaps and kernel
time: on hand-made intervals, and on a small trace recorded on a TPU v5e
chip by ``record_trace.py`` (``data/trace_small.xplane.pb``)."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench.lib import trace as tr

DATA = Path(__file__).resolve().parent / "data" / "trace_small.xplane.pb"


def test_interval_arithmetic():
    ops = [("a", 10, 20), ("b", 15, 30), ("c", 40, 50), ("d", 95, 120)]
    busy = tr.merged(ops, 0, 100)
    assert busy == [(10, 30), (40, 50), (95, 100)]
    assert tr.gaps(busy, 0, 100) == [(0, 10), (30, 40), (50, 95)]
    spans = [("bench.execute", 0, 60), ("bench.switch_wait", 28, 45)]
    assert tr.innermost(spans, [5, 35, 70]) == ["bench.execute",
                                                "bench.switch_wait", None]


def test_summary_of_hand_made_trace():
    t = tr.Trace(devices=[[("flash_attention.3", 1e9, 1.5e9),
                           ("fusion.1", 1.4e9, 2e9), ("fusion.1", 3e9, 4e9)]],
                 spans=[(tr.WINDOW_SPAN, 0, 5e9),
                        ("bench.execute", 0.5e9, 2.5e9),
                        ("bench.switch_wait", 2e9, 3e9)])
    s = tr.summarize(t)
    assert s.window_s == 5.0
    assert s.busy_s == 2.0
    assert s.ops == {"flash_attention.3": (1, 0.5), "fusion.1": (2, 1.6)}
    # each gap goes to the innermost host span at its middle
    assert dict(s.idle_gaps) == {"bench.execute": 1.0,
                                 "bench.switch_wait": 1.0, "no span": 1.0}
    assert tr.top_ops(s, 1) == [["fusion.1", 1.6]]


def test_summary_needs_one_window_and_a_device():
    with pytest.raises(RuntimeError):
        tr.summarize(tr.Trace(devices=[[]], spans=[]))
    with pytest.raises(RuntimeError):
        tr.summarize(tr.Trace(devices=[], spans=[(tr.WINDOW_SPAN, 0, 1)]))


def test_nested_ops_count_their_own_time():
    ops = [("while.3", 0, 100), ("fusion.1", 10, 30), ("fusion.2", 40, 90),
           ("flash_attention.6", 50, 60)]
    assert sorted(tr.self_times(ops, 0, 100)) == [
        ("flash_attention.6", 1e-8), ("fusion.1", 2e-8), ("fusion.2", 4e-8),
        ("while.3", 3e-8)]
    assert tr.op_name("%fusion.133 = bf16[8,128]{1,0} fusion(%a)") == \
        "fusion.133"


def test_recorded_tpu_trace():
    """Numbers read off the trace by hand (``record_trace.py`` prints its
    events): one op line on /device:TPU:0 with the kernel, the matmul's
    copy-start/copy-done and fusion; the host spans as recorded. The device
    clock runs ~1 ms ahead of the host's here, so each op lies just before
    the host span that launched it, and the idle time falls to the spans
    around them."""
    t = tr.load(str(DATA))
    assert [[op[0] for op in dev] for dev in t.devices] == [
        ["flash_attention.1", "copy-start", "copy-done", "fusion"]]
    assert [sp[0] for sp in t.spans] == [
        "bench.window", "bench.schedule", "bench.execute",
        "bench.switch_wait", "bench.execute", "bench.schedule"]
    s = tr.summarize(t)
    assert s.window_s == pytest.approx(143476078e-9 - 48836909e-9, abs=1e-12)
    # the four ops do not overlap (1 ns gaps between the matmul's three)
    assert s.busy_s == pytest.approx((32994 + 13 + 11442 + 90842) * 1e-9,
                                     abs=1e-12)
    assert s.ops["flash_attention.1"] == (1, pytest.approx(32994e-9))
    assert s.ops["fusion"] == (1, pytest.approx(90842e-9))
    assert dict(s.idle_gaps) == pytest.approx(
        {"bench.switch_wait": 0.052178453, "bench.schedule": 0.042325425})
    assert s.busy_s + sum(v for _, v in s.idle_gaps) == \
        pytest.approx(s.window_s)
