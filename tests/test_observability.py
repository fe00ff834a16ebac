"""Flight recorder (repro.obs): tracer semantics, trace-off neutrality,
deterministic event streams, per-request latency decomposition, Chrome
trace export round-trip, the stall-attribution report and the telemetry
sample-count markers."""
import dataclasses
import json
import os
import subprocess
import sys

import pytest

from repro.api import (BoardSection, DeploymentSpec, FleetSection, ModelSpec,
                       ObservabilitySection, ServingSection, Session,
                       SpecError, TenantSection, WorkloadSection)
from repro.obs import NULL_TRACER, Event, Tracer
from repro.obs.export import (chrome_trace, load_chrome_trace, save_events,
                              validate_chrome_trace)
from repro.obs.timeline import reconcile, request_timelines, stage_records

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# small thrash-prone board so a 250-request run produces loads, evictions
# and transfers in a couple hundred milliseconds of wall time
BOARD = BoardSection(name="OBS", n_components=40, n_active=24,
                     avg_quantity=2.0, n_detection=6, zipf_s=1.4)


def _spec(trace: str = "off", requests: int = 250, trace_path: str = "",
          **obs_kwargs) -> DeploymentSpec:
    return DeploymentSpec(
        model=ModelSpec(kind="board", board=BOARD.name, boards=(BOARD,)),
        fleet=FleetSection(gpu_per_device=2, cpu=1),
        serving=ServingSection(mode="sim"),
        workload=WorkloadSection(requests=requests),
        observability=ObservabilitySection(trace=trace,
                                           trace_path=trace_path,
                                           **obs_kwargs))


def _run(spec: DeploymentSpec):
    sess = Session(spec)
    out = sess.run()
    return sess, out


# --------------------------------------------------------------------------- #
# tracer core
# --------------------------------------------------------------------------- #

def test_tracer_levels_and_guards():
    assert not NULL_TRACER.enabled and not NULL_TRACER.full
    t = Tracer(level="summary")
    assert t.enabled and not t.full
    t = Tracer(level="full")
    assert t.enabled and t.full
    with pytest.raises(ValueError):
        Tracer(level="loud")


def test_ring_buffer_bounds_and_counts_drops():
    t = Tracer(level="full", capacity=8)
    for i in range(20):
        t.emit(i * 0.1, "exec", "gpu0", f"e{i}", dur=0.05)
    assert len(t.events) == 8
    assert t.dropped == 12
    # the ring keeps the NEWEST events
    assert [e.name for e in t.events] == [f"e{i}" for i in range(12, 20)]
    assert t.snapshot()["dropped"] == 12


def test_event_dict_round_trip():
    e = Event(t=1.25, kind="load", actor="gpu0", name="cls001", dur=0.5,
              attrs={"demand": True, "via": "host", "bytes": 123})
    assert Event.from_dict(e.to_dict()) == e


# --------------------------------------------------------------------------- #
# spec surface
# --------------------------------------------------------------------------- #

def test_observability_section_validation():
    with pytest.raises(SpecError):
        ObservabilitySection(trace="loud")
    with pytest.raises(SpecError):
        ObservabilitySection(trace="full", buffer_events=0)
    with pytest.raises(SpecError):
        ObservabilitySection(trace="off", trace_path="t.json")
    ObservabilitySection(trace="summary", trace_path="t.json")   # valid


def test_save_events_requires_enabled_tracer():
    sess = Session(_spec(trace="off"))
    with pytest.raises(RuntimeError, match="observability.trace"):
        sess.save_events("nowhere.json")


# --------------------------------------------------------------------------- #
# trace-off neutrality + determinism
# --------------------------------------------------------------------------- #

def test_trace_off_metrics_byte_identical():
    """Tracing must be observer-only: a trace=full run's metrics and result
    dict match a trace=off run's exactly (wall_s is real time, excluded)."""
    sess_off, out_off = _run(_spec(trace="off"))
    sess_full, out_full = _run(_spec(trace="full"))
    assert json.dumps(out_off, sort_keys=True, default=str) == \
        json.dumps(out_full, sort_keys=True, default=str)
    def _virtual(m) -> dict:
        # wall-clock-measured overhead fields vary run to run regardless of
        # tracing; everything virtual-clock-derived must match exactly
        d = dataclasses.asdict(m)
        for k in ("wall_s", "sched_time", "mgmt_time"):
            d.pop(k)
        for stats in d["per_executor"].values():
            stats.pop("mgmt_time", None)
        return d

    assert _virtual(sess_off.metrics()) == _virtual(sess_full.metrics())
    assert len(sess_off.system.tracer.events) == 0


def test_event_stream_deterministic_under_fixed_seed():
    streams = []
    for _ in range(2):
        sess, _ = _run(_spec(trace="full"))
        streams.append(sess.system.tracer.to_dicts())
    assert streams[0] == streams[1]
    kinds = {e["kind"] for e in streams[0]}
    assert {"load", "exec", "assign", "sched", "xfer"} <= kinds


def test_tracing_overhead_bounded():
    """Recording must stay cheap: a fully-traced run's wall time within a
    generous constant factor of the untraced run's (CI-noise tolerant)."""
    sess_off, _ = _run(_spec(trace="off"))
    sess_full, _ = _run(_spec(trace="full"))
    off, full = sess_off.metrics().wall_s, sess_full.metrics().wall_s
    assert full < off * 3 + 0.5, f"tracing overhead: {off:.4f}s -> {full:.4f}s"


# --------------------------------------------------------------------------- #
# per-request decomposition
# --------------------------------------------------------------------------- #

def test_decomposition_sums_to_e2e():
    sess, _ = _run(_spec(trace="full"))
    events = list(sess.system.tracer.events)
    timelines = request_timelines(events)
    assert timelines
    for root, tl in timelines.items():
        parts = (tl["queue_wait"] + tl["switch_load_wait"]
                 + tl["peer_copy_wait"] + tl["exec"])
        assert abs(parts - tl["e2e"]) < 1e-6, f"root {root}"
        for s in tl["stages"]:
            stage_parts = (s["queue_wait"] + s["switch_load_wait"]
                           + s["peer_copy_wait"] + s["exec"])
            assert abs(stage_parts - (s["end"] - s["arrival"])) < 1e-9
            assert s["queue_wait"] >= -1e-9


def test_decomposition_reconciles_with_metrics():
    sess, _ = _run(_spec(trace="full"))
    m = sess.metrics()
    rec = reconcile(sess.system.tracer.events, m)
    assert rec["completed_events"] == m.completed
    assert abs(rec["avg_latency_delta"]) < 1e-6
    assert abs(rec["stall_events_s"] - rec["stall_metrics_s"]) < 1e-6


def test_stage_records_survive_assign_falloff():
    """Exec events whose assign fell off the ring buffer are skipped, not
    crashed on (truncated traces are still viewable)."""
    ev = [Event(t=1.0, kind="exec", actor="gpu0", name="cls000", dur=0.1,
                attrs={"requests": [7], "n": 1})]
    assert stage_records(ev) == []


# --------------------------------------------------------------------------- #
# Chrome trace export
# --------------------------------------------------------------------------- #

def test_chrome_trace_round_trip(tmp_path):
    sess, _ = _run(_spec(trace="full"))
    path = tmp_path / "trace.json"
    doc = sess.save_events(str(path))
    loaded = load_chrome_trace(str(path))
    assert loaded == doc
    evs = loaded["traceEvents"]
    # executor and channel tracks are announced via metadata events
    threads = {(e["pid"], e["args"]["name"]) for e in evs
               if e["ph"] == "M" and e["name"] == "thread_name"}
    exec_tracks = {n for pid, n in threads if pid == 1}
    chan_tracks = {n for pid, n in threads if pid == 2}
    assert any(n.startswith("gpu") for n in exec_tracks)
    assert chan_tracks, "no transfer-channel tracks"
    cats = {e.get("cat") for e in evs if e["ph"] != "M"}
    assert {"exec", "xfer"} <= cats
    # otherData carries the reconciliation inputs
    other = loaded["otherData"]
    assert other["tracer"]["level"] == "full"
    assert other["metrics"]["completed"] == sess.metrics().completed


def test_chrome_trace_demand_stalls_only_on_executor_tracks():
    t = Tracer(level="full")
    t.emit(0.0, "load", "gpu0", "cls000", dur=0.1, demand=True, via="host")
    t.emit(0.2, "load", "gpu0", "cls001", dur=0.1, demand=False, via="host")
    doc = chrome_trace(t.events)
    loads = [e for e in doc["traceEvents"] if e.get("cat") == "load"]
    assert [e["name"] for e in loads] == ["stall:cls000"]


def test_validate_chrome_trace_rejects_malformed():
    with pytest.raises(ValueError):
        validate_chrome_trace({"events": []})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"ph": "X", "name": "x",
                                                "pid": 1, "tid": 1,
                                                "ts": 0.0, "dur": -1}]})
    validate_chrome_trace({"traceEvents": []})   # empty is fine


def test_run_auto_exports_via_trace_path(tmp_path):
    path = tmp_path / "auto.json"
    _run(_spec(trace="full", trace_path=str(path)))
    doc = load_chrome_trace(str(path))
    assert doc["otherData"]["metrics"]["completed"] == 250


def test_truncated_ring_buffer_still_exports(tmp_path):
    sess, _ = _run(_spec(trace="full", buffer_events=64))
    tracer = sess.system.tracer
    assert tracer.dropped > 0 and len(tracer.events) == 64
    path = tmp_path / "truncated.json"
    save_events(tracer, str(path), metrics=sess.metrics())
    assert load_chrome_trace(str(path))["otherData"]["tracer"]["dropped"] \
        == tracer.dropped


# --------------------------------------------------------------------------- #
# online control-plane events (shed / scale / admit)
# --------------------------------------------------------------------------- #

def test_online_gateway_emits_control_events():
    spec = DeploymentSpec(
        model=ModelSpec(kind="tenants"),
        fleet=FleetSection(gpu_per_device=2, cpu=1),
        serving=ServingSection(mode="online", admission="queue_depth",
                               max_queue=20, autoscale="2,4"),
        workload=WorkloadSection(requests=400, tenants=(
            TenantSection(name="hot", board="A", rate=60.0,
                          slo_seconds=2.0),)),
        observability=ObservabilitySection(trace="full"))
    sess, _ = _run(spec)
    kinds = sess.system.tracer.by_kind()
    assert kinds.get("admit", 0) > 0
    assert kinds.get("shed", 0) > 0, "overloaded queue never shed"
    sheds = [e for e in sess.system.tracer.events if e.kind == "shed"]
    assert all(e.actor == "gateway" for e in sheds)


# --------------------------------------------------------------------------- #
# trace_report CLI
# --------------------------------------------------------------------------- #

def test_trace_report_strict_reconciles(tmp_path):
    path = tmp_path / "report_in.json"
    _run(_spec(trace="full", trace_path=str(path)))
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "trace_report.py"),
         str(path), "--strict", "--top", "3"],
        capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": os.path.join(ROOT, "src")})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "stall reconciliation" in proc.stdout
    assert "top experts by demand-stall time" in proc.stdout


# --------------------------------------------------------------------------- #
# telemetry sample counts
# --------------------------------------------------------------------------- #

def test_latency_tracker_marks_low_confidence_tails():
    from repro.serve.telemetry import LatencyTracker
    lt = LatencyTracker()
    for i in range(20):
        lt.add(0.01 * (i + 1))
    snap = lt.snapshot()
    assert snap["count"] == 20
    # 20 samples: p50 has 10 tail samples (ok), p95/p99 have 1 / 0.2
    assert snap["low_confidence"] == ["p95", "p99"]
    for i in range(2000):
        lt.add(0.01)
    assert lt.snapshot()["low_confidence"] == []


# --------------------------------------------------------------------------- #
# wall-clock spans (the real serving path)
# --------------------------------------------------------------------------- #

from repro.obs.tracer import NULL_SPAN

EXECUTE_PHASES = ("coserve.execute.inputs", "coserve.execute.dispatch",
                  "coserve.execute.device_wait", "coserve.execute.outputs")


def _real_spec(trace: str = "full", requests: int = 40) -> DeploymentSpec:
    return DeploymentSpec(
        model=ModelSpec(kind="tiny", tiny_components=8, tiny_detection=2,
                        tiny_pool_experts=3, tiny_d_hidden=64),
        serving=ServingSection(mode="real"),
        workload=WorkloadSection(requests=requests),
        observability=ObservabilitySection(trace=trace))


def _pinned_profiles(monkeypatch):
    """Pin the offline profile (normally measured) so two builds schedule
    alike."""
    import repro.api.build as build

    measure = build.microbenchmark_arch

    def pinned(*a, **kw):
        return dataclasses.replace(measure(*a, **kw), k=1e-3, b=2e-3,
                                   max_batch=4, cpu_k=0.0, cpu_b=0.0)
    monkeypatch.setattr(build, "microbenchmark_arch", pinned)


def _fixed_latency(sess):
    """Report each batch's latency as a function of its size, so the
    sim-time stream depends on the code alone."""
    engine = sess.system.engine
    execute = engine.execute

    def fixed(ex, expert_id, batch):
        out, _ = execute(ex, expert_id, batch)
        return out, 1e-3 * len(batch) + 2e-3
    engine.execute = fixed


@pytest.fixture(scope="module")
def real_traced():
    """One tiny real-engine run with wall spans on."""
    sess, out = _run(_real_spec())
    return sess, out, list(sess.system.tracer.wall_records)


def test_wall_spans_off_costs_a_null_context():
    assert NULL_TRACER.wall is False
    assert NULL_TRACER.span("coserve.execute", expert="e") is NULL_SPAN
    assert Tracer("full").span("x") is NULL_SPAN
    # only an enabled tracer records wall spans
    assert Tracer("off", wall=True).wall is False
    with NULL_SPAN as sp:
        sp.set(bytes=1)
    assert len(NULL_TRACER.wall_records) == 0


def test_trace_off_real_run_keeps_null_tracer_and_no_wall_record():
    sess, out = _run(_real_spec(trace="off", requests=12))
    assert sess.system.tracer is NULL_TRACER
    assert sess.system.engine.tracer is NULL_TRACER
    assert len(NULL_TRACER.wall_records) == 0
    assert sess.metrics().wall == {} and "spans" not in out


def test_sim_runs_never_turn_wall_spans_on():
    sess, _ = _run(_spec(trace="full", requests=40))
    assert sess.system.tracer.wall is False
    assert len(sess.system.tracer.wall_records) == 0
    assert sess.metrics().wall == {}


def test_wall_ring_is_bounded_and_counts_drops():
    tr = Tracer("summary", capacity=2, wall=True)
    for i in range(3):
        with tr.span("coserve.test", i=i):
            pass
    assert [r.attrs["i"] for r in tr.wall_records] == [1, 2]
    assert tr.wall_dropped == 1


def test_wall_ring_keeps_every_record_of_concurrent_threads():
    """The transfer threads record beside the serving thread: no record or
    drop may be lost, and ids stay unique."""
    import threading
    tr = Tracer("summary", capacity=500, wall=True)
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(200):
                with tr.span("coserve.outer"):
                    with tr.span("coserve.outer.inner"):
                        pass
        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(switch)
    assert len(tr.wall_records) + tr.wall_dropped == 8 * 200 * 2
    assert len({r.id for r in tr.wall_records}) == len(tr.wall_records)
    # each inner span's parent is the outer span of its own thread
    by_id = {r.id: r for r in tr.wall_records}
    inner = [r for r in tr.wall_records if r.name == "coserve.outer.inner"]
    assert inner and all(by_id[r.parent].thread == r.thread
                         for r in inner if r.parent in by_id)
    assert all(r.parent is None for r in tr.wall_records
               if r.name == "coserve.outer")


def test_span_records_when_its_body_raises_and_nests_by_thread():
    tr = Tracer("summary", wall=True)
    with pytest.raises(KeyError):
        with tr.span("coserve.outer") as outer:
            with tr.span("coserve.inner") as inner:
                inner.set(rows=3)
                raise KeyError("x")
    by_name = {r.name: r for r in tr.wall_records}
    assert by_name["coserve.inner"].parent == by_name["coserve.outer"].id
    assert by_name["coserve.outer"].parent is None
    assert by_name["coserve.inner"].attrs == {"rows": 3}
    t0 = tr.clock()
    tr.record("coserve.queue", t0 - 0.5, t0, request=7)
    rec = tr.wall_records[-1]
    assert rec.parent is None and rec.dur == pytest.approx(0.5)
    assert outer.id != inner.id != rec.id


def test_real_run_children_nest_inside_their_parent(real_traced):
    _, _, records = real_traced
    by_id = {r.id: r for r in records}
    children = [r for r in records if r.parent is not None]
    assert {r.name for r in children} >= set(EXECUTE_PHASES) | {
        "coserve.transfer.fetch", "coserve.transfer.device_put"}
    for r in children:
        p = by_id[r.parent]
        assert p.thread == r.thread
        assert p.t0 <= r.t0 <= r.t1 <= p.t1, (p, r)
        assert r.name.startswith(p.name + ".") or p.name in (
            "coserve.evict", "coserve.route", "coserve.schedule")
    # every model step has its four phases, summing to at most the step
    steps = [r for r in records if r.name == "coserve.execute"]
    assert steps
    for step in steps:
        kids = [r for r in children if r.parent == step.id]
        assert sorted(k.name for k in kids) == sorted(EXECUTE_PHASES)
        assert sum(k.dur for k in kids) <= step.dur
        assert step.attrs["rows"] <= step.attrs["bucket"]
        assert len(step.attrs["requests"]) == step.attrs["rows"]
    outputs = [r for r in records if r.name == "coserve.execute.outputs"]
    assert all(r.attrs["bytes"] > 0 for r in outputs)


def test_real_run_one_queue_record_per_executed_stage(real_traced):
    sess, _, records = real_traced
    executed = [rid for e in sess.system.tracer.events if e.kind == "exec"
                for rid in e.attrs["requests"]]
    queued = [r.attrs["request"] for r in records
              if r.name == "coserve.queue"]
    assert sorted(queued) == sorted(executed)
    stepped = [rid for r in records if r.name == "coserve.execute"
               for rid in r.attrs["requests"]]
    assert sorted(stepped) == sorted(executed)
    assert all(r.t1 >= r.t0 for r in records if r.name == "coserve.queue")
    # follow-up stages name the request they continue
    assert any(r.attrs["parent_request"] is not None for r in records
               if r.name == "coserve.queue")


def test_real_run_transfer_thread_spans_carry_bytes_and_tier(real_traced):
    _, _, records = real_traced
    transfers = [r for r in records if r.name == "coserve.transfer"]
    timed = [r for r in transfers if r.attrs["timed"]]
    assert timed and all(r.attrs["predicted_s"] > 0 for r in timed)
    for r in transfers:
        # switches ride a transfer thread; warm placement runs in line
        assert (r.thread != "MainThread") == r.attrs["timed"]
        assert r.attrs["bytes"] > 0 and r.attrs["tier"] in ("host", "disk")
    fetches = [r for r in records if r.name == "coserve.transfer.fetch"]
    puts = [r for r in records if r.name == "coserve.transfer.device_put"]
    assert len(fetches) == len(puts) == len(transfers)
    assert all(r.attrs["tier"] in ("host", "disk") for r in fetches)
    assert all(r.attrs["bytes"] > 0 for r in puts)
    # a switch the executor waited for, and an eviction that made room
    names = {r.name for r in records}
    assert {"coserve.switch_wait", "coserve.evict", "coserve.schedule",
            "coserve.route"} <= names


def test_real_run_reports_wall_summary(real_traced):
    sess, out, records = real_traced
    wall = sess.metrics().wall
    assert out["spans"] == wall
    assert wall["coserve.execute"]["count"] == sum(
        r.name == "coserve.execute" for r in records)
    q = wall["coserve.queue"]
    assert 0 <= q["p50_s"] <= q["p90_s"]
    # a switch's measured seconds sit beside the predicted ones
    transfers = [r for r in records if r.name == "coserve.transfer"]
    assert wall["coserve.transfer"]["predicted_s"] == pytest.approx(sum(
        r.attrs["predicted_s"] for r in transfers if r.attrs["timed"]))
    assert wall["coserve.transfer.device_put"]["bytes"] > 0
    assert wall["coserve.execute.outputs"]["bytes"] > 0
    assert q["seconds"] == pytest.approx(sum(
        r.dur for r in records if r.name == "coserve.queue"))


def test_wall_spans_leave_the_sim_time_stream_identical(monkeypatch):
    _pinned_profiles(monkeypatch)
    streams, walls = [], []
    for wall in (False, True):
        sess = Session(_real_spec(requests=30))
        if wall:
            assert sess.system.tracer.wall
        else:
            sess.system.tracer.wall = False     # the same build, spans off
        _fixed_latency(sess)
        before = len(sess.system.tracer.wall_records)   # warm placement
        sess.run()
        streams.append(sess.system.tracer.to_dicts())
        walls.append(len(sess.system.tracer.wall_records) - before)
    assert streams[0] == streams[1]
    assert {"load", "exec", "assign", "sched"} <= {e["kind"]
                                                  for e in streams[0]}
    assert walls[0] == 0 < walls[1]
