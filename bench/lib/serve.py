"""The served path, built as CoServe's real engine runs it, and the closed
loop that drives it for one window.

Set-up makes each expert's weights on the device from the seed (one jitted
call), keeps them in the ``HostStore`` host tier, profiles the model step
with CoServe's offline profiler over batch sizes 1, 2, 4 and 8 (which also
compiles every batch bucket the window can use), and builds a
``CoServeSystem`` (policy ``COSERVE``) over a ``RealEngine`` whose device
pool holds ``catalog.pool_experts`` experts, warm-placed by usage.

The catalog is ``catalog.domain_experts`` domain experts and one verifier
that depends on all of them. Every request runs on its domain expert, then on
the verifier, whose prompt is the domain prompt shifted by one with the
domain expert's served token appended.

The window is a closed loop of the traffic's clients: each client's next
request is issued when its previous one completes its chain. Every request is
stamped on the host clock when it is issued and when it completes; those
stamps, and nothing on the simulator's clock, give the latencies. At the
close the loop stops at the next completion, and the transfers still in
flight are waited for.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import time
from typing import Callable, List, Optional

import jax
import numpy as np

from bench.lib.traffic import Traffic
from repro.core import (COSERVE, CoEModel, CoServeSystem, DeviceProfile,
                        ExecutorSpec, ExpertSpec, HostStore, RealEngine,
                        Request, RoutingModule, Simulation, TierSpec,
                        microbenchmark_arch)

VERIFIER = "verify"
BATCH_SIZES = (1, 2, 4, 8)


class WindowClosed(Exception):
    """Raised from the completion hook at the first completion after the
    window's close; it ends the simulation loop."""


@dataclasses.dataclass
class Counters:
    """What the window's wrappers record, stamped on the host clock."""
    # (start, end, expert, real requests, padded batch, measured latency,
    #  the executor's max batch for the expert)
    executes: List[tuple] = dataclasses.field(default_factory=list)
    loads: List[float] = dataclasses.field(default_factory=list)
    transfers: List[float] = dataclasses.field(default_factory=list)
    compiles: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Served:
    system: CoServeSystem
    engine: RealEngine
    store: HostStore
    domains: List[str]
    expert_bytes: int
    counters: Counters
    annotate: bool


@dataclasses.dataclass
class Window:
    t_open: float
    t_close: float
    t_end: float              # the loop stopped and transfers landed
    issued: int
    completed: List[dict]     # chain-terminal requests done by t_close
    measured_load_s: float    # RealEngine.measured_load_time over the run


def _span(name: str, fn: Callable, on: bool) -> Callable:
    if not on:
        return fn

    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **k)
    return wrapped


def _delete(tree) -> None:
    for leaf in jax.tree.leaves(tree):
        leaf.delete()


def _next_expert(req: Request, expert_id: str, logits) -> Optional[str]:
    """The routing rule: domain expert, then the verifier. It keeps what the
    stage served: its last-position logits and their greedy token."""
    data = req.data
    token = int(np.argmax(logits))
    data["stages"].append(expert_id)
    data["served"][expert_id] = token
    data["logits"][expert_id] = logits
    if expert_id == VERIFIER:
        return None
    prompt = data["inputs"][expert_id]
    data["inputs"][VERIFIER] = np.concatenate(
        [prompt[1:], np.asarray([token], prompt.dtype)])
    return VERIFIER


def set_up(cfg: dict, traffic: Traffic, seed: int, program, reference, *,
           annotate: bool, log: Callable) -> Served:
    """Weights, the offline profile, and the warm-placed system."""
    catalog = cfg["catalog"]
    domains = [f"domain{i}" for i in range(catalog["domain_experts"])]
    ids = domains + [VERIFIER]
    mc = program.program_config(cfg)
    init = program.init_fn(cfg)
    mem = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(
        jax.eval_shape(init, reference.expert_key(0, 0))))
    store = HostStore()
    t0 = time.perf_counter()
    for i, eid in enumerate(ids):
        params = init(reference.expert_key(seed, i))
        store.put_host(eid, params)      # device_get: NumPy in host memory
        _delete(params)
    log(f"expert bytes: {mem} x {len(ids)} experts of {mc.name}, made and "
        f"copied to the host tier in {time.perf_counter() - t0:.1f} s")

    serve = program.serve_fn(cfg)
    seq = traffic.prompt_len
    sample = jax.device_put(store.fetch(domains[0])[0])

    def run_batch(n: int) -> float:
        x = np.zeros((n, seq), np.int32)
        jax.block_until_ready(serve(sample, x))
        t = time.perf_counter()
        jax.block_until_ready(serve(sample, x))
        return time.perf_counter() - t

    t0 = time.perf_counter()
    pool = catalog["pool_experts"]
    tier = TierSpec(name="lm", unified=True, host_cache_bytes=0,
                    device_bytes=(pool + 1) * mem)
    prof = microbenchmark_arch("lm", run_batch, mem, seq * 4, tier,
                               batch_sizes=BATCH_SIZES, repeats=2)
    _delete(sample)
    del sample
    log(f"profile: max_batch={prof.max_batch} latency = {prof.k!r} * n + "
        f"{prof.b!r} s ({time.perf_counter() - t0:.1f} s)")
    t0 = time.perf_counter()

    def make_batch(reqs):
        return np.stack([r.data["inputs"][r.expert_id] for r in reqs])

    payload = {
        "make_batch": _span("bench.make_batch", make_batch, annotate),
        "interpret": list,           # one row of last-position logits each
    }
    share = traffic.counts / traffic.counts.sum()
    experts = [ExpertSpec(id=e, arch="lm", mem_bytes=mem, payload=payload,
                          usage_prob=float(p))
               for e, p in zip(domains, share)]
    experts.append(ExpertSpec(id=VERIFIER, arch="lm", mem_bytes=mem,
                              payload=payload, depends_on=tuple(domains),
                              usage_prob=1.0))
    coe = CoEModel(experts, RoutingModule(
        first_expert_fn=lambda data: data["domain"],
        next_expert_fn=_next_expert,
        chain_prob={e: {VERIFIER: 1.0} for e in domains}))
    engine = RealEngine(coe, store, {"lm": serve})
    counters = Counters()
    _instrument(engine, counters, annotate)
    system = CoServeSystem(
        coe, [ExecutorSpec("tpu", DeviceProfile("tpu", tier, {"lm": prof}),
                           8 * seq * 4, "tpu")],
        {"tpu": pool * mem}, policy=COSERVE, tier=tier, engine=engine)
    system.assign = _span("bench.schedule", system.assign, annotate)
    log(f"warm pool: {sorted(engine.device_params)} "
        f"({time.perf_counter() - t0:.1f} s)")
    return Served(system=system, engine=engine, store=store, domains=domains,
                  expert_bytes=mem, counters=counters,
                  annotate=annotate)


def _instrument(engine: RealEngine, c: Counters, annotate: bool) -> None:
    """Count the engine's calls (and, when tracing, open a host span around
    each) without changing what they do."""
    execute, load, transfer = engine.execute, engine.load, engine._transfer

    def counted_execute(ex, expert_id, batch):
        t0 = time.perf_counter()
        out, lat = execute(ex, expert_id, batch)
        n = len(batch)
        c.executes.append((t0, time.perf_counter(), expert_id, n,
                           1 << (n - 1).bit_length(), lat,
                           ex.max_batch_for(expert_id)))
        return out, lat

    def counted_load(ex, expert_id, now=0.0):
        c.loads.append(time.perf_counter())
        return load(ex, expert_id, now)

    def counted_transfer(expert_id, timed=True):
        transfer(expert_id, timed)
        if timed:
            c.transfers.append(time.perf_counter())

    engine.execute = _span("bench.execute", counted_execute, annotate)
    engine.load = counted_load
    engine._transfer = counted_transfer
    engine.wait_load = _span("bench.switch_wait", engine.wait_load, annotate)


def run_window(s: Served, traffic: Traffic, seconds: float) -> Window:
    sim = Simulation(s.system)
    sim.kick = _span("bench.schedule", sim.kick, s.annotate)
    ids = itertools.count()
    completed: List[dict] = []
    issued = 0
    t_close = float("inf")

    def issue(client: int, now: float) -> None:
        nonlocal issued
        d, prompt = traffic.next()
        eid = s.domains[d]
        data = {"client": client, "domain": eid,
                "t_issue": time.perf_counter(), "inputs": {eid: prompt},
                "stages": [], "served": {}, "logits": {}}
        sim.submit([Request(id=next(ids), expert_id=eid, arrival_time=now,
                            data=data)])
        issued += 1

    def on_complete(_sim, req: Request, now: float) -> None:
        t = time.perf_counter()
        if t > t_close:
            raise WindowClosed
        req.data["t_done"] = t
        completed.append(req.data)
        issue(req.data["client"], now)

    sim.on_complete = on_complete
    load_s0 = s.engine.measured_load_time
    span = jax.profiler.TraceAnnotation("bench.window") if s.annotate \
        else contextlib.nullcontext()
    with span:
        t_open = time.perf_counter()
        t_close = t_open + seconds
        for client in range(traffic.clients):
            issue(client, 0.0)
        try:
            sim.run()
        except WindowClosed:
            pass
    for expert_id in list(s.engine._pending):
        s.engine.wait_load(None, expert_id)
    return Window(t_open=t_open, t_close=t_close, t_end=time.perf_counter(),
                  issued=issued, completed=completed,
                  measured_load_s=s.engine.measured_load_time - load_s0)


def release(s: Served) -> None:
    """Free the pool's device buffers and the host tier."""
    for expert_id in list(s.engine.device_params):
        s.engine.unload(None, expert_id)
    s.store.host.clear()
