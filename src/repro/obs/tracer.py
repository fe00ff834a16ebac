"""Flight recorder: typed structured events in a bounded ring buffer.

Source of truth: the only event sink in the serving stack — the simulator
loop, ``RequestScheduler``, ``MemoryHierarchy``/``TransferEngine``,
executors, the admission gate and the autoscaler all emit here, so "what
happened during this run, in order" has exactly one definition.

Design constraints (pinned by tests):

  * zero-cost when disabled — every call site guards with
    ``if tracer.enabled:`` / ``if tracer.full:`` (plain attribute reads; no
    call, no allocation), and the system-wide default is ``NULL_TRACER``,
    so a ``trace: off`` run's metrics are byte-identical to an untraced
    build;
  * bounded — events land in a ``deque(maxlen=capacity)`` ring: a runaway
    stream overwrites the oldest events and counts the drops instead of
    growing without bound (a recorder must never OOM the thing it records);
  * deterministic — events carry *sim time* only, never wall clock, so two
    runs of the same seeded spec produce identical event streams.

Event vocabulary (``kind`` / who emits it / level):

  ``load``    executor begins an expert transfer (demand or overlap
              prefetch) — ``Executor.start_load``; summary
  ``evict``   executor evicts a pool resident to make room; summary
  ``xfer``    one channel leg of a transfer occupies a link (SSD / PCIe /
              peer ingress) — ``TransferEngine``; summary
  ``exec``    executor runs a batch — ``Executor.start_next_batch``; full.
              ``attrs["on"]`` is ``"host"`` when the batch executed in
              place on a CPU executor (heterogeneous co-execution),
              ``"device"`` otherwise
  ``assign``  scheduler placed a request on an executor queue
              (``CoServeSystem.assign``); full
  ``sched``   the scheduler's decision record (policy mode + choice)
              (``RequestScheduler.assign``); full
  ``admit`` / ``shed``  the admission gate's verdict on a fresh arrival
              (online gateway); full / summary
  ``scale``   autoscaler fleet action; summary
  ``decode``  one token-level decode step of an executor's continuous batch
              (``DecodeRuntime``) — ``attrs["requests"]`` is the step's
              membership, ``attrs["kv_wait"]`` the KV-reload portion of
              ``dur``; full
  ``kv``      a KV-block lifecycle transition (alloc / grow / offload /
              reload / spill / release) on a device pool — the bytes side
              of a decode event; the matching channel occupancy rides an
              ``xfer`` event with ``op`` ``kv_offload``/``kv_reload``;
              summary

``actor`` is the track the event belongs to (executor id, channel name,
"scheduler", "gateway", "autoscaler"); ``name`` is the subject (expert id,
tenant, action); ``dur`` > 0 makes it an interval, 0 an instant; free-form
``attrs`` carry the payload (bytes, link leg, request ids, ...).

Wall-clock side (``Tracer(wall=True)``, the real serving path only): the
sim-time events above are the source of truth for *what the scheduler
decided*; on the real path their durations mix measured execution with
*predicted* switch times (``RealEngine.load`` returns the profile's
prediction). ``span(name, **attrs)`` measures what really happened instead:
it opens a ``jax.profiler.TraceAnnotation`` (so the span lands in the
profiler's trace on the same clock as the device ops) and keeps one
``WallRecord`` in a second bounded ring, with ``time.perf_counter()``
bounds and the enclosing span of the same thread as its parent.
``record(name, t0, t1, **attrs)`` adds an interval that is no lexical scope
(a request's wait in a queue). Wall records are not ``Event``s: they never
enter the sim-time stream, so its determinism contract is untouched. With
``wall`` off, ``span`` returns one shared null context and ``record`` is
never reached (call sites test ``tracer.wall``). The span names are listed
in docs/observability.md.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

TRACE_LEVELS = ("off", "summary", "full")
DEFAULT_CAPACITY = 262_144        # events; ~60 MB worst case, plenty for the
#                                   bench smokes the CI traces end to end

EVENT_KINDS = ("load", "evict", "xfer", "exec", "assign", "sched",
               "admit", "shed", "scale", "decode", "kv")


@dataclasses.dataclass
class Event:
    """One recorded occurrence, in sim time (seconds)."""
    t: float                      # sim time the event begins
    kind: str                     # one of EVENT_KINDS
    actor: str                    # track: executor / channel / control loop
    name: str                     # subject: expert id, tenant, action, ...
    dur: float = 0.0              # interval length (0 = instant)
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def to_dict(self) -> dict:
        return {"t": self.t, "kind": self.kind, "actor": self.actor,
                "name": self.name, "dur": self.dur, "attrs": self.attrs}

    @classmethod
    def from_dict(cls, d: dict) -> "Event":
        return cls(t=d["t"], kind=d["kind"], actor=d["actor"],
                   name=d["name"], dur=d.get("dur", 0.0),
                   attrs=dict(d.get("attrs", {})))


class WallRecord(NamedTuple):
    """One wall-clock interval of the real serving path."""
    id: int                       # unique within its tracer
    name: str                     # ``coserve.*`` span name
    t0: float                     # time.perf_counter() seconds
    t1: float
    parent: Optional[int]         # id of the enclosing span, same thread
    thread: str                   # the recording thread's name
    attrs: Dict[str, Any]

    @property
    def dur(self) -> float:
        return self.t1 - self.t0


class _NullSpan:
    """The shared context every disabled ``span()`` call returns."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        pass


NULL_SPAN = _NullSpan()


class _WallSpan:
    """One open wall-clock span: a profiler annotation plus the record
    kept when it closes (also when the body raises)."""
    __slots__ = ("_tracer", "name", "attrs", "id", "parent", "t0",
                 "_annotation", "_stack")

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs

    def set(self, **attrs):
        """Add attributes known only inside the span (kept in memory; the
        profiler's annotation carries the ones given at ``span()``)."""
        self.attrs.update(attrs)

    def __enter__(self):
        tr = self._tracer
        self._annotation = tr._annotation(self.name, **self.attrs)
        self._annotation.__enter__()
        stack = tr._open_spans()
        self.parent = stack[-1] if stack else None
        self.id = next(tr._ids)
        stack.append(self.id)
        self._stack = stack
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._stack.pop()
        self._annotation.__exit__(*exc)
        self._tracer._keep(WallRecord(
            self.id, self.name, self.t0, t1, self.parent,
            threading.current_thread().name, self.attrs))
        return False


class Tracer:
    """The ring-buffer recorder. ``enabled``/``full``/``wall`` are plain
    booleans so disabled call sites cost one attribute read and nothing
    else (a ``span`` site: one call that returns ``NULL_SPAN``)."""

    def __init__(self, level: str = "summary",
                 capacity: int = DEFAULT_CAPACITY, wall: bool = False):
        if level not in TRACE_LEVELS:
            raise ValueError(f"trace level must be one of {TRACE_LEVELS}, "
                             f"got {level!r}")
        if capacity < 0:
            raise ValueError(f"capacity must be >= 0, got {capacity}")
        self.level = level
        self.enabled = level != "off"
        self.full = level == "full"
        self.capacity = capacity
        self.events: "collections.deque[Event]" = \
            collections.deque(maxlen=capacity)
        self.dropped = 0
        # wall-clock side: only an enabled tracer records wall spans
        self.wall = bool(wall) and self.enabled
        self.wall_records: "collections.deque[WallRecord]" = \
            collections.deque(maxlen=capacity)
        self.wall_dropped = 0
        if self.wall:
            import jax.profiler   # lazily: the core stays dependency-free
            self._annotation = jax.profiler.TraceAnnotation
            self._ids = itertools.count()
            self._local = threading.local()
            self._lock = threading.Lock()

    # ------------------------------------------------------------------ #
    def emit(self, t: float, kind: str, actor: str, name: str,
             dur: float = 0.0, **attrs):
        if len(self.events) == self.capacity:
            self.dropped += 1          # the deque evicts the oldest event
        self.events.append(Event(t, kind, actor, name, dur, attrs))

    # --- wall-clock side ------------------------------------------------ #
    @staticmethod
    def clock() -> float:
        """The wall spans' clock (``time.perf_counter``), for stamps that
        a later ``record`` closes."""
        return time.perf_counter()

    def span(self, name: str, **attrs):
        """A context manager timing its body on the wall clock (and, in a
        profiled process, on the device trace's clock). The shared
        ``NULL_SPAN`` when the wall side is off."""
        if not self.wall:
            return NULL_SPAN
        return _WallSpan(self, name, attrs)

    def record(self, name: str, t0: float, t1: float, **attrs):
        """Keep an interval that began before this call, such as a
        request's wait in a queue. In memory only: the profiler has no API
        to add an event after the fact. Callers test ``tracer.wall``."""
        self._keep(WallRecord(next(self._ids), name, t0, t1, None,
                              threading.current_thread().name, attrs))

    def _open_spans(self) -> List[int]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _keep(self, rec: WallRecord):
        with self._lock:       # the transfer threads record too
            if len(self.wall_records) == self.capacity:
                self.wall_dropped += 1
            self.wall_records.append(rec)

    # ------------------------------------------------------------------ #
    def to_dicts(self) -> List[dict]:
        return [e.to_dict() for e in self.events]

    def by_kind(self) -> Dict[str, int]:
        counts: Dict[str, int] = {}
        for e in self.events:
            counts[e.kind] = counts.get(e.kind, 0) + 1
        return counts

    def snapshot(self) -> dict:
        return {"level": self.level, "capacity": self.capacity,
                "events": len(self.events), "dropped": self.dropped,
                "by_kind": self.by_kind()}


# the system-wide default: every traced object points here unless a real
# Tracer is wired in, so call sites never need a None check
NULL_TRACER = Tracer(level="off", capacity=0)
