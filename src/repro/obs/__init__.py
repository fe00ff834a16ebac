"""repro.obs: the observability layer (flight recorder + logging).

``Tracer`` records typed events from the whole serving stack into a
bounded ring buffer (``repro.obs.tracer``), and on the real serving path
wall-clock spans (``WallRecord``) beside them; ``repro.obs.export`` writes
them as Perfetto-loadable Chrome trace JSON; ``repro.obs.timeline``
decomposes per-request end-to-end latency from them; ``repro.obs.log`` is
the CLIs' leveled logger. See docs/observability.md.

Only the tracer core is imported eagerly — it is on the hot serving path
and must stay dependency-free; export/timeline load on demand.
"""
from repro.obs.tracer import (DEFAULT_CAPACITY, EVENT_KINDS, NULL_TRACER,
                              TRACE_LEVELS, Event, Tracer, WallRecord)

__all__ = ["DEFAULT_CAPACITY", "EVENT_KINDS", "Event", "NULL_TRACER",
           "TRACE_LEVELS", "Tracer", "WallRecord"]
