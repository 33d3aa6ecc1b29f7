"""Serving engine: paged KV, scheduled continuous batching, load gen.

Public surface:

* :class:`repro.serve.engine.ServeEngine` / ``Request`` -- the engine
* :class:`repro.serve.kv.PagedKV` -- paged KV-cache accounting
* :class:`repro.serve.scheduler.Scheduler` / ``SchedulerConfig``
* :mod:`repro.serve.loadgen` -- seeded arrivals + latency rollups
* :class:`repro.serve.spans.Span` -- the engine's host spans and counters
"""

from .engine import Request, ServeEngine          # noqa: F401
from .kv import PagedKV                           # noqa: F401
from .scheduler import Scheduler, SchedulerConfig  # noqa: F401
