"""Host spans of the serve loop.

A span of name ``n`` does two things at once:

* it opens ``jax.profiler.TraceAnnotation("serve.<n>", **ids)``, so a
  profiler trace shows it on the host plane, on the same clock as the
  device's ``XLA Ops`` and ``XLA Modules`` lines, nested under the span
  that encloses it on the host thread;
* it adds its host seconds to ``stats["<n>_s"]`` and 1 to
  ``stats["<n>_n"]``, whether or not a profiler is running.

A plain class rather than a generator-based context manager: with the
profiler off a span costs about as much as the annotation alone.
"""

from __future__ import annotations

import time

from jax.profiler import TraceAnnotation

#: every span :class:`repro.serve.engine.ServeEngine` opens
#: (docs/serve.md, "Observability", has the tree)
NAMES = ("step", "prefill", "prefill_launch", "merge", "first_token",
         "decode", "kv_append", "probe", "decode_launch", "sync", "lanes")


def counters(names=NAMES) -> dict:
    """``<n>_s`` (seconds) and ``<n>_n`` (count) at 0 for every span."""
    out = {}
    for n in names:
        out[f"{n}_s"] = 0.0
        out[f"{n}_n"] = 0
    return out


class Span:
    """``with Span(stats, "merge"):`` -- one span of the serve loop."""

    __slots__ = ("_stats", "_name", "_ann", "_t0")

    def __init__(self, stats: dict, name: str, **ids):
        self._stats = stats
        self._name = name
        self._ann = TraceAnnotation(f"serve.{name}", **ids)

    def annotate(self, **ids):
        """Attach ids known only once the span is open."""
        self._ann.set_metadata(**ids)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        self._stats[f"{self._name}_s"] += dt
        self._stats[f"{self._name}_n"] += 1
        return False
