"""From a profiler trace (``*.xplane.pb``) to busy and idle time.

Planes named ``/device:TPU:<n>`` (or ``GPU``) are the chips.  On each, the ``XLA Ops`` line holds one event per operation the
chip ran, and the ``XLA Modules`` line one event per execution of a
compiled program (``jit_<name>``).  Busy time is the union of the
operation intervals inside the traced window, averaged over the chips.
The window is the host span ``bench.window`` that the benchmark opens
around the traced slice.  Each gap between busy intervals is named by
what the host was doing at its middle: the benchmark's own span
(``bench.step``, ``bench.sleep``, ...) and the innermost host event
inside it.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10
OP_NAME_CHARS = 96


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float
    chips: int
    modules: dict          # program name -> [executions, device seconds]
    ops: dict              # operation name -> device seconds
    gaps: list             # [(host activity, seconds)]: the longest
    host_s: float = 0.0    # the slice on the benchmark's clock
    # the window's steps in the slice: (t_end, live keys of each lane)
    steps: list = dataclasses.field(default_factory=list)

    def module(self, prefix: str):
        """(executions, device seconds) of the programs whose name,
        without its ``(id)`` suffix, is ``prefix``."""
        n, s = 0, 0.0
        for name, (c, t) in self.modules.items():
            if _base(name) == prefix:
                n += c
                s += t
        return n, s

    def breakdown(self) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:TOP]]}


def _op_name(name: str) -> str:
    """An operation's name and the start of its result type."""
    return name[:OP_NAME_CHARS]


def _base(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def _union(intervals):
    """Total length of the union of (start, end) intervals, and the gaps
    between them, both clipped to nothing."""
    total, gaps, cur = 0.0, [], None
    for a, b in sorted(intervals):
        if cur is None:
            cur = [a, b]
        elif a > cur[1]:
            total += cur[1] - cur[0]
            gaps.append((cur[1], a))
            cur = [a, b]
        else:
            cur[1] = max(cur[1], b)
    if cur is not None:
        total += cur[1] - cur[0]
    return total, gaps


def reduce_file(path: str) -> Reduction:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    window = None
    host = []              # (start, end, name) of host events
    devices = []
    for plane in pd.planes:
        name = plane.name
        if name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    s = e.start_ns
                    host.append((s, s + e.duration_ns, e.name))
                    if e.name == WINDOW_SPAN:
                        window = (s, s + e.duration_ns)
        elif DEVICE_PLANE.match(name):
            devices.append(plane)
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    if not devices:
        raise ValueError(f"no device plane in {path}")
    w0, w1 = window
    modules = collections.defaultdict(lambda: [0, 0.0])
    ops = collections.defaultdict(float)
    busy_total = 0.0
    all_gaps = []
    for plane in devices:
        spans = []
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                a = max(e.start_ns, w0)
                b = min(e.start_ns + e.duration_ns, w1)
                if b <= a:
                    continue
                if line.name == OPS_LINE:
                    spans.append((a, b))
                    if not e.name.startswith("%while"):   # a loop holds ops
                        ops[_op_name(e.name)] += (b - a) * 1e-9
                else:
                    m = modules[e.name]
                    m[0] += 1
                    m[1] += (b - a) * 1e-9
        busy, gaps = _union(spans)
        busy_total += busy
        if spans:
            first = min(a for a, _ in spans)
            last = max(b for _, b in spans)
            gaps = [(w0, first)] + gaps + [(last, w1)]
        else:
            gaps = [(w0, w1)]
        all_gaps += [g for g in gaps if g[1] > g[0]]
    n = len(devices)
    all_gaps.sort(key=lambda g: g[0] - g[1])
    named = [(_host_activity(host, (a + b) / 2), (b - a) * 1e-9)
             for a, b in all_gaps[:TOP]]
    return Reduction(window_s=(w1 - w0) * 1e-9, busy_s=busy_total * 1e-9 / n,
                     chips=n, modules={k: list(v) for k, v in modules.items()},
                     ops=dict(ops), gaps=named)


def _host_activity(host, t: float) -> str:
    """The benchmark's span and the innermost host event at time ``t``."""
    around = [(b - a, name) for a, b, name in host
              if a <= t <= b and name != WINDOW_SPAN]
    if not around:
        return "host idle"
    bench = [x for x in around if x[1].startswith("bench.")]
    inner = min(around)[1]
    outer = min(bench)[1] if bench else ""
    return inner if not outer or outer == inner else f"{outer}/{inner}"


def reduce_dir(trace_dir: str) -> Reduction:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one trace under {trace_dir}, "
                         f"found {files}")
    return reduce_file(files[0])
