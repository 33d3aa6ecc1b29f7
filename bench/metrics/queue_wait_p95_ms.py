"""queue_wait_p95_ms: the scheduler's wait, 95th percentile of
(admission - due time), admission as the engine stamps it.

Starting and stopping the profiler stalls the benchmark's loop for
seconds while requests keep falling due, so in a traced run the
requests counted are those due in the window and admitted before the
profiler started."""

from bench import pct


def read(ctx):
    cut = ctx["trace_started_at"] or ctx["t_end"]
    waits = [(r.req.t_admit - r.due) * 1e3 for r in ctx["records"]
             if r.due is not None and r.req.t_admit is not None
             and ctx["t0"] <= r.due and r.req.t_admit <= cut]
    return pct.pct(waits, 95) if waits else None
