"""One run of one cell: set-up, the measured window, the check, the
result line.

Everything a cell needs is found by name: its entry in
``BENCHMARK.json``, its configuration file, the module of the
configuration's architecture (``bench/arch/<arch>.py``), its traffic
mix (``bench/traffic/<mix>.json``), its limits
(``bench/limits/<cell>.json``) and one reader per per-layer metric
(``bench/metrics/<metric>.py``).
The system under test is the repo's serving stack, ``ServeEngine``
driven through ``add`` and ``step``; of the program the benchmark reads
only its counters (``ServeEngine.stats``), its request timestamps and
the device trace.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from . import arch as archs
from . import check, loadgen, pct, peaks, trace_reduce, weights

#: after the window, how long requests due in it may still wait for
#: their first token before they count as never answered
DRAIN_S = 60.0
#: longest traced slice of the window, in seconds
TRACE_S = 6.0


# ---------------------------------------------------------------------------
# The cell, by name
# ---------------------------------------------------------------------------
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(root: Path, spec: dict, workload: str) -> dict:
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    return {
        "cell": cell,
        "cj": load_json(root / conf["file"]),
        "mix": load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json"),
        "limits": load_json(root / "bench" / "limits" / f"{workload}.json"),
        "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
        "per_layer": [m for m in spec["per_layer"] if applies(m)],
        "metrics_dir": root / "bench" / "metrics",
        "arch_dir": root / "bench" / "arch",
    }


def devices(chips: int, require_chip: bool) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    if require_chip and (d.platform == "cpu" or len(devs) < chips):
        raise SystemExit(f"no accelerator for this cell: JAX found "
                         f"{len(devs)} {d.platform} device(s), the cell "
                         f"needs {chips} chip(s)")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def memory_peak() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def buckets(lo: int, hi: int, capacity: int):
    """The engine's prefill shapes a prompt in [lo, hi] can reach, each
    with a prompt length that reaches it."""
    out, b = [], 1 << max(0, (lo - 1).bit_length())
    while True:
        shape = min(b, capacity)
        out.append((shape, max(lo, min(b, hi))))
        if b >= hi or shape == capacity:
            return out
        b *= 2


class _CompileCounter:
    """Counts compiles (persistent-cache loads included) as JAX reports
    them."""

    def __init__(self):
        from jax import monitoring

        self.n = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


# ---------------------------------------------------------------------------
# The window
# ---------------------------------------------------------------------------
class Record:
    """What the benchmark itself sees of one request."""

    __slots__ = ("req", "due", "stamps")

    def __init__(self, req, due):
        self.req, self.due, self.stamps = req, due, []


class Window:
    """Drives ``ServeEngine.add``/``step`` and stamps every token on the
    host clock after the ``step()`` that produced it returned."""

    def __init__(self, engine, trace: bool):
        self.engine = engine
        self.recs: dict = {}
        self.steps: list = []      # (t_end, live keys of each lane) per step
        self.queued: list = []     # requests waiting, after each step
        self.lag: list = []
        if trace:
            import jax

            self.span = jax.profiler.TraceAnnotation
        else:
            self.span = lambda name: contextlib.nullcontext()

    def add(self, spec, due: float, now: float):
        from repro.serve.engine import Request

        req = Request(rid=spec.rid, prompt=spec.prompt, max_new=spec.max_new)
        self.recs[spec.rid] = Record(req, due)
        with self.span("bench.add"):
            self.engine.add(req)
        if now is not None:
            self.lag.append(now - due)

    def step(self):
        eng = self.engine
        with self.span("bench.step"):
            finished = eng.step()
        now = time.perf_counter()
        with self.span("bench.stamp"):
            lens = []
            for req in [r for r in eng.slots if r is not None] + finished:
                rec = self.recs[req.rid]
                new = len(req.out) - len(rec.stamps)
                if new <= 0:
                    continue
                # a lane took part in this step's decode unless its only
                # token came from its admission
                if new >= 2 or rec.stamps:
                    lens.append(len(req.prompt) + len(req.out) - 1)
                rec.stamps.extend([now] * new)
            self.steps.append((now, tuple(lens)))
            self.queued.append(len(eng.queue))
        return now

    def busy(self) -> bool:
        eng = self.engine
        return bool(eng.queue) or any(s is not None for s in eng.slots)


def open_loop(win: Window, specs, t0: float, t_end: float, on_tick):
    i = 0
    while True:
        now = time.perf_counter()
        while i < len(specs) and t0 + specs[i].due <= now:
            win.add(specs[i], t0 + specs[i].due, now)
            i += 1
        on_tick(now)
        if now >= t_end:
            return
        if win.busy():
            win.step()
        else:
            nxt = t0 + specs[i].due if i < len(specs) else t_end
            with win.span("bench.sleep"):
                time.sleep(max(0.0, min(nxt, t_end) - now))


def drain_first_tokens(win: Window, t_end: float):
    """Step on, with no new arrivals, until every request due in the
    window has its first token or ``DRAIN_S`` has passed."""
    waiting = [r for r in win.recs.values()
               if r.due <= t_end and not r.stamps
               and r.req.status != "rejected"]
    limit = time.perf_counter() + DRAIN_S
    while waiting and win.busy() and time.perf_counter() < limit:
        win.step()
        waiting = [r for r in waiting if not r.stamps
                   and r.req.status != "rejected"]


def closed_loop(win: Window, backlog, slots: int, t_end: float, on_tick):
    while True:
        now = time.perf_counter()
        on_tick(now)
        if now >= t_end:
            return
        while len(win.engine.queue) < slots:
            win.add(backlog.take(), now, None)
        win.step()


# ---------------------------------------------------------------------------
# End-to-end metrics, from the benchmark's own stamps
# ---------------------------------------------------------------------------
def end_to_end(win: Window, t0: float, t_end: float, open_: bool,
               t_process: float, drained_at: float) -> dict:
    recs = list(win.recs.values())
    gaps = []
    tokens = 0
    for r in recs:
        s = r.stamps
        tokens += sum(1 for t in s if t0 <= t <= t_end)
        gaps += [(b - a) * 1e3 for a, b in zip(s, s[1:]) if t0 <= b <= t_end]
    out = {"setup_s": t0 - t_process,
           "output_tok_s": tokens / (t_end - t0),
           "itl_p95_ms": pct.pct(gaps, 95) if gaps else None}
    if open_:
        due = [r for r in recs if r.due <= t_end]
        # a request never answered counts with the whole wait it was
        # given, a lower bound on its time to first token
        ttft = [((r.stamps[0] if r.stamps else drained_at) - r.due) * 1e3
                for r in due]
        out["ttft_p95_ms"] = pct.pct(ttft, 95) if ttft else None
    return out


# ---------------------------------------------------------------------------
# Per-layer metrics, one reader each
# ---------------------------------------------------------------------------
def reader(metrics_dir: Path, name: str):
    path = metrics_dir / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _stats_delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after
            if isinstance(after[k], (int, float))}


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------
def run(root: Path, spec: dict, workload: str, seed: int, seconds: float,
        trace: bool, t_process: float, require_chip: bool = True,
        controls=(), arrival=None, keep=None) -> dict:
    """One run of ``workload``.  For setting a cell up, not for its
    runs: ``controls`` names lower-precision modes of the reference
    (the architecture's ``logits_rows``) to read on the same sample
    beside the program; ``arrival`` replaces the mix's arrival
    parameters (a sweep for the knee); ``keep`` (a dict) receives the
    window's records."""
    import jax

    from repro.launch.cache import enable_compile_cache
    from repro.models.model import LM
    from repro.serve.engine import Request, ServeEngine

    enable_compile_cache()
    c = resolve(root, spec, workload)
    cell, cj, mix, limits = c["cell"], c["cj"], c["mix"], c["limits"]
    if arrival is not None:
        mix = dict(mix, arrival=dict(mix["arrival"], **arrival))
    dev = devices(cell["chips"], require_chip)
    pk = peaks.peaks_for(dev["kind"]) if require_chip else None
    compiles = _CompileCounter()

    # -- set-up: weights on the device, engine, every shape warmed -------
    arch = archs.load(cj, c["arch_dir"])
    model = LM(arch.model_config(cj))
    key = weights.seed_key(seed)
    weights.check_layout(jax.eval_shape(model.init, key), arch.layout(cj))
    params = jax.block_until_ready(arch.program_weights(cj, key))
    # a mix may reserve fewer, longer slots than the model's default
    slots = mix.get("batch_slots", cj["serve"]["batch_slots"])
    capacity = mix["capacity"]
    engine = ServeEngine(model, params, batch_slots=slots, capacity=capacity,
                         page_size=cj["serve"]["page_size"])
    lo, hi = loadgen.prompt_range(mix)
    for i, (_, plen) in enumerate(buckets(lo, hi, capacity)):
        engine.add(Request(rid=-1 - i, max_new=2,
                           prompt=np.zeros((plen,), np.int32)))
    engine.run()

    open_ = mix["loop"] == "open"
    win = Window(engine, trace)
    if open_:
        specs = loadgen.open_loop(mix, seconds, seed, cj["vocab_size"])
    else:
        backlog = loadgen.Backlog(mix, seed, cj["vocab_size"], slots)
        for _ in range(slots):
            win.add(backlog.take(), 0.0, None)
        win.step()                # every lane admitted, staggered budgets
    jax.block_until_ready(engine.caches)
    before = dict(engine.stats)
    compiles_before = compiles.n

    # -- the window ------------------------------------------------------
    t0 = time.perf_counter()
    t_end = t0 + seconds
    tr_start = t0 + max(0.0, (seconds - TRACE_S) / 2)
    tr_stop = min(t_end, tr_start + TRACE_S)
    tracer = _Tracer(trace, tr_start, tr_stop, win)
    if open_:
        open_loop(win, specs, t0, t_end, tracer.tick)
    else:
        closed_loop(win, backlog, slots, t_end, tracer.tick)
    tracer.finish()
    window_compiles = compiles.n - compiles_before
    stats = _stats_delta(engine.stats, before)
    drained_at = t_end
    if open_:
        drain_first_tokens(win, t_end)
        drained_at = time.perf_counter()
    e2e = end_to_end(win, t0, t_end, open_, t_process, drained_at)
    kv = engine.kv_report()
    mem_peak = memory_peak()
    red = tracer.reduce()

    # -- per-layer metrics (traced run) ----------------------------------
    # everything a reader may take; readers that later cells add find
    # their inputs here, since this file does not change with them
    ctx = {"cell": cell, "cj": cj, "arch": arch, "mix": mix, "stats": stats,
           "kv": kv, "records": list(win.recs.values()), "t0": t0,
           "t_end": t_end, "steps": win.steps, "queued": win.queued,
           "lag": win.lag, "window_compiles": window_compiles, "trace": red,
           "peaks": pk, "trace_started_at": tracer.started_at, "e2e": e2e,
           "memory_peak_bytes": mem_peak}
    metrics = {}
    wanted = c["per_layer"] if trace else c["end_to_end"]
    for m in wanted:
        v = (reader(c["metrics_dir"], m["name"])(ctx) if trace
             else e2e.get(m["name"]))
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # -- counts ----------------------------------------------------------
    recs = list(win.recs.values())
    due = [r for r in recs if not open_ or r.due <= t_end]
    rejected = sum(1 for r in due if r.req.status == "rejected")
    unanswered = sum(1 for r in due if not r.stamps
                     and r.req.status != "rejected") if open_ else 0
    done = [r.req for r in recs if r.req.status == "done"]
    lag = np.asarray(win.lag) * 1e3 if win.lag else np.zeros((1,))
    # share of the decoding lanes' reserved positions that hold a key
    in_win = [lens for t, lens in win.steps if t0 <= t <= t_end and lens]
    fill = (sum(sum(lens) for lens in in_win)
            / max(1, sum(len(lens) for lens in in_win) * capacity))
    print(f"run: workload={workload} seed={seed} seconds={seconds} "
          f"sent={len(due)} done={len(done)} rejected={rejected} "
          f"unanswered={unanswered} window_compiles={window_compiles} "
          f"generator_lag_p95_ms={pct.pct(lag, 95):.3f} "
          f"generator_lag_max_ms={float(lag.max()):.3f} "
          f"memory_peak_bytes={mem_peak} steps={stats.get('steps', 0)} "
          f"admitted={stats.get('admitted', 0)} ring_fill={fill:.4f}",
          file=sys.stderr,
          flush=True)

    # -- the check, once the program's state is freed -------------------
    sample = check.sample(done, seed)
    if keep is not None:
        keep.update(records=recs, t0=t0, t_end=t_end, queued=win.queued,
                    steps=win.steps, stats=stats)
    del engine, params, win
    gc.collect()
    t_chk = time.perf_counter()
    w = arch.reference_weights(cj, key)
    rows = int(mix["output_len"]["max"])
    g = check.gaps(arch, cj, w, sample, capacity, rows)
    control = {m: check.gaps(arch, cj, w, sample, capacity, rows, m)
               for m in controls}
    del w
    checks, correct = judge(g, rejected, unanswered, limits)
    print(f"check: requests={len(sample)} tokens={g.size} "
          f"seconds={time.perf_counter() - t_chk:.3f}", file=sys.stderr)
    for k, v in checks.items():
        print(f"check {k}: {v['value']} limit {v['limit']}", file=sys.stderr)
    sys.stderr.flush()

    device = dict(dev, memory_peak_bytes=mem_peak)
    result = {"correct": bool(correct), "attempted": len(due),
              "failed": rejected + unanswered, "metrics": metrics,
              "device": device}
    if trace and red is not None:
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        result["breakdown"] = red.breakdown()
    if control:
        # the control put in the program's place, judged by the same gate
        result["control"] = {
            m: dict(zip(("checks", "correct"),
                        judge(cg, rejected, unanswered, limits)))
            for m, cg in control.items()}
    result["checks"] = checks
    return result


def judge(g: np.ndarray, rejected: int, unanswered: int, limits: dict):
    """The numbers compared, each beside its limit, and ``correct``."""
    gap = float(g.max()) if g.size else float("inf")
    checks = {
        "max_logit_gap": {"value": gap, "limit": limits["max_logit_gap"]},
        "checked_tokens": {"value": int(g.size),
                           "limit": limits["min_checked_tokens"]},
        "rejected": {"value": rejected, "limit": 0},
        "unanswered": {"value": unanswered, "limit": 0},
    }
    correct = (gap <= limits["max_logit_gap"]
               and g.size >= limits["min_checked_tokens"]
               and rejected == 0 and unanswered == 0)
    return checks, bool(correct)


class _Tracer:
    """Starts and stops the profiler around a slice of the window."""

    def __init__(self, on: bool, start: float, stop: float, win: Window):
        self.on, self.start, self.stop, self.win = on, start, stop, win
        self.dir = None
        self.started_at = None   # the profiler stalls the loop from here
        self.state = "before"
        self.t = [None, None]
        self.steps = [None, None]

    def tick(self, now: float):
        import jax

        if not self.on:
            return
        if self.state == "before" and now >= self.start:
            self.started_at = now
            self.dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # host spans, not every call
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation("bench.window")
            self._ann.__enter__()
            self.t[0] = time.perf_counter()
            self.steps[0] = len(self.win.steps)
            self.state = "on"
        elif self.state == "on" and now >= self.stop:
            self.finish()

    def finish(self):
        import jax

        if self.state != "on":
            return
        self._ann.__exit__(None, None, None)
        self.t[1] = time.perf_counter()
        self.steps[1] = len(self.win.steps)
        jax.profiler.stop_trace()
        self.state = "after"
        held = time.perf_counter() - self.started_at - (self.t[1] - self.t[0])
        print(f"trace: the profiler held the loop {held:.3f} s beyond the "
              f"{self.t[1] - self.t[0]:.3f} s slice", file=sys.stderr)

    def reduce(self):
        if self.dir is None or self.state != "after":
            return None
        try:
            red = trace_reduce.reduce_dir(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        red.host_s = self.t[1] - self.t[0]
        red.steps = self.win.steps[self.steps[0]:self.steps[1]]
        return red
