"""Serving-engine throughput (smoke-scale model on CPU; the derived
column carries the architectural quantity: decode step tokens/s scale).

Writes ``BENCH_serve.json`` (ROADMAP "benchmark hygiene" -- JSON
artifact + CI floor, mirroring the engine/fabric benches): tokens
served, per-token latency split by phase (prefill vs decode, and the
first -- cold -- decode step vs the warm steady state), and the
continuous-batching accounting.  Wall-clock on shared CI is noisy, so
the hard gates are *integrity* floors -- ``--min-tokens N`` fails when
the engine stops producing the expected token count (a
scheduling/slot-refill regression), and the **fabric leg** fails when
its tokens diverge from the ref leg's.

The fabric leg reruns the same request stream with the decode loop on
the simulated Compute RAM grid, two ways:

* a :class:`repro.pim.fabric.FabricLinearProbe` holding ONE
  :class:`FabricSession` across every decode step (the engine's live
  per-step activations through the fused QKV program; weights go
  resident at step 1, steps 2..N schedule warm) -- tokens must be
  bit-identical to the ref run;
* a multi-step decode loop through ``PimConfig(mode="fabric",
  fabric_session=...)`` / ``fused_linear_apply`` on the same layer-0
  projection weights, asserted bit-identical per step to the
  sessionless fabric path (residency is accounting, never arithmetic).

The **load sweep** drives hundreds of seeded Poisson arrivals through
the paged continuous-batching engine (chunked prefill, deadline-aware
admission, a couple of deliberately oversize prompts) and rolls the
per-request timestamps into serving SLOs: p50/p99 decode ms-per-token
and aggregate tokens/sec.  Its hard gates are integrity-first:

* every completed request's token chain must be **bit-identical** to a
  sequential single-slot reference run (batching, chunking, admission
  order, and preemption may never change tokens);
* the oversize prompts must be **rejected with accounting** on both
  legs (the old engine crashed);
* a **pressure** sub-leg with a deliberately undersized page pool must
  preempt at least once and still match the reference chains
  (recompute-style preemption is lossless under greedy decoding);
* ``--max-p99-ms-per-token`` / ``--min-tokens-per-s`` bound the SLO
  numbers (loose on shared CI -- wall-clock there is noisy; the chain
  identity above is the real regression tripwire).

On gate failure the sweep payload is preserved to
``BENCH_serve_repro.json`` (CI uploads it) and no artifact is written.

CLI: ``python benchmarks/serve_bench.py [--quick] [--json PATH]
[--min-tokens N] [--requests N] [--seed S]
[--max-p99-ms-per-token MS] [--min-tokens-per-s TPS]``.
"""

import argparse
import pathlib
import sys
import time

import numpy as np

import jax

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import bench_util  # noqa: E402

from repro import configs  # noqa: E402
from repro.models.model import LM  # noqa: E402
from repro.serve.engine import Request, ServeEngine  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402

BENCH_JSON = "BENCH_serve.json"


def _engine_run(model, cfg, params, slots, n_req, max_new, probe=None):
    """One full continuous-batching run; same seeded request stream."""
    eng = ServeEngine(model, params, batch_slots=slots, capacity=64,
                      fabric_probe=probe)
    rng = np.random.default_rng(0)
    for rid in range(n_req):
        eng.add(Request(
            rid=rid, prompt=rng.integers(0, cfg.vocab, 8).astype(np.int32),
            max_new=max_new))
    t0 = time.perf_counter()
    done = eng.run()
    dt = time.perf_counter() - t0
    return eng, sorted(done, key=lambda r: r.rid), dt


def _phase_split(stats: dict) -> dict:
    """prefill vs decode, from the engine's span counters."""
    return {
        "prefill_us_per_token": round(
            stats["prefill_s"] * 1e6 / max(stats["prefill_tokens"], 1)),
        "decode_us_per_token": round(
            stats["decode_s"] * 1e6 / max(stats["decode_tokens"], 1)),
        "decode_us_per_step": round(
            stats["decode_s"] * 1e6 / max(stats["decode_n"], 1)),
        "decode_steps": stats["decode_n"],
    }


def _bench_pim_decode(params, quick=False):
    """Multi-step decode loop through ``PimConfig(mode="fabric")``.

    The smoke model's layer-0 / head-0 Q/K/V projection slices, packed
    offline (``pack_linear``), applied to a fresh activation per decode
    step -- once through a shared :class:`FabricSession` (the
    weight-stationary loop) and once sessionless; outputs must match
    bit-for-bit, and the session trajectory shows the fetch collapse.
    """
    from repro.pim import fabric as fabric_mod
    from repro.pim.linear import PimConfig, fused_linear_apply, pack_linear

    attn = params["unit"]["b0"]["attn"]
    w3 = [np.asarray(attn["wq"][0][:, 0, :], np.float32),
          np.asarray(attn["wk"][0][:, 0, :], np.float32),
          np.asarray(attn["wv"][0][:, 0, :], np.float32)]
    packed = [pack_linear({"w": w}, PimConfig(weight_bits=8)) for w in w3]

    fcfg = fabric_mod.FabricConfig(n_blocks=8)
    sess = fabric_mod.FabricSession(fcfg)
    cfg_s = PimConfig(mode="fabric", weight_bits=8, act_bits=8,
                      fabric=fcfg, fabric_session=sess)
    cfg_0 = PimConfig(mode="fabric", weight_bits=8, act_bits=8, fabric=fcfg)
    steps = 3 if quick else 6
    rng = np.random.default_rng(1)
    identical = True
    for _ in range(steps):
        x = rng.normal(size=(1, w3[0].shape[0])).astype(np.float32)
        sess.begin_step()
        ys = fused_linear_apply(packed, x, cfg_s)
        y0 = fused_linear_apply(packed, x, cfg_0)
        identical &= all(
            np.array_equal(np.asarray(a, np.float32),
                           np.asarray(b, np.float32))
            for a, b in zip(ys, y0))
    traj = sess.trajectory()
    rep = traj.report()
    rep["bit_identical_vs_sessionless"] = bool(identical)
    return rep


def _bench_load_sweep(model, cfg, params, quick, n_requests, seed,
                      print_fn=print):
    """Seeded Poisson load sweep vs a sequential reference.

    One generated load set drives three engines: a single-slot
    sequential reference (defines the truth token chain per request
    id), the gated continuous-batching sweep (chunked prefill +
    deadline-aware admission), and a page-pressure sub-leg whose
    undersized pool forces preemption.  Chains must match the
    reference everywhere; latency rollups come from the sweep leg.
    """
    from repro.serve import loadgen

    capacity = 64
    lcfg = loadgen.LoadConfig(
        n_requests=n_requests, seed=seed, arrival="poisson", rate=2.0,
        prompt_len=(4, 16), max_new=(2, 8), vocab=cfg.vocab,
        deadline_frac=0.25,
        # a couple of oversize prompts per sweep: the admission-
        # rejection path runs under real traffic on every leg
        oversize_frac=2.5 / n_requests, oversize_len=capacity + 1)
    arrivals = loadgen.generate(lcfg)

    # --- sequential reference: 1 slot, whole prefill, no arrival noise
    ref_eng = ServeEngine(model, params, batch_slots=1, capacity=capacity)
    ref = loadgen.drive(
        ref_eng, [(0.0, r) for _, r in loadgen.clone_requests(arrivals)])
    truth = {r.rid: list(r.out) for r in ref["done"]}
    ref_rejected = {r.rid for r in ref_eng.rejected}

    # --- the gated sweep: paged continuous batching under open load
    slots = 4 if quick else 8
    eng = ServeEngine(model, params, batch_slots=slots, capacity=capacity,
                      prefill_chunk=16, admission="deadline")
    rec = loadgen.drive(eng, loadgen.clone_requests(arrivals))
    rep = loadgen.latency_report(rec["done"], rec["wall_s"], eng)
    chains_ok = ({r.rid: list(r.out) for r in rec["done"]} == truth)
    rejects_ok = ({r.rid for r in eng.rejected} == ref_rejected
                  and (len(ref_rejected) > 0) == (lcfg.oversize_frac > 0))

    # --- pressure sub-leg: undersized pool -> preemption, same chains
    n_press = min(40, n_requests)
    press_arr = [(at, r) for at, r in loadgen.clone_requests(arrivals)
                 if r.rid < n_press]
    peng = ServeEngine(model, params, batch_slots=4, capacity=capacity,
                       page_size=8, num_pages=6, prefill_chunk=8)
    prec = loadgen.drive(peng, press_arr)
    press_ok = all(truth.get(r.rid) == list(r.out) for r in prec["done"]) \
        and {r.rid for r in prec["done"]} == \
            {rid for rid in truth if rid < n_press}

    rep.update({
        "arrival": lcfg.arrival, "rate": lcfg.rate, "seed": seed,
        "requests": n_requests, "slots": slots,
        "prefill_chunk": 16, "admission": "deadline",
        "chains_bit_identical": bool(chains_ok),
        "rejections_match_reference": bool(rejects_ok),
        "kv": eng.kv_report(),
        "pressure": {
            "requests": n_press,
            "num_pages": 6, "page_size": 8,
            "preemptions": peng.stats["preemptions"],
            "resumes": peng.stats["resumes"],
            "chains_bit_identical": bool(press_ok),
            "kv_high_water_pages":
                peng.kv.stats["high_water_pages"],
        },
    })
    print_fn(f"serve/load_sweep,{rep['p99_ms']},p99_ms_per_token;"
             f"requests={n_requests};done={rep['requests_done']};"
             f"tokens_per_s={rep['tokens_per_s']};"
             f"p50={rep['p50_ms']};rejected={rep['rejected']};"
             f"chains_identical={chains_ok}")
    print_fn(f"serve/load_pressure,{peng.stats['preemptions']},"
             f"preemptions;resumes={peng.stats['resumes']};"
             f"chains_identical={press_ok}")
    return rep


def run(print_fn=print, json_path=BENCH_JSON, quick=False,
        n_requests=None, seed=0):
    from repro.pim import fabric as fabric_mod

    cfg = configs.get_config("qwen2-0.5b", smoke=True)
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    slots = 2 if quick else 4
    n_req, max_new = (4, 4) if quick else (8, 8)

    # --- ref leg: host decode, no fabric --------------------------------
    eng, done, dt = _engine_run(model, cfg, params, slots, n_req, max_new)
    toks = sum(len(r.out) for r in done)
    us_per_token = dt * 1e6 / max(toks, 1)
    split = _phase_split(eng.stats)
    print_fn(f"serve/continuous_batching,{us_per_token:.0f},"
             f"us_per_token;requests={len(done)};slots={slots};"
             f"tokens={toks}")
    print_fn(f"serve/phase_split,{split['decode_us_per_step']},"
             f"decode_us_per_step;"
             f"prefill={split['prefill_us_per_token']};"
             f"decode={split['decode_us_per_token']};"
             f"steps={split['decode_steps']}")

    # --- fabric leg: same stream, decode loop on the block grid ---------
    attn = params["unit"]["b0"]["attn"]
    w3 = [np.asarray(attn["wq"][0][:, 0, :], np.float32),
          np.asarray(attn["wk"][0][:, 0, :], np.float32),
          np.asarray(attn["wv"][0][:, 0, :], np.float32)]
    probe = fabric_mod.FabricLinearProbe(
        w3, cfg=fabric_mod.FabricConfig(n_blocks=8), bits=8,
        max_steps=n_req * max_new, session=True)
    feng, fdone, fdt = _engine_run(model, cfg, params, slots, n_req,
                                   max_new, probe=probe)
    ftoks = sum(len(r.out) for r in fdone)
    identical = [r.out for r in done] == [r.out for r in fdone]
    fsplit = _phase_split(feng.stats)
    straj = probe.session.trajectory()
    print_fn(f"serve/fabric_decode,{fdt * 1e6 / max(ftoks, 1):.0f},"
             f"us_per_token;steps={len(probe.costs)};"
             f"tokens_bit_identical={identical};"
             f"steady_fetch_reduction="
             f"{straj.steady_fetch_reduction:.2f}")

    pim = _bench_pim_decode(params, quick=quick)
    print_fn(f"serve/pim_fabric_decode,"
             f"{pim['steady_fetch_reduction']:.2f},"
             f"steady_fetch_reduction;steps={pim['steps']};"
             f"bit_identical={pim['bit_identical_vs_sessionless']}")

    # --- load sweep: seeded open-loop traffic through the paged engine
    if n_requests is None:
        n_requests = 120 if quick else 500
    load = _bench_load_sweep(model, cfg, params, quick, n_requests, seed,
                             print_fn=print_fn)

    payload = {
        "quick": quick,
        "model": "qwen2-0.5b-smoke",
        "slots": slots,
        "requests": len(done),
        "tokens": toks,
        "expected_tokens": n_req * max_new,
        "us_per_token": round(us_per_token),
        "wall_s": round(dt, 3),
        **split,
        "fabric": {
            "tokens": ftoks,
            "tokens_bit_identical": identical,
            "us_per_token": round(fdt * 1e6 / max(ftoks, 1)),
            "decode_steps_on_fabric": len(probe.costs),
            "decode_us_per_step": fsplit["decode_us_per_step"],
            "session": straj.report(),
            "probe": probe.report(),
        },
        "pim_decode": pim,
        "load": load,
    }
    if json_path:
        bench_util.atomic_write_json(json_path, payload, print_fn,
                                     tag="serve")
    return payload


def check_tokens(payload: dict, floor: int):
    """Failure strings when the engine under-produces tokens."""
    t = payload["tokens"]
    return [] if t >= floor else [f"tokens served: {t} < {floor}"]


def check_fabric_identity(payload: dict):
    """The fabric leg must serve the exact ref-path token stream, and
    the session-vs-sessionless PIM decode must match bit-for-bit."""
    bad = []
    if not payload["fabric"]["tokens_bit_identical"]:
        bad.append("fabric leg tokens diverge from the ref path")
    if not payload["pim_decode"]["bit_identical_vs_sessionless"]:
        bad.append("PimConfig(fabric) session outputs diverge from "
                   "the sessionless path")
    return bad


def check_load(payload: dict, max_p99_ms=None, min_tokens_per_s=None):
    """The load sweep's integrity gates (always on) plus the optional
    latency/throughput SLO bounds."""
    load = payload["load"]
    bad = []
    if not load["chains_bit_identical"]:
        bad.append("load sweep token chains diverge from the sequential "
                   "reference")
    if not load["rejections_match_reference"]:
        bad.append("oversize-prompt rejections differ between the sweep "
                   "and the reference leg")
    press = load["pressure"]
    if press["preemptions"] < 1:
        bad.append("pressure leg never preempted: the undersized pool "
                   "is not exercising the preemption path")
    if not press["chains_bit_identical"]:
        bad.append("pressure-leg chains diverge after preemption/resume")
    if max_p99_ms is not None and load["p99_ms"] > max_p99_ms:
        bad.append(f"p99 ms/token {load['p99_ms']} > {max_p99_ms}")
    if min_tokens_per_s is not None \
            and load["tokens_per_s"] < min_tokens_per_s:
        bad.append(f"tokens/s {load['tokens_per_s']} < {min_tokens_per_s}")
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="smaller batch + fewer requests (CI tier-1)")
    ap.add_argument("--json", default=BENCH_JSON,
                    help=f"output path (default {BENCH_JSON})")
    ap.add_argument("--min-tokens", type=int, default=None, metavar="N",
                    help="fail (exit 1) if fewer than N tokens are served "
                    "(continuous-batching integrity gate)")
    ap.add_argument("--requests", type=int, default=None, metavar="N",
                    help="load-sweep request count "
                    "(default: 120 quick / 500 full)")
    ap.add_argument("--seed", type=int, default=0,
                    help="load-sweep arrival/prompt seed (default 0)")
    ap.add_argument("--max-p99-ms-per-token", type=float, default=None,
                    metavar="MS", help="fail if the sweep's p99 decode "
                    "ms-per-token exceeds MS")
    ap.add_argument("--min-tokens-per-s", type=float, default=None,
                    metavar="TPS", help="fail if sweep throughput drops "
                    "below TPS generated tokens/sec")
    args = ap.parse_args(argv)
    enable_compile_cache()
    # gates run BEFORE the artifact exists (see bench_util)
    payload = run(json_path=None, quick=args.quick,
                  n_requests=args.requests, seed=args.seed)
    bad = []
    if args.min_tokens is not None:
        bad = check_tokens(payload, args.min_tokens)
    bad += check_fabric_identity(payload)
    bad += check_load(payload, args.max_p99_ms_per_token,
                      args.min_tokens_per_s)
    if bench_util.gate_and_write(payload, bad, args.json, "serve",
                                 repro_path="BENCH_serve_repro.json"):
        return 1
    if args.min_tokens is not None:
        print(f"tokens served >= {args.min_tokens}: OK")
    print("fabric leg tokens bit-identical to ref: OK")
    load = payload["load"]
    print(f"load sweep: {load['requests_done']} requests, chains "
          f"bit-identical to sequential reference, "
          f"{load['rejected']} rejected, "
          f"{load['pressure']['preemptions']} pressure preemptions: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
