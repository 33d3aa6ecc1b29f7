"""Fault-injection sweep: escape rates, repair overhead, graceful serve.

Writes ``BENCH_faults.json`` (ROADMAP "fault tolerance" -- JSON
artifact + CI gate, mirroring the engine/fabric/serve benches).  Three
legs, all seeded and therefore deterministic:

* **GEMM sweep** -- a signed int4 fabric GEMM replayed under bit-flip
  rates ``{0, 1e-5, 1e-4}`` (plus ``1e-3`` in full mode) x scrub
  {on, off}.  Escapes are counted the only way that matters: the
  fabric output is compared element-wise against the exact host
  ``x @ w`` in int64.  The hard gate is the paper-level claim of the
  fault stack: **zero escaped corruptions at rates <= 1e-4 with the
  parity scrub on**.  The scrub-off row of the same sweep must escape
  at the top rate -- proving the sweep actually injects and the gate
  is not vacuously green.
* **Repair** -- a dead block remapped to a spare (bit-exact, >= 1
  remap charged) and a dead block on a spare-less grid absorbed by the
  degraded-grid reschedule (bit-exact on fewer blocks).
* **Serve** -- the smoke LM served end to end with a fabric probe
  carrying a live fault model at the gated rate (1e-4, scrub on):
  every request must complete with its full token budget and zero
  escaped probe outputs -- graceful degradation never drops traffic.

A failing gate writes a ``BENCH_faults_repro.json`` repro artifact
(the exact sweep + failure list) via the shared ``bench_util`` abort
path; CI uploads it so the failure is preserved even though no real
artifact is written.

CLI: ``python benchmarks/faults_bench.py [--quick] [--json PATH]
[--gate]``.
"""

import argparse
import pathlib
import sys

import numpy as np

import jax

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import bench_util  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

from repro.core.faults import FaultModel  # noqa: E402
from repro.pim import fabric  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402

BENCH_JSON = "BENCH_faults.json"
REPRO_JSON = "BENCH_faults_repro.json"

#: the gate line from docs/faults.md: scrub-on serving must be clean
#: at (and below) this rate
GATED_RATE = 1e-4


def _grid(n_blocks=8, spare_blocks=0):
    return fabric.FabricConfig(n_blocks=n_blocks, rows=256, cols=32,
                               spare_blocks=spare_blocks)


def _gemm_cell(rate, scrub, repeats, rng_ops):
    """One sweep cell: ``repeats`` seeded fabric GEMMs at one
    (rate, scrub) point; escapes counted vs the int64 host oracle."""
    cell = {"rate": rate, "scrub": scrub, "runs": repeats,
            "injected_flips": 0, "detected": 0, "repaired": 0,
            "escaped_runs": 0, "escaped_elems": 0, "energy_pj": 0.0}
    for seed in range(repeats):
        x = rng_ops.integers(-8, 8, (8, 48)).astype(np.int64)
        w = rng_ops.integers(-8, 8, (48, 8)).astype(np.int64)
        fm = FaultModel(bit_rate=rate, scrub=scrub, seed=seed)
        res = fabric.fabric_matmul(x, w, nbits=4, signed=True,
                                   cfg=_grid(), faults=fm)
        wrong = int(np.sum(np.asarray(res.out, np.int64) != x @ w))
        cell["injected_flips"] += fm.injected_flips
        cell["detected"] += fm.detected
        cell["repaired"] += fm.repaired
        cell["escaped_elems"] += wrong
        cell["escaped_runs"] += int(wrong > 0)
        cell["energy_pj"] += float(res.cost.energy_pj)
    cell["energy_pj"] = round(cell["energy_pj"], 3)
    return cell


def _repair_leg(rng_ops):
    """Dead-block repair: spare remap + spare-less degraded reschedule."""
    x = rng_ops.integers(-8, 8, (8, 48)).astype(np.int64)
    w = rng_ops.integers(-8, 8, (48, 8)).astype(np.int64)
    out = {}
    fm = FaultModel(dead_blocks=(2,), seed=0)
    res = fabric.fabric_matmul(x, w, nbits=4, signed=True,
                               cfg=_grid(8, spare_blocks=2), faults=fm)
    out["spare"] = {"dead_blocks": [2], "spare_blocks": 2,
                    "remaps": fm.remaps,
                    "exact": bool(np.array_equal(
                        np.asarray(res.out, np.int64), x @ w))}
    fm2 = FaultModel(dead_blocks=(1, 3), seed=0)
    res2 = fabric.fabric_matmul(x, w, nbits=4, signed=True,
                                cfg=_grid(8), faults=fm2)
    out["degraded"] = {"dead_blocks": [1, 3], "spare_blocks": 0,
                       "alive_blocks": 6, "remaps": fm2.remaps,
                       "exact": bool(np.array_equal(
                           np.asarray(res2.out, np.int64), x @ w))}
    return out


def _serve_leg(quick):
    """Smoke-LM serving with a faulted fabric probe at the gated rate."""
    from repro import configs
    from repro.models.model import LM
    from repro.serve.engine import Request, ServeEngine

    cfg = configs.get_config("qwen2-0.5b", smoke=True)
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    slots = 2
    n_req, max_new = (3, 3) if quick else (4, 6)
    fm = FaultModel(bit_rate=GATED_RATE, scrub=True, seed=0)
    probe = fabric.FabricLinearProbe(
        np.linspace(-1, 1, cfg.d_model * 16).reshape(cfg.d_model, 16)
        .astype(np.float32),
        cfg=_grid(4), bits=8, max_steps=n_req * max_new, faults=fm)
    eng = ServeEngine(model, params, batch_slots=slots, capacity=32,
                      fabric_probe=probe, probe_retries=2)
    rng = np.random.default_rng(0)
    for rid in range(n_req):
        eng.add(Request(
            rid=rid, prompt=rng.integers(0, cfg.vocab, 5).astype(np.int32),
            max_new=max_new))
    done = eng.run()
    rep = eng.fault_report()
    return {
        "rate": GATED_RATE,
        "requests": len(done),
        "expected_requests": n_req,
        "tokens": sum(len(r.out) for r in done),
        "expected_tokens": n_req * max_new,
        "probe_steps_observed": len(probe.costs),
        "probe_retries": rep["probe_retries"],
        "probe_fallbacks": rep["probe_fallbacks"],
        "escaped_outputs": rep["probe_escaped_outputs"],
        "injected_flips": fm.injected_flips,
        "repaired": fm.repaired,
    }


def run(print_fn=print, json_path=BENCH_JSON, quick=False):
    rates = [0.0, 1e-5, 1e-4] + ([] if quick else [1e-3])
    repeats = 2 if quick else 4
    rng_ops = np.random.default_rng(42)
    sweep = [_gemm_cell(rate, scrub, repeats, rng_ops)
             for rate in rates for scrub in (True, False)]
    for cell in sweep:
        print_fn(f"faults/gemm_sweep,rate={cell['rate']:g},"
                 f"scrub={int(cell['scrub'])};"
                 f"flips={cell['injected_flips']};"
                 f"repaired={cell['repaired']};"
                 f"escaped_runs={cell['escaped_runs']}")
    repair = _repair_leg(rng_ops)
    print_fn(f"faults/repair,spare_exact={int(repair['spare']['exact'])},"
             f"remaps={repair['spare']['remaps']};"
             f"degraded_exact={int(repair['degraded']['exact'])}")
    serve = _serve_leg(quick)
    print_fn(f"faults/serve,{serve['tokens']},tokens;"
             f"requests={serve['requests']};"
             f"retries={serve['probe_retries']};"
             f"fallbacks={serve['probe_fallbacks']};"
             f"escaped={serve['escaped_outputs']}")
    top_rate = max(rates)
    payload = {
        "quick": quick,
        "gated_rate": GATED_RATE,
        "rates": rates,
        "sweep": sweep,
        "repair": repair,
        "serve": serve,
        "escape_demo_rate": top_rate,
        "scrub_off_escaped": any(
            c["escaped_runs"] for c in sweep
            if not c["scrub"] and c["rate"] == top_rate),
    }
    if json_path:
        bench_util.atomic_write_json(json_path, payload, print_fn,
                                     tag="faults")
    return payload


def check_gates(payload: dict):
    """Failure strings for the fault-tolerance gates (docs/faults.md)."""
    bad = []
    for c in payload["sweep"]:
        if c["scrub"] and c["rate"] <= payload["gated_rate"] \
                and c["escaped_runs"]:
            bad.append(f"{c['escaped_runs']} run(s) escaped at rate "
                       f"{c['rate']:g} with scrub ON")
    if not payload["scrub_off_escaped"]:
        bad.append(f"scrub-off sweep never escaped at rate "
                   f"{payload['escape_demo_rate']:g} -- injection is "
                   f"not exercising the outputs")
    for leg in ("spare", "degraded"):
        if not payload["repair"][leg]["exact"]:
            bad.append(f"{leg} repair output is not bit-exact")
    if payload["repair"]["spare"]["remaps"] < 1:
        bad.append("spare repair charged no remaps")
    sv = payload["serve"]
    if sv["requests"] != sv["expected_requests"] \
            or sv["tokens"] != sv["expected_tokens"]:
        bad.append(f"serve dropped traffic: {sv['requests']}/"
                   f"{sv['expected_requests']} requests, {sv['tokens']}/"
                   f"{sv['expected_tokens']} tokens")
    if sv["escaped_outputs"]:
        bad.append(f"{sv['escaped_outputs']} serve probe output(s) "
                   f"escaped at the gated rate with scrub on")
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="smaller sweep (CI tier-1)")
    ap.add_argument("--json", default=BENCH_JSON,
                    help=f"output path (default {BENCH_JSON})")
    ap.add_argument("--repro-json", default=REPRO_JSON,
                    help="repro artifact written on gate failure "
                    f"(default {REPRO_JSON})")
    ap.add_argument("--gate", action="store_true",
                    help="enforce the fault gates (exit 1 on failure)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    # gates run BEFORE the artifact exists (see bench_util)
    payload = run(json_path=None, quick=args.quick)
    bad = check_gates(payload) if args.gate else []
    if bench_util.gate_and_write(payload, bad, args.json, "faults",
                                 repro_path=args.repro_json):
        return 1
    if args.gate:
        print(f"zero escapes at rate <= {payload['gated_rate']:g} with "
              f"scrub on; repair bit-exact; serve complete: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
