"""Fabric scheduler benchmarks: overlap model, batched replay, autotuner,
cross-round operand residency, and cross-PROGRAM session residency.

Five numbers the fabric work is accountable for, written to
``BENCH_fabric.json`` (ROADMAP "benchmark hygiene" -- JSON artifact +
CI floor, mirroring ``engine_bench.py``):

* **modeled overlap** -- serial vs double-buffered
  (``ScheduleCost.overlapped_cycles``) latency for representative
  schedules; overlapped must be strictly below serial whenever a
  schedule has >= 2 rounds.
* **batched replay wall-clock** -- per-round ``execute_schedule`` vs
  batching every round into one ``engine.execute_blocks`` launch
  (rounds ride the compiled wide-block path as extra block-columns).
  This is the real CPU-time speedup; ``--min-batch-speedup X`` exits
  non-zero when it regresses below the floor (the CI gate).
* **residency** -- total ``TileLoad`` fetch count with the resident-tile
  map vs the reload-every-round baseline (the PR 4 data-movement win),
  on a weight-stationary schedule with >= 8 rounds and on a fused-QKV
  program; ``--min-residency-fetch-reduction X`` exits non-zero when
  the weight-stationary reduction drops below the floor (the CI gate).
* **session** -- a weight-stationary decode loop through ONE
  ``FabricSession``: per-step fetch trajectory, cold step-1 fetches vs
  the steady state (steps 2..N reuse the resident weight tiles), with
  outputs asserted bit-identical to the sessionless replay;
  ``--min-steady-state-fetch-reduction X`` exits non-zero when the
  cold/steady fetch ratio drops below the floor (the CI gate).
* **autotuner** -- ``search_schedule`` argmin vs the default geometry,
  priced by the costmodel (no execution), plus the chosen config and
  placement; ``tuned <= default`` is always asserted (the leg can't
  silently degrade) and ``--min-autotune-gain X`` gates a real win.

CLI: ``python benchmarks/fabric_bench.py [--quick] [--json PATH]
[--min-batch-speedup X] [--min-residency-fetch-reduction X]
[--min-steady-state-fetch-reduction X] [--min-autotune-gain X]``.
"""

import argparse
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import bench_util  # noqa: E402

from repro.pim import fabric  # noqa: E402
from repro.pim.fabric import FabricConfig  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402

BENCH_JSON = "BENCH_fabric.json"


def _min_of(f, n=10):
    """Min-of-n wall clock (load-noise resistant); f() warmed up twice."""
    f(), f()
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        f()
        best = min(best, time.perf_counter() - t0)
    return best


def bench_modeled(print_fn=print, quick=False):
    """Serial vs overlapped modeled cycles (pure costmodel, no sim)."""
    cases = [
        ("int4_16blk", 8, 96, 64, 4, FabricConfig(n_blocks=16)),
        ("int8_8blk", 4, 128, 40, 8, FabricConfig(n_blocks=8)),
    ]
    if not quick:
        cases.append(
            ("int8_64blk", 16, 256, 80, 8, FabricConfig(n_blocks=64)))
    results = {}
    for name, M, K, N, nbits, cfg in cases:
        sched = fabric.schedule_gemm(M, K, N, nbits, cfg=cfg, signed=True)
        cost = fabric.schedule_cost(sched)
        speedup = cost.overlap_speedup
        results[name] = {
            "shape": f"{M}x{K}x{N}", "nbits": nbits,
            "blocks": cfg.n_blocks, "rounds": len(sched.rounds),
            "serial_cycles": round(cost.serial_cycles_, 1),
            "overlapped_cycles": round(cost.overlapped_cycles_, 1),
            "overlap_speedup": round(speedup, 3),
        }
        print_fn(f"fabric/overlap_{name}/speedup,{speedup:.2f},"
                 f"serial={cost.serial_cycles_:.0f};"
                 f"overlapped={cost.overlapped_cycles_:.0f};"
                 f"rounds={len(sched.rounds)}")
        if len(sched.rounds) >= 2:
            assert cost.overlapped_cycles_ < cost.serial_cycles_, name
    return results


def bench_replay(print_fn=print, quick=False):
    """Wall-clock: per-round execute_schedule vs batched multi-round
    replay (one compiled wide-block launch for all rounds)."""
    rng = np.random.default_rng(0)
    # all-compute grid: every operand spills, many small rounds -- the
    # per-launch dispatch overhead the batched path amortizes
    cfg = FabricConfig(n_blocks=4, rows=128, cols=8, min_compute_blocks=4)
    M, K, N, nbits = (16, 40, 16, 4) if quick else (32, 80, 16, 4)
    sched = fabric.schedule_gemm(M, K, N, nbits, cfg=cfg)
    x = rng.integers(0, 1 << nbits, (M, K), dtype=np.uint64)
    w = rng.integers(0, 1 << nbits, (K, N), dtype=np.uint64)

    out_serial = fabric.execute_schedule(sched, x, w, batch_rounds=False)
    out_batch = fabric.execute_schedule(sched, x, w, batch_rounds=True)
    np.testing.assert_array_equal(out_serial, out_batch)   # bit-identical

    n = 5 if quick else 10
    t_serial = _min_of(
        lambda: fabric.execute_schedule(sched, x, w, batch_rounds=False), n)
    t_batch = _min_of(
        lambda: fabric.execute_schedule(sched, x, w, batch_rounds=True), n)
    speedup = t_serial / t_batch
    print_fn(f"fabric/batched_replay/speedup,{speedup:.2f},"
             f"rounds={len(sched.rounds)};serial_ms={t_serial*1e3:.2f};"
             f"batched_ms={t_batch*1e3:.2f}")
    return {
        "shape": f"{M}x{K}x{N}", "nbits": nbits,
        "rounds": len(sched.rounds), "n_compute": sched.n_compute,
        "per_round_ms": round(t_serial * 1e3, 3),
        "batched_ms": round(t_batch * 1e3, 3),
        "speedup": round(speedup, 2),
    }


def bench_residency(print_fn=print, quick=False):
    """TileLoad fetch counts: resident-tile map vs reload-every-round.

    The gated case is activation-stationary at M == n_compute (every
    activation slice returns to the block that already holds it) with
    the weight tiles broadcast once -- the schedule shape the residency
    refactor is accountable for.  A fused-QKV program is reported
    alongside (shared activation residency across three GEMMs).
    """
    cfg = FabricConfig(n_blocks=8, rows=128, cols=8, min_compute_blocks=8)
    M, K, N, nbits = 8, 10, 64, 4
    sched = fabric.schedule_gemm(M, K, N, nbits, cfg=cfg, signed=True)
    st = fabric.residency_stats(sched)
    assert len(sched.rounds) >= 8, "gate needs a many-round schedule"
    print_fn(f"fabric/residency/fetch_reduction,"
             f"{st['fetch_reduction']:.2f},"
             f"fetches={st['fetches']};reload={st['reload_fetches']};"
             f"hit_rate={st['hit_rate']:.2f};rounds={len(sched.rounds)}")

    # fused QKV: three GEMMs sharing activations in ONE grid allocation
    specs = tuple(fabric.GemmSpec(n_, M, K, N // 2) for n_ in "qkv")
    fused = fabric.schedule_program(specs, nbits, cfg=cfg, signed=True)
    stf = fabric.residency_stats(fused)
    print_fn(f"fabric/residency_qkv/fetch_reduction,"
             f"{stf['fetch_reduction']:.2f},"
             f"hit_rate={stf['hit_rate']:.2f};"
             f"rounds={len(fused.rounds)};gemms={len(fused.gemms)}")
    return {
        "shape": f"{M}x{K}x{N}", "nbits": nbits, "blocks": cfg.n_blocks,
        "rounds": len(sched.rounds),
        "fetches": st["fetches"],
        "reload_fetches": st["reload_fetches"],
        "fetch_reduction": round(st["fetch_reduction"], 3),
        "hit_rate": round(st["hit_rate"], 3),
        "qkv_fetch_reduction": round(stf["fetch_reduction"], 3),
        "qkv_hit_rate": round(stf["hit_rate"], 3),
    }


def bench_session(print_fn=print, quick=False):
    """Cross-program residency: a weight-stationary decode loop through
    ONE :class:`fabric.FabricSession`.

    One (1, K) activation per step against a FIXED weight: step 1
    fetches every weight tile (cold), steps 2..N reuse the session's
    resident tiles and fetch only the step's fresh activation row -- the
    per-step trajectory collapses, and the cold/steady fetch ratio is
    the gated number.  Outputs are asserted bit-identical to the
    sessionless replay of the same operands (residency is accounting,
    never arithmetic).
    """
    rng = np.random.default_rng(0)
    cfg = FabricConfig(n_blocks=8, rows=128, cols=8, min_compute_blocks=8)
    M, K, N, nbits = 1, 10, 64, 4
    steps = 4 if quick else 8
    lo, hi = -(1 << (nbits - 1)), (1 << (nbits - 1)) - 1
    xs = [rng.integers(lo, hi + 1, (M, K)).astype(np.int64)
          for _ in range(steps)]
    w = rng.integers(lo, hi + 1, (K, N)).astype(np.int64)

    sess = fabric.FabricSession(cfg)
    for x in xs:
        sess.begin_step()
        out = fabric.fabric_matmul(x, w, nbits=nbits, cfg=cfg,
                                   signed=True, session=sess).out
        ref = fabric.fabric_matmul(x, w, nbits=nbits, cfg=cfg,
                                   signed=True).out
        np.testing.assert_array_equal(out, ref)      # bit-identical
    traj = sess.trajectory()
    red = traj.steady_fetch_reduction
    print_fn(f"fabric/session/steady_state_fetch_reduction,{red:.2f},"
             f"cold={traj.cold_fetches};steady={traj.steady_fetches:.1f};"
             f"steps={steps};per_step={list(traj.fetches)}")
    rep = traj.report()
    rep.update({
        "shape": f"{M}x{K}x{N}", "nbits": nbits, "blocks": cfg.n_blocks,
        "decode_steps": steps,
        "steady_state_fetch_reduction": round(red, 3),
        "bit_identical_vs_sessionless": True,
    })
    return rep


def bench_autotune(print_fn=print, quick=False):
    """search_schedule argmin vs the default geometry (costmodel only).

    The shape is a single-row decode GEMM with a deep K: the default
    even storage/compute split starves compute, so the split/placement
    sweep has a real, deterministic win to find -- tuned strictly below
    default (both asserted and gated in ``main``).
    """
    M, K, N, nbits = 1, 256, 64, 8
    base = FabricConfig(n_blocks=16)
    default_cost = fabric.schedule_cost(
        fabric.schedule_gemm(M, K, N, nbits, cfg=base, signed=True))
    sr = fabric.search_schedule(M, K, N, nbits, base=base, signed=True)
    tuned = sr.cost
    gain = default_cost.overlapped_cycles_ / tuned.overlapped_cycles_
    cfg = sr.schedule.cfg
    print_fn(f"fabric/autotune/gain,{gain:.2f},"
             f"pick={cfg.rows}x{cfg.cols}mc{cfg.min_compute_blocks}"
             f"-{cfg.placement};candidates={len(sr.candidates)}")
    return {
        "shape": f"{M}x{K}x{N}", "nbits": nbits, "blocks": base.n_blocks,
        "candidates": len(sr.candidates),
        "default_overlapped_cycles": round(
            default_cost.overlapped_cycles_, 1),
        "tuned_overlapped_cycles": round(tuned.overlapped_cycles_, 1),
        "tuned_geometry": f"{cfg.rows}x{cfg.cols}",
        "tuned_min_compute": cfg.min_compute_blocks,
        "tuned_placement": cfg.placement,
        "gain": round(gain, 3),
    }


def run(print_fn=print, json_path=BENCH_JSON, quick=False):
    payload = {
        "quick": quick,
        "modeled": bench_modeled(print_fn, quick=quick),
        "replay": bench_replay(print_fn, quick=quick),
        "residency": bench_residency(print_fn, quick=quick),
        "session": bench_session(print_fn, quick=quick),
        "autotune": bench_autotune(print_fn, quick=quick),
    }
    if json_path:
        bench_util.atomic_write_json(json_path, payload, print_fn,
                                     tag="fabric")
    return payload


def check_batch_speedup(payload: dict, floor: float):
    """Return failure strings when the batched replay misses the floor."""
    s = payload["replay"]["speedup"]
    return [] if s >= floor else [f"batched replay: {s:.2f}x < {floor}x"]


def check_residency_reduction(payload: dict, floor: float):
    """Return failure strings when the residency fetch win regresses."""
    r = payload["residency"]["fetch_reduction"]
    return [] if r >= floor else \
        [f"residency fetch reduction: {r:.2f}x < {floor}x"]


def check_steady_state_reduction(payload: dict, floor: float):
    """Return failure strings when the session's cold/steady-state
    per-step fetch ratio regresses below the floor."""
    r = payload["session"]["steady_state_fetch_reduction"]
    return [] if r >= floor else \
        [f"session steady-state fetch reduction: {r:.2f}x < {floor}x"]


def check_autotune(payload: dict, min_gain=None):
    """Tuned must never degrade; optionally require a real win."""
    a = payload["autotune"]
    tuned, default = (a["tuned_overlapped_cycles"],
                      a["default_overlapped_cycles"])
    bad = []
    if tuned > default:
        bad.append(f"autotune degraded: tuned {tuned} > default {default}")
    if min_gain is not None and a["gain"] < min_gain:
        bad.append(f"autotune gain: {a['gain']:.3f}x < {min_gain}x")
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="smaller schedules + fewer replays (CI tier-1)")
    ap.add_argument("--json", default=BENCH_JSON,
                    help=f"output path (default {BENCH_JSON})")
    ap.add_argument("--min-batch-speedup", type=float, default=None,
                    metavar="X",
                    help="fail (exit 1) if batched-vs-per-round replay "
                    "speedup drops below X")
    ap.add_argument("--min-residency-fetch-reduction", type=float,
                    default=None, metavar="X",
                    help="fail (exit 1) if the residency fetch-count "
                    "reduction drops below X")
    ap.add_argument("--min-steady-state-fetch-reduction", type=float,
                    default=None, metavar="X",
                    help="fail (exit 1) if the session's cold vs "
                    "steady-state per-step fetch ratio drops below X")
    ap.add_argument("--min-autotune-gain", type=float, default=None,
                    metavar="X",
                    help="fail (exit 1) if the autotuner's gain over "
                    "the default geometry drops below X")
    args = ap.parse_args(argv)
    enable_compile_cache()
    # gates run BEFORE the artifact exists (see bench_util)
    payload = run(json_path=None, quick=args.quick)
    bad = []
    if args.min_batch_speedup is not None:
        bad += check_batch_speedup(payload, args.min_batch_speedup)
    if args.min_residency_fetch_reduction is not None:
        bad += check_residency_reduction(
            payload, args.min_residency_fetch_reduction)
    if args.min_steady_state_fetch_reduction is not None:
        bad += check_steady_state_reduction(
            payload, args.min_steady_state_fetch_reduction)
    bad += check_autotune(payload, args.min_autotune_gain)
    if bench_util.gate_and_write(payload, bad, args.json, "fabric"):
        return 1
    if args.min_batch_speedup is not None:
        print(f"batched replay speedup >= {args.min_batch_speedup}x: OK")
    if args.min_residency_fetch_reduction is not None:
        print(f"residency fetch reduction >= "
              f"{args.min_residency_fetch_reduction}x: OK")
    if args.min_steady_state_fetch_reduction is not None:
        print(f"session steady-state fetch reduction >= "
              f"{args.min_steady_state_fetch_reduction}x: OK")
    if args.min_autotune_gain is not None:
        print(f"autotune gain >= {args.min_autotune_gain}x: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
