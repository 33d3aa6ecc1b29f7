"""Compute RAM engine benchmarks: cycle counts per op + executor
replay comparison (scan controller vs compiled fast path) + multi-block
scaling (one FPGA = hundreds of Compute RAM sites executing in
parallel), plus instruction-memory footprints (paper §III-A2).

Writes the executor numbers to ``BENCH_engine.json`` so regressions in
the compiled path show up as a diff, not just a log line.

CLI: ``python benchmarks/engine_bench.py [--quick] [--json PATH]
[--min-idot-speedup X] [--max-compile-s S] [--min-blocks-scaling X]``.
``--quick`` runs a reduced program set with fewer replays (CI tier-1
budget) but still covers the full 1/16/64 blocks sweep;
``--min-idot-speedup`` exits non-zero if any ``idot`` compiled-vs-scan
speedup falls below the floor, which is how CI fails loudly on executor
regressions (ROADMAP "benchmark hygiene"); ``--max-compile-s`` exits
non-zero if the float-program compile (bf16 add through the jaxpr-level
CSE pass) exceeds the ceiling -- the compile-time regression guard;
``--min-blocks-scaling`` exits non-zero when the 64-block packed-
resident replay stops scaling over the 1-block one (the multi-block
replay wall this sweep exists to catch).
"""

import argparse
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import bench_util  # noqa: E402

from repro.core import costmodel as cm, engine, harness, programs  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402

BENCH_JSON = "BENCH_engine.json"


def _replay_pair(f1, f2, n=25):
    """Interleaved min-of-n for two functions (load-noise resistant)."""
    f1(), f2(), f1(), f2()
    b1 = b2 = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        f1()
        b1 = min(b1, time.perf_counter() - t0)
        t0 = time.perf_counter()
        f2()
        b2 = min(b2, time.perf_counter() - t0)
    return b1, b2


def bench_executors(print_fn=print, rows=512, cols=40, quick=False):
    """Replay scan vs compiled on the paper geometry; return results."""
    rng = np.random.default_rng(0)
    results = {}
    cases = [
        ("idot4", programs.idot(4, rows=rows)),
        ("idot8", programs.idot(8, rows=rows)),
        ("iadd8", programs.iadd(8, rows=rows)),
    ] if quick else [
        ("imul4", programs.imul(4, rows=rows)),
        ("imul8", programs.imul(8, rows=rows)),
        ("imul16", programs.imul(16, rows=rows)),
        ("idot4", programs.idot(4, rows=rows)),
        ("idot8", programs.idot(8, rows=rows)),
        ("idot16", programs.idot(16, rows=rows)),
        ("iadd8", programs.iadd(8, rows=rows)),
    ]
    for name, (prog, lay) in cases:
        a = rng.integers(0, 1 << lay.nbits, (lay.tuples, cols),
                         dtype=np.uint64)
        b = rng.integers(0, 1 << lay.nbits, (lay.tuples, cols),
                         dtype=np.uint64)
        state = harness.make_jax_state(
            harness.pack_state(lay, {"a": a, "b": b}, cols))

        scan_fn = jax.jit(lambda s, p=prog: engine.execute_scan(p, s))

        t0 = time.perf_counter()
        fn = engine.compile_program(prog, rows, cols)
        jax.block_until_ready(fn(state).array)
        t_compile = time.perf_counter() - t0

        t_scan, t_compiled = _replay_pair(
            lambda: jax.block_until_ready(scan_fn(state).array),
            lambda: jax.block_until_ready(fn(state).array),
            n=8 if quick else 25)

        speedup = t_scan / t_compiled
        results[name] = {
            "cycles": prog.cycles(),
            "scan_replay_ms": round(t_scan * 1e3, 4),
            "compiled_replay_ms": round(t_compiled * 1e3, 4),
            "compile_s": round(t_compile, 2),
            "speedup": round(speedup, 2),
        }
        print_fn(f"engine/executor_{name}/speedup,{speedup:.1f},"
                 f"scan_ms={t_scan*1e3:.2f};compiled_ms="
                 f"{t_compiled*1e3:.2f};compile_s={t_compile:.1f}")
    return results


def bench_blocks(print_fn=print, rows=512, cols=40, quick=False):
    """Multi-block fabric simulation (int4 dot product per block).

    The compiled replay is measured in its *packed-resident* form: the
    block batch is packed once (``engine.pack_block_states``), replayed
    as one wide uint32 launch per round, and unpacked once at the end --
    which is how replay loops (fabric rounds, :func:`engine.run_chain`)
    actually run the program.  Measuring the single-shot
    ``execute_blocks`` launch instead would time the per-launch bool
    pack/unpack ladder (recorded separately as ``launch_ms``), which is
    amortized over a replay loop and at 64 blocks costs ~3x the inner
    compute.  The vmapped scan controller is the baseline.
    ``--min-blocks-scaling`` gates blocks64/blocks1 throughput.
    """
    prog, lay = programs.idot(4, rows=rows)
    results = {}
    for blocks in (1, 16, 64):
        states = engine.CRState(
            array=jnp.zeros((blocks, rows, cols), jnp.bool_),
            carry=jnp.zeros((blocks, cols), jnp.bool_),
            tag=jnp.ones((blocks, cols), jnp.bool_),
        )
        f_scan = jax.jit(
            lambda s: engine.execute_blocks(prog, s, executor="scan"))
        wide = jax.block_until_ready(engine.pack_block_states(states))
        fn = engine.compile_packed(prog, rows, blocks * cols)
        jax.block_until_ready(fn(wide).array)               # compile
        jax.block_until_ready(
            engine.execute_blocks(prog, states).array)      # compile e2e
        t_scan, t_comp = _replay_pair(
            lambda: jax.block_until_ready(f_scan(states).array),
            lambda: jax.block_until_ready(fn(wide).array),
            n=4 if quick else 8)
        t_launch = float("inf")                  # single-shot, with ladder
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(engine.execute_blocks(prog, states).array)
            t_launch = min(t_launch, time.perf_counter() - t0)
        ops_total = lay.tuples * cols * blocks   # int4 MACs simulated
        results[f"blocks{blocks}"] = {
            "scan_replay_ms": round(t_scan * 1e3, 4),
            "compiled_replay_ms": round(t_comp * 1e3, 4),
            "launch_ms": round(t_launch * 1e3, 4),
            "speedup": round(t_scan / t_comp, 2),
            "sim_mops_compiled": round(ops_total / (t_comp * 1e6), 1),
        }
        print_fn(f"engine/multiblock_idot4/{blocks}blk,"
                 f"{t_comp*1e6:.0f},ops={ops_total};"
                 f"sim_mops={ops_total/(t_comp*1e6):.1f};"
                 f"speedup_vs_scan={t_scan/t_comp:.1f};"
                 f"launch_ms={t_launch*1e3:.2f}")
    scaling = (results["blocks64"]["sim_mops_compiled"]
               / results["blocks1"]["sim_mops_compiled"])
    results["scaling_64v1"] = round(scaling, 2)
    print_fn(f"engine/multiblock_idot4/scaling_64v1,{scaling:.2f},"
             f"resident_replay")
    return results


def bench_float_compile(print_fn=print, quick=False):
    """Compile-time regression guard for float programs.

    Times one cold ``compile_program`` of the bf16 adder (the heaviest
    flat-lowered program family, ~5-10 s each on a fast host) with the
    jaxpr-level CSE pass forced on, and records the pass's equation
    counts.  ``--max-compile-s`` gates on the seconds.
    """
    rows = 256 if quick else 512
    prog, lay = programs.bf16_add(rows=rows)
    engine.clear_compile_cache()              # force a cold compile
    state = harness.make_jax_state(np.zeros((rows, 40), bool))
    t0 = time.perf_counter()
    fn = engine.compile_program(prog, rows, 40, cse=True)
    jax.block_until_ready(fn(state).array)
    t_compile = time.perf_counter() - t0
    stats = engine.last_cse_stats or {}
    print_fn(f"engine/float_compile_bf16add/s,{t_compile:.2f},"
             f"rows={rows};cycles={prog.cycles()};"
             f"cse_removed={stats.get('removed', 0)}")
    return {
        "program": f"bf16_add@{rows}", "cycles": prog.cycles(),
        "compile_s": round(t_compile, 2),
        "cse_eqns_before": stats.get("eqns_before", 0),
        "cse_eqns_after": stats.get("eqns_after", 0),
        "cse_removed": stats.get("removed", 0),
    }


def bench_float_dot(print_fn=print, quick=False):
    """Scan-vs-compiled replay + compile time for the bf16 fused MAC.

    The float tuple loops now get a lane plan (complementary-predication
    coverage) and the copy/fill-run batcher, so the compiled path must
    beat the scan controller -- ``--min-fdot-speedup`` gates the ratio
    and ``--max-compile-s`` covers this compile alongside the bf16-add
    one.  ``lane_plan``/``serial_start`` are recorded so a silent fall
    back to flat lowering shows up in the artifact.
    """
    from repro.core import compiler, floatprog

    rows, cols = 512, 40
    tuples = 2 if quick else None
    prog, lay = floatprog.float_dot(floatprog.BF16, rows=rows,
                                    tuples=tuples)
    plan = compiler.analyze(prog)
    rng = np.random.default_rng(0)

    def bits(shape):
        s = rng.integers(0, 2, shape).astype(np.uint64)
        e = rng.integers(100, 150, shape).astype(np.uint64)
        m = rng.integers(0, 128, shape).astype(np.uint64)
        return (s << 15) | (e << 7) | m

    state = harness.make_jax_state(harness.pack_state(
        lay, {"a": bits((lay.tuples, cols)), "b": bits((lay.tuples, cols))},
        cols))
    engine.clear_compile_cache()              # force a cold compile
    t0 = time.perf_counter()
    fn = engine.compile_program(prog, rows, cols)
    jax.block_until_ready(fn(state).array)
    t_compile = time.perf_counter() - t0
    scan_fn = jax.jit(lambda s, p=prog: engine.execute_scan(p, s))
    jax.block_until_ready(scan_fn(state).array)
    t_scan, t_comp = _replay_pair(
        lambda: jax.block_until_ready(scan_fn(state).array),
        lambda: jax.block_until_ready(fn(state).array),
        n=5 if quick else 15)
    speedup = t_scan / t_comp
    print_fn(f"engine/float_dot_bf16/speedup,{speedup:.2f},"
             f"tuples={lay.tuples};scan_ms={t_scan*1e3:.2f};"
             f"compiled_ms={t_comp*1e3:.2f};compile_s={t_compile:.1f};"
             f"serial_start={plan.serial_start if plan else -1}")
    return {
        "program": f"bf16_dot@{rows}x{lay.tuples}",
        "cycles": prog.cycles(),
        "compile_s": round(t_compile, 2),
        "scan_replay_ms": round(t_scan * 1e3, 4),
        "compiled_replay_ms": round(t_comp * 1e3, 4),
        "speedup": round(speedup, 2),
        "lane_plan": plan is not None,
        "serial_start": plan.serial_start if plan else -1,
        "body_len": len(plan.body) if plan else 0,
    }


def run(print_fn=print, json_path=BENCH_JSON, quick=False):
    if not quick:
        for (op, prec), gen in programs.GENERATORS.items():
            prog, lay = gen(rows=512)
            cyc = prog.cycles()
            per_op = cyc / lay.tuples
            us = cyc / cm.FREQ_CR_MHZ
            print_fn(f"engine/{op}_{prec}/cycles,{cyc},"
                     f"per_op={per_op:.1f};imem_slots={prog.footprint()}"
                     f";time_us={us:.2f}@{cm.FREQ_CR_MHZ:.0f}MHz")

    payload = {
        "geometry": {"rows": 512, "cols": 40},
        "quick": quick,
        "executors": bench_executors(print_fn, quick=quick),
        "blocks": bench_blocks(print_fn, quick=quick),
        "float_compile": bench_float_compile(print_fn, quick=quick),
        "float_dot": bench_float_dot(print_fn, quick=quick),
    }
    if json_path:
        bench_util.atomic_write_json(json_path, payload, print_fn,
                                     tag="engine")
    return payload


def check_idot_speedup(payload: dict, floor: float) -> list:
    """Return the idot entries whose compiled-vs-scan speedup < floor."""
    return [f"{k}: {v['speedup']:.2f}x < {floor}x"
            for k, v in sorted(payload["executors"].items())
            if k.startswith("idot") and v["speedup"] < floor]


def check_compile_time(payload: dict, ceiling: float) -> list:
    """Return failure strings when a float compile exceeds the cap.

    Covers both the bf16 adder (``float_compile``) and the fused MAC
    (``float_dot``).  A payload with no measurement is a FAILURE, not a
    pass -- the gate must not silently disarm if the bench stops
    measuring."""
    bad = []
    for section in ("float_compile", "float_dot"):
        fc = payload.get(section, {})
        s = fc.get("compile_s")
        if s is None:
            bad.append(f"{section}/compile_s missing from payload "
                       "(gate has nothing to check)")
        elif s > ceiling:
            bad.append(f"{fc.get('program', section)}: "
                       f"compile {s:.1f}s > {ceiling}s")
    return bad


def check_blocks_scaling(payload: dict, floor: float) -> list:
    """Fail when 64-block packed-resident throughput doesn't scale.

    The whole point of the wide-block lowering is that B blocks cost one
    launch, so simulated MACs/s must GROW with the block count; this
    gate pins blocks64/blocks1 >= ``floor``.  A payload missing either
    endpoint is a FAILURE (the gate must not silently disarm)."""
    bl = payload.get("blocks", {})
    lo = bl.get("blocks1", {}).get("sim_mops_compiled")
    hi = bl.get("blocks64", {}).get("sim_mops_compiled")
    if not lo or hi is None:
        return ["blocks sweep missing blocks1/blocks64 sim_mops_compiled "
                "(gate has nothing to check)"]
    if hi / lo < floor:
        return [f"blocks scaling: {hi / lo:.2f}x < {floor}x "
                f"(blocks64 {hi} vs blocks1 {lo} sim_mops)"]
    return []


def check_fdot_speedup(payload: dict, floor: float) -> list:
    """Fail when the compiled fused-MAC replay drops below the floor or
    the lane plan silently fell back to flat lowering."""
    fd = payload.get("float_dot", {})
    s = fd.get("speedup")
    if s is None:
        return ["float_dot/speedup missing from payload "
                "(gate has nothing to check)"]
    bad = []
    if s < floor:
        bad.append(f"float_dot: {s:.2f}x < {floor}x")
    if not fd.get("lane_plan", False):
        bad.append("float_dot: lane analysis fell back to flat lowering")
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="reduced program set + fewer replays (CI tier-1)")
    ap.add_argument("--json", default=BENCH_JSON,
                    help=f"output path (default {BENCH_JSON})")
    ap.add_argument("--min-idot-speedup", type=float, default=None,
                    metavar="X",
                    help="fail (exit 1) if any idot compiled-vs-scan "
                    "speedup drops below X")
    ap.add_argument("--min-fdot-speedup", type=float, default=None,
                    metavar="X",
                    help="fail (exit 1) if the bf16 float_dot compiled-"
                    "vs-scan speedup drops below X (or the lane plan "
                    "falls back to flat lowering)")
    ap.add_argument("--max-compile-s", type=float, default=None,
                    metavar="S",
                    help="fail (exit 1) if a float-program compile "
                    "(bf16 add or bf16 dot) takes longer than S seconds")
    ap.add_argument("--min-blocks-scaling", type=float, default=None,
                    metavar="X",
                    help="fail (exit 1) if blocks64/blocks1 packed-"
                    "resident throughput (sim_mops_compiled) is below X")
    args = ap.parse_args(argv)
    enable_compile_cache()
    # gates run BEFORE the artifact exists: a failing gate exits 1 with
    # one line and writes nothing for CI to "validate"
    payload = run(json_path=None, quick=args.quick)
    bad = []
    if args.min_idot_speedup is not None:
        bad += check_idot_speedup(payload, args.min_idot_speedup)
    if args.min_fdot_speedup is not None:
        bad += check_fdot_speedup(payload, args.min_fdot_speedup)
    if args.max_compile_s is not None:
        bad += check_compile_time(payload, args.max_compile_s)
    if args.min_blocks_scaling is not None:
        bad += check_blocks_scaling(payload, args.min_blocks_scaling)
    if bench_util.gate_and_write(payload, bad, args.json, "engine"):
        return 1
    if args.min_idot_speedup is not None:
        print(f"idot speedups >= {args.min_idot_speedup}x: OK")
    if args.min_fdot_speedup is not None:
        print(f"float_dot speedup >= {args.min_fdot_speedup}x: OK")
    if args.max_compile_s is not None:
        print(f"float compiles <= {args.max_compile_s}s: OK")
    if args.min_blocks_scaling is not None:
        print(f"blocks scaling >= {args.min_blocks_scaling}x: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
