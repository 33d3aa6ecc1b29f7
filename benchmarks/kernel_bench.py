"""Pallas kernel micro-benchmarks (wall time is CPU-interpret, so the
derived columns carry the architectural quantities: packed-weight HBM
traffic reduction and arithmetic intensity).

Writes ``BENCH_kernels.json`` (ROADMAP "benchmark hygiene" -- JSON
artifact + CI floor, mirroring ``engine_bench.py`` / ``fabric_bench.py``):
per-precision quant-matmul interpret times with the packed-vs-bf16
weight-traffic reduction, the popcount kernel's arithmetic intensity,
and the flash-attention working set.  The traffic reduction is exact
arithmetic (``16 / bits``), so ``--min-traffic-reduction X`` is a
deterministic CI gate on the packed-storage claim -- it fails loudly if
a layout change silently grows the weight bytes the serving path moves.

CLI: ``python benchmarks/kernel_bench.py [--quick] [--json PATH]
[--min-traffic-reduction X]``.
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

from repro.kernels import ops, ref  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402

BENCH_JSON = "BENCH_kernels.json"


def _time(fn, *args, iters=3):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def run(print_fn=print, json_path=BENCH_JSON, quick=False):
    rng = np.random.default_rng(0)
    m, k, n = (64, 512, 256) if quick else (128, 1024, 512)
    iters = 2 if quick else 3
    a = jnp.asarray(rng.integers(-128, 128, (m, k)), jnp.int8)
    w = jnp.asarray(rng.integers(-8, 8, (k, n)), jnp.int8)
    scale = jnp.ones((n,), jnp.float32)

    payload = {"quick": quick, "shape": f"{m}x{k}x{n}",
               "quant_matmul": {}}
    for bits in (4, 8):
        wp = ref.pack_bitplanes(w, bits, axis=0)
        us = _time(lambda: ops.quant_matmul(a, wp, scale, bits=bits,
                                            interpret=True), iters=iters)
        dense_bytes = k * n * 2                       # bf16 weights
        # measured from the ACTUAL packed array, not the closed-form
        # `bits * (k // 32) * n * 4`: a layout change that pads planes
        # or stores extra words shows up here and trips the CI gate
        packed_bytes = int(wp.size) * wp.dtype.itemsize
        reduction = dense_bytes / packed_bytes
        payload["quant_matmul"][f"w{bits}"] = {
            "interp_us": round(us),
            "hbm_weight_bytes": packed_bytes,
            "bf16_bytes": dense_bytes,
            "traffic_reduction": round(reduction, 3),
        }
        print_fn(f"kernel/quant_matmul_w{bits}/interp,{us:.0f},"
                 f"hbm_weight_bytes={packed_bytes}"
                 f";bf16_bytes={dense_bytes}"
                 f";traffic_reduction={reduction:.2f}x")

    ap = ref.pack_bitplanes(a, 8, axis=1)
    wp4 = ref.pack_bitplanes(w, 4, axis=0)
    us = _time(lambda: ops.popcount_matmul(
        ap, wp4, interpret=True, block_m=32, block_n=128,
        block_k=min(k, 256)), iters=iters)
    ai = (2.0 * m * k * n * 32) / ((m * k + k * n) * 4 / 8 * 32)
    payload["popcount"] = {"interp_us": round(us), "plane_pairs": 8 * 4,
                           "arith_intensity": round(ai)}
    print_fn(f"kernel/popcount_matmul_a8w4/interp,{us:.0f},"
             f"plane_pairs={8*4};arith_intensity~{ai:.0f}")

    # dense reference for scale
    af = a.astype(jnp.bfloat16)
    wf = w.astype(jnp.bfloat16)
    us = _time(lambda: af @ wf, iters=iters)
    payload["dense_bf16"] = {"us": round(us)}
    print_fn(f"kernel/dense_bf16_matmul,{us:.0f},reference")

    # flash attention kernel (interpret mode)
    from repro.kernels.flash_attention import flash_attention
    bh, s_, hd = (2, 128, 64) if quick else (4, 256, 64)
    q = jnp.asarray(rng.normal(0, 1, (bh, s_, hd)), jnp.float32)
    kk = jnp.asarray(rng.normal(0, 1, (bh, s_, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (bh, s_, hd)), jnp.float32)
    us = _time(lambda: flash_attention(q, kk, v, interpret=True,
                                       block_q=128, block_k=128),
               iters=iters)
    vmem = (128 * hd * 3 + 128 * 128 + 128 * (hd + 2)) * 4
    payload["flash_attention"] = {
        "interp_us": round(us), "shape": f"{bh}x{s_}x{hd}",
        "vmem_working_set_bytes": vmem,
    }
    print_fn(f"kernel/flash_attention_{s_},{us:.0f},"
             f"vmem_working_set_bytes={vmem};never_materializes_SxS")

    if json_path:
        bench_util.atomic_write_json(json_path, payload, print_fn,
                                     tag="kernel")
    return payload


def check_traffic_reduction(payload: dict, floor: float):
    """Failure strings when any packed path misses the traffic floor.

    ``floor`` is expressed for the int4 path (ideal 4x vs bf16); wider
    precisions gate at the precision-scaled equivalent (w8 ideal is 2x,
    so its floor is ``floor / 2``) -- one flag covers every packed
    layout without under-gating the headline w4 claim.
    """
    bad = []
    for name, rec in payload["quant_matmul"].items():
        bits = int(name.lstrip("w"))
        required = floor * 4 / bits
        r = rec["traffic_reduction"]
        if r < required:
            bad.append(f"quant_matmul/{name}: {r:.2f}x < {required:.2f}x")
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="smaller shapes + fewer replays (CI tier-1)")
    ap.add_argument("--json", default=BENCH_JSON,
                    help=f"output path (default {BENCH_JSON})")
    ap.add_argument("--min-traffic-reduction", type=float, default=None,
                    metavar="X",
                    help="fail (exit 1) if the packed-weight HBM traffic "
                    "reduction (vs bf16) drops below X for any precision")
    args = ap.parse_args(argv)
    enable_compile_cache()
    # gates run BEFORE the artifact exists (see bench_util)
    payload = run(json_path=None, quick=args.quick)
    bad = []
    if args.min_traffic_reduction is not None:
        bad = check_traffic_reduction(payload, args.min_traffic_reduction)
    if bench_util.gate_and_write(payload, bad, args.json, "kernel"):
        return 1
    if args.min_traffic_reduction is not None:
        print(f"packed-weight traffic reduction >= "
              f"{args.min_traffic_reduction}x: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
