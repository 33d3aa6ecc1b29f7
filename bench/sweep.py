#!/usr/bin/env python3
"""Find an open-loop cell's knee once, on the chip: the highest offered
rate at which the queue does not grow over the window and 90% of the
requests meet both latency limits (time to first token, and mean gap
between their tokens).

    python bench/sweep.py --workload <name> --rates 10,15,20 \\
        --seconds 20 --ttft-ms 1000 --itl-ms 50 [--seed 1]

Prints one JSON line per rate.  The rate found is written into the
mix's file by hand, as a number; benchmark runs never sweep.
"""

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--ttft-ms", type=float, required=True)
    p.add_argument("--itl-ms", type=float, required=True)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import numpy as np

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import harness, pct

    spec = harness.load_json(ROOT / "BENCHMARK.json")
    for rate in (float(r) for r in args.rates.split(",")):
        keep = {}
        r = harness.run(ROOT, spec, args.workload, args.seed, args.seconds,
                        False, time.perf_counter(),
                        arrival={"rate_per_s": rate}, keep=keep)
        recs = [x for x in keep["records"] if x.due <= keep["t_end"]]
        lat = []                  # (ttft, mean gap) per request, ms
        for x in recs:
            s = x.stamps
            if not s:
                lat.append((float("inf"), float("inf")))
                continue
            itl = (s[-1] - s[0]) * 1e3 / (len(s) - 1) if len(s) > 1 else 0.0
            lat.append(((s[0] - x.due) * 1e3, itl))
        met = sum(t <= args.ttft_ms and i <= args.itl_ms for t, i in lat)
        q = np.asarray(keep["queued"], np.float64)
        half = len(q) // 2
        print(json.dumps({
            "workload": args.workload, "rate_per_s": rate,
            "requests": len(recs), "attainment": met / max(1, len(recs)),
            "queue_first_half_mean": float(q[:half].mean()) if half else 0.0,
            "queue_second_half_mean": float(q[half:].mean()) if half else 0.0,
            "queue_end": int(q[-1]) if len(q) else 0,
            "ttft_ms_p50_p90": [pct.pct([t for t, _ in lat], q)
                                for q in (50, 90)],
            "mean_gap_ms_p50_p90": [pct.pct([i for _, i in lat], q)
                                    for q in (50, 90)],
            "correct": r["correct"], "metrics": r["metrics"],
            "admit_ms": keep["stats"]["prefill_s"] * 1e3
            / max(1, keep["stats"]["admitted"])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
