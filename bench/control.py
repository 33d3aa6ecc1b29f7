#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's widest logit gap on
many seeds, and the control's (the reference in float8) on some of
them, all in one process so that set-up is paid once.

    python bench/control.py --workload <name> --seeds 1,2,3 \\
        --control-seeds 1,2,3 --seconds 10

Prints one JSON line per seed; with ``--out`` also writes them there.
``--fault <name>`` plants one of ``bench/faults.py``'s faults under the
timed path.  Benchmark runs never run the control or a fault.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--fault", choices=("unchanged_state", "altered_token",
                                       "half_batch"))
    p.add_argument("--out")
    args = p.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import faults, harness

    if args.fault:
        faults.plant(setattr, args.fault)

    spec = harness.load_json(ROOT / "BENCHMARK.json")
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    lines = []
    for s in (int(x) for x in args.seeds.split(",")):
        r = harness.run(ROOT, spec, args.workload, s, args.seconds, False,
                        time.perf_counter(),
                        controls=("fp8",) if s in controls else ())
        line = {"workload": args.workload, "seed": s, "fault": args.fault,
                "max_logit_gap": r["checks"]["max_logit_gap"]["value"],
                "checked_tokens": r["checks"]["checked_tokens"]["value"],
                "control": r.get("control", {}),
                "correct": r["correct"], "metrics": r["metrics"],
                "memory_peak_bytes": r["device"]["memory_peak_bytes"]}
        print(json.dumps(line), flush=True)
        lines.append(line)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "a") as f:
            for line in lines:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
