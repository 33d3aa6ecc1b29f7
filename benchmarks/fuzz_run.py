"""Constrained-random microcode fuzzing CLI (differential replay).

Drives :mod:`repro.core.fuzz`: generates seeded random-but-valid CR
programs and differentially replays each across the full executor x
packing matrix (unroll oracle vs scan / compiled x {packed False, True,
None} / ragged execute_blocks / two-program run_chain).  On a mismatch
the repro is delta-debug shrunk and written to the corpus directory,
and the process exits non-zero printing the exact reproduce command.

Modes::

    # bounded CI budget: N programs, fail loudly on any mismatch
    PYTHONPATH=src python benchmarks/fuzz_run.py --budget 200 --seed 0

    # unbounded soak (nightly): run until wall clock expires
    PYTHONPATH=src python benchmarks/fuzz_run.py --soak --max-minutes 20

    # replay one corpus file (regression / triage)
    PYTHONPATH=src python benchmarks/fuzz_run.py --replay tests/corpus/fuzz_X.txt

    # demonstrate the shrinking pipeline against a known-bad mutation
    PYTHONPATH=src python benchmarks/fuzz_run.py --force-bug fa-flip --budget 50

    # force the fault-escape bug: disable the parity scrub so injected
    # bit flips reach the outputs and the "faults" variant mismatches
    PYTHONPATH=src python benchmarks/fuzz_run.py --no-fault-scrub --budget 5

Seed discipline: ``--seed N --budget B`` fuzzes seeds ``N..N+B-1``; the
soak derives its base seed from the clock and prints it, so any soak
finding is reproducible from the log line alone.
"""
import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

from repro.core import fuzz  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402

DEFAULT_CORPUS = pathlib.Path(__file__).resolve().parents[1] / "tests" / "corpus"
BENCH_JSON = "BENCH_fuzz.json"


def _fail_banner(stats: dict) -> None:
    rep = stats["mismatch"]
    print("=" * 72)
    print(f"FUZZ MISMATCH at seed {rep.fp.seed} "
          f"(shrunk to {stats['shrunk_ops']} micro-ops):")
    for m in rep.mismatches:
        print(f"  {m.variant} / {m.field}: {m.detail}")
    if stats["repro_path"]:
        print(f"repro written: {stats['repro_path']}")
        print("reproduce with:")
        print(f"  PYTHONPATH=src python benchmarks/fuzz_run.py "
              f"--replay {stats['repro_path']}")
    print(f"or regenerate the unshrunk scenario:")
    print(f"  PYTHONPATH=src python benchmarks/fuzz_run.py "
          f"--seed {rep.fp.seed} --budget 1")
    print("=" * 72)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--budget", type=int, default=200, metavar="N",
                    help="number of programs to fuzz (default 200)")
    ap.add_argument("--seed", type=int, default=0,
                    help="base seed; seeds seed..seed+budget-1 are fuzzed")
    ap.add_argument("--soak", action="store_true",
                    help="unbounded mode: ignore --budget, run until "
                    "--max-minutes expires (base seed from the clock)")
    ap.add_argument("--max-minutes", type=float, default=20.0,
                    help="wall-clock cap for --soak (default 20)")
    ap.add_argument("--replay", metavar="FILE", default=None,
                    help="replay one corpus file instead of fuzzing")
    ap.add_argument("--corpus", default=str(DEFAULT_CORPUS), metavar="DIR",
                    help=f"directory for shrunken repros "
                    f"(default {DEFAULT_CORPUS})")
    ap.add_argument("--force-bug", choices=sorted(fuzz.MUTATIONS),
                    default=None, metavar="NAME",
                    help="apply a known-bad mutation to one replay leg "
                    "(tests the mismatch->shrink->corpus pipeline; "
                    f"choices: {', '.join(sorted(fuzz.MUTATIONS))})")
    ap.add_argument("--rows", type=int, default=48)
    ap.add_argument("--cols", type=int, default=8)
    ap.add_argument("--max-ops", type=int, default=320)
    ap.add_argument("--fault-rate", type=float,
                    default=fuzz.FuzzConfig().fault_rate,
                    help="per-bit flip rate of the 'faults' replay "
                    "variant (0 disables injection)")
    ap.add_argument("--no-fault-scrub", action="store_true",
                    help="disable the parity scrub in the 'faults' "
                    "variant: injected flips escape into outputs, the "
                    "mismatch is shrunk and written to the corpus")
    ap.add_argument("--no-shrink", action="store_true",
                    help="skip delta-debugging on mismatch (fast triage)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help=f"also write campaign stats JSON (e.g. "
                    f"{BENCH_JSON})")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = fuzz.FuzzConfig(rows=args.rows, cols=args.cols,
                          max_ops=args.max_ops,
                          fault_rate=args.fault_rate,
                          fault_scrub=not args.no_fault_scrub)
    mutate = fuzz.MUTATIONS[args.force_bug] if args.force_bug else None

    # -- replay mode --------------------------------------------------------
    if args.replay:
        fp, pins = fuzz.load_corpus(args.replay)
        print(f"replaying {args.replay}: {fp.describe()}")
        for k, v in pins.items():
            got = getattr(fp.program, k)()
            if got != v:
                print(f"FUZZ REPLAY: {k} drifted: recorded {v}, now {got}")
                return 1
        rep = fuzz.replay(fp, mutate=mutate)
        if rep.ok:
            print(f"replay OK: bit-identical across {len(rep.variants)} "
                  f"variants ({rep.cycles} cycles)")
            return 0
        print("FUZZ REPLAY MISMATCH:")
        for m in rep.mismatches:
            print(f"  {m.variant} / {m.field}: {m.detail}")
        return 1

    # -- budget / soak mode -------------------------------------------------
    if args.soak:
        base_seed = int(time.time()) % 1_000_000_000
        budget = 10 ** 9                      # wall clock is the bound
        max_minutes = args.max_minutes
        print(f"soak: base seed {base_seed}, max {max_minutes} min "
              f"(reproduce any finding with --seed <seed> --budget 1)")
    else:
        base_seed, budget, max_minutes = args.seed, args.budget, None

    stats = fuzz.run_budget(
        budget, seed=base_seed, cfg=cfg, mutate=mutate,
        corpus_dir=args.corpus, do_shrink=not args.no_shrink,
        max_minutes=max_minutes, log=print)

    print(f"fuzz: {stats['programs']} programs, {stats['ops']} micro-ops "
          f"replayed across {len(fuzz.VARIANTS)} variants in "
          f"{stats['seconds']:.0f}s; sequence mix {stats['seq_histogram']}")
    if args.json:
        payload = {k: v for k, v in stats.items() if k != "mismatch"}
        payload["clean"] = stats["mismatch"] is None
        payload["base_seed"] = base_seed
        tmp = pathlib.Path(args.json + ".tmp")
        tmp.write_text(json.dumps(payload, indent=2))
        tmp.replace(args.json)
    if stats["mismatch"] is not None:
        _fail_banner(stats)
        return 1
    print("fuzz: all programs bit-identical across the replay matrix")
    return 0


if __name__ == "__main__":
    sys.exit(main())
