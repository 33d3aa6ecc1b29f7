"""Benchmark harness: one section per paper table/figure.

Prints ``name,value,derived`` CSV lines.  Sections:
  table2  -- paper Table II (block properties)
  fig4/5/6 -- paper Figures 4-6 (add / mul / dot-product comparisons)
  engine  -- instruction-sequence cycle counts + footprints
  kernel  -- Pallas kernel micro-benchmarks
  app     -- application-level MLP projection (paper §VI future work)
  serve   -- serving-engine throughput (continuous batching)
  dryrun  -- roofline terms per dry-run cell (if results/ exists)
"""

import json
import pathlib


def main() -> None:
    from repro.launch.cache import enable_compile_cache

    from . import (app_projection, engine_bench, figures, kernel_bench,
                   serve_bench, table2_blocks)
    enable_compile_cache()
    print("name,value,derived")
    table2_blocks.run()
    figures.run()
    engine_bench.run()
    kernel_bench.run()
    app_projection.run()
    serve_bench.run()

    res = pathlib.Path("results/dryrun")
    if res.exists():
        from repro.launch import analysis
        ok = skip = err = 0
        for f in sorted(res.glob("*.json")):
            d = json.loads(f.read_text())
            if d["status"] == "ok":
                ok += 1
                r = analysis.roofline(
                    max(d["hlo_flops"], d["analytic_flops"]),
                    max(d["hlo_bytes"], d["analytic_bytes"]),
                    d["collective_bytes"], d["chips"])
                print(f"dryrun/{f.stem},{r['roofline_s']*1e3:.2f},"
                      f"dominant={r['dominant']}"
                      f";compute_ms={r['t_compute_s']*1e3:.2f}"
                      f";memory_ms={r['t_memory_s']*1e3:.2f}"
                      f";collective_ms={r['t_collective_s']*1e3:.2f}")
            elif d["status"] == "skipped":
                skip += 1
            else:
                err += 1
        print(f"dryrun/summary,{ok},skipped={skip};errors={err}")


if __name__ == "__main__":
    main()
