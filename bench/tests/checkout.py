"""A throwaway checkout holding one tiny cell, for driving whole runs
on the CPU."""

import json
import shutil
from pathlib import Path

DATA = Path(__file__).resolve().parent / "data"
BENCH = DATA.parents[1]          # bench/

E2E = [{"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
        "source": "host_clock"},
       {"name": "itl_p95_ms", "unit": "ms", "better": "lower", "bound": 0.1,
        "source": "host_clock"},
       {"name": "ttft_p95_ms", "unit": "ms", "better": "lower", "bound": 0.1,
        "source": "host_clock", "workloads": ["tiny.open", "tiny-w4.open"]},
       {"name": "output_tok_s", "unit": "tokens/s", "better": "higher",
        "bound": 0.1, "source": "host_clock",
        "workloads": ["tiny.closed"]}]


def make(root: Path) -> dict:
    """Lay out BENCHMARK.json and the cell files under ``root``."""
    for sub in ("configs", "traffic", "limits"):
        (root / "bench" / sub).mkdir(parents=True, exist_ok=True)
    (root / "bench" / "metrics").symlink_to(BENCH / "metrics")
    for name in ("tiny", "tiny-w4"):
        shutil.copy(DATA / f"{name}.json", root / "bench" / "configs")
    for mix in ("open", "closed"):
        shutil.copy(DATA / f"tiny_{mix}.json",
                    root / "bench" / "traffic" / f"{mix}.json")
    cells = [("tiny", "open"), ("tiny", "closed"), ("tiny-w4", "open")]
    for conf, mix in cells:
        shutil.copy(DATA / "limits.json",
                    root / "bench" / "limits" / f"{conf}.{mix}.json")
    spec = {
        "configs": [{"name": n, "file": f"bench/configs/{n}.json"}
                    for n in ("tiny", "tiny-w4")],
        "workloads": [{"name": f"{c}.{m}", "config": c, "traffic": m,
                       "chips": 1} for c, m in cells],
        "end_to_end": E2E,
        "per_layer": [{k: v for k, v in m.items() if k != "workloads"}
                      for m in json.loads((BENCH.parent / "BENCHMARK.json")
                                          .read_text())["per_layer"]],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return spec
