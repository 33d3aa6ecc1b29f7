"""A throwaway checkout holding tiny cells, for driving whole runs on
the CPU.  Its ``tiny-stub`` configuration names an architecture,
``stub``, whose module exists only in the checkout: the dense decoder
under another name, which records the name of each of its functions
that is called in ``bench/arch/stub.calls``."""

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
        "source": "host_clock",
        "workloads": ["tiny.open", "tiny-w4.open", "tiny-stub.open"]},
       {"name": "output_tok_s", "unit": "tokens/s", "better": "higher",
        "bound": 0.1, "source": "host_clock",
        "workloads": ["tiny.closed"]}]

STUB = '''"""The dense decoder under another name; each call is recorded."""

from pathlib import Path

from bench.arch import dense

CALLS = Path(__file__).with_suffix(".calls")


def _recorded(f):
    def call(*args):
        with open(CALLS, "a") as out:
            out.write(f.__name__ + "\\n")
        return f(*args)
    return call


model_config = _recorded(dense.model_config)
layout = _recorded(dense.layout)
program_weights = _recorded(dense.program_weights)
reference_weights = _recorded(dense.reference_weights)
logits_rows = _recorded(dense.logits_rows)
decode_step = _recorded(dense.decode_step)
'''


def make(root: Path) -> dict:
    """Lay out BENCHMARK.json and the cell files under ``root``."""
    for sub in ("configs", "traffic", "limits", "arch"):
        (root / "bench" / sub).mkdir(parents=True, exist_ok=True)
    (root / "bench" / "metrics").symlink_to(BENCH / "metrics")
    (root / "bench" / "arch" / "dense.py").symlink_to(
        BENCH / "arch" / "dense.py")
    (root / "bench" / "arch" / "stub.py").write_text(STUB)
    for name in ("tiny", "tiny-w4"):
        shutil.copy(DATA / f"{name}.json", root / "bench" / "configs")
    stub = dict(json.loads((DATA / "tiny.json").read_text()),
                name="tiny-stub", arch="stub")
    (root / "bench" / "configs" / "tiny-stub.json").write_text(
        json.dumps(stub))
    for mix in ("open", "closed"):
        shutil.copy(DATA / f"tiny_{mix}.json",
                    root / "bench" / "traffic" / f"{mix}.json")
    cells = [("tiny", "open"), ("tiny", "closed"), ("tiny-w4", "open"),
             ("tiny-stub", "open")]
    for conf, mix in cells:
        shutil.copy(DATA / "limits.json",
                    root / "bench" / "limits" / f"{conf}.{mix}.json")
    spec = {
        "configs": [{"name": n, "file": f"bench/configs/{n}.json"}
                    for n in ("tiny", "tiny-w4", "tiny-stub")],
        "workloads": [{"name": f"{c}.{m}", "config": c, "traffic": m,
                       "chips": 1} for c, m in cells],
        "end_to_end": E2E,
        "per_layer": [{k: v for k, v in m.items() if k != "workloads"}
                      for m in json.loads((BENCH.parent / "BENCHMARK.json")
                                          .read_text())["per_layer"]],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return spec
