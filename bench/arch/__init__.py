"""Everything in the benchmark that depends on a model's architecture,
one module per architecture: ``bench/arch/<arch>.py``, named by the
configuration file's ``"arch"`` key (``dense`` where it has none).

Each module defines:

* ``model_config(cj)``: the serving stack's ``ModelConfig``; it raises
  on a configuration that names parts it does not build;
* ``layout(cj)``: leaf path -> (shape, dtype) of the tree the serving
  stack takes (``weights.check_layout`` compares it with ``LM.init``);
* ``program_weights(cj, key)``: that tree on the device, stored as the
  configuration says, as ``ServeEngine`` takes it;
* ``reference_weights(cj, key)``: the same weights again, made anew from
  the key, in the form the module's reference reads;
* ``logits_rows(cj, w, tokens, start, rows, mode)``: the plain
  reference's logits (rows, vocab) of one sequence at positions
  ``start .. start + rows - 1``; ``mode`` is ``"f32"`` or ``"fp8"``
  (the control).  It imports nothing of the serving stack;
* ``decode_step(cj, lens)``: (FLOPs, bytes) one decode step needs, where
  ``lens`` holds the live keys of each active lane, its new position
  included.

A module is loaded by its file path, so a new architecture is a new
file and nothing else changes.
"""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

DIR = Path(__file__).resolve().parent


def load(cj: dict, directory: Path = DIR):
    """The architecture module that ``cj`` names, from ``directory``."""
    name = cj.get("arch", "dense")
    path = Path(directory) / f"{name}.py"
    if not (isinstance(name, str) and name.isidentifier()
            and path.is_file()):
        found = sorted(p.stem for p in Path(directory).glob("*.py")
                       if p.stem != "__init__")
        raise ValueError(f"unknown arch {name!r}; modules in {directory}: "
                         f"{found}")
    spec = importlib.util.spec_from_file_location(f"bench_arch_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod      # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod
