"""Production mesh construction (TPU v5e pods).

Single pod = 16x16 = 256 chips, axes (data, model).
Multi-pod  = 2 pods = 512 chips, axes (pod, data, model); the "pod" axis
carries only data parallelism (gradient all-reduce over DCN/ICI), the
"model" axis never crosses pods.

Defined as functions so importing this module never touches jax device
state (dryrun.py's entry point sets XLA_FLAGS before the first device
query).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: the models place activations with logical
    # with_sharding_constraint specs (models/common.shard), which only
    # Auto axes accept.  Enter the mesh with `jax.set_mesh(mesh)`.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh(data: int, model: int, pod: int = 1):
    """Arbitrary mesh (tests, elastic re-mesh after node loss)."""
    if pod > 1:
        return _mesh((pod, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))


def data_parallel_size(mesh) -> int:
    n = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            n *= mesh.shape[a]
    return n
