"""Shared model utilities: sharding constraints, norms, rope, init."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


# ---------------------------------------------------------------------------
# Sharding: specs are written with logical axes; `shard()` silently drops
# axes the active mesh doesn't have ("pod" on single-pod runs) and is a
# no-op outside a mesh context (unit tests on one device).  The active
# mesh is the one set by `jax.set_mesh`.
# ---------------------------------------------------------------------------
def _active_mesh():
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def _axis_size(mesh, s):
    if s is None:
        return 1
    if isinstance(s, tuple):
        out = 1
        for a in s:
            out *= mesh.shape[a]
        return out
    return mesh.shape[s]


def resolve_spec(mesh, shape, spec):
    """Resolve a logical spec against a mesh *and* a shape: logical axes
    missing from the mesh or not dividing the dimension are dropped."""
    names = set(mesh.axis_names)

    def fix(s, dim):
        if s == "batch":
            s = tuple(a for a in ("pod", "data") if a in names)
            if not s:
                return None
            s = s if len(s) > 1 else s[0]
        elif isinstance(s, str):
            s = s if s in names else None
        elif isinstance(s, tuple):
            t = tuple(a for a in s if a in names)
            s = t if t else None
        if s is None:
            return None
        if dim is not None and dim % _axis_size(mesh, s) != 0:
            return None                      # uneven: leave replicated
        return s

    dims = list(shape) + [None] * (len(spec) - len(shape))
    return P(*[fix(s, d) for s, d in zip(spec, dims)])


def shard(x, *spec):
    """with_sharding_constraint with mesh/shape-aware axis filtering.

    spec entries: None, "model", "batch" (expands to present pod/data axes),
    or explicit axis names / tuples.  Axes that don't divide the dimension
    (e.g. 14 heads on a 16-way model axis) are silently dropped.
    """
    mesh = _active_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, resolve_spec(mesh, x.shape, spec))


def spec_for(mesh, *spec) -> P:
    """Resolve a logical spec to a concrete PartitionSpec for ``mesh``."""
    names = set(mesh.axis_names)

    def fix(s):
        if s == "batch":
            ax = tuple(a for a in ("pod", "data") if a in names)
            return ax if len(ax) > 1 else (ax[0] if ax else None)
        if isinstance(s, str):
            return s if s in names else None
        if isinstance(s, tuple):
            t = tuple(a for a in s if a in names)
            return t if t else None
        return s

    return P(*[fix(s) for s in spec])


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------
def rmsnorm(x, w, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    normed = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (normed * (1.0 + w)).astype(x.dtype)


def rope(q, positions, theta):
    """Rotary embedding.  q: (..., S, H, hd); positions: (..., S)."""
    hd = q.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..,S,half)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    q1, q2 = q[..., :half], q[..., half:]
    out = jnp.concatenate([q1 * cos - q2 * sin, q2 * cos + q1 * sin], -1)
    return out.astype(q.dtype)


def dense_init(key, shape, in_axis=0, dtype=jnp.bfloat16):
    fan_in = shape[in_axis]
    return (jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5
            ).astype(dtype)


def split_keys(key, n):
    return list(jax.random.split(key, n))
