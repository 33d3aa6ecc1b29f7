"""Parameter / input / cache sharding rules (GSPMD via NamedSharding).

Rules are path+shape based; scanned stacks (leading layer dim) get a
leading ``None``.  Anything whose dimension doesn't divide the mesh axis
stays replicated on that dim (``resolve_spec`` guard) -- e.g. qwen2's 14
heads on a 16-way model axis.
"""

from __future__ import annotations

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.models.common import resolve_spec

# logical specs by parameter name; "+L" variants handled by rank check
_RULES = {
    # name: (ndim-without-stack, spec)
    "embed": (2, ("model", None)),
    "head": (2, (None, "model")),
    "wq": (3, (None, "model", None)),
    "wk": (3, (None, "model", None)),
    "wv": (3, (None, "model", None)),
    "wo": (3, ("model", None, None)),
    "bq": (2, ("model", None)),
    "bk": (2, ("model", None)),
    "bv": (2, ("model", None)),
    "w_gate": (2, (None, "model")),
    "w_up": (2, (None, "model")),
    "w_down": (2, ("model", None)),
    "router": (2, (None, None)),
    "in_proj": (2, (None, "model")),
    "x_proj": (2, ("model", None)),
    "dt_w": (2, (None, "model")),
    "dt_b": (1, ("model",)),
    "A_log": (2, ("model", None)),
    "D": (1, ("model",)),
    "out_proj": (2, ("model", None)),
    "conv_w": (2, (None, "model")),
    "conv_b": (1, ("model",)),
    "wx": (2, (None, "model")),
    "wy": (2, (None, "model")),
    "wi": (2, (None, "model")),
    "wr": (2, (None, "model")),
    "lambda_p": (1, ("model",)),
    "out": (2, ("model", None)),
}
# MoE expert-stacked weights: experts on the model axis (EP)
_MOE_RULES = {
    "w_gate": (3, ("model", None, None)),
    "w_up": (3, ("model", None, None)),
    "w_down": (3, ("model", None, None)),
}


def _spec_for_path(path, leaf):
    keys = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
    name = keys[-1]
    # storage-mode quantized weights: {"q","scale"} / PackedWeight planes
    if name == "q" and len(keys) >= 2:
        name = keys[-2]
    elif name == "planes":           # (.., K//32, N): K folds the TP axis
        return (None,) * (leaf.ndim - 2) + ("model", None)
    elif name == "scale":
        return (None,) * leaf.ndim
    in_moe = "moe" in keys
    rules = _MOE_RULES if (in_moe and name in _MOE_RULES) else _RULES
    if name not in rules:
        return (None,) * leaf.ndim
    nd, spec = rules[name]
    if leaf.ndim == nd + 1:          # scanned stack
        return (None,) + tuple(spec)
    if leaf.ndim == nd:
        return tuple(spec)
    return (None,) * leaf.ndim


def params_sharding(params, mesh):
    """NamedSharding pytree for a params (or grads/opt moment) pytree."""
    def one(path, leaf):
        spec = _spec_for_path(path, leaf)
        return NamedSharding(mesh, resolve_spec(mesh, leaf.shape, spec))
    return jax.tree_util.tree_map_with_path(one, params)


def batch_sharding(batch, mesh):
    def one(leaf):
        spec = ("batch",) + (None,) * (leaf.ndim - 1)
        return NamedSharding(mesh, resolve_spec(mesh, leaf.shape, spec))
    return jax.tree.map(one, batch)


def cache_sharding(cache, mesh):
    """Decode caches: (stack, B, ...) -> batch on dim 1, heads/features on
    the model axis where divisible.  The dense KV ring is head-major,
    ``k``/``v`` (B, KV, cap, hd) with scales (B, KV, cap), so its KV
    axis, dim 1 after the batch, takes the model axis.  The serve
    engine's decode and merge programs donate the cache and update it in
    place."""
    def one(path, leaf):
        keys = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
        name = keys[-1]
        stack = (None,) if "unit" in keys else ()   # scanned stacks only
        if name in ("k", "v"):       # (B, KV, cap, hd)
            spec = stack + ("batch", "model", None, None)
        elif name in ("k_s", "v_s"):  # (B, KV, cap) int8/4-bit scales
            spec = stack + ("batch", "model", None)
        elif name == "pos":          # (B, cap)
            spec = stack + ("batch", None)
        elif name == "h":            # ssm (B, di, st) | rglru (B, w)
            spec = stack + (("batch", "model", None)
                            if leaf.ndim - len(stack) == 3
                            else ("batch", "model"))
        elif name == "conv":         # (B, cw-1, di)
            spec = stack + ("batch", None, "model")
        else:
            spec = (None,) * leaf.ndim
        assert len(spec) == leaf.ndim, (keys, leaf.shape, spec)
        return NamedSharding(mesh, resolve_spec(mesh, leaf.shape, spec))
    return jax.tree_util.tree_map_with_path(one, cache)


def opt_sharding(opt_state, params_shardings, mesh):
    """Optimizer state mirrors parameter shardings; step is replicated."""
    from repro.train.optimizer import OptState
    rep = NamedSharding(mesh, P())
    return OptState(step=rep, mu=params_shardings, nu=params_shardings)
