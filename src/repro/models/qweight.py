"""Storage-mode quantized weights for serving (the Compute RAM dual-mode
idea applied at model scale).

``quantize_tree`` converts selected weight leaves into compact storage:

* ``bits=8``: ``{"q": int8, "scale": f32[out]}``  (2x HBM reduction)
* ``bits=4``: ``{"planes": uint32[4, in//32, out], "scale": f32[out]}``
  -- true bit-plane packing, the same buffer format the Pallas
  bit-serial kernels consume (4x HBM reduction vs bf16).

``dq(leaf)`` transparently expands either form (or passes raw arrays
through) at the point of use; XLA fuses the dequant into the consuming
matmul so no expanded copy lives in HBM.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref as kref

# weights worth quantizing (2D+ matmul operands)
_QUANT_NAMES = {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
                "in_proj", "out_proj", "x_proj", "dt_w", "wx", "wy",
                "wi", "wr", "out", "embed", "head"}


@jax.tree_util.register_pytree_node_class
class PackedWeight:
    """Bit-plane packed weight: planes uint32 (bits, K//32, N) + scale."""

    def __init__(self, planes, scale, shape):
        self.planes = planes
        self.scale = scale
        self.shape = tuple(shape)

    def tree_flatten(self):
        return (self.planes, self.scale), self.shape

    @classmethod
    def tree_unflatten(cls, shape, leaves):
        return cls(leaves[0], leaves[1], shape)


def _quantize_leaf(w, bits: int, stacked: bool = False):
    """``stacked``: leading dim is the scan-layer axis -- every produced
    leaf keeps it so lax.scan can slice per layer."""
    wf = w.astype(jnp.float32)
    qmax = (1 << (bits - 1)) - 1
    if stacked:
        flat = wf.reshape(wf.shape[0], -1, wf.shape[-1])    # (L, K, N)
        amax = jnp.maximum(jnp.max(jnp.abs(flat), axis=1), 1e-8)
        scale = (amax / qmax).astype(jnp.float32)           # (L, N)
        q = jnp.clip(jnp.round(flat / scale[:, None, :]), -qmax - 1, qmax)
        if bits == 4 and flat.shape[1] % 32 == 0:
            planes = jax.vmap(
                lambda qq: kref.pack_bitplanes(qq.astype(jnp.int8), 4,
                                               axis=0))(q)  # (L,4,K/32,N)
            return PackedWeight(planes, scale, w.shape[1:])
        return {"q": q.astype(jnp.int8).reshape(w.shape), "scale": scale}
    flat = wf.reshape(-1, wf.shape[-1])
    amax = jnp.maximum(jnp.max(jnp.abs(flat), axis=0), 1e-8)
    scale = (amax / qmax).astype(jnp.float32)
    q = jnp.clip(jnp.round(flat / scale), -qmax - 1, qmax)
    if bits == 4 and flat.shape[0] % 32 == 0:
        planes = kref.pack_bitplanes(q.astype(jnp.int8), 4, axis=0)
        return PackedWeight(planes, scale, w.shape)
    return {"q": q.astype(jnp.int8).reshape(w.shape), "scale": scale}


def dq(leaf, dtype=jnp.bfloat16):
    """Dequantize a (possibly) quantized weight leaf.  Its operations
    carry the name scope ``dq`` in their metadata."""
    if isinstance(leaf, PackedWeight):
        with jax.named_scope("dq"):
            w = kref.unpack_bitplanes(leaf.planes, axis=0, signed=True)
            w = w.astype(jnp.float32) * leaf.scale
            return w.reshape(leaf.shape).astype(dtype)
    if isinstance(leaf, dict) and "q" in leaf:
        with jax.named_scope("dq"):
            return (leaf["q"].astype(jnp.float32)
                    * leaf["scale"]).astype(dtype)
    return leaf


def quantize_tree(params, bits: int = 8, names=None):
    """Quantize matching 2D+ weight leaves of a params pytree.

    Leaves under a scanned "unit" stack keep their leading layer axis.
    """
    names = names or _QUANT_NAMES

    def walk(tree, stacked=False):
        if isinstance(tree, dict):
            out = {}
            for k, v in tree.items():
                min_nd = 3 if stacked else 2
                if k in names and hasattr(v, "ndim") and v.ndim >= min_nd:
                    out[k] = _quantize_leaf(v, bits, stacked)
                else:
                    out[k] = walk(v, stacked or k == "unit")
            return out
        if isinstance(tree, list):
            return [walk(v, stacked) for v in tree]
        return tree

    return walk(params)


def tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves(tree) if hasattr(x, "size"))
