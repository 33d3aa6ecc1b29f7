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

An expert stack (``w_gate``/``w_up``/``w_down`` directly under ``moe``,
(E, in, out) a layer) keeps its expert axis in front of the planes and
the scales: ``planes`` (E, 4, in//32, out), ``scale`` (E, out), one scale
per expert and output channel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref as kref

# weights worth quantizing (2D+ matmul operands)
_QUANT_NAMES = {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
                "in_proj", "out_proj", "x_proj", "dt_w", "wx", "wy",
                "wi", "wr", "out", "embed", "head",
                "wkv_a", "wk_b", "wv_b"}
#: expert stacks: these names directly under a ``moe`` node
_EXPERT_NAMES = {"w_gate", "w_up", "w_down"}


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


def _quantize_leaf(w, bits: int, stacked: bool = False,
                   experts: bool = False):
    """``stacked``: leading dim is the scan-layer axis -- every produced
    leaf keeps it so lax.scan can slice per layer.  ``experts``: the
    next dim is the expert axis, kept in front of planes and scales."""
    wf = w.astype(jnp.float32)
    qmax = (1 << (bits - 1)) - 1
    lead = int(stacked) + int(experts)
    flat = wf.reshape(wf.shape[:lead] + (-1, wf.shape[-1]))  # (..., K, N)
    amax = jnp.maximum(jnp.max(jnp.abs(flat), axis=-2), 1e-8)
    scale = (amax / qmax).astype(jnp.float32)               # (..., N)
    q = jnp.clip(jnp.round(flat / scale[..., None, :]), -qmax - 1, qmax)
    if bits == 4 and flat.shape[-2] % 32 == 0:
        pack = lambda qq: kref.pack_bitplanes(qq.astype(jnp.int8), 4, axis=0)
        for _ in range(lead):
            pack = jax.vmap(pack)
        # planes (..., 4, K/32, N); shape is one layer's
        return PackedWeight(pack(q), scale, w.shape[1:] if stacked
                            else w.shape)
    # an expert stack's scales broadcast against its (E, K, N) layers
    return {"q": q.astype(jnp.int8).reshape(w.shape),
            "scale": scale[..., None, :] if experts else scale}


def dq(leaf, dtype=jnp.bfloat16):
    """Dequantize a (possibly) quantized weight leaf.  Its operations
    carry the name scope ``dq`` in their metadata."""
    if isinstance(leaf, PackedWeight):
        with jax.named_scope("dq"):
            if leaf.planes.ndim == 4:
                # an expert stack, one expert at a time: expanded whole,
                # the unpacking's intermediates take 8 bytes a weight
                return jax.lax.map(
                    lambda ps: dq(PackedWeight(ps[0], ps[1],
                                               leaf.shape[1:]), dtype),
                    (leaf.planes, leaf.scale))
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

    Leaves under a scanned "unit" stack keep their leading layer axis;
    expert stacks keep their expert axis too.
    """
    names = names or _QUANT_NAMES

    def walk(tree, stacked=False, moe=False):
        if isinstance(tree, dict):
            out = {}
            for k, v in tree.items():
                experts = moe and k in _EXPERT_NAMES
                min_nd = (3 if stacked else 2) + experts
                if k in names and hasattr(v, "ndim") and v.ndim >= min_nd:
                    out[k] = _quantize_leaf(v, bits, stacked, experts)
                else:
                    out[k] = walk(v, stacked or k == "unit", k == "moe")
            return out
        if isinstance(tree, list):
            return [walk(v, stacked) for v in tree]
        return tree

    return walk(params)


def tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves(tree) if hasattr(x, "size"))
