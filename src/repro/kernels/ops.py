"""Public jit'd API over the Pallas kernels.

``interpret`` defaults to True on the CPU backend, so the whole framework
runs (and is tested) there; on every other backend the kernels compile
to Mosaic, and a backend Mosaic cannot target raises instead of quietly
interpreting.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import bitserial_matmul as _bsm
from . import ref as kref

pack_bitplanes = kref.pack_bitplanes
unpack_bitplanes = kref.unpack_bitplanes
plane_coefs = kref.plane_coefs


def _default_interpret() -> bool:
    return jax.default_backend() == "cpu"


def quant_matmul(a, w_packed, scale_w, *, bits: int, interpret=None, **kw):
    """Performance path: packed-weight matmul (see bitserial_matmul.py)."""
    if interpret is None:
        interpret = _default_interpret()
    return _bsm.quant_matmul(a, w_packed, scale_w, bits=bits,
                             interpret=interpret, **kw)


def popcount_matmul(a_packed, w_packed, *, interpret=None, **kw):
    """PIM-faithful path: AND+popcount bit-serial matmul."""
    if interpret is None:
        interpret = _default_interpret()
    return _bsm.popcount_matmul(a_packed, w_packed, interpret=interpret,
                                **kw)


@functools.partial(jax.jit, static_argnames=("bits", "axis"))
def quantize(x, *, bits: int, axis: int = 0):
    """Symmetric per-channel quantization to signed ``bits`` integers.

    Returns (q int8, scale f32) with ``x ~= q * scale`` and scales taken
    along every axis except ``axis`` (i.e. one scale per slice of
    ``axis``... reduced over the other axes).
    """
    reduce_axes = tuple(i for i in range(x.ndim) if i != axis)
    amax = jnp.max(jnp.abs(x), axis=reduce_axes, keepdims=True)
    qmax = (1 << (bits - 1)) - 1
    scale = jnp.maximum(amax, 1e-8) / qmax
    q = jnp.clip(jnp.round(x / scale), -qmax - 1, qmax).astype(jnp.int8)
    return q, scale.reshape(x.shape[axis]).astype(jnp.float32)
