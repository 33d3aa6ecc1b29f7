"""Pallas TPU kernels: bit-plane-decomposed ("bit-serial") matmul.

TPU adaptation of the Compute RAM idea (DESIGN.md §2).  The FPGA block
keeps operands in SRAM and computes across bit-lines; the TPU-native
equivalent keeps operands **bit-plane packed in HBM** (the "storage
mode" buffer) and computes on them **inside VMEM** without ever
materializing the expanded tensor in HBM (the "compute mode"):

* :func:`unpack_matmul_kernel` -- the performance path.  Weight tiles
  arrive as packed ``uint32`` bit planes (``bits/32`` of the bf16
  footprint), are expanded to int8 *inside VMEM*, and hit the MXU as a
  regular int32-accumulating matmul.  HBM traffic for weights drops by
  ``16/bits`` vs bf16 (4x for int4), which is precisely the "don't move
  the data to the DSP" energy/bandwidth argument of the paper, restated
  for the HBM<->VMEM hierarchy.

* :func:`popcount_matmul_kernel` -- the PIM-faithful path.  Both
  operands stay as bit planes and partial products are formed as
  ``popcount(AND)`` per plane pair with power-of-two recombination --
  the exact arithmetic the in-array engine performs (AND on the
  bit-line, add via the carry chain), vectorized over the VPU.

Both are validated in ``interpret=True`` mode against ``ref.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref

def _tile(dim: int, target: int, align: int) -> int:
    """Block size along one array dimension for Mosaic.

    The largest divisor of ``dim`` that is <= ``target`` and a multiple
    of ``align`` (the TPU tiling of that dimension); the full ``dim``
    when there is none, since a block spanning the whole dimension is
    always legal.  Odd model widths (896, 4864) thus still tile."""
    for d in range(min(target, dim) // align * align, 0, -align):
        if dim % d == 0:
            return d
    return dim


# ---------------------------------------------------------------------------
# Performance path: packed weights -> VMEM unpack -> MXU matmul
# ---------------------------------------------------------------------------
def _unpack_matmul_kernel(a_ref, w_ref, s_ref, o_ref, acc_ref, *,
                          bits: int, block_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]                                        # (bm, bk) int8
    shifts = jnp.arange(32, dtype=jnp.uint32)
    coefs = ref.plane_coefs(bits, signed=True)

    bn = w_ref.shape[-1]
    w = jnp.zeros((block_k, bn), jnp.int32)
    for b in range(bits):
        wp = w_ref[b]                                     # (bk//32, bn) u32
        bitv = (wp[:, None, :] >> shifts[None, :, None]) & jnp.uint32(1)
        w = w + coefs[b] * bitv.reshape(block_k, bn).astype(jnp.int32)

    # int8 x int8 on the MXU: a two's-complement weight of <= 8 bits
    # fits int8 exactly
    acc_ref[...] += jax.lax.dot_general(
        a, w.astype(jnp.int8), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = (acc_ref[...].astype(jnp.float32)
                      * s_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "block_m", "block_n",
                                             "block_k", "interpret",
                                             "out_dtype"))
def quant_matmul(a, w_packed, scale_w, *, bits: int,
                 block_m: int = 128, block_n: int = 128, block_k: int = 512,
                 interpret: bool = False, out_dtype=jnp.float32):
    """C = (A @ unpack(W_packed)) * scale_w.

    a: (M, K) int8;  w_packed: (bits, K//32, N) uint32;  scale_w: (N,) f32.
    The block sizes are targets (see :func:`_tile`): int8 rows tile by
    32, lanes by 128, and ``block_k`` by 256 so that the packed-word
    block (``block_k // 32``) is a whole number of 8-row tiles.
    """
    m, k = a.shape
    n = w_packed.shape[-1]
    assert w_packed.shape == (bits, k // 32, n), w_packed.shape
    assert bits <= 8, bits
    block_m = _tile(m, block_m, 32)
    block_n = _tile(n, block_n, 128)
    block_k = _tile(k, block_k, 256)

    grid = (m // block_m, n // block_n, k // block_k)
    return pl.pallas_call(
        functools.partial(_unpack_matmul_kernel, bits=bits, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, t: (i, t)),
            pl.BlockSpec((bits, block_k // 32, block_n),
                         lambda i, j, t: (0, t, j)),
            pl.BlockSpec((1, block_n), lambda i, j, t: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, t: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, w_packed, scale_w.reshape(1, n).astype(jnp.float32))


# ---------------------------------------------------------------------------
# PIM-faithful path: AND + popcount over bit-plane pairs
# ---------------------------------------------------------------------------
def _popcount_kernel(ap_ref, wp_ref, o_ref, acc_ref, *, ca, cw):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for i, ci in enumerate(ca):
        a = ap_ref[i]                                     # (bm, bkw) u32
        for j, cj in enumerate(cw):
            w = wp_ref[j]                                 # (bkw, bn) u32
            anded = a[:, :, None] & w[None, :, :]         # (bm, bkw, bn)
            pc = jax.lax.population_count(anded).astype(jnp.int32)
            acc_ref[...] += (ci * cj) * jnp.sum(pc, axis=1)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("a_signed", "w_signed",
                                             "block_m", "block_n", "block_k",
                                             "interpret"))
def popcount_matmul(a_packed, w_packed, *, a_signed: bool = True,
                    w_signed: bool = True, block_m: int = 8,
                    block_n: int = 128, block_k: int = 4096,
                    interpret: bool = False):
    """(M, N) int32 = bit-serial matmul of packed planes (exact).

    a_packed: (Ba, M, K//32) uint32;  w_packed: (Bw, K//32, N) uint32.
    The packed-word block (``block_k // 32``) is the lane dimension of
    the activation block, so it tiles by 128 words or spans all of K.
    ``block_m`` stays at 8 rows: the kernel's (bm, words, bn) AND
    intermediates must fit the 16 MiB of scoped VMEM on a v5e at
    K = 4864, which 32 rows overflow.
    """
    ba, m, kw = a_packed.shape
    bw, kw2, n = w_packed.shape
    assert kw == kw2, (kw, kw2)
    block_m = _tile(m, block_m, 8)
    block_n = _tile(n, block_n, 128)
    block_kw = _tile(kw, block_k // 32, 128)

    ca = ref.plane_coefs(ba, a_signed)
    cw = ref.plane_coefs(bw, w_signed)
    grid = (m // block_m, n // block_n, kw // block_kw)
    return pl.pallas_call(
        functools.partial(_popcount_kernel, ca=ca, cw=cw),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ba, block_m, block_kw),
                         lambda i, j, t: (0, i, t)),
            pl.BlockSpec((bw, block_kw, block_n),
                         lambda i, j, t: (0, t, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, t: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a_packed, w_packed)
