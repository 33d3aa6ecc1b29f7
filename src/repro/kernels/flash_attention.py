"""Pallas TPU flash-attention kernel (causal, online softmax).

The chunked-jnp attention in ``models/attention.py`` is the portable
path; this kernel is the fused VMEM-resident version for the serving /
single-shard hot spot: q/k/v tiles stream HBM->VMEM once, scores and the
online-softmax state (m, l, acc) never leave VMEM, and fully-masked
key blocks are skipped structurally by the causal grid bound.

On a TPU pod this slots in per-shard under ``shard_map`` (heads on the
model axis); the dry-run meshes use the jnp path, which lowers to the
same blockwise schedule.  Validated in interpret mode vs ``ref``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  block_q: int, block_k: int, causal: bool):
    i = pl.program_id(1)        # query block
    t = pl.program_id(2)        # key block

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                    # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                    # (bk, hd)
    v = v_ref[0].astype(jnp.float32)
    s = q @ k.T * (q.shape[-1] ** -0.5)                 # (bq, bk)

    if causal:
        rows = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = t * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(cols <= rows, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + p @ v
    m_ref[...] = m_new

    @pl.when(t == pl.num_programs(2) - 1)
    def _done():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q, k, v: (BH, S, hd) -> (BH, S, hd).  Heads folded into the batch
    dim (callers reshape (B, S, H, hd) -> (B*H, S, hd))."""
    bh, s, hd = q.shape
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)

    grid = (bh, s // block_q, s // block_k)
    return pl.pallas_call(
        functools.partial(_flash_kernel, block_q=block_q, block_k=block_k,
                          causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, t: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, t: (b, t, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, t: (b, t, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, t: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)


def attention_ref(q, k, v, causal=True):
    """Naive oracle."""
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (q.shape[-1] ** -0.5)
    if causal:
        sq, sk = s.shape[-2:]
        mask = jnp.tril(jnp.ones((sq, sk), bool))
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
