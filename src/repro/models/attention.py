"""Attention: GQA / MQA / sliding-window / cross, with chunked
online-softmax (memory-safe at 32k+ contexts) and ring-buffer KV caches;
and DeepSeek-V2's multi-head latent attention (MLA), which caches one
normalised latent and one rotary key a position for all heads.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import common
from .common import dense_init, rope, shard
from .qweight import dq

NEG_INF = -1e30
# keeps f32 dot operands in f32: at DEFAULT precision the TPU's MXU takes
# them as bf16, which rounds decode's query and softmax weights
F32 = jax.lax.Precision.HIGHEST


def attn_init(key, cfg, cross: bool = False) -> dict:
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = common.split_keys(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, H, hd)),
        "wk": dense_init(ks[1], (d, KV, hd)),
        "wv": dense_init(ks[2], (d, KV, hd)),
        "wo": dense_init(ks[3], (H, hd, d), in_axis=0),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((H, hd), jnp.bfloat16)
        p["bk"] = jnp.zeros((KV, hd), jnp.bfloat16)
        p["bv"] = jnp.zeros((KV, hd), jnp.bfloat16)
    return p


def _qkv(params, x, kv_src, cfg, positions, kv_positions, use_rope=True):
    q = jnp.einsum("bsd,dhk->bshk", x, dq(params["wq"]))
    k = jnp.einsum("bsd,dhk->bshk", kv_src, dq(params["wk"]))
    v = jnp.einsum("bsd,dhk->bshk", kv_src, dq(params["wv"]))
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, kv_positions, cfg.rope_theta)
    return q, k, v


def _repeat_kv(k, n_heads):
    """(B, S, KV, hd) -> (B, S, H, hd) by repeating each group."""
    g = n_heads // k.shape[2]
    return jnp.repeat(k, g, axis=2) if g > 1 else k


@jax.named_scope("attention")
def chunked_attention(q, k, v, pos_q, pos_k, *, causal: bool,
                      window=None, chunk: int = 1024, scale=None):
    """Online-softmax attention, scanning over KV chunks.

    q, k: (B, Sq|Sk, H, hd);  v: (B, Sk, H, hv) (KV already repeated);
    pos_q: (B, Sq), pos_k: (B, Sk) int32 (-1 = invalid key slot);
    ``scale`` defaults to hd ** -0.5.  Working set per step is
    O(Sq * chunk), never O(Sk^2).  Its operations carry the name scope
    ``attention``.
    """
    b, sq, h, hd = q.shape
    hv = v.shape[-1]
    sk = k.shape[1]
    chunk = min(chunk, sk)
    assert sk % chunk == 0, (sk, chunk)
    n = sk // chunk
    scale = hd ** -0.5 if scale is None else scale

    qf = q.astype(jnp.float32) * scale
    ks = jnp.moveaxis(k.reshape(b, n, chunk, h, hd), 1, 0)
    vs = jnp.moveaxis(v.reshape(b, n, chunk, h, hv), 1, 0)
    ps = jnp.moveaxis(pos_k.reshape(b, n, chunk), 1, 0)

    def body(carry, xs):
        m, l, acc = carry
        kc, vc, pc = xs
        s = jnp.einsum("bqhd,bchd->bqhc", qf, kc.astype(jnp.float32))
        valid = (pc >= 0)[:, None, :]
        if causal:
            valid = valid & (pc[:, None, :] <= pos_q[:, :, None])
        if window is not None:
            valid = valid & (pc[:, None, :] > pos_q[:, :, None] - window)
        s = jnp.where(valid[:, :, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bqhc,bchd->bqhd", p, vc.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, sq, h), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, sq, h), jnp.float32)
    a0 = jnp.zeros((b, sq, h, hv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (ks, vs, ps))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out


def attn_apply(params, x, cfg, positions, *, causal=True, window=None,
               kv_src=None, kv_positions=None, chunk=1024):
    """Full-sequence attention (training / prefill / encoder / cross)."""
    b, s, d = x.shape
    cross = kv_src is not None
    src = kv_src if cross else x
    kpos = kv_positions if cross else positions
    q, k, v = _qkv(params, x, src, cfg, positions, kpos,
                   use_rope=not cross)
    q = shard(q, "batch", None, "model", None)
    k = _repeat_kv(k, cfg.n_heads)
    v = _repeat_kv(v, cfg.n_heads)
    k = shard(k, "batch", None, "model", None)
    v = shard(v, "batch", None, "model", None)
    out = chunked_attention(q, k, v, positions, kpos,
                            causal=causal and not cross,
                            window=window, chunk=chunk)
    out = out.astype(x.dtype)
    y = jnp.einsum("bshk,hkd->bsd", out, dq(params["wo"]))
    return shard(y, "batch", None, None)


# ---------------------------------------------------------------------------
# Decode path: ring-buffer KV cache (optionally int8- or 4-bit-quantized
# "storage mode", the Compute RAM dual-mode idea applied to the cache: it
# shrinks the dominant HBM term of decode)
# ---------------------------------------------------------------------------
def init_kv_cache(cfg, batch: int, capacity: int, window=None) -> dict:
    """The dense KV ring, head-major: ``k`` and ``v`` (B, KV, cap, hd),
    so each KV group's keys are contiguous rows of hd, the layout the
    grouped einsums of ``attn_decode`` read; int8 and 4-bit caches add
    scales ``k_s`` and ``v_s`` (B, KV, cap); ``pos`` (B, cap) int32
    (-1 = empty slot).  cap = min(capacity, window).  The serve
    engine's decode and merge programs donate the cache and update it
    in place."""
    cap = capacity if window is None else min(capacity, window)
    shape = (batch, cfg.n_kv_heads, cap, cfg.hd)
    if cfg.kv_quant_bits == 4:
        # two nibbles per byte along hd: 4x smaller than bf16
        assert cfg.hd % 2 == 0
        pshape = shape[:3] + (cfg.hd // 2,)
        return {
            "k": jnp.zeros(pshape, jnp.uint8),
            "v": jnp.zeros(pshape, jnp.uint8),
            "k_s": jnp.zeros(shape[:3], jnp.bfloat16),
            "v_s": jnp.zeros(shape[:3], jnp.bfloat16),
            "pos": jnp.full((batch, cap), -1, jnp.int32),
        }
    if cfg.kv_quant_bits:
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_s": jnp.zeros(shape[:3], jnp.bfloat16),
            "v_s": jnp.zeros(shape[:3], jnp.bfloat16),
            "pos": jnp.full((batch, cap), -1, jnp.int32),
        }
    return {
        "k": jnp.zeros(shape, jnp.bfloat16),
        "v": jnp.zeros(shape, jnp.bfloat16),
        "pos": jnp.full((batch, cap), -1, jnp.int32),
    }


def _kv_quantize(x, bits: int):
    """x: (..., hd) -> (int8 / nibble-packed uint8 values, bf16 scale)."""
    qmax = (1 << (bits - 1)) - 1
    amax = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1),
                       1e-6)
    scale = amax / qmax
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -qmax - 1, qmax).astype(jnp.int32)
    if bits == 4:
        u = (q & 0xF).astype(jnp.uint8)                 # two's complement
        lo, hi = u[..., 0::2], u[..., 1::2]
        return (lo | (hi << 4)).astype(jnp.uint8), scale.astype(jnp.bfloat16)
    return q.astype(jnp.int8), scale.astype(jnp.bfloat16)


def _nib_signed(u):
    s = u.astype(jnp.int32)
    return jnp.where(s >= 8, s - 16, s)


def _kv_read(cache, name):
    """Cache entry ``name`` in f32, its scale applied: (B, KV, cap, hd)."""
    x = cache[name]
    if x.dtype == jnp.uint8:                            # 4-bit packed
        lo = _nib_signed(x & 0xF)
        hi = _nib_signed(x >> 4)
        vals = jnp.stack([lo, hi], axis=-1).reshape(x.shape[:-1] +
                                                    (x.shape[-1] * 2,))
        return vals.astype(jnp.float32) \
            * cache[name + "_s"].astype(jnp.float32)[..., None]
    if x.dtype == jnp.int8:
        return x.astype(jnp.float32) \
            * cache[name + "_s"].astype(jnp.float32)[..., None]
    return x.astype(jnp.float32)


def _ring_hit(pos, cap):
    """(B, cap) bool, true at each lane's ring slot ``pos % cap``."""
    return jnp.arange(cap)[None, :] == (pos % cap)[:, None]


def _ring_put(ring, new, hit, axis):
    """``ring`` with ``new`` at each lane's slot: ``hit`` from
    ``_ring_hit``, ``axis`` the ring's cap axis, ``new`` the ring's
    shape without it.  A select over the whole ring, which XLA fuses
    into the in-place update of a donated cache; a per-lane scatter
    relayouts the ring on both sides of it instead."""
    shape = [1] * ring.ndim
    shape[0], shape[axis] = hit.shape
    return jnp.where(hit.reshape(shape), jnp.expand_dims(new, axis), ring)


def attn_decode(params, x, cache, cfg, pos, *, window=None):
    """One-token decode.  x: (B, 1, d); pos: (B,) int32 current position;
    ``cache`` the head-major ring of ``init_kv_cache``.

    The new position goes to slot ``pos % cap`` of each lane by
    ``_ring_put``, in place where the caller donates the cache, as the
    serve engine's ``jit_decode_step`` does.  Attention is computed per
    KV group: the H query heads are viewed as (KV, G) with G = H // KV,
    head h reading group h // G as ``_repeat_kv`` would map it, so K and
    V are never repeated to the query heads.  The cache update and the
    attention (not the q/k/v/o projections) carry the name scope
    ``attention``."""
    b, s, d = x.shape
    assert s == 1
    positions = pos[:, None]
    q, k, v = _qkv(params, x, x, cfg, positions, positions)

    # the cache write and the attention itself, not the projections
    with jax.named_scope("attention"):
        hit = _ring_hit(pos, cache["pos"].shape[1])
        if cfg.kv_quant_bits:
            kq, ks_ = _kv_quantize(k[:, 0], cfg.kv_quant_bits)
            vq, vs_ = _kv_quantize(v[:, 0], cfg.kv_quant_bits)
            new = {"k": kq, "v": vq, "k_s": ks_, "v_s": vs_}
        else:
            new = {"k": k[:, 0].astype(jnp.bfloat16),
                   "v": v[:, 0].astype(jnp.bfloat16)}
        new_cache = {n: _ring_put(cache[n], t, hit, 2)
                     for n, t in new.items()}
        new_cache["pos"] = _ring_put(cache["pos"], pos, hit, 1)
        ck = _kv_read(new_cache, "k")
        cv = _kv_read(new_cache, "v")
        cp = new_cache["pos"]

        n_kv, hd = ck.shape[1], ck.shape[3]
        scale = cfg.hd ** -0.5
        qg = (q.astype(jnp.float32) * scale).reshape(b, n_kv, -1, hd)
        qg = shard(qg, "batch", "model", None, None)
        ck = shard(ck, "batch", "model", None, None)
        cv = shard(cv, "batch", "model", None, None)
        s_ = jnp.einsum("bkgd,bkcd->bkgc", qg, ck, precision=F32)
        valid = (cp >= 0) & (cp <= pos[:, None])
        if window is not None:
            valid = valid & (cp > pos[:, None] - window)
        s_ = jnp.where(valid[:, None, None, :], s_, NEG_INF)
        p = jax.nn.softmax(s_, axis=-1)
        out = jnp.einsum("bkgc,bkcd->bkgd", p, cv, precision=F32)
        out = out.reshape(b, 1, -1, hd).astype(x.dtype)
    y = jnp.einsum("bshk,hkd->bsd", out, dq(params["wo"]))
    return y, new_cache


def prefill_kv_cache(params, x, cfg, positions, capacity, window=None):
    """Build a head-major cache from a prefilled sequence (keys of the
    last `cap`), each position at ring slot pos % cap."""
    b, s, d = x.shape
    _, k, v = _qkv(params, x, x, cfg, positions, positions)
    cap = capacity if window is None else min(capacity, window)
    if s >= cap:
        ks, vs, ps = k[:, -cap:], v[:, -cap:], positions[:, -cap:]
    else:
        pad = cap - s
        ks = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vs = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        ps = jnp.pad(positions, ((0, 0), (0, pad)), constant_values=-1)
    # ring-consistent placement: slot = pos % cap
    slot = jnp.where(ps >= 0, ps % cap, jnp.arange(cap)[None, :] % cap)
    bidx = jnp.arange(b)[:, None]
    cache = init_kv_cache(cfg, b, cap)

    def place(name, x):
        """x (B, cap, KV, ...) in sequence order into the ring: the
        indices, split by the KV slice, index x's leading (B, cap)."""
        return cache[name].at[bidx, :, slot].set(x)

    if cfg.kv_quant_bits:
        kq, ks_ = _kv_quantize(ks, cfg.kv_quant_bits)
        vq, vs_ = _kv_quantize(vs, cfg.kv_quant_bits)
        new = {"k": place("k", kq), "v": place("v", vq),
               "k_s": place("k_s", ks_), "v_s": place("v_s", vs_)}
    else:
        new = {"k": place("k", ks.astype(jnp.bfloat16)),
               "v": place("v", vs.astype(jnp.bfloat16))}
    new["pos"] = cache["pos"].at[bidx, slot].set(ps)
    return new


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2, ``cfg.kv_lora_rank`` set)
#
# Per position the layer projects x to q (H heads of nope + rope dims),
# to a latent c of width r (RMS-normalised) and to one rotary key k_pe
# shared by every head.  Keys and values are up-projections of c:
# k_h = [W_uk,h c ; k_pe], v_h = W_uv,h c.  Prefill and training use
# that expanded form; the cache holds c and k_pe only (r + rope values a
# position for all heads), and decode absorbs W_uk into the query and
# W_uv into the output, so it never expands the cache:
#   score_h = (W_uk,h^T q_nope,h) . c + q_pe,h . k_pe
#   out_h   = W_uv,h (sum_s p_h,s c_s)
# ---------------------------------------------------------------------------
def yarn_mscale(scale: float, m: float) -> float:
    """YaRN's attention temperature: 0.1 m ln s + 1 (1 where s <= 1)."""
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def rope_inv_freq(cfg):
    """Inverse frequencies of the rotary dims (rope_head_dim / 2), YaRN-
    blended where ``cfg.rope_scaling`` is set: interpolated (÷ factor)
    below the correction range of β_fast..β_slow rotations, untouched
    above it, a linear ramp between."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    y = cfg.rope_scaling
    if y is None:
        return extra

    def corr_dim(rot):
        return (dim * math.log(y.original_max_position / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr_dim(y.beta_fast)), 0)
    high = min(math.ceil(corr_dim(y.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp               # 1 keeps the original frequency
    return extra / y.factor * (1.0 - mask) + extra * mask


def mla_softmax_scale(cfg) -> float:
    """(nope + rope)^-0.5, times YaRN's mscale(factor, mscale_all_dim)^2."""
    s = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        s *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return s


def _rope_mla(x, positions, cfg):
    """Rotary embedding of the rope part (..., S, heads, rope): the
    published interleaved pairs are first de-interleaved (evens, then
    odds), then rotated half against half; cos and sin carry YaRN's
    mscale(factor, mscale) / mscale(factor, mscale_all_dim)."""
    d = x.shape[-1]
    half = d // 2
    xf = x.astype(jnp.float32)
    xf = jnp.swapaxes(xf.reshape(x.shape[:-1] + (half, 2)), -1, -2
                      ).reshape(x.shape)
    y = cfg.rope_scaling
    m = 1.0 if y is None else (yarn_mscale(y.factor, y.mscale)
                               / yarn_mscale(y.factor, y.mscale_all_dim))
    ang = positions[..., :, None].astype(jnp.float32) * rope_inv_freq(cfg)
    cos = (jnp.cos(ang) * m)[..., None, :]
    sin = (jnp.sin(ang) * m)[..., None, :]
    x1, x2 = xf[..., :half], xf[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def mla_init(key, cfg) -> dict:
    d, H = cfg.d_model, cfg.n_heads
    r, nope, rope_d = cfg.kv_lora_rank, cfg.qk_nope_head_dim, \
        cfg.qk_rope_head_dim
    ks = common.split_keys(key, 5)
    return {
        "wq": dense_init(ks[0], (d, H, nope + rope_d)),
        "wkv_a": dense_init(ks[1], (d, r + rope_d)),
        "kv_norm": jnp.zeros((r,), jnp.float32),
        "wk_b": dense_init(ks[2], (r, H, nope)),
        "wv_b": dense_init(ks[3], (r, H, cfg.v_head_dim)),
        "wo": dense_init(ks[4], (H, cfg.v_head_dim, d), in_axis=0),
    }


def _mla_q(params, x, cfg, positions):
    """(q_nope (B, S, H, nope), roped q_pe (B, S, H, rope))."""
    q = jnp.einsum("bsd,dhk->bshk", x, dq(params["wq"]))
    nope = cfg.qk_nope_head_dim
    return q[..., :nope], _rope_mla(q[..., nope:], positions, cfg)


def _mla_latent(params, x, cfg, positions):
    """(normalised latent c (B, S, r), roped shared key k_pe (B, S, rope))."""
    kv = x @ dq(params["wkv_a"])
    r = cfg.kv_lora_rank
    c = common.rmsnorm(kv[..., :r], params["kv_norm"], cfg.norm_eps)
    k_pe = _rope_mla(kv[..., None, r:], positions, cfg)[..., 0, :]
    return c, k_pe


@jax.named_scope("mla")
def mla_apply(params, x, cfg, positions, *, causal=True, chunk=1024):
    """Full-sequence MLA in the expanded form (training and prefill):
    per-head keys and values up-projected from the latent."""
    b, s, _ = x.shape
    q_nope, q_pe = _mla_q(params, x, cfg, positions)
    c, k_pe = _mla_latent(params, x, cfg, positions)
    k_nope = jnp.einsum("bsr,rhk->bshk", c, dq(params["wk_b"]))
    v = jnp.einsum("bsr,rhk->bshk", c, dq(params["wv_b"]))
    h = cfg.n_heads
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None, :],
                                  (b, s, h, k_pe.shape[-1]))], -1)
    q = jnp.concatenate([q_nope, q_pe], -1)
    out = chunked_attention(q, k, v, positions, positions, causal=causal,
                            chunk=chunk, scale=mla_softmax_scale(cfg))
    y = jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), dq(params["wo"]))
    return shard(y, "batch", None, None)


def init_mla_cache(cfg, batch: int, capacity: int) -> dict:
    """The latent cache: ``c`` (B, cap, r) and ``k_pe`` (B, cap, rope)
    in bf16, ``pos`` (B, cap) int32 (-1 = empty slot)."""
    return {
        "c": jnp.zeros((batch, capacity, cfg.kv_lora_rank), jnp.bfloat16),
        "k_pe": jnp.zeros((batch, capacity, cfg.qk_rope_head_dim),
                          jnp.bfloat16),
        "pos": jnp.full((batch, capacity), -1, jnp.int32),
    }


@jax.named_scope("mla")
def prefill_mla_cache(params, x, cfg, positions, capacity):
    """The latent cache of a prefilled sequence (its last ``capacity``
    positions), ring-placed at slot = pos % capacity."""
    b, s, _ = x.shape
    c, k_pe = _mla_latent(params, x, cfg, positions)
    cap = capacity
    if s >= cap:
        c, k_pe, ps = c[:, -cap:], k_pe[:, -cap:], positions[:, -cap:]
    else:
        pad = ((0, 0), (0, cap - s), (0, 0))
        c, k_pe = jnp.pad(c, pad), jnp.pad(k_pe, pad)
        ps = jnp.pad(positions, ((0, 0), (0, cap - s)), constant_values=-1)
    slot = jnp.where(ps >= 0, ps % cap, jnp.arange(cap)[None, :] % cap)
    bidx = jnp.arange(b)[:, None]
    cache = init_mla_cache(cfg, b, cap)
    return {
        "c": cache["c"].at[bidx, slot].set(c.astype(jnp.bfloat16)),
        "k_pe": cache["k_pe"].at[bidx, slot].set(k_pe.astype(jnp.bfloat16)),
        "pos": cache["pos"].at[bidx, slot].set(ps),
    }


@jax.named_scope("mla")
def mla_decode(params, x, cache, cfg, pos):
    """One-token MLA against the latent cache, in the absorbed form.
    x: (B, 1, d); pos: (B,) int32.  The cache write and the attention
    carry the name scope ``attention`` inside ``mla``."""
    positions = pos[:, None]
    q_nope, q_pe = _mla_q(params, x, cfg, positions)
    c, k_pe = _mla_latent(params, x, cfg, positions)
    w_uk = dq(params["wk_b"]).astype(jnp.float32)     # (r, H, nope)
    w_uv = dq(params["wv_b"]).astype(jnp.float32)     # (r, H, v)
    with jax.named_scope("attention"):
        hit = _ring_hit(pos, cache["pos"].shape[1])
        new_cache = {
            "c": _ring_put(cache["c"], c[:, 0].astype(jnp.bfloat16), hit, 1),
            "k_pe": _ring_put(cache["k_pe"],
                              k_pe[:, 0].astype(jnp.bfloat16), hit, 1),
            "pos": _ring_put(cache["pos"], pos, hit, 1),
        }
        cc = new_cache["c"].astype(jnp.float32)           # (B, cap, r)
        cr = new_cache["k_pe"].astype(jnp.float32)        # (B, cap, rope)
        cp = new_cache["pos"]
        q_lat = jnp.einsum("bhk,rhk->bhr", q_nope[:, 0].astype(jnp.float32),
                           w_uk, precision=F32)
        s_ = (jnp.einsum("bhr,bcr->bhc", q_lat, cc, precision=F32)
              + jnp.einsum("bhk,bck->bhc", q_pe[:, 0].astype(jnp.float32),
                           cr, precision=F32)) * mla_softmax_scale(cfg)
        valid = (cp >= 0) & (cp <= pos[:, None])
        s_ = jnp.where(valid[:, None, :], s_, NEG_INF)
        p = jax.nn.softmax(s_, axis=-1)
        o_lat = jnp.einsum("bhc,bcr->bhr", p, cc, precision=F32)
        out = jnp.einsum("bhr,rhk->bhk", o_lat, w_uv, precision=F32)
    y = jnp.einsum("bhk,hkd->bd", out.astype(x.dtype), dq(params["wo"]))
    return y[:, None, :], new_cache
