"""Grouped-query decode attention against the repeated-KV formulation.

``attn_decode`` computes attention per KV group and never repeats K or V
to the query heads.  The reference here does: it repeats each group to
its heads (head h reads group h // G) and runs an f32 softmax over the
whole ring.  The arithmetic is the same, so the two agree to f32
round-off, for every head layout, cache precision and window.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.launch.mesh import make_mesh
from repro.models import attention as attn

B, CAP, HD, D = 4, 8, 8, 32
# lane 0 has wrapped its ring (pos >= CAP), lane 1 is half full, lane 2
# decodes its first token into an empty ring, lane 3 fills its last slot
POS = np.array([13, 4, 0, 7], np.int32)


def _cfg(n_heads, n_kv_heads, bits):
    return ModelConfig(name="t", family="dense", n_layers=1, d_model=D,
                       n_heads=n_heads, n_kv_heads=n_kv_heads, d_ff=64,
                       vocab=16, head_dim=HD, qkv_bias=True,
                       kv_quant_bits=bits)


def _inputs(cfg, seed=0):
    """Parameters, a (B, 1, D) f32 input and a ring whose occupied slots
    hold the positions before each lane's current one (-1 elsewhere)."""
    kp, kx, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 4)
    params = attn.attn_init(kp, cfg)
    params = {**params, **{n: jax.random.normal(jax.random.fold_in(kp, i),
                                                params[n].shape)
                           .astype(jnp.bfloat16)
                           for i, n in enumerate(("bq", "bk", "bv"))}}
    x = jax.random.normal(kx, (B, 1, D), jnp.float32)
    shape = (B, CAP, cfg.n_kv_heads, HD)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)
    ring = np.full((B, CAP), -1, np.int32)
    for b, p in enumerate(POS):
        for q in range(max(0, p - CAP), p):
            ring[b, q % CAP] = q
    cache = {"pos": jnp.asarray(ring)}
    if cfg.kv_quant_bits:
        cache["k"], cache["k_s"] = attn._kv_quantize(k, cfg.kv_quant_bits)
        cache["v"], cache["v_s"] = attn._kv_quantize(v, cfg.kv_quant_bits)
    else:
        cache["k"] = k.astype(jnp.bfloat16)
        cache["v"] = v.astype(jnp.bfloat16)
    return params, x, cache


def _reference(params, x, cache, cfg, pos, window):
    """Repeat K/V to every query head, then an f32 softmax over the
    ring: the formulation ``attn_decode`` replaces."""
    positions = pos[:, None]
    q, k, v = attn._qkv(params, x, x, cfg, positions, positions)
    bidx, slot = jnp.arange(B), pos % CAP
    new = dict(cache)
    if cfg.kv_quant_bits:
        for n, t in (("k", k), ("v", v)):
            tq, ts = attn._kv_quantize(t[:, 0], cfg.kv_quant_bits)
            new[n] = cache[n].at[bidx, slot].set(tq)
            new[n + "_s"] = cache[n + "_s"].at[bidx, slot].set(ts)
    else:
        new["k"] = cache["k"].at[bidx, slot].set(k[:, 0].astype(jnp.bfloat16))
        new["v"] = cache["v"].at[bidx, slot].set(v[:, 0].astype(jnp.bfloat16))
    new["pos"] = cache["pos"].at[bidx, slot].set(pos)
    g = cfg.n_heads // cfg.n_kv_heads
    kh = jnp.repeat(attn._kv_read(new, "k"), g, axis=2)
    vh = jnp.repeat(attn._kv_read(new, "v"), g, axis=2)
    qh = q.astype(jnp.float32) * cfg.hd ** -0.5
    s = jnp.einsum("bqhd,bchd->bqhc", qh, kh)
    cp = new["pos"]
    valid = (cp >= 0) & (cp <= pos[:, None])
    if window is not None:
        valid = valid & (cp > pos[:, None] - window)
    s = jnp.where(valid[:, None, None, :], s, attn.NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqhc,bchd->bqhd", p, vh).astype(x.dtype)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"]), new


@pytest.mark.parametrize("window", [None, 3])
@pytest.mark.parametrize("bits", [None, 8, 4])
@pytest.mark.parametrize("n_heads,n_kv_heads", [(4, 4), (8, 2), (8, 1)])
def test_grouped_decode_matches_repeated_kv(n_heads, n_kv_heads, bits,
                                            window):
    cfg = _cfg(n_heads, n_kv_heads, bits)
    params, x, cache = _inputs(cfg)
    pos = jnp.asarray(POS)
    y, new = jax.jit(lambda p, x, c, pos: attn.attn_decode(
        p, x, c, cfg, pos, window=window))(params, x, cache, pos)
    y_ref, new_ref = _reference(params, x, cache, cfg, pos, window)
    assert y.shape == (B, 1, D) and y.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-6)
    assert set(new) == set(new_ref)
    for n in new:
        assert new[n].dtype == new_ref[n].dtype, n
        np.testing.assert_array_equal(np.asarray(new[n], np.float32),
                                      np.asarray(new_ref[n], np.float32),
                                      err_msg=n)


@pytest.mark.parametrize("n_heads,n_kv_heads", [(8, 2), (8, 1)])
def test_grouped_decode_under_a_mesh_matches_one_device(n_heads,
                                                        n_kv_heads):
    """On a (data, model) mesh the KV axis carries the ``model``
    constraint where it divides and is left replicated where it does
    not (MQA); either way the result is the single-device one."""
    cfg = _cfg(n_heads, n_kv_heads, None)
    params, x, cache = _inputs(cfg, seed=1)
    pos = jnp.asarray(POS)

    def step(p, x, c, pos):
        return attn.attn_decode(p, x, c, cfg, pos)

    y_one, _ = jax.jit(step)(params, x, cache, pos)
    with jax.set_mesh(make_mesh(2, 2)):
        y_mesh, _ = jax.jit(step)(params, x, cache, pos)
    np.testing.assert_allclose(np.asarray(y_mesh), np.asarray(y_one),
                               rtol=1e-5, atol=1e-6)
