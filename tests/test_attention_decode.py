"""Grouped-query decode attention against the repeated-KV formulation.

``attn_decode`` computes attention per KV group and never repeats K or V
to the query heads, and writes the new position into the head-major
ring (B, KV, cap, hd) by a select over the whole ring.  The reference
here writes it by a per-lane scatter, repeats each group to its heads
(head h reads group h // G) and runs an f32 softmax over the whole
ring.  The arithmetic is the same, so the two agree to f32 round-off,
for every head layout, cache precision and window, alone and through
the carried, donated layer loop of an LM's decode step.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.launch.mesh import make_mesh
from repro.models import attention as attn
from repro.models.common import rmsnorm
from repro.models.model import LM, mlp_apply
from repro.serve.engine import jit_decode

B, CAP, HD, D = 4, 8, 8, 32
# lane 0 has wrapped its ring (pos >= CAP), lane 1 is half full, lane 2
# decodes its first token into an empty ring, lane 3 fills its last slot
POS = np.array([13, 4, 0, 7], np.int32)


def _cfg(n_heads, n_kv_heads, bits):
    return ModelConfig(name="t", family="dense", n_layers=1, d_model=D,
                       n_heads=n_heads, n_kv_heads=n_kv_heads, d_ff=64,
                       vocab=16, head_dim=HD, qkv_bias=True,
                       kv_quant_bits=bits)


def _ring(cfg, cap, key):
    """A head-major ring of ``cap`` slots whose occupied slots hold
    random keys and values at the positions before each lane's current
    one (-1 elsewhere)."""
    kk, kv = jax.random.split(key)
    shape = (B, cfg.n_kv_heads, cap, HD)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)
    ring = np.full((B, cap), -1, np.int32)
    for b, p in enumerate(POS):
        for q in range(max(0, p - cap), p):
            ring[b, q % cap] = q
    cache = {"pos": jnp.asarray(ring)}
    if cfg.kv_quant_bits:
        cache["k"], cache["k_s"] = attn._kv_quantize(k, cfg.kv_quant_bits)
        cache["v"], cache["v_s"] = attn._kv_quantize(v, cfg.kv_quant_bits)
    else:
        cache["k"] = k.astype(jnp.bfloat16)
        cache["v"] = v.astype(jnp.bfloat16)
    return cache


def _inputs(cfg, seed=0):
    """Parameters, a (B, 1, D) f32 input and a ring of CAP slots."""
    kp, kx, kc = jax.random.split(jax.random.PRNGKey(seed), 3)
    params = attn.attn_init(kp, cfg)
    params = {**params, **{n: jax.random.normal(jax.random.fold_in(kp, i),
                                                params[n].shape)
                           .astype(jnp.bfloat16)
                           for i, n in enumerate(("bq", "bk", "bv"))}}
    x = jax.random.normal(kx, (B, 1, D), jnp.float32)
    return params, x, _ring(cfg, CAP, kc)


def _reference(params, x, cache, cfg, pos, window):
    """Scatter the new position into each lane's ring slot, repeat K/V
    to every query head, then an f32 softmax over the ring: the
    formulation ``attn_decode`` replaces."""
    positions = pos[:, None]
    q, k, v = attn._qkv(params, x, x, cfg, positions, positions)
    bidx, slot = jnp.arange(B), pos % cache["pos"].shape[1]
    new = dict(cache)
    # the indices, split by the KV slice, take (B, KV, ...) updates
    if cfg.kv_quant_bits:
        for n, t in (("k", k), ("v", v)):
            tq, ts = attn._kv_quantize(t[:, 0], cfg.kv_quant_bits)
            new[n] = cache[n].at[bidx, :, slot].set(tq)
            new[n + "_s"] = cache[n + "_s"].at[bidx, :, slot].set(ts)
    else:
        new["k"] = cache["k"].at[bidx, :, slot].set(
            k[:, 0].astype(jnp.bfloat16))
        new["v"] = cache["v"].at[bidx, :, slot].set(
            v[:, 0].astype(jnp.bfloat16))
    new["pos"] = cache["pos"].at[bidx, slot].set(pos)
    g = cfg.n_heads // cfg.n_kv_heads
    kh = jnp.repeat(attn._kv_read(new, "k"), g, axis=1)
    vh = jnp.repeat(attn._kv_read(new, "v"), g, axis=1)
    qh = q.astype(jnp.float32) * cfg.hd ** -0.5
    s = jnp.einsum("bqhd,bhcd->bqhc", qh, kh)
    cp = new["pos"]
    valid = (cp >= 0) & (cp <= pos[:, None])
    if window is not None:
        valid = valid & (cp > pos[:, None] - window)
    s = jnp.where(valid[:, None, None, :], s, attn.NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqhc,bhcd->bqhd", p, vh).astype(x.dtype)
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


def _ref_decode_step(model, params, rings, tokens, pos, window):
    """One decode step of a stack of ``attn`` layers with ``_reference``
    for attention, the stacked rings ``rings`` passed to the layer scan
    and emitted whole by it.  Returns (logits, new rings)."""
    cfg = model.cfg

    def layer(h, xs):
        lp, ring = xs
        x = rmsnorm(h, lp["ln1"], cfg.norm_eps)
        y, ring = _reference(lp["attn"], x, ring, cfg, pos, window)
        h = h + y
        h = h + mlp_apply(lp["mlp"], rmsnorm(h, lp["ln2"], cfg.norm_eps))
        return h, ring

    h, rings = jax.lax.scan(layer, model._embed(params, tokens),
                            (params["unit"]["b0"], rings))
    return model._head(params, h), rings


@pytest.mark.parametrize("window", [None, 3])
@pytest.mark.parametrize("bits", [None, 8, 4])
def test_carried_donated_decode_matches_per_layer_reference(bits, window):
    """Three decode steps of a 2-layer LM through the serve engine's
    program (the stacked cache carried through the layer loop and
    donated) against the reference layer scan.  Lane 0 has wrapped its
    ring and lane 3 wraps it in the second step; under a window of 3
    the ring holds 3 slots, so every lane but lane 2 wraps."""
    cfg = dataclasses.replace(_cfg(8, 2, bits), n_layers=2,
                              sliding_window=window)
    model = LM(cfg)
    key = jax.random.PRNGKey(2)
    params = model.init(key)
    cap = CAP if window is None else min(CAP, window)
    rings = jax.tree.map(lambda *a: jnp.stack(a), *[
        _ring(cfg, cap, jax.random.fold_in(key, 10 + i)) for i in range(2)])
    caches = model.init_cache(B, CAP)
    assert jax.tree.map(jnp.shape, caches["unit"]["b0"]["kv"]) \
        == jax.tree.map(jnp.shape, rings)
    caches["unit"]["b0"]["kv"] = jax.tree.map(jnp.copy, rings)
    tokens = jax.random.randint(jax.random.fold_in(key, 3), (B, 1), 0,
                                cfg.vocab)
    step = jit_decode(model)
    ref_step = jax.jit(_ref_decode_step, static_argnums=(0, 5))
    for t in range(3):
        pos = jnp.asarray(POS + t)
        given = jax.tree.leaves(caches)
        logits, caches = step(params, caches, tokens, pos)
        assert all(a.is_deleted() for a in given)
        ref, rings = ref_step(model, params, rings, tokens, pos, window)
        np.testing.assert_allclose(np.asarray(logits, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=1e-5, atol=1e-6)
        for n, ring in rings.items():
            np.testing.assert_array_equal(
                np.asarray(caches["unit"]["b0"]["kv"][n], np.float32),
                np.asarray(ring, np.float32), err_msg=f"step {t} {n}")
        tokens = jnp.argmax(ref, axis=-1).astype(jnp.int32)
