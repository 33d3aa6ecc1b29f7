"""DeepSeek-V2-Lite in the serving stack: multi-head latent attention
(absorbed decode against the latent cache, expanded prefill), YaRN
rotary scaling, the dropless expert layer with un-normalised gates and
shared experts, the leading dense layer, 4-bit expert stacks, and the
engine's expert counters.  Smoke widths on the CPU, seeded weights."""

import dataclasses
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import attention as attn
from repro.models import moe, qweight
from repro.models.model import LM
from repro.serve.engine import Request, ServeEngine


def _smoke(**moe_kw):
    cfg = configs.get_config("deepseek-v2-lite", smoke=True)
    if moe_kw:
        cfg = dataclasses.replace(cfg,
                                  moe=dataclasses.replace(cfg.moe, **moe_kw))
    return cfg


def _f64(a):
    return np.asarray(jnp.asarray(a, jnp.float32), np.float64)


# ---------------------------------------------------------------------------
# Latent attention
# ---------------------------------------------------------------------------
def _rope_np(x, pos, cfg):
    """De-interleave, then rotate half against half (float64)."""
    half = x.shape[-1] // 2
    x = np.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    ang = pos * _f64(attn.rope_inv_freq(cfg))
    cos, sin = np.cos(ang), np.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def test_absorbed_mla_decode_equals_expanded_attention():
    """``mla_decode`` folds W_uk into the query and W_uv after the
    weighted sum of latents; expanding keys and values per head from
    the same cached latents (float64 here) gives the same output."""
    cfg = _smoke()
    p = jax.tree.map(lambda a: a.astype(jnp.float32),
                     attn.mla_init(jax.random.PRNGKey(0), cfg))
    b, s = 2, 7
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s + 1, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    cache = jax.jit(lambda p, x: attn.prefill_mla_cache(p, x, cfg, pos, 16))(
        p, x[:, :s])
    y, new = jax.jit(lambda p, x, c: attn.mla_decode(
        p, x, c, cfg, jnp.full((b,), s, jnp.int32)))(p, x[:, s:], cache)

    nope, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    xq = _f64(x[:, s])
    q = np.einsum("bd,dhk->bhk", xq, _f64(p["wq"]))
    q_pe = _rope_np(q[..., nope:], s, cfg)
    c = _f64(new["c"][:, :s + 1])                       # stored latents
    k_pe = _f64(new["k_pe"][:, :s + 1])
    k = np.concatenate([np.einsum("bsr,rhk->bshk", c, _f64(p["wk_b"])),
                        np.broadcast_to(k_pe[:, :, None, :],
                                        (b, s + 1, cfg.n_heads,
                                         k_pe.shape[-1]))], -1)
    v = np.einsum("bsr,rhk->bshk", c, _f64(p["wv_b"]))
    qq = np.concatenate([q[..., :nope], q_pe], -1)
    sc = np.einsum("bhk,bshk->bhs", qq, k) * attn.mla_softmax_scale(cfg)
    pr = np.exp(sc - sc.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    out = np.einsum("bhs,bshk->bhk", pr, v)
    want = np.einsum("bhk,hkd->bd", out, _f64(p["wo"]))
    assert np.asarray(new["pos"])[0, :s + 1].tolist() == list(range(s + 1))
    assert r == c.shape[-1]
    # f32 at highest precision against float64: rounding of ~1e-7
    # relative in each of a few hundred-term sums
    np.testing.assert_allclose(_f64(y[:, 0]), want, rtol=1e-4, atol=1e-5)


def test_latent_cache_holds_latent_and_rotary_key_only():
    cfg = configs.get_config("deepseek-v2-lite")
    c = jax.eval_shape(lambda: attn.init_mla_cache(cfg, 32, 1024))
    per_pos = sum(a.dtype.itemsize * a.shape[2]
                  for k, a in c.items() if k != "pos")
    assert per_pos == (512 + 64) * 2 == 1152           # one layer


@pytest.mark.parametrize("storage", ["bf16", "w4"])
def test_prefill_then_decode_equals_full_forward(storage):
    """Prefill, then decode through the latent cache (absorbed form),
    gives the full forward's (expanded form) logits.  The router picks
    every expert here (top_k = E), so that bf16 rounding, which differs
    between the two forms, cannot pick another set of experts."""
    cfg = _smoke(top_k=8)
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(2))
    if storage == "w4":
        params = qweight.quantize_tree(params, bits=4)
    b, s, n = 2, 6, 4
    toks = jnp.asarray(np.random.default_rng(3).integers(
        0, cfg.vocab, (b, s + n)), jnp.int32)
    full, _ = jax.jit(model.apply)(params, tokens=toks)
    _, caches = jax.jit(lambda p, t: model.prefill(p, tokens=t, capacity=16))(
        params, toks[:, :s])
    decode = jax.jit(model.decode_step)
    for i in range(n):
        step, caches = decode(params, caches, toks[:, s + i:s + i + 1],
                              jnp.full((b,), s + i, jnp.int32))
        # bf16 activations: the logits (|x| < 8, a bf16 step of at most
        # 0.03 there) differ by a few steps between the two forms
        np.testing.assert_allclose(np.asarray(step[:, 0], np.float32),
                                   np.asarray(full[:, s + i], np.float32),
                                   atol=0.1, rtol=0)


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------
def test_yarn_frequencies_and_softmax_scale_by_hand():
    cfg = configs.get_config("deepseek-v2-lite")
    inv = np.asarray(attn.rope_inv_freq(cfg), np.float64)
    # correction dims at 4096 positions, base 1e4, dim 64:
    # 64 ln(4096 / (32 * 2 pi)) / (2 ln 1e4) = 10.47 -> floor 10
    # 64 ln(4096 / (1 * 2 pi)) / (2 ln 1e4)  = 22.51 -> ceil 23
    base = 1e4 ** (-np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    want = base / 40 * ramp + base * (1 - ramp)
    # the stack computes in f32: a few units of its 6e-8 rounding
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    assert inv[0] == 1.0 and inv[10] == pytest.approx(base[10], rel=1e-6)
    assert inv[31] == pytest.approx(1e4 ** (-62 / 64) / 40, rel=1e-6)
    # 192^-0.5 x (0.1 x 0.707 x ln 40 + 1)^2 = 0.0721688 x 1.260804^2
    assert attn.mla_softmax_scale(cfg) == pytest.approx(0.1147222, abs=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert attn.yarn_mscale(40, 0.707) == pytest.approx(m)
    # cos and sin carry mscale(40, 0.707) / mscale(40, 0.707) = 1: a
    # roped vector keeps its norm, to f32 rounding of cos and sin at
    # angles up to 4000 radians (~4000 x 6e-8)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 5, 1, 64))
    pos = jnp.arange(5, dtype=jnp.int32)[None] * 1000
    y = attn._rope_mla(x, pos, cfg)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(y), axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5)


# ---------------------------------------------------------------------------
# The dropless expert layer
# ---------------------------------------------------------------------------
def test_dropless_moe_equals_per_token_loop_with_every_token_on_one_expert():
    """Every token routes to expert 0 (far past any capacity); the layer
    equals, token by token, the sum over its top-k of the un-normalised
    softmax gate times that expert, plus the shared experts; nothing is
    dropped."""
    cfg = _smoke()
    spec = cfg.moe
    p = moe.moe_init(jax.random.PRNGKey(5), cfg)
    t, d = 40, cfg.d_model
    u = np.zeros(d, np.float32)
    u[0] = 1.0
    noise = np.random.default_rng(6).normal(0, 1, (t, d)).astype(np.float32)
    noise[:, 0] = 0.0
    x = jnp.asarray(noise + 4.0 * u, jnp.bfloat16)[None]
    router = np.asarray(p["router"]).copy()
    router[0, 0] = 3.0                      # expert 0 tops every token
    p["router"] = jnp.asarray(router)
    y, _, counts = jax.jit(lambda p, x: moe.moe_dropless(p, x, cfg))(p, x)

    xf = _f64(x[0])
    logits = xf @ _f64(p["router"])
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)

    def swiglu(v, g, up, dn):
        a = v @ g
        return (a / (1 + np.exp(-a)) * (v @ up)) @ dn

    want = np.zeros((t, d))
    chosen = set()
    for i in range(t):
        top = np.argsort(-probs[i])[:spec.top_k]
        assert top[0] == 0
        chosen.update(top.tolist())
        for e in top:
            want[i] += probs[i, e] * swiglu(
                xf[i], _f64(p["w_gate"][e]), _f64(p["w_up"][e]),
                _f64(p["w_down"][e]))
        sp = p["shared"]
        want[i] += swiglu(xf[i], _f64(sp["w_gate"]), _f64(sp["w_up"]),
                          _f64(sp["w_down"]))
    got = _f64(y[0])
    # each expert product takes bf16 inputs and gives a bf16 output
    # (2^-9 relative each), and the sum is rounded to bf16 once more
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=0.02 * np.abs(want).max())
    assert int(counts["dropped"]) == 0
    assert int(counts["experts_hit"]) == len(chosen)


def test_dropless_moe_drops_nothing_at_any_length():
    cfg = _smoke()
    p = moe.moe_init(jax.random.PRNGKey(7), cfg)
    for b, s in [(1, 1), (3, 1), (2, 37)]:
        x = jax.random.normal(jax.random.PRNGKey(s), (b, s, cfg.d_model),
                              jnp.bfloat16)
        _, _, counts = jax.jit(lambda p, x: moe.moe_dropless(p, x, cfg))(p, x)
        assert int(counts["dropped"]) == 0
        assert 1 <= int(counts["experts_hit"]) <= cfg.moe.num_experts


# the capacity path's output on the smoke configs of the existing MoE
# models (sha256 of the bf16 output, first 16 hex digits, and the aux
# loss), recorded before the dropless layer was added
CAPACITY_PINNED = {
    "mixtral-8x7b": ("02d166e9062fb7af", 1.0277302265167236),
    "granite-moe-3b-a800m": ("779cf318996bf108", 1.0085450410842896),
}


@pytest.mark.parametrize("arch", sorted(CAPACITY_PINNED))
def test_capacity_path_unchanged(arch):
    cfg = configs.get_config(arch, smoke=True)
    assert not cfg.moe.dropless and cfg.moe.norm_topk
    p = moe.moe_init(jax.random.PRNGKey(11), cfg)
    x = jax.random.normal(jax.random.PRNGKey(12), (2, 24, cfg.d_model),
                          jnp.float32).astype(jnp.bfloat16)
    y, aux = jax.jit(lambda p, x: moe.moe_apply(p, x, cfg))(p, x)
    digest = hashlib.sha256(np.asarray(y).tobytes()).hexdigest()[:16]
    assert (digest, float(aux)) == CAPACITY_PINNED[arch]


# ---------------------------------------------------------------------------
# Layers, storage, counters
# ---------------------------------------------------------------------------
def test_leading_layer_is_dense_and_the_rest_are_expert_layers():
    cfg = configs.get_config("deepseek-v2-lite")
    model = LM(cfg)
    assert model.lead == ["dense"]
    assert (model.unit, model.n_units, model.rest) == (["attn"], 26, [])
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    lead = shapes["lead"][0]
    assert "moe" not in lead and lead["mlp"]["w_gate"].shape == (2048, 10944)
    b0 = shapes["unit"]["b0"]
    assert "mlp" not in b0
    assert b0["moe"]["w_gate"].shape == (26, 64, 2048, 1408)
    assert b0["moe"]["shared"]["w_down"].shape == (26, 2816, 2048)
    assert b0["attn"]["wkv_a"].shape == (26, 2048, 576)
    assert cfg.param_count() == 15_706_371_584


def test_expert_stack_w4_keeps_a_scale_per_expert():
    """Values on each expert's own int4 grid come back exactly: one scale
    per (layer, expert, output channel)."""
    rng = np.random.default_rng(8)
    ints = rng.integers(-7, 8, (2, 3, 64, 16))
    ints[..., 0, :] = 7                              # pin every amax
    steps = np.array([0.01, 0.1, 1.0])[None, :, None, None]
    w = jnp.asarray(ints * steps, jnp.float32)
    q = qweight.quantize_tree({"unit": {"moe": {"w_up": w}}},
                              bits=4)["unit"]["moe"]["w_up"]
    assert isinstance(q, qweight.PackedWeight)
    assert q.planes.shape == (2, 3, 4, 2, 16) and q.scale.shape == (2, 3, 16)
    for layer in range(2):
        one = qweight.PackedWeight(q.planes[layer], q.scale[layer], q.shape)
        np.testing.assert_allclose(np.asarray(qweight.dq(one, jnp.float32)),
                                   np.asarray(w[layer]), rtol=1e-6)


def test_engine_counts_the_experts_each_decode_step_reached():
    cfg = _smoke()
    model = LM(cfg)
    params = qweight.quantize_tree(model.init(jax.random.PRNGKey(9)), bits=4)
    eng = ServeEngine(model, params, batch_slots=3, capacity=32)
    for i in range(5):
        eng.add(Request(rid=i, max_new=4 + i,
                        prompt=np.arange(3 + 2 * i, dtype=np.int32) % 50))
    done = eng.run()
    assert len(done) == 5
    st = eng.stats
    launches = st["decode_launch_n"]
    n_moe = cfg.n_layers - cfg.first_dense_layers
    assert st["moe_layer_steps"] == launches * n_moe > 0
    assert launches * n_moe <= st["moe_experts_hit"] \
        <= launches * n_moe * cfg.moe.num_experts
    assert st["moe_dropped"] == 0
    dense = LM(configs.get_config("qwen2-0.5b", smoke=True))
    assert dense.counters == ()
    eng = ServeEngine(dense, dense.init(jax.random.PRNGKey(0)),
                      batch_slots=2, capacity=32)
    assert not any(k.startswith("moe_") for k in eng.stats)
