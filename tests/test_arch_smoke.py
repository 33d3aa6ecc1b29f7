"""Per-architecture smoke tests: reduced configs, one forward + one
train-ish step on CPU, asserting output shapes and no NaNs; plus a
decode-vs-prefill consistency check per family."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models.model import LM

ARCHS = configs.list_archs()


def _batch(cfg, b=2, s=16, key=0):
    rng = np.random.default_rng(key)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab, (b, s)), jnp.int32)}
    if cfg.is_encdec:
        batch["src_embeds"] = jnp.asarray(
            rng.normal(0, 1, (b, s, cfg.d_model)), jnp.bfloat16)
    return batch


@pytest.mark.slow     # full-model jit per arch: minutes on CPU
@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finite(arch):
    cfg = configs.get_config(arch, smoke=True)
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    enc_out = enc_pos = None
    if cfg.is_encdec:
        b, s = batch["src_embeds"].shape[:2]
        enc_pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        enc_out = model.encode(params, batch["src_embeds"], enc_pos)
        assert enc_out.shape == (b, s, cfg.d_model)
    logits, aux = jax.jit(model.apply)(params, tokens=batch["tokens"],
                                       enc_out=enc_out, enc_pos=enc_pos)
    assert logits.shape == (2, 16, cfg.vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()


@pytest.mark.slow     # full-model jit per arch: minutes on CPU
@pytest.mark.parametrize("arch", ARCHS)
def test_loss_and_grad_step(arch):
    cfg = configs.get_config(arch, smoke=True)
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(1))
    batch = _batch(cfg, key=1)

    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, batch)
    assert np.isfinite(float(loss))
    flat = jax.tree.leaves(grads)
    assert all(np.isfinite(np.asarray(g, np.float32)).all() for g in flat)
    # SGD step changes the loss
    new_params = jax.tree.map(lambda p, g: p - 0.5 * g.astype(p.dtype),
                              params, grads)
    loss2 = jax.jit(model.loss)(new_params, batch)
    assert float(loss2) != float(loss)


@pytest.mark.slow     # full-model jit per arch: minutes on CPU
@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_prefill(arch):
    """Prefill s tokens, then decode token s; compare against a full
    forward over s+1 tokens (the KV/state caches must be consistent)."""
    cfg = configs.get_config(arch, smoke=True)
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(2))
    b, s = 2, 8
    rng = np.random.default_rng(3)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (b, s + 1)), jnp.int32)

    enc_out = enc_pos = None
    if cfg.is_encdec:
        src = jnp.asarray(rng.normal(0, 1, (b, s, cfg.d_model)),
                          jnp.bfloat16)
        enc_pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        enc_out = model.encode(params, src, enc_pos)

    full, _ = model.apply(params, tokens=toks, enc_out=enc_out,
                          enc_pos=enc_pos)
    _, caches = model.prefill(params, tokens=toks[:, :s], capacity=s + 1,
                              enc_out=enc_out, enc_pos=enc_pos)
    step_logits, _ = model.decode_step(
        params, caches, toks[:, s:s + 1],
        jnp.full((b,), s, jnp.int32), enc_out=enc_out, enc_pos=enc_pos)

    got = np.asarray(step_logits[:, 0], np.float32)
    want = np.asarray(full[:, s], np.float32)
    np.testing.assert_allclose(got, want, rtol=0.05, atol=0.05)


def test_param_counts_plausible():
    """Full configs must be in the advertised parameter range."""
    expect = {
        "falcon-mamba-7b": (6e9, 9e9),
        "granite-20b": (18e9, 24e9),
        "llama3.2-1b": (1.0e9, 1.8e9),
        "qwen2-0.5b": (0.4e9, 0.7e9),
        "h2o-danube-1.8b": (1.5e9, 2.2e9),
        "granite-moe-3b-a800m": (2.5e9, 4e9),
        "mixtral-8x7b": (42e9, 50e9),
        "recurrentgemma-9b": (7e9, 11e9),
        "chameleon-34b": (30e9, 38e9),
        "seamless-m4t-large-v2": (1.2e9, 3e9),
        "deepseek-v2-lite": (15e9, 16.5e9),
    }
    for arch, (lo, hi) in expect.items():
        cfg = configs.get_config(arch)
        n = cfg.param_count()
        assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B not in [{lo/1e9}, {hi/1e9}]"
