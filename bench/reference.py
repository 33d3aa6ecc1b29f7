"""Plain reference of the served models: the published decoder forward
in straightforward ``jax.numpy`` and float32, no cache and no batching.

It imports nothing of the serving stack.  Its weights are the
benchmark's own (``bench/weights.py``); where the configuration stores
weights at 4 bits it rounds them itself, by the storage rule that the
configuration states (``serve.w4``): symmetric, one f32 scale per entry
of a leaf's last axis (per layer for stacked leaves), absmax / 7, values
clipped to [-8, 7], and the dequantized weight kept in bf16.

Layer equations (Qwen2 and Mistral/Llama decoders alike): RMSNorm, then
grouped-query attention with rotary position embedding (rotate-half,
inverse frequencies ``theta^(-2i/hd)``), causal and, where the config
has one, limited to ``sliding_window`` keys; then RMSNorm and a SwiGLU
MLP; a final RMSNorm; logits against the head (the embedding table
where the config ties them).

``mode="fp8"`` is the control: every linear layer's activations (per
row) and weights (per output channel) rounded to float8 e4m3 with an
absmax scale, the precision below bf16 that a faster path would use.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _q8(a, axis):
    """Round ``a`` to float8 e4m3 with an absmax scale along ``axis``."""
    amax = jnp.maximum(jnp.max(jnp.abs(a), axis=axis, keepdims=True), 1e-30)
    s = amax / F8_MAX
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def quant_dequant(w, stacked: bool, bits: int = 4):
    """The configuration's 4-bit storage of one leaf, dequantized to bf16."""
    wf = w.astype(jnp.float32)
    qmax = (1 << (bits - 1)) - 1
    lead = (wf.shape[0],) if stacked else ()
    flat = wf.reshape(lead + (-1, wf.shape[-1]))
    amax = jnp.maximum(jnp.max(jnp.abs(flat), axis=-2, keepdims=True), 1e-8)
    scale = amax / qmax
    q = jnp.clip(jnp.round(flat / scale), -qmax - 1, qmax)
    deq = jax.lax.reduce_precision(q * scale, exponent_bits=8, mantissa_bits=7)
    return deq.astype(jnp.bfloat16).reshape(w.shape)


def stored_weights(cj: dict, tree: dict) -> dict:
    """The weights as the configuration stores them, still bf16."""
    w4 = cj["serve"]["weights"] == "w4"
    names = set(cj["serve"].get("w4_leaves", ())) if w4 else set()

    def walk(node, stacked):
        out = {}
        for k, v in node.items():
            if isinstance(v, dict):
                out[k] = walk(v, stacked or k == "unit")
            elif k in names:
                out[k] = quant_dequant(v, stacked)
            else:
                out[k] = v
        return out

    return walk(tree, False)


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _rope(x, theta):
    """x: (S, heads, hd); rotate-half rotary embedding at 0..S-1."""
    s, hd = x.shape[0], x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def hidden(cj: dict, w: dict, tokens, mode: str = "f32"):
    """Final-normed hidden states (S, d) of one sequence ``tokens``."""
    f32 = lambda a: a.astype(jnp.float32)
    eps, theta = cj["rms_norm_eps"], cj["rope_theta"]
    h, kvh = cj["num_attention_heads"], cj["num_key_value_heads"]
    window = cj.get("sliding_window") if cj.get("use_sliding_window", True) \
        else None

    def lin(x, wt, spec):
        if mode == "fp8":
            x = _q8(x, -1)
            wt = _q8(wt, tuple(range(wt.ndim - 1)) if spec == "o" else 0)
        eq = {"in": "sd,dhk->shk", "o": "shk,hkd->sd", "mlp": "sd,df->sf"}
        return jnp.einsum(eq[spec], x, wt, precision=HI)

    s = tokens.shape[0]
    pos = jnp.arange(s)
    mask = pos[None, :] <= pos[:, None]
    if window is not None:
        mask = mask & (pos[None, :] > pos[:, None] - window)
    x = f32(w["embed"])[tokens]

    def layer(x, lp):
        a = lp["attn"]
        y = _rms(x, lp["ln1"], eps)
        q = lin(y, f32(a["wq"]), "in")
        k = lin(y, f32(a["wk"]), "in")
        v = lin(y, f32(a["wv"]), "in")
        if "bq" in a:
            q, k, v = q + f32(a["bq"]), k + f32(a["bk"]), v + f32(a["bv"])
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, h // kvh, axis=1)
        v = jnp.repeat(v, h // kvh, axis=1)
        sc = jnp.einsum("qhk,shk->hqs", q, k, precision=HI) \
            * q.shape[-1] ** -0.5
        sc = jnp.where(mask[None], sc, -jnp.inf)
        o = jnp.einsum("hqs,shk->qhk", jax.nn.softmax(sc, axis=-1), v,
                       precision=HI)
        x = x + lin(o, f32(a["wo"]), "o")
        m = lp["mlp"]
        y = _rms(x, lp["ln2"], eps)
        g = lin(y, f32(m["w_gate"]), "mlp")
        u = lin(y, f32(m["w_up"]), "mlp")
        x = x + lin(jax.nn.silu(g) * u, f32(m["w_down"]), "mlp")
        return x, None

    x, _ = jax.lax.scan(layer, x, w["unit"]["b0"])
    return _rms(x, w["final_norm"], eps)


def head_matrix(cj: dict, w: dict):
    """(d, V) f32."""
    if cj["tie_word_embeddings"]:
        return w["embed"].astype(jnp.float32).T
    return w["head"].astype(jnp.float32)


def logits_rows(cj: dict, w: dict, tokens, start, rows: int,
                mode: str = "f32"):
    """Logits (rows, V) at positions ``start .. start + rows - 1``."""
    hs = hidden(cj, w, tokens, mode)
    hs = jax.lax.dynamic_slice_in_dim(hs, start, rows, axis=0)
    wh = head_matrix(cj, w)
    if mode == "fp8":
        hs, wh = _q8(hs, -1), _q8(wh, 0)
    return jnp.einsum("sd,dv->sv", hs, wh, precision=HI)
