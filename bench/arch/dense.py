"""The dense decoder (Qwen2, Mistral and Llama alike): RMSNorm, then
grouped-query attention with rotary position embedding, then RMSNorm
and a SwiGLU MLP, in every layer; a final RMSNorm; logits against the
head, or against the embedding table where the configuration ties them.

The serving stack's tree is one scanned unit ``unit/b0`` of
``attn/{wq,wk,wv,wo}`` (and q/k/v biases where the config has them) and
``mlp/{w_gate,w_up,w_down}``.  A configuration that names experts, a
latent KV rank or more than one layer type is refused: this module
builds none of them.

Weights.  Every matrix, embedding and bias is drawn from a normal with
the published ``initializer_range`` as its standard deviation and stored
in bf16; every norm weight is stored as its offset from 1 (the stack
scales by ``1 + w``), drawn with standard deviation 0.1 in f32.  The
program and the reference each make the tree anew from the key, in one
jitted call, so neither side takes anything that the other made.  Where
the configuration stores weights at 4 bits (``serve.weights == "w4"``),
the program's tree goes through ``qweight.quantize_tree`` and the
reference rounds its own copy by the storage rule the configuration
states: symmetric, one f32 scale per entry of a leaf's last axis (per
layer for stacked leaves), absmax / 7, values clipped to [-8, 7], the
dequantized weight kept in bf16.

Reference.  The published decoder forward in straightforward
``jax.numpy`` and float32, no cache and no batching: rotate-half rotary
embedding with inverse frequencies ``theta^(-2i/hd)``, causal and, where
the config has one, limited to ``sliding_window`` keys.  It imports
nothing of the serving stack.  ``mode="fp8"`` is the control: every
linear layer's activations (per row) and weights (per output channel)
rounded to float8 e4m3 with an absmax scale, the precision below bf16
that a faster path would use.

Cost of a decode step.  What is counted is what the algorithm needs,
whatever implements it:

* every weight read once, at its stored precision (bf16: 2 bytes a
  parameter; w4: half a byte a parameter plus one f32 scale per output
  channel of each stored matrix), except the embedding table, of which
  a step needs only the rows of its tokens -- unless the head is tied
  to it, when the head reads the whole table;
* the keys and values of the live positions of the active lanes, read
  once, and the new position's written once (bf16 cache);
* the logits written once (bf16);
* FLOPs: 2 x matmul parameters x lanes, plus 4 x heads x head size per
  live key per layer (scores and the weighted sum of values).

A program that reads fewer bytes than this (packed weights consumed as
they are, a cache that reads only live positions) comes closer to the
least time; none can read fewer and still compute the step.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

NORM_STD = 0.1
HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0

#: keys of parts this module does not build: experts and a latent KV rank
REFUSED = ("num_experts", "num_local_experts", "n_routed_experts",
           "moe_intermediate_size", "kv_lora_rank")


# ---------------------------------------------------------------------------
# The serving stack's model
# ---------------------------------------------------------------------------
def model_config(cj: dict):
    """The serving stack's ``ModelConfig`` from the published keys."""
    from repro.configs.base import ModelConfig

    named = [k for k in REFUSED if cj.get(k)]
    if named:
        raise ValueError(f"{cj['name']}: the dense decoder builds no "
                         f"experts or latent KV; the config names {named}")
    kinds = sorted(set(cj.get("layer_types") or ()))
    if len(kinds) > 1:
        raise ValueError(f"{cj['name']}: the dense decoder builds one kind "
                         f"of layer; layer_types holds {kinds}")
    if cj["hidden_act"] != "silu":
        raise ValueError(f"unsupported hidden_act {cj['hidden_act']!r}")
    window = (cj.get("sliding_window")
              if cj.get("use_sliding_window", True) else None)
    return ModelConfig(
        name=cj["name"], family="dense",
        n_layers=cj["num_hidden_layers"], d_model=cj["hidden_size"],
        n_heads=cj["num_attention_heads"],
        n_kv_heads=cj["num_key_value_heads"],
        d_ff=cj["intermediate_size"], vocab=cj["vocab_size"],
        head_dim=cj.get("head_dim"), qkv_bias=cj["attention_bias"],
        sliding_window=window, rope_theta=float(cj["rope_theta"]),
        tie_embeddings=cj["tie_word_embeddings"],
        norm_eps=cj["rms_norm_eps"], mlp_variant="swiglu")


def layout(cj: dict) -> dict:
    """Leaf path -> (shape, dtype) of the tree the serving stack takes."""
    d, h = cj["hidden_size"], cj["num_attention_heads"]
    kv, f = cj["num_key_value_heads"], cj["intermediate_size"]
    L, V = cj["num_hidden_layers"], cj["vocab_size"]
    hd = cj.get("head_dim") or d // h
    bf, f32 = jnp.bfloat16, jnp.float32
    out = {
        ("embed",): ((V, d), bf),
        ("final_norm",): ((d,), f32),
        ("unit", "b0", "ln1"): ((L, d), f32),
        ("unit", "b0", "ln2"): ((L, d), f32),
        ("unit", "b0", "attn", "wq"): ((L, d, h, hd), bf),
        ("unit", "b0", "attn", "wk"): ((L, d, kv, hd), bf),
        ("unit", "b0", "attn", "wv"): ((L, d, kv, hd), bf),
        ("unit", "b0", "attn", "wo"): ((L, h, hd, d), bf),
        ("unit", "b0", "mlp", "w_gate"): ((L, d, f), bf),
        ("unit", "b0", "mlp", "w_up"): ((L, d, f), bf),
        ("unit", "b0", "mlp", "w_down"): ((L, f, d), bf),
    }
    if cj["attention_bias"]:
        out[("unit", "b0", "attn", "bq")] = ((L, h, hd), bf)
        out[("unit", "b0", "attn", "bk")] = ((L, kv, hd), bf)
        out[("unit", "b0", "attn", "bv")] = ((L, kv, hd), bf)
    if not cj["tie_word_embeddings"]:
        out[("head",)] = ((d, V), bf)
    return out


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------
def _make(cj: dict, key) -> dict:
    """The bf16 weight tree, as nested dicts.  Call under ``jax.jit``."""
    std = cj["initializer_range"]
    tree: dict = {}
    for i, (path, (shape, dtype)) in enumerate(sorted(layout(cj).items())):
        k = jax.random.fold_in(key, i)
        s = NORM_STD if dtype == jnp.float32 else std
        leaf = jax.random.normal(k, shape, jnp.float32) * s
        if dtype == jnp.bfloat16:
            # round here, so that no later fusion may quantize the f32
            # value in place of the bf16 one it stands for
            leaf = jax.lax.reduce_precision(leaf, exponent_bits=8,
                                            mantissa_bits=7)
        leaf = leaf.astype(dtype)
        node = tree
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = leaf
    return tree


def program_weights(cj: dict, key) -> dict:
    """The tree ``ServeEngine`` takes, made on the device in one call."""
    if cj["serve"]["weights"] == "w4":
        from repro.models import qweight

        make = jax.jit(lambda k: qweight.quantize_tree(
            _make(cj, k), bits=4, names=set(cj["serve"]["w4_leaves"])))
    else:
        make = jax.jit(lambda k: _make(cj, k))
    return make(key)


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


def _stored(cj: dict, tree: dict) -> dict:
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


def reference_weights(cj: dict, key) -> dict:
    """The benchmark's weights again, made anew from the key, as the
    configuration stores them, in bf16 for the reference."""
    return jax.jit(lambda k: _stored(cj, _make(cj, k)))(key)


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------
def _q8(a, axis):
    """Round ``a`` to float8 e4m3 with an absmax scale along ``axis``."""
    amax = jnp.maximum(jnp.max(jnp.abs(a), axis=axis, keepdims=True), 1e-30)
    s = amax / F8_MAX
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


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


def _hidden(cj: dict, w: dict, tokens, mode: str):
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


def _head_matrix(cj: dict, w: dict):
    """(d, V) f32."""
    if cj["tie_word_embeddings"]:
        return w["embed"].astype(jnp.float32).T
    return w["head"].astype(jnp.float32)


def logits_rows(cj: dict, w: dict, tokens, start, rows: int, mode: str):
    """Logits (rows, V) at positions ``start .. start + rows - 1``."""
    if mode not in ("f32", "fp8"):
        raise ValueError(f"unknown reference mode {mode!r}")
    hs = _hidden(cj, w, tokens, mode)
    hs = jax.lax.dynamic_slice_in_dim(hs, start, rows, axis=0)
    wh = _head_matrix(cj, w)
    if mode == "fp8":
        hs, wh = _q8(hs, -1), _q8(wh, 0)
    return jnp.einsum("sd,dv->sv", hs, wh, precision=HI)


# ---------------------------------------------------------------------------
# Work of one decode step
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Shape:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    qkv_bias: bool
    weight_bits: int          # 16 (bf16) or 4 (w4 bit-planes)

    @classmethod
    def from_config(cls, cj: dict) -> "Shape":
        d, h = cj["hidden_size"], cj["num_attention_heads"]
        return cls(layers=cj["num_hidden_layers"], d=d, heads=h,
                   kv_heads=cj["num_key_value_heads"],
                   head_dim=cj.get("head_dim") or d // h,
                   d_ff=cj["intermediate_size"], vocab=cj["vocab_size"],
                   tied=bool(cj["tie_word_embeddings"]),
                   qkv_bias=bool(cj["attention_bias"]),
                   weight_bits=16 if cj["serve"]["weights"] == "bf16" else 4)


def _layer_matrices(s: Shape):
    """(in size, out channels) of each stored matrix of one layer, with
    the output channels as the storage scales them: the w4 store keeps
    one scale per entry of a leaf's last axis."""
    return [(s.d * s.heads, s.head_dim),              # wq (d, H, hd)
            (s.d * s.kv_heads, s.head_dim),           # wk (d, KV, hd)
            (s.d * s.kv_heads, s.head_dim),           # wv
            (s.heads * s.head_dim, s.d),              # wo (H, hd, d)
            (s.d, s.d_ff),                            # w_gate
            (s.d, s.d_ff),                            # w_up
            (s.d_ff, s.d)]                            # w_down


def matmul_params(s: Shape) -> int:
    """Parameters multiplied once per token: the layers' matrices and
    the head."""
    per_layer = sum(k * n for k, n in _layer_matrices(s))
    return s.layers * per_layer + s.d * s.vocab


def _matrix_bytes(s: Shape, k: int, n: int) -> float:
    if s.weight_bits == 16:
        return 2.0 * k * n
    return k * n * s.weight_bits / 8 + 4.0 * n


def weight_bytes(s: Shape, lanes: int) -> float:
    """Bytes of weights one decode step of ``lanes`` tokens must read."""
    per_layer = sum(_matrix_bytes(s, k, n) for k, n in _layer_matrices(s))
    per_layer += 4.0 * 2 * s.d                        # two f32 norm weights
    if s.qkv_bias:
        per_layer += 2.0 * (s.heads + 2 * s.kv_heads) * s.head_dim
    total = s.layers * per_layer + 4.0 * s.d          # final norm
    total += _matrix_bytes(s, s.d, s.vocab)           # head: whole table
    if not s.tied:                                    # the tokens' rows
        total += _matrix_bytes(s, lanes, s.d)
    return total


def kv_bytes_per_position(s: Shape) -> int:
    """Keys and values of one position over all layers, bf16."""
    return s.layers * 2 * s.kv_heads * s.head_dim * 2


def decode_step(cj: dict, lens):
    """(FLOPs, bytes) of one decode step whose active lanes attend over
    ``lens[i]`` positions each, the new position included.  Every live
    key counts in every layer (no cell reaches a sliding window), so
    only the lanes' number and the keys' sum matter."""
    s = Shape.from_config(cj)
    lanes, live_keys = len(lens), sum(lens)
    flops = 2.0 * matmul_params(s) * lanes
    flops += 4.0 * s.layers * s.heads * s.head_dim * live_keys
    nbytes = weight_bytes(s, lanes)
    nbytes += kv_bytes_per_position(s) * (live_keys + lanes)  # read + write
    nbytes += 2.0 * s.vocab * lanes                           # logits
    return flops, nbytes
