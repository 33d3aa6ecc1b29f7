"""DeepSeek-V2 (``model_type`` ``deepseek_v2``, without a query LoRA):
multi-head latent attention (MLA) with a YaRN-scaled rotary key, then
either a dense SwiGLU MLP (the first ``first_k_dense_replace`` layers)
or an expert layer: a softmax router over ``n_routed_experts``, the
greedy top ``num_experts_per_tok``, and ``n_shared_experts`` shared
experts that act as one SwiGLU of width ``n_shared_experts x
moe_intermediate_size``; a final RMSNorm and an untied head.

The layer, following the published equations (HF
``modeling_deepseek.py``, DeepSeek-V2):

* q = x W_q, split per head into q_nope (``qk_nope_head_dim``) and q_pe
  (``qk_rope_head_dim``);
* [c ; k_pe] = x W_kv_a; c = RMSNorm(c) (``kv_lora_rank`` wide); k_pe is
  one rotary key shared by every head;
* k_h = [c W_uk,h ; k_pe], v_h = c W_uv,h (``kv_b_proj`` split per head
  into its key and value parts);
* the rotary part of q and k is de-interleaved (the pairs' first
  elements, then their second) and rotated half against half, with
  YaRN inverse frequencies: theta^(-2i/d) blended with theta^(-2i/d) /
  factor by a linear ramp between the correction dims of ``beta_fast``
  and ``beta_slow`` rotations at ``original_max_position_embeddings``;
  cos and sin scaled by mscale(factor, mscale) / mscale(factor,
  mscale_all_dim), mscale(s, m) = 0.1 m ln s + 1;
* softmax scale (nope + rope)^-0.5 x mscale(factor, mscale_all_dim)^2;
* expert layer: y = sum over the top k of gate x expert(x) + shared(x),
  the gates the router's softmax probabilities, not renormalised
  (``norm_topk_prob`` false) and scaled by ``routed_scaling_factor``.

The serving stack's tree: ``lead/0`` (the dense layer) and one scanned
unit ``unit/b0`` of the expert layers, each with ``attn/{wq, wkv_a,
kv_norm, wk_b, wv_b, wo}``; ``lead/0/mlp/{w_gate, w_up, w_down}``;
``unit/b0/moe/{router, w_gate, w_up, w_down}`` (experts stacked (E, in,
out) a layer) and ``unit/b0/moe/shared/{w_gate, w_up, w_down}``.

Departures from the published model, all of them the serving stack's:

* norm weights are offsets from 1 (the stack scales by ``1 + w``);
* the router's weight is stored in f32 (its values bf16-rounded); the
  router runs in f32 at highest precision, as published;
* rotary cos and sin are computed in f32 and the roped part rounded
  to bf16 once (published: cos and sin cached in the model's dtype);
* the latent cache is bf16; decode attends to it in the absorbed form
  (W_uk folded into the query, W_uv applied after the weighted sum of
  latents), in f32 at highest precision; prefill uses the expanded form
  with bf16 keys and values;
* the routed experts are summed in f32 and rounded to bf16 once before
  the shared experts are added, as published; the expert products run
  on bf16 inputs with f32 accumulation.

Weights.  Every leaf, and every layer of a stacked leaf, is drawn from
its own key: leaf i (of the layout's sorted paths) from ``fold_in(key,
i)``, layer l of it from ``fold_in(fold_in(key, i), l)``.  Matrices and
embeddings are normal with ``initializer_range`` as the standard
deviation, rounded to bf16 (the router too); norm weights are offsets
with standard deviation 0.1 in f32.  Built whole, the model is 31 GB in
bf16, so ``program_weights`` builds one layer of one leaf at a time and
packs it at 4 bits before the next (a stacked expert leaf is 4.8 GB in
bf16), and the reference makes each layer anew inside its own forward
pass, rounds it by the configuration's storage rule and drops it: its
"weights" are the key alone.  The storage rule (``serve.w4_leaves``):
symmetric, one f32 scale per entry of a leaf's last axis, per layer and
per expert, absmax / 7, values in [-8, 7], dequantized to bf16.

Reference.  The forward pass in plain ``jax.numpy`` and float32 at
highest precision, no cache and no batching, with MLA in the expanded
form (keys and values up-projected from c per head), so it is a
different formulation from the program's absorbed decode, and the
experts as the per-token sum over the chosen k (computed as every
expert on every position, weighted by a gate that is zero off the
chosen k).  It imports nothing of the serving stack.  ``mode="fp8"``
is the control: every linear layer but the router with its activations
(per row) and weights (per output channel, per expert) rounded to
float8 e4m3.

Cost of a decode step.  As ``dense``: every weight read once at its
stored precision (w4: half a byte a parameter plus an f32 scale per
output channel, per expert for the experts), the embedding rows of the
step's tokens, the whole head, the latent cache of the live positions
read once ((kv_lora_rank + qk_rope_head_dim) x 2 bytes a position a
layer) and the new position's written once, the logits written once.
Routed experts: a step of ``lanes`` tokens reads E (1 - (1 - k/E)^lanes)
experts in each expert layer, the expected number of distinct experts
when every token picks k of E uniformly at random (61.3 of 64 at 32
lanes); the stack's ``moe_experts_hit`` counter shows how far the real
routing is from it.  The dense layer, the shared experts, the router and
the attention weights count once.  FLOPs: 2 x the parameters a token
multiplies (attention projections, k/v up-projections, the dense MLP or
the k chosen experts and the shared experts, the router, the head) per
lane, plus per live key per layer 2 x heads x (kv_lora_rank + rope) for
the scores and 2 x heads x kv_lora_rank for the weighted sum of latents.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

NORM_STD = 0.1
HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0
NORMS = ("ln1", "ln2", "kv_norm", "final_norm")
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def _is_expert_stack(path) -> bool:
    return len(path) >= 2 and path[-2] == "moe" and path[-1] in EXPERT_LEAVES


# ---------------------------------------------------------------------------
# The serving stack's model
# ---------------------------------------------------------------------------
def _check(cj: dict) -> None:
    """Raise on published settings this module does not build."""
    want = {"hidden_act": "silu", "q_lora_rank": None,
            "scoring_func": "softmax", "topk_method": "greedy",
            "moe_layer_freq": 1, "n_group": 1, "topk_group": 1,
            "attention_bias": False, "tie_word_embeddings": False,
            "routed_scaling_factor": 1}
    bad = {k: cj.get(k) for k, v in want.items() if cj.get(k, v) != v}
    rs = cj.get("rope_scaling") or {}
    if rs and rs.get("type") != "yarn":
        bad["rope_scaling.type"] = rs.get("type")
    if bad:
        raise ValueError(f"{cj['name']}: the DeepSeek-V2 module does not "
                         f"build {bad}")


def model_config(cj: dict):
    """The serving stack's ``ModelConfig`` from the published keys."""
    _check(cj)
    try:
        from repro.configs.base import ModelConfig, MoESpec, YarnSpec
    except ImportError as e:
        raise SystemExit(f"{cj['name']}: this serving stack has no latent "
                         f"attention or YaRN rotary scaling ({e})")
    rs = cj.get("rope_scaling")
    yarn = None if not rs else YarnSpec(
        factor=float(rs["factor"]),
        original_max_position=int(rs["original_max_position_embeddings"]),
        beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
        mscale=float(rs["mscale"]),
        mscale_all_dim=float(rs["mscale_all_dim"]))
    return ModelConfig(
        name=cj["name"], family="moe",
        n_layers=cj["num_hidden_layers"], d_model=cj["hidden_size"],
        n_heads=cj["num_attention_heads"],
        n_kv_heads=cj["num_key_value_heads"],
        d_ff=cj["intermediate_size"], vocab=cj["vocab_size"],
        rope_theta=float(cj["rope_theta"]),
        tie_embeddings=False, norm_eps=cj["rms_norm_eps"],
        mlp_variant="swiglu",
        moe=MoESpec(num_experts=cj["n_routed_experts"],
                    top_k=cj["num_experts_per_tok"],
                    d_ff=cj["moe_intermediate_size"],
                    n_shared=cj["n_shared_experts"],
                    norm_topk=bool(cj["norm_topk_prob"]),
                    dropless=True),
        kv_lora_rank=cj["kv_lora_rank"],
        qk_nope_head_dim=cj["qk_nope_head_dim"],
        qk_rope_head_dim=cj["qk_rope_head_dim"],
        v_head_dim=cj["v_head_dim"], rope_scaling=yarn,
        first_dense_layers=cj["first_k_dense_replace"])


def layout(cj: dict) -> dict:
    """Leaf path -> (shape, dtype) of the tree the serving stack takes."""
    d, h, V = cj["hidden_size"], cj["num_attention_heads"], cj["vocab_size"]
    r, nope = cj["kv_lora_rank"], cj["qk_nope_head_dim"]
    rope, hv = cj["qk_rope_head_dim"], cj["v_head_dim"]
    k_dense = cj["first_k_dense_replace"]
    L = cj["num_hidden_layers"] - k_dense
    E, f = cj["n_routed_experts"], cj["moe_intermediate_size"]
    fs, fd = cj["n_shared_experts"] * f, cj["intermediate_size"]
    bf, f32 = jnp.bfloat16, jnp.float32

    def attn(prefix, lead):
        return {
            prefix + ("ln1",): (lead + (d,), f32),
            prefix + ("ln2",): (lead + (d,), f32),
            prefix + ("attn", "wq"): (lead + (d, h, nope + rope), bf),
            prefix + ("attn", "wkv_a"): (lead + (d, r + rope), bf),
            prefix + ("attn", "kv_norm"): (lead + (r,), f32),
            prefix + ("attn", "wk_b"): (lead + (r, h, nope), bf),
            prefix + ("attn", "wv_b"): (lead + (r, h, hv), bf),
            prefix + ("attn", "wo"): (lead + (h, hv, d), bf),
        }

    out = {("embed",): ((V, d), bf), ("head",): ((d, V), bf),
           ("final_norm",): ((d,), f32)}
    for i in range(k_dense):
        out.update(attn(("lead", i), ()))
        out[("lead", i, "mlp", "w_gate")] = ((d, fd), bf)
        out[("lead", i, "mlp", "w_up")] = ((d, fd), bf)
        out[("lead", i, "mlp", "w_down")] = ((fd, d), bf)
    u = ("unit", "b0")
    out.update(attn(u, (L,)))
    out[u + ("moe", "router")] = ((L, d, E), f32)
    out[u + ("moe", "w_gate")] = ((L, E, d, f), bf)
    out[u + ("moe", "w_up")] = ((L, E, d, f), bf)
    out[u + ("moe", "w_down")] = ((L, E, f, d), bf)
    out[u + ("moe", "shared", "w_gate")] = ((L, d, fs), bf)
    out[u + ("moe", "shared", "w_up")] = ((L, d, fs), bf)
    out[u + ("moe", "shared", "w_down")] = ((L, fs, d), bf)
    return out


# ---------------------------------------------------------------------------
# Weights, a leaf and a layer at a time
# ---------------------------------------------------------------------------
def _leaves(cj: dict):
    """(index, path, per-layer shape, dtype, stacked) of every leaf."""
    for i, (path, (shape, dtype)) in enumerate(sorted(layout(cj).items(),
                                                      key=lambda kv: str(kv[0]))):
        stacked = path[0] == "unit"
        yield i, path, tuple(shape[1:] if stacked else shape), dtype, stacked


def _draw(cj: dict, path, shape, dtype, key):
    """One layer of one leaf as the program stores it before packing."""
    if path[-1] in NORMS:
        return jax.random.normal(key, shape, jnp.float32) * NORM_STD
    w = jax.random.normal(key, shape, jnp.float32) * cj["initializer_range"]
    # round here, so that no later fusion may quantize the f32 value in
    # place of the bf16 one it stands for
    w = jax.lax.reduce_precision(w, exponent_bits=8, mantissa_bits=7)
    return w.astype(dtype)


def _w4(cj: dict, path) -> bool:
    return (cj["serve"]["weights"] == "w4"
            and path[-1] in cj["serve"].get("w4_leaves", ()))


def _set(tree: dict, path, leaf) -> None:
    node = tree
    for name in path[:-1]:
        node = node.setdefault(name, {})
    node[path[-1]] = leaf


def _lists(tree):
    """Turn the integer-keyed ``lead`` node into the stack's list."""
    if isinstance(tree, dict):
        if tree and all(isinstance(k, int) for k in tree):
            return [_lists(tree[i]) for i in range(len(tree))]
        return {k: _lists(v) for k, v in tree.items()}
    return tree


def program_weights(cj: dict, key) -> dict:
    """The tree ``ServeEngine`` takes: each leaf made by its own call, a
    stacked leaf one layer at a time (``lax.map``), each layer packed at
    4 bits where the configuration stores it so before the next is
    drawn."""
    from repro.models import qweight

    L = cj["num_hidden_layers"] - cj["first_k_dense_replace"]
    tree: dict = {}
    for i, path, shape, dtype, stacked in _leaves(cj):
        def one(k, path=path, shape=shape, dtype=dtype):
            w = _draw(cj, path, shape, dtype, k)
            if _w4(cj, path):
                return qweight._quantize_leaf(
                    w, 4, experts=_is_expert_stack(path))
            return w

        if stacked:
            make = jax.jit(lambda k, one=one: jax.lax.map(
                lambda l: one(jax.random.fold_in(k, l)),
                jnp.arange(L, dtype=jnp.uint32)))
        else:
            make = jax.jit(one)
        _set(tree, path, jax.block_until_ready(
            make(jax.random.fold_in(key, i))))
    return _lists(tree)


def reference_weights(cj: dict, key) -> dict:
    """The reference's weights: the key they are all made from, layer by
    layer, inside ``logits_rows``."""
    return {"key": jnp.asarray(key)}


def quant_dequant(w, experts: bool, bits: int = 4):
    """The configuration's 4-bit storage of one layer of a leaf,
    dequantized to bf16: one scale per entry of the last axis (and per
    expert for an expert stack)."""
    wf = w.astype(jnp.float32)
    qmax = (1 << (bits - 1)) - 1
    lead = wf.shape[:1] if experts else ()
    flat = wf.reshape(lead + (-1, wf.shape[-1]))
    amax = jnp.maximum(jnp.max(jnp.abs(flat), axis=-2, keepdims=True), 1e-8)
    scale = amax / qmax
    q = jnp.clip(jnp.round(flat / scale), -qmax - 1, qmax)
    deq = jax.lax.reduce_precision(q * scale, exponent_bits=8, mantissa_bits=7)
    return deq.astype(jnp.bfloat16).reshape(w.shape)


def _stored(cj: dict, path, shape, dtype, key):
    """One layer of one leaf as the configuration stores it, in f32."""
    w = _draw(cj, path, shape, dtype, key)
    if _w4(cj, path):
        w = quant_dequant(w, _is_expert_stack(path))
    return w.astype(jnp.float32)


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


def _yarn_mscale(scale: float, m: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def yarn_inv_freq(cj: dict) -> np.ndarray:
    """Inverse frequencies of the rotary dims, as the published
    ``DeepseekV2YarnRotaryEmbedding`` computes them."""
    dim, base = cj["qk_rope_head_dim"], float(cj["rope_theta"])
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    rs = cj.get("rope_scaling")
    if not rs:
        return extra.astype(np.float32)
    orig = rs["original_max_position_embeddings"]

    def corr(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    inter = extra / rs["factor"]
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def softmax_scale(cj: dict) -> float:
    s = (cj["qk_nope_head_dim"] + cj["qk_rope_head_dim"]) ** -0.5
    rs = cj.get("rope_scaling")
    if rs and rs.get("mscale_all_dim"):
        s *= _yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return s


def _rope(x, cj: dict):
    """x: (S, heads, d) at positions 0..S-1: de-interleave, then rotate
    half against half."""
    s, d = x.shape[0], x.shape[-1]
    half = d // 2
    x = jnp.swapaxes(x.reshape(x.shape[:-1] + (half, 2)), -1, -2
                     ).reshape(x.shape)
    rs = cj.get("rope_scaling")
    m = 1.0 if not rs else (_yarn_mscale(rs["factor"], rs["mscale"])
                            / _yarn_mscale(rs["factor"],
                                           rs["mscale_all_dim"]))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] \
        * jnp.asarray(yarn_inv_freq(cj))[None, :]
    cos, sin = (jnp.cos(ang) * m)[:, None, :], (jnp.sin(ang) * m)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _lin(x, w, eq, w_axes, mode):
    """A linear layer; in the control both sides rounded to float8:
    ``x`` per row, ``w`` per output channel (``w_axes`` reduced)."""
    if mode == "fp8":
        x, w = _q8(x, -1), _q8(w, w_axes)
    return jnp.einsum(eq, x, w, precision=HI)


def _mlp(x, g, u, dn, mode):
    h = jax.nn.silu(_lin(x, g, "sd,df->sf", 0, mode)) \
        * _lin(x, u, "sd,df->sf", 0, mode)
    return _lin(h, dn, "sf,fd->sd", 0, mode)


def _layer(cj: dict, x, get, dense: bool, mode: str):
    """One layer; ``get(name...)`` makes that weight of this layer."""
    eps = cj["rms_norm_eps"]
    nope, r = cj["qk_nope_head_dim"], cj["kv_lora_rank"]
    s = x.shape[0]
    y = _rms(x, get("ln1"), eps)
    q = _lin(y, get("attn", "wq"), "sd,dhk->shk", 0, mode)
    kv = _lin(y, get("attn", "wkv_a"), "sd,dk->sk", 0, mode)
    c = _rms(kv[:, :r], get("attn", "kv_norm"), eps)
    k_pe = _rope(kv[:, None, r:], cj)                      # (S, 1, rope)
    k_nope = _lin(c, get("attn", "wk_b"), "sr,rhk->shk", 0, mode)
    v = _lin(c, get("attn", "wv_b"), "sr,rhk->shk", 0, mode)
    q_pe = _rope(q[..., nope:], cj)
    h = q.shape[1]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_pe, (s, h, k_pe.shape[-1]))], -1)
    qq = jnp.concatenate([q[..., :nope], q_pe], -1)
    sc = jnp.einsum("qhk,shk->hqs", qq, k, precision=HI) * softmax_scale(cj)
    pos = jnp.arange(s)
    sc = jnp.where((pos[None, :] <= pos[:, None])[None], sc, -jnp.inf)
    o = jnp.einsum("hqs,shk->qhk", jax.nn.softmax(sc, axis=-1), v,
                   precision=HI)
    x = x + _lin(o, get("attn", "wo"), "shk,hkd->sd", (0, 1), mode)
    y = _rms(x, get("ln2"), eps)
    if dense:
        return x + _mlp(y, get("mlp", "w_gate"), get("mlp", "w_up"),
                        get("mlp", "w_down"), mode)
    # the router in f32 in both modes: the configuration keeps it so
    probs = jax.nn.softmax(jnp.einsum("sd,de->se", y, get("moe", "router"),
                                      precision=HI), axis=-1)
    gate, idx = jax.lax.top_k(probs, cj["num_experts_per_tok"])
    if cj["norm_topk_prob"]:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    else:
        gate = gate * cj["routed_scaling_factor"]
    g = jnp.zeros(probs.shape).at[pos[:, None], idx].set(gate)   # (S, E)
    wg, wu, wd = (get("moe", n) for n in EXPERT_LEAVES)
    he = jax.nn.silu(_lin(y, wg, "sd,edf->esf", 1, mode)) \
        * _lin(y, wu, "sd,edf->esf", 1, mode)
    if mode == "fp8":
        he, wd = _q8(he, -1), _q8(wd, 1)
    routed = jnp.einsum("esf,efd->sd", he * g.T[:, :, None], wd,
                        precision=HI)
    shared = _mlp(y, get("moe", "shared", "w_gate"),
                  get("moe", "shared", "w_up"),
                  get("moe", "shared", "w_down"), mode)
    return x + routed + shared


def _hidden(cj: dict, key, tokens, mode: str):
    """Final-normed hidden states (S, d) of one sequence ``tokens``."""
    leaves = {path: (i, shape, dtype)
              for i, path, shape, dtype, _ in _leaves(cj)}

    def get(path, layer=None):
        i, shape, dtype = leaves[path]
        k = jax.random.fold_in(key, i)
        if layer is not None:
            k = jax.random.fold_in(k, layer)
        return _stored(cj, path, shape, dtype, k)

    x = get(("embed",))[tokens]
    for i in range(cj["first_k_dense_replace"]):
        x = _layer(cj, x, lambda *n, i=i: get(("lead", i) + n), True, mode)

    def body(x, l):
        return _layer(cj, x, lambda *n: get(("unit", "b0") + n, l), False,
                      mode), None

    L = cj["num_hidden_layers"] - cj["first_k_dense_replace"]
    x, _ = jax.lax.scan(body, x, jnp.arange(L, dtype=jnp.uint32))
    return _rms(x, get(("final_norm",)), cj["rms_norm_eps"]), get(("head",))


def logits_rows(cj: dict, w: dict, tokens, start, rows: int, mode: str):
    """Logits (rows, V) at positions ``start .. start + rows - 1``."""
    if mode not in ("f32", "fp8"):
        raise ValueError(f"unknown reference mode {mode!r}")
    with jax.default_matmul_precision("highest"):
        hs, wh = _hidden(cj, w["key"], tokens, mode)
        hs = jax.lax.dynamic_slice_in_dim(hs, start, rows, axis=0)
        if mode == "fp8":
            hs, wh = _q8(hs, -1), _q8(wh, 0)
        return jnp.einsum("sd,dv->sv", hs, wh, precision=HI)


# ---------------------------------------------------------------------------
# Work of one decode step
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Shape:
    layers: int
    dense_layers: int
    d: int
    heads: int
    rank: int
    nope: int
    rope: int
    v: int
    d_ff: int
    experts: int
    top_k: int
    expert_ff: int
    shared_ff: int
    vocab: int
    weight_bits: int          # 16 (bf16) or 4 (w4 bit-planes)

    @classmethod
    def from_config(cls, cj: dict) -> "Shape":
        return cls(layers=cj["num_hidden_layers"],
                   dense_layers=cj["first_k_dense_replace"],
                   d=cj["hidden_size"], heads=cj["num_attention_heads"],
                   rank=cj["kv_lora_rank"], nope=cj["qk_nope_head_dim"],
                   rope=cj["qk_rope_head_dim"], v=cj["v_head_dim"],
                   d_ff=cj["intermediate_size"],
                   experts=cj["n_routed_experts"],
                   top_k=cj["num_experts_per_tok"],
                   expert_ff=cj["moe_intermediate_size"],
                   shared_ff=cj["n_shared_experts"]
                   * cj["moe_intermediate_size"],
                   vocab=cj["vocab_size"],
                   weight_bits=16 if cj["serve"]["weights"] == "bf16" else 4)


def _attn_matrices(s: Shape):
    """(in size, out channels) of each stored attention matrix, the
    output channels as the storage scales them (the last axis)."""
    return [(s.d * s.heads, s.nope + s.rope),         # wq (d, H, nope+rope)
            (s.d, s.rank + s.rope),                   # wkv_a
            (s.rank * s.heads, s.nope),               # wk_b (r, H, nope)
            (s.rank * s.heads, s.v),                  # wv_b (r, H, v)
            (s.heads * s.v, s.d)]                     # wo (H, v, d)


def _mlp_matrices(d: int, f: int):
    return [(d, f), (d, f), (f, d)]


def _matrix_bytes(s: Shape, k: int, n: int) -> float:
    if s.weight_bits == 16:
        return 2.0 * k * n
    return k * n * s.weight_bits / 8 + 4.0 * n


def experts_reached(s: Shape, lanes: int) -> float:
    """Expected distinct experts of one expert layer when each of
    ``lanes`` tokens picks top_k of the experts uniformly at random."""
    return s.experts * (1.0 - (1.0 - s.top_k / s.experts) ** lanes)


def matmul_params(s: Shape) -> int:
    """Parameters one token multiplies: attention (with the k and v
    up-projections), the dense MLP or the chosen and shared experts and
    the router, and the head."""
    attn = sum(k * n for k, n in _attn_matrices(s))
    dense = sum(k * n for k, n in _mlp_matrices(s.d, s.d_ff))
    expert = sum(k * n for k, n in _mlp_matrices(s.d, s.expert_ff))
    shared = sum(k * n for k, n in _mlp_matrices(s.d, s.shared_ff))
    moe = s.top_k * expert + shared + s.d * s.experts
    n_moe = s.layers - s.dense_layers
    return (s.layers * attn + s.dense_layers * dense + n_moe * moe
            + s.d * s.vocab)


def weight_bytes(s: Shape, lanes: int) -> float:
    """Bytes of weights one decode step of ``lanes`` tokens must read."""
    attn = sum(_matrix_bytes(s, k, n) for k, n in _attn_matrices(s))
    attn += 4.0 * (2 * s.d + s.rank)                  # ln1, ln2, kv_norm
    dense = sum(_matrix_bytes(s, k, n)
                for k, n in _mlp_matrices(s.d, s.d_ff))
    expert = sum(_matrix_bytes(s, k, n)
                 for k, n in _mlp_matrices(s.d, s.expert_ff))
    shared = sum(_matrix_bytes(s, k, n)
                 for k, n in _mlp_matrices(s.d, s.shared_ff))
    moe = experts_reached(s, lanes) * expert + shared + 4.0 * s.d * s.experts
    n_moe = s.layers - s.dense_layers
    total = s.layers * attn + s.dense_layers * dense + n_moe * moe
    total += 4.0 * s.d                                # final norm
    total += _matrix_bytes(s, s.d, s.vocab)           # head: whole
    total += _matrix_bytes(s, lanes, s.d)             # the tokens' rows
    return total


def cache_bytes_per_position(s: Shape) -> int:
    """The latent and the rotary key of one position over all layers."""
    return s.layers * (s.rank + s.rope) * 2


def decode_step(cj: dict, lens):
    """(FLOPs, bytes) of one decode step whose active lanes attend over
    ``lens[i]`` positions each, the new position included."""
    s = Shape.from_config(cj)
    lanes, live_keys = len(lens), sum(lens)
    flops = 2.0 * matmul_params(s) * lanes
    flops += s.layers * (2.0 * s.heads * (s.rank + s.rope)
                         + 2.0 * s.heads * s.rank) * live_keys
    nbytes = weight_bytes(s, lanes)
    nbytes += cache_bytes_per_position(s) * (live_keys + lanes)
    nbytes += 2.0 * s.vocab * lanes                   # logits
    return flops, nbytes
