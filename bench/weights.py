"""Weights made by the benchmark from the seed, on the device, in one
jitted call, in the layout the serving stack takes.

Every matrix, embedding and bias is drawn from a normal with the
published ``initializer_range`` as its standard deviation and stored in
bf16; every norm weight is stored as its offset from 1 (the stack
scales by ``1 + w``), drawn with standard deviation 0.1 in f32.  The
same tree feeds the plain reference, so neither side takes anything
that the other made.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NORM_STD = 0.1


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


def seed_key(seed: int):
    """A PRNG key from a seed of any size (PRNGKey alone keeps 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def make(cj: dict, key) -> dict:
    """The weight tree, as nested dicts.  Call under ``jax.jit``."""
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


def check_layout(tree_shapes, expected) -> None:
    """Raise unless ``tree_shapes`` (``jax.eval_shape`` of the stack's own
    init) has exactly the paths, shapes and dtypes of ``expected``."""
    got = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree_shapes)[0]:
        key = tuple(getattr(p, "key", getattr(p, "idx", None)) for p in path)
        got[key] = (tuple(leaf.shape), jnp.dtype(leaf.dtype))
    want = {k: (tuple(s), jnp.dtype(t)) for k, (s, t) in expected.items()}
    if got != want:
        raise ValueError(f"weight layout differs from the serving stack's: "
                         f"missing {sorted(set(want) - set(got))}, "
                         f"extra {sorted(set(got) - set(want))}, "
                         f"differing {[k for k in want if k in got and got[k] != want[k]]}")
