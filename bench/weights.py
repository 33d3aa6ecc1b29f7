"""What every architecture's weights share: the key they are drawn
from, and the check that a layout (``bench/arch/<arch>.py``, ``layout``)
is the serving stack's own."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from a seed of any size (PRNGKey alone keeps 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


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
