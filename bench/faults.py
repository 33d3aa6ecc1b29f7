"""Faults planted under the timed path, to show that the check catches
them: each wraps the serve engine's jitted decode step."""

import jax.numpy as jnp


def unchanged_state(decode):
    """The step returns the caches it was given."""
    def f(params, caches, tokens, pos):
        logits, _ = decode(params, caches, tokens, pos)
        return logits, caches
    return f


def altered_token(decode):
    """Lane 0's token is altered where it is produced."""
    def f(params, caches, tokens, pos):
        logits, caches = decode(params, caches, tokens, pos)
        return logits.at[0, 0, 7].add(100.0), caches
    return f


def half_batch(decode):
    """Only the first half of the lanes is decoded; the others are given
    its results."""
    def f(params, caches, tokens, pos):
        b = tokens.shape[0]
        h = b // 2
        idx = jnp.concatenate([jnp.arange(h), jnp.arange(b - h) % h])
        return decode(params, caches, tokens[idx], pos[idx])
    return f


FAULTS = {"unchanged_state": unchanged_state,
          "altered_token": altered_token,
          "half_batch": half_batch}


def plant(monkeypatch_setattr, name: str):
    """Make every ServeEngine built from now on decode through fault
    ``name``.  ``monkeypatch_setattr(obj, attr, value)`` does the
    patching (pytest's ``monkeypatch.setattr``, or ``setattr``)."""
    import jax

    from repro.serve import engine as serve_engine

    init = serve_engine.ServeEngine.__init__
    wrap = FAULTS[name]

    def broken_init(self, *a, **kw):
        init(self, *a, **kw)
        self._decode = jax.jit(wrap(self._decode))

    monkeypatch_setattr(serve_engine.ServeEngine, "__init__", broken_init)
