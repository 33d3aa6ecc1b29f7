"""The comparison that decides ``correct`` for a served model.

Once the window has closed, a sample of the finished requests is drawn
from the seed, the request with the longest sequence always in it, until
it holds ``SAMPLE_TOKENS`` served tokens.  The plain reference of the
configuration's architecture (``bench/arch/<arch>.py``) runs once over
each prompt with its served tokens, and every served token is read as
the gap between the reference's best logit at its position and the
reference's logit of the served token.  The widest gap is compared with
the cell's limit (``bench/limits/<workload>.json``).  Greedy decoding
serves the argmax, so a sound server reads a gap of rounding size and a
wrong cache, position, weight or token reads far more.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

SAMPLE_TOKENS = 768
SAMPLE_MAX = 24


def sample(done, seed: int):
    """Finished requests to check: the longest sequence, then others in
    an order drawn from the seed, until ``SAMPLE_TOKENS`` are served."""
    if not done:
        return []
    done = sorted(done, key=lambda r: r.rid)
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.out), r.rid))
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7])
    picked, tokens = [longest], len(longest.out)
    for i in rng.permutation(len(done)):
        r = done[int(i)]
        if tokens >= SAMPLE_TOKENS or len(picked) >= SAMPLE_MAX:
            break
        if r is not longest:
            picked.append(r)
            tokens += len(r.out)
    return picked


def _scorer(arch, cj: dict, rows: int, mode: str):
    @jax.jit
    def score(w, tokens, start, served):
        ref = arch.logits_rows(cj, w, tokens, start, rows, "f32")
        if mode == "program":
            pick = served
        else:
            pick = jnp.argmax(
                arch.logits_rows(cj, w, tokens, start, rows, mode), -1)
        got = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
        return jnp.max(ref, axis=-1) - got
    return score


def gaps(arch, cj: dict, w: dict, reqs, width: int, rows: int,
         mode: str = "program") -> np.ndarray:
    """Per served token, the reference's best logit minus the logit of
    the token that ``mode`` puts there: the served token (``program``),
    or the control's argmax (``fp8``).  Sequences are padded to
    ``width`` (the forward is causal) and ``rows`` bounds the outputs.
    ``arch`` is the configuration's architecture module."""
    score = _scorer(arch, cj, rows, mode)
    out = []
    for r in reqs:
        seq = np.concatenate([np.asarray(r.prompt, np.int32),
                              np.asarray(r.out, np.int32)])
        n = len(r.out)
        p = len(r.prompt)
        tokens = np.zeros((width,), np.int32)
        tokens[:len(seq)] = seq
        start = min(p - 1, width - rows)
        off = p - 1 - start
        if off + n > rows:
            raise ValueError("rows too few for the served tokens")
        shifted = np.zeros((rows,), np.int32)
        shifted[off:off + n] = seq[p:]
        g = np.asarray(score(w, jnp.asarray(tokens), np.int32(start),
                             jnp.asarray(shifted)))
        out.append(g[off:off + n])
    return np.concatenate(out) if out else np.zeros((0,))
