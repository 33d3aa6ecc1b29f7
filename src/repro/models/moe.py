"""Mixture-of-Experts FFN with sort-based capacity dispatch.

Expert-parallel friendly: the expert buffer ``(E, C, d)`` is sharded on
the "model" axis; the token->expert resharding lowers to all-to-all-like
collectives under pjit.  Dispatch is sort-based (argsort by expert id +
within-expert rank via an exclusive running count), which avoids the
O(T*E*C) one-hot dispatch tensors of the Switch formulation.

A spec with ``dropless`` set (DeepSeek-V2) takes ``moe_dropless``
instead: no capacity, every routed token computed by one grouped
product per matrix, gates renormalised or not as the spec says, and
shared experts added for every token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common
from .common import dense_init, shard
from .qweight import dq


def moe_init(key, cfg) -> dict:
    spec = cfg.moe
    d, e, f = cfg.d_model, spec.num_experts, spec.d_ff
    ks = common.split_keys(key, 4)
    p = {
        "router": dense_init(ks[0], (d, e), dtype=jnp.float32),
        "w_gate": dense_init(ks[1], (e, d, f)),
        "w_up": dense_init(ks[2], (e, d, f)),
        "w_down": dense_init(ks[3], (e, f, d), in_axis=1),
    }
    if spec.n_shared:
        fs = spec.n_shared * f
        kk = common.split_keys(jax.random.fold_in(key, 4), 3)
        p["shared"] = {"w_gate": dense_init(kk[0], (d, fs)),
                       "w_up": dense_init(kk[1], (d, fs)),
                       "w_down": dense_init(kk[2], (fs, d))}
    return p


def _route(params, xf, spec):
    """Router in f32: softmax over the experts, greedy top-k, the gates
    renormalised or not as the spec says.
    Returns (probs (..., E), gate (..., k), expert ids (..., k))."""
    logits = jnp.matmul(xf.astype(jnp.float32), dq(params["router"],
                                                   jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = jax.lax.top_k(probs, spec.top_k)
    if spec.norm_topk:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    return probs, gate, eidx


def _balance_loss(probs, eidx, e):
    """Switch-style load-balancing loss over all tokens."""
    lead = tuple(range(probs.ndim - 1))
    density = jnp.mean(jax.nn.one_hot(eidx[..., 0], e, dtype=jnp.float32),
                       lead)
    return e * jnp.sum(density * jnp.mean(probs, axis=lead))


def _capacity(tokens: int, spec) -> int:
    c = int(tokens * spec.top_k * spec.capacity_factor / spec.num_experts)
    return max(spec.top_k, -(-c // 8) * 8)


def _chunks_for(t: int, requested: int) -> int:
    c = max(1, min(requested, t))
    while t % c:
        c -= 1
    return c


def moe_apply(params, x, cfg):
    """x: (B, S, d) -> (B, S, d); load-balance aux loss returned too."""
    spec = cfg.moe
    b, s, d = x.shape
    t = b * s
    e, k = spec.num_experts, spec.top_k
    X = _chunks_for(t, spec.dispatch_chunks)
    tc = t // X
    cap = _capacity(tc, spec)
    xf = x.reshape(X, tc, d)
    xf = shard(xf, "batch", None, None)

    logits = xf.astype(jnp.float32) @ dq(params["router"], jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                     # (X, Tc, E)
    gate, eidx = jax.lax.top_k(probs, k)                        # (X, Tc, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    # aux load-balancing loss (Switch-style, over all tokens)
    aux = _balance_loss(probs, eidx, e)

    # ---- per-chunk sort-based dispatch (local capacity) -------------------
    def dispatch(xc, gate_c, eidx_c):
        fe = eidx_c.reshape(-1)                                 # (Tc*k,)
        fg = gate_c.reshape(-1)
        tok = jnp.repeat(jnp.arange(tc), k)
        order = jnp.argsort(fe)
        se, stok = fe[order], tok[order]
        counts = jnp.bincount(fe, length=e)
        starts = jnp.cumsum(counts) - counts
        rank = jnp.arange(tc * k) - starts[se]
        keep = rank < cap
        slot = se * cap + jnp.where(keep, rank, 0)
        buf = jnp.zeros((e * cap, d), x.dtype)
        buf = buf.at[slot].add(jnp.where(keep[:, None], xc[stok], 0))
        return buf.reshape(e, cap, d), (order, stok, keep, slot, fg)

    buf, meta = jax.vmap(dispatch)(xf, gate, eidx)   # (X, E, C, d)
    buf = shard(buf, "batch", "model", None, None)

    # ---- expert FFN (chunks on data axes, experts on model axis) ----------
    h = jax.nn.silu(jnp.einsum("xecd,edf->xecf", buf, dq(params["w_gate"]))) \
        * jnp.einsum("xecd,edf->xecf", buf, dq(params["w_up"]))
    y = jnp.einsum("xecf,efd->xecd", h, dq(params["w_down"]))
    y = shard(y, "batch", "model", None, None)

    # ---- per-chunk combine -------------------------------------------------
    def combine(y_c, m):
        order, stok, keep, slot, fg = m
        ye = y_c.reshape(e * cap, d)[slot]
        contrib = jnp.where(keep[:, None],
                            ye * fg[order][:, None].astype(x.dtype), 0)
        return jnp.zeros((tc, d), x.dtype).at[stok].add(contrib)

    out = jax.vmap(combine)(y, meta)
    out = shard(out, "batch", None, None)
    return out.reshape(b, s, d), aux


@jax.named_scope("moe")
def moe_dropless(params, x, cfg):
    """x: (B, S, d) -> (y, aux, counts).  Every token's top-k experts
    are computed: the (token, expert) pairs are sorted by expert and each
    matrix is one grouped product (``lax.ragged_dot``) over the stacked
    experts, so no token is dropped at any batch or length.  y is
    sum_k gate_k expert_k(x) (f32 sum, as the published layer) plus the
    shared experts.  ``counts``: ``experts_hit``, the distinct experts
    the tokens reached, and ``dropped``, the routed pairs not computed
    (0 by construction)."""
    spec = cfg.moe
    b, s, d = x.shape
    t, e, k = b * s, spec.num_experts, spec.top_k
    xf = x.reshape(t, d)
    probs, gate, eidx = _route(params, xf, spec)
    fe = eidx.reshape(-1)                                   # (T*k,)
    order = jnp.argsort(fe, stable=True)
    tok = order // k
    sizes = jnp.bincount(fe, length=e).astype(jnp.int32)
    xs = xf[tok]
    h = jax.nn.silu(jax.lax.ragged_dot(xs, dq(params["w_gate"]), sizes)) \
        * jax.lax.ragged_dot(xs, dq(params["w_up"]), sizes)
    ys = jax.lax.ragged_dot(h, dq(params["w_down"]), sizes)
    contrib = ys.astype(jnp.float32) * gate.reshape(-1)[order][:, None]
    y = jnp.zeros((t, d), jnp.float32).at[tok].add(contrib).astype(x.dtype)
    if "shared" in params:
        sp = params["shared"]
        hs = jax.nn.silu(xf @ dq(sp["w_gate"])) * (xf @ dq(sp["w_up"]))
        y = y + hs @ dq(sp["w_down"])
    counts = {"experts_hit": jnp.sum(sizes > 0, dtype=jnp.int32),
              "dropped": jnp.int32(t * k) - jnp.sum(sizes)}
    return y.reshape(b, s, d), _balance_loss(probs, eidx, e), counts
