"""LM assembly: embeds -> scanned layer groups -> norm -> logits.

Layers are stacked per repeating unit and executed with ``jax.lax.scan``
so HLO size (and compile time) is independent of depth -- essential for
the 80-cell multi-pod dry-run.  Three execution modes:

* ``apply``       -- full-sequence forward (training, encoder)
* ``prefill``     -- full-sequence forward that also emits decode caches
* ``decode_step`` -- one token with ring-buffer KV / recurrent state

Linear layers can be routed through the PIM backend (``repro.pim``) for
quantized serving -- the paper's Compute RAM as a framework feature.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from . import attention as attn
from . import common, moe as moe_mod, rglru as rg, ssm as ssm_mod
from .common import dense_init, rmsnorm, shard
from .qweight import dq


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------
def mlp_init(key, cfg):
    ks = common.split_keys(key, 3)
    d, f = cfg.d_model, cfg.d_ff
    p = {"w_up": dense_init(ks[1], (d, f)),
         "w_down": dense_init(ks[2], (f, d))}
    if cfg.mlp_variant == "swiglu":
        p["w_gate"] = dense_init(ks[0], (d, f))
    return p


def mlp_apply(params, x):
    if "w_gate" in params:
        h = jax.nn.silu(x @ dq(params["w_gate"])) * (x @ dq(params["w_up"]))
    else:
        h = jax.nn.gelu(x @ dq(params["w_up"]))
    h = shard(h, "batch", None, "model")
    return shard(h @ dq(params["w_down"]), "batch", None, None)


# ---------------------------------------------------------------------------
# Blocks (one per layer type)
# ---------------------------------------------------------------------------
def _block_init(key, cfg: ModelConfig, btype: str):
    """``dense`` is an attention layer with a dense MLP in a model whose
    other layers hold experts."""
    d = cfg.d_model
    ks = common.split_keys(key, 4)
    p = {"ln1": jnp.zeros((d,), jnp.float32)}
    if btype in ("attn", "dense"):
        init = attn.mla_init if cfg.kv_lora_rank else attn.attn_init
        p["attn"] = init(ks[0], cfg)
        p["ln2"] = jnp.zeros((d,), jnp.float32)
        if cfg.moe is not None and btype == "attn":
            p["moe"] = moe_mod.moe_init(ks[1], cfg)
        elif cfg.d_ff > 0:
            p["mlp"] = mlp_init(ks[1], cfg)
    elif btype == "xattn":       # decoder layer of an encoder-decoder
        p["attn"] = attn.attn_init(ks[0], cfg)
        p["lnx"] = jnp.zeros((d,), jnp.float32)
        p["xattn"] = attn.attn_init(ks[1], cfg, cross=True)
        p["ln2"] = jnp.zeros((d,), jnp.float32)
        p["mlp"] = mlp_init(ks[2], cfg)
    elif btype == "ssm":
        p["ssm"] = ssm_mod.ssm_init(ks[0], cfg)
    elif btype == "rec":
        p["rec"] = rg.rglru_init(ks[0], cfg)
        p["ln2"] = jnp.zeros((d,), jnp.float32)
        p["mlp"] = mlp_init(ks[1], cfg)
    else:
        raise ValueError(btype)
    return p


def _window_for(cfg, btype):
    if cfg.rglru is not None and btype == "attn":
        return cfg.rglru.window
    return cfg.sliding_window


def _ffn(params, cfg, x):
    """(y, aux, counts); counts (``moe_dropless``) is empty elsewhere."""
    if "moe" in params:
        if cfg.moe.dropless:
            return moe_mod.moe_dropless(params["moe"], x, cfg)
        y, aux = moe_mod.moe_apply(params["moe"], x, cfg)
        return y, aux, {}
    if "mlp" in params:
        return mlp_apply(params["mlp"], x), 0.0, {}
    return None, 0.0, {}


def _block_apply(params, h, cfg, btype, positions, mode, cache,
                 enc_out=None, enc_pos=None, causal=True):
    """Returns (h, new_cache, aux, counts)."""
    new_cache = {}
    aux = 0.0
    counts = {}
    x = rmsnorm(h, params["ln1"], cfg.norm_eps)

    if btype in ("attn", "dense", "xattn"):
        window = _window_for(cfg, btype)
        if cfg.kv_lora_rank:                 # latent attention (MLA)
            if mode == "decode":
                y, new_cache["kv"] = attn.mla_decode(
                    params["attn"], x, cache["kv"], cfg, positions[:, 0])
            else:
                y = attn.mla_apply(params["attn"], x, cfg, positions,
                                   causal=causal)
                if mode == "prefill":
                    cap = cache["kv"]["c"].shape[1]
                    new_cache["kv"] = attn.prefill_mla_cache(
                        params["attn"], x, cfg, positions, cap)
        elif mode == "decode":
            pos = positions[:, 0]
            y, new_cache["kv"] = attn.attn_decode(
                params["attn"], x, cache["kv"], cfg, pos, window=window)
        else:
            y = attn.attn_apply(params["attn"], x, cfg, positions,
                                causal=causal, window=window)
            if mode == "prefill":
                cap = cache["kv"]["k"].shape[1]
                new_cache["kv"] = attn.prefill_kv_cache(
                    params["attn"], x, cfg, positions, cap, window=window)
        h = h + y
        if btype == "xattn":
            xx = rmsnorm(h, params["lnx"], cfg.norm_eps)
            y = attn.attn_apply(params["xattn"], xx, cfg, positions,
                                causal=False, kv_src=enc_out,
                                kv_positions=enc_pos)
            h = h + y
        f = rmsnorm(h, params["ln2"], cfg.norm_eps)
        y, aux, counts = _ffn(params, cfg, f)
        if y is not None:
            h = h + y

    elif btype == "ssm":
        y, c = ssm_mod.ssm_apply(params["ssm"], x, cfg,
                                 cache=cache.get("ssm") if cache else None)
        if mode != "train":
            new_cache["ssm"] = c
        h = h + y

    elif btype == "rec":
        y, c = rg.rglru_apply(params["rec"], x, cfg,
                              cache=cache.get("rec") if cache else None)
        if mode != "train":
            new_cache["rec"] = c
        h = h + y
        f = rmsnorm(h, params["ln2"], cfg.norm_eps)
        y, _, _ = _ffn(params, cfg, f)
        if y is not None:
            h = h + y

    return h, new_cache, aux, counts


def _add_counts(a: dict, b: dict) -> dict:
    return {k: a.get(k, 0) + b.get(k, 0) for k in {**a, **b}}


def _block_cache(cfg, btype, batch, capacity):
    if cfg.kv_lora_rank and btype in ("attn", "dense"):
        return {"kv": attn.init_mla_cache(cfg, batch, capacity)}
    if btype in ("attn", "dense", "xattn"):
        window = _window_for(cfg, btype)
        return {"kv": attn.init_kv_cache(cfg, batch, capacity, window)}
    if btype == "ssm":
        return {"ssm": ssm_mod.ssm_init_cache(cfg, batch)}
    if btype == "rec":
        return {"rec": rg.rglru_init_cache(cfg, batch)}
    raise ValueError(btype)


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------
class LM:
    """Decoder-only LM (also hosts the encoder stack for enc-dec)."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        # leading layers (DeepSeek's dense first layer), run one by one
        # before the scanned unit
        self.lead = cfg.layer_types()[:cfg.first_dense_layers]
        self.unit, self.n_units, self.rest = cfg.scan_plan()
        if cfg.is_encdec:
            # decoder layers are xattn; encoder handled separately
            self.unit, self.n_units, self.rest = ["xattn"], cfg.n_layers, []

    # -- init ---------------------------------------------------------------
    def init(self, key):
        cfg = self.cfg
        k_embed, k_units, k_rest, k_head, k_enc = jax.random.split(key, 5)

        def unit_init(k):
            kk = common.split_keys(k, len(self.unit))
            return {f"b{i}": _block_init(kk[i], cfg, t)
                    for i, t in enumerate(self.unit)}

        params = {
            "embed": dense_init(k_embed, (cfg.vocab, cfg.d_model)),
            "unit": jax.vmap(unit_init)(
                jax.random.split(k_units, self.n_units)),
            "final_norm": jnp.zeros((cfg.d_model,), jnp.float32),
        }
        if self.rest:
            kk = common.split_keys(k_rest, len(self.rest))
            params["rest"] = [
                _block_init(kk[i], cfg, t) for i, t in enumerate(self.rest)]
        if self.lead:
            kk = common.split_keys(jax.random.fold_in(key, 5),
                                   len(self.lead))
            params["lead"] = [
                _block_init(kk[i], cfg, t) for i, t in enumerate(self.lead)]
        if not cfg.tie_embeddings:
            params["head"] = dense_init(k_head, (cfg.d_model, cfg.vocab))
        if cfg.is_encdec:
            def enc_init(k):
                return {"b0": _block_init(k, cfg, "attn")}
            params["encoder"] = {
                "unit": jax.vmap(enc_init)(
                    jax.random.split(k_enc, cfg.encoder_layers)),
                "final_norm": jnp.zeros((cfg.d_model,), jnp.float32),
            }
        return params

    # -- scanned group execution ---------------------------------------------
    def _run_unit(self, stacked, h, positions, mode, caches, unit=None,
                  enc_out=None, enc_pos=None, causal=True):
        cfg = self.cfg
        unit = unit or self.unit

        def body(carry, xs):
            hh = carry
            lp, lc = xs
            new_lc = {}
            aux = 0.0
            counts = {}
            for i, t in enumerate(unit):
                c_i = lc[f"b{i}"] if lc is not None else None
                hh, nc, a, n = _block_apply(lp[f"b{i}"], hh, cfg, t,
                                            positions, mode, c_i, enc_out,
                                            enc_pos, causal)
                new_lc[f"b{i}"] = nc
                aux = aux + a
                counts = _add_counts(counts, n)
            return hh, (new_lc, aux, counts)

        if mode == "train" and cfg.remat_policy != "none":
            # remat each scanned layer: activation memory stays O(L * B*S*d)
            # carries.  "full" recomputes everything in backward; "dots"
            # saves matmul outputs (less recompute FLOPs, more memory).
            if cfg.remat_policy == "dots":
                body = jax.checkpoint(
                    body, policy=jax.checkpoint_policies
                    .dots_with_no_batch_dims_saveable)
            else:
                body = jax.checkpoint(body)
        xs = (stacked, caches)
        h, (new_caches, auxs, counts) = jax.lax.scan(body, h, xs)
        return h, new_caches, jnp.sum(auxs), jax.tree.map(jnp.sum, counts)

    def _embed(self, params, tokens=None, embeds=None):
        if embeds is not None:
            return embeds
        e = jnp.take(dq(params["embed"]), tokens, axis=0).astype(jnp.bfloat16)
        return shard(e, "batch", None, None)

    def _head(self, params, h):
        h = rmsnorm(h, params["final_norm"], self.cfg.norm_eps)
        w = (dq(params["embed"]).T if self.cfg.tie_embeddings
             else dq(params["head"]))
        logits = h @ w.astype(h.dtype)
        return shard(logits, "batch", None, "model")

    def encode(self, params, embeds, positions):
        """Bidirectional encoder stack (enc-dec archs)."""
        enc = params["encoder"]
        h = embeds

        def body(hh, lp):
            hh, _, _, _ = _block_apply(lp["b0"], hh, self.cfg, "attn",
                                       positions, "train", None,
                                       causal=False)
            return hh, None

        h, _ = jax.lax.scan(jax.checkpoint(body), h, enc["unit"])
        return rmsnorm(h, enc["final_norm"], self.cfg.norm_eps)

    # -- public entry points --------------------------------------------------
    def _forward(self, params, tokens, embeds, positions, mode, caches,
                 enc_out=None, enc_pos=None):
        cfg = self.cfg
        b = (tokens if tokens is not None else embeds).shape[0]
        s = (tokens if tokens is not None else embeds).shape[1]
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32),
                                         (b, s))
        h = self._embed(params, tokens, embeds)
        new_lead, lead_aux, counts = [], 0.0, {}
        for i, t in enumerate(self.lead):
            c_i = caches["lead"][i] if caches is not None else None
            h, nc, a, n = _block_apply(params["lead"][i], h, cfg, t,
                                       positions, mode, c_i)
            new_lead.append(nc)
            lead_aux = lead_aux + a
            counts = _add_counts(counts, n)
        unit_caches = caches["unit"] if caches is not None else None
        h, new_unit_caches, aux, n = self._run_unit(
            params["unit"], h, positions, mode, unit_caches,
            enc_out=enc_out, enc_pos=enc_pos)
        aux = aux + lead_aux if self.lead else aux
        counts = _add_counts(counts, n)
        new_rest = []
        if self.rest:
            for i, t in enumerate(self.rest):
                c_i = caches["rest"][i] if caches is not None else None
                h, nc, a, n = _block_apply(params["rest"][i], h, cfg, t,
                                           positions, mode, c_i,
                                           enc_out, enc_pos)
                new_rest.append(nc)
                aux = aux + a
                counts = _add_counts(counts, n)
        logits = self._head(params, h)
        new_caches = None
        if mode != "train":
            new_caches = {"unit": new_unit_caches, "rest": new_rest}
            if self.lead:
                new_caches["lead"] = new_lead
        return logits, new_caches, aux, counts

    def apply(self, params, tokens=None, embeds=None, positions=None,
              enc_out=None, enc_pos=None):
        logits, _, aux, _ = self._forward(params, tokens, embeds, positions,
                                          "train", None, enc_out, enc_pos)
        return logits, aux

    @property
    def prefill_pad_safe(self) -> bool:
        """True when tail-padding a prompt cannot perturb the post-prefill
        cache.  Positional KV caches only hold pad entries at positions
        the decoder overwrites (or masks) before attending, but ``ssm`` /
        ``rec`` layers fold every pad token into their recurrent state --
        the serve engine's power-of-two prompt bucketing checks this
        before padding."""
        return not any(t in ("ssm", "rec")
                       for t in (*self.unit, *self.rest))

    def init_cache(self, batch: int, capacity: int):
        cfg = self.cfg

        def one_unit(_):
            return {f"b{i}": _block_cache(cfg, t, batch, capacity)
                    for i, t in enumerate(self.unit)}

        unit_cache = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (self.n_units,) + x.shape).copy()
            if self.n_units > 1 else x[None],
            one_unit(None))
        rest = [ _block_cache(cfg, t, batch, capacity) for t in self.rest ]
        caches = {"unit": unit_cache, "rest": rest}
        if self.lead:
            caches["lead"] = [_block_cache(cfg, t, batch, capacity)
                              for t in self.lead]
        return caches

    def prefill(self, params, tokens=None, embeds=None, capacity=None,
                enc_out=None, enc_pos=None):
        s = (tokens if tokens is not None else embeds).shape[1]
        b = (tokens if tokens is not None else embeds).shape[0]
        caches = self.init_cache(b, capacity or s)
        logits, caches, _, _ = self._forward(params, tokens, embeds, None,
                                             "prefill", caches,
                                             enc_out, enc_pos)
        return logits, caches

    @property
    def counters(self) -> tuple:
        """Names of the counters ``decode_step(..., counts=True)``
        returns: the dropless expert layer's, summed over its layers."""
        if self.cfg.moe is not None and self.cfg.moe.dropless:
            return ("moe_experts_hit", "moe_layer_steps", "moe_dropped")
        return ()

    def decode_step(self, params, caches, tokens, pos,
                    enc_out=None, enc_pos=None, counts=False):
        """tokens: (B, 1); pos: (B,) int32.  With ``counts`` it returns
        (logits, caches, int32 vector of ``counters``) as well."""
        positions = pos[:, None]
        logits, new_caches, _, n = self._forward(
            params, tokens, None, positions, "decode", caches,
            enc_out, enc_pos)
        if not counts:
            return logits, new_caches
        n_moe = self.cfg.n_layers - self.cfg.first_dense_layers
        vec = jnp.stack([n["experts_hit"], jnp.int32(n_moe), n["dropped"]])
        return logits, new_caches, vec

    # -- loss -----------------------------------------------------------------
    def loss(self, params, batch):
        """Next-token cross entropy (+ MoE aux)."""
        tokens = batch["tokens"]
        enc_out = enc_pos = None
        if self.cfg.is_encdec:
            b, ss = batch["src_embeds"].shape[:2]
            enc_pos = jnp.broadcast_to(
                jnp.arange(ss, dtype=jnp.int32), (b, ss))
            enc_out = self.encode(params, batch["src_embeds"], enc_pos)
        embeds = batch.get("embeds")
        logits, aux = self.apply(params, tokens=tokens, embeds=embeds,
                                 enc_out=enc_out, enc_pos=enc_pos)
        targets = tokens[:, 1:]
        lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), -1)
        nll = -jnp.take_along_axis(lp, targets[..., None], -1)[..., 0]
        return jnp.mean(nll) + 0.01 * aux
