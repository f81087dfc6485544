"""Decoder-only transformer LM (dense / MoE / VLM families).

Layers are stacked along a leading axis and executed with ``lax.scan``
(O(1) HLO in depth — critical for 40-cell x 512-device dry-run compile
times) with optional remat. Decode carries the layer-stacked KV cache
and the layer index through the same scan, and each layer writes and
reads its own part of it in place.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import constrain, batch_axes
from repro.models import attention as attn
from repro.models import common as cm
from repro.models import mlp as mlp_mod


# ------------------------------------------------------------------ layers
def layer_init(key, cfg, dtype):
    ks = jax.random.split(key, 4)
    p, s = {}, {}
    p["ln1"], s["ln1"] = cm.rmsnorm_init(cfg.d_model, dtype)
    p["attn"], s["attn"] = attn.attn_init(ks[0], cfg, dtype)
    p["ln2"], s["ln2"] = cm.rmsnorm_init(cfg.d_model, dtype)
    if cfg.family == "moe":
        p["moe"], s["moe"] = mlp_mod.moe_init(ks[1], cfg, dtype)
    else:
        p["mlp"], s["mlp"] = mlp_mod.mlp_init(ks[1], cfg, dtype)
    return p, s


def layer_forward(p, cfg, h, positions, mrope_pos=None):
    a = attn.attn_forward(p["attn"], cfg, cm.rmsnorm(h, p["ln1"], cfg.norm_eps),
                          positions, mrope_pos)
    h = h + a
    x = cm.rmsnorm(h, p["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        y, aux = mlp_mod.moe_forward(p["moe"], cfg, x)
    else:
        y, aux = mlp_mod.mlp_forward(p["mlp"], cfg, x), 0.0
    return h + y, aux


# The serving programs name their parts with ``jax.named_scope`` (in the
# op_name metadata of every HLO op; no op changes): ``embed``, ``layers``
# (the scan) and ``unembed``; inside a layer ``attn_qkv`` (norm,
# projections, rope), ``kv_write`` (decode's write of one cache row per
# sequence), ``attention`` (the kernel call and its wrapper), ``attn_out``
# and ``mlp``.
def layer_prefill(p, cfg, h, positions, mrope_pos=None):
    with jax.named_scope("attn_qkv"):
        xn = cm.rmsnorm(h, p["ln1"], cfg.norm_eps)
    a, kv = attn.attn_prefill(p["attn"], cfg, xn, positions, mrope_pos)
    with jax.named_scope("attn_out"):
        h = h + a
    with jax.named_scope("mlp"):
        x = cm.rmsnorm(h, p["ln2"], cfg.norm_eps)
        if cfg.family == "moe":
            y, _ = mlp_mod.moe_forward(p["moe"], cfg, x)
        else:
            y = mlp_mod.mlp_forward(p["mlp"], cfg, x)
        return h + y, kv


def layer_decode(p, cfg, h, ck, cv, layer, lengths, mrope_pos=None):
    with jax.named_scope("attn_qkv"):
        xn = cm.rmsnorm(h, p["ln1"], cfg.norm_eps)
    a, ck, cv = attn.attn_decode(p["attn"], cfg, xn, ck, cv, layer, lengths,
                                 mrope_pos)
    with jax.named_scope("attn_out"):
        h = h + a
    with jax.named_scope("mlp"):
        x = cm.rmsnorm(h, p["ln2"], cfg.norm_eps)
        if cfg.family == "moe":
            y, _ = mlp_mod.moe_forward(p["moe"], cfg, x[:, None, :])
            y = y[:, 0, :]
        else:
            y = mlp_mod.mlp_forward(p["mlp"], cfg, x)
        return h + y, ck, cv


# ------------------------------------------------------------------- model
def init(key, cfg, max_seq: int = 4096):
    dtype = cm.compute_dtype(cfg)
    k_emb, k_layers = jax.random.split(key)
    p, s = {}, {}
    p["emb"], s["emb"] = cm.embedding_init(k_emb, cfg, dtype)
    p["layers"], s["layers"] = cm.stacked(
        lambda k: layer_init(k, cfg, dtype), k_layers, cfg.n_layers)
    p["ln_f"], s["ln_f"] = cm.rmsnorm_init(cfg.d_model, dtype)
    return p, s


def _positions_and_embeds(params, cfg, batch: Dict):
    """Token (+vision) embedding and (m)rope positions."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    h = cm.embed_tokens(params["emb"], tokens)
    mrope_pos = None
    if cfg.family == "vlm":
        ve = batch["vision_embeds"].astype(h.dtype)     # (B, V, d)
        V = ve.shape[1]
        h = jnp.concatenate([ve, h], axis=1)
        side = max(int(V ** 0.5), 1)
        vis_t = jnp.zeros((V,), jnp.int32)
        vis_h = jnp.arange(V) // side
        vis_w = jnp.arange(V) % side
        txt = side + jnp.arange(S)
        pos3 = jnp.stack([
            jnp.concatenate([vis_t, txt]),
            jnp.concatenate([vis_h, txt]),
            jnp.concatenate([vis_w, txt]),
        ])                                              # (3, V+S)
        mrope_pos = jnp.broadcast_to(pos3[:, None, :], (3, B, V + S))
        positions = None
    else:
        positions = jnp.arange(S)[None, :]
    return h, positions, mrope_pos


def forward(params, cfg, batch: Dict):
    """Teacher-forced logits (B, S_total, Vp)."""
    h, positions, mrope_pos = _positions_and_embeds(params, cfg, batch)
    h = constrain(h, batch_axes(), None, None)

    def body(carry, lp):
        h, aux = carry
        h2, a = layer_forward(lp, cfg, h, positions, mrope_pos)
        h2 = constrain(h2, batch_axes(), None, None)
        return (h2, aux + a), None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    (h, aux), _ = jax.lax.scan(body_fn, (h, 0.0), params["layers"])
    h = cm.rmsnorm(h, params["ln_f"], cfg.norm_eps)
    logits = cm.unembed(params["emb"], cfg, h)
    return constrain(logits, batch_axes(), None, "model"), aux


# ------------------------------------------------------------------ serving
def init_cache(cfg, batch_size: int, max_len: int, dtype=jnp.bfloat16):
    """K/V are (L, B, max_len, KH*hd): token major, the kv heads folded
    into the minor dim, the layout decode_attention reads in place."""
    L, KH, hd = cfg.n_layers, cfg.n_kv_heads, cfg.resolved_head_dim
    dp = ("data",)
    # kv_seq_shard: shard the sequence dim over "model" when kv heads
    # cannot use it (GQA kv < TP) — attention reductions over the sharded
    # seq become scalar psums (EXPERIMENTS §Perf C3)
    kv_spec = P(None, dp, "model", None) if cfg.kv_seq_shard \
        else P(None, dp, None, "model")
    cache = {
        "k": jnp.zeros((L, batch_size, max_len, KH * hd), dtype),
        "v": jnp.zeros((L, batch_size, max_len, KH * hd), dtype),
        "len": jnp.zeros((batch_size,), jnp.int32),
    }
    specs = {"k": kv_spec, "v": kv_spec, "len": P(dp)}
    return cache, specs


def prefill(params, cfg, batch: Dict, last_pos=None):
    """Run the prompt; returns (logits at the last prompt position
    (B, Vp), cache). ``last_pos`` (B,) overrides the sampled position for
    bucket-padded prompts (pads are never attended: cache len is set by
    the engine)."""
    with jax.named_scope("embed"):
        h, positions, mrope_pos = _positions_and_embeds(params, cfg, batch)

    def body(h, lp):
        h2, kv = layer_prefill(lp, cfg, h, positions, mrope_pos)
        return constrain(h2, batch_axes(), None, None), kv

    with jax.named_scope("layers"):
        h, (ks, vs) = jax.lax.scan(body, h, params["layers"])
    with jax.named_scope("unembed"):
        hl = h[:, -1] if last_pos is None else \
            jnp.take_along_axis(h, last_pos[:, None, None].astype(jnp.int32)
                                .repeat(h.shape[-1], -1), axis=1)[:, 0]
        hl = cm.rmsnorm(hl, params["ln_f"], cfg.norm_eps)
        logits = cm.unembed(params["emb"], cfg, hl)
    L, B, S_tot = ks.shape[:3]                  # (L, B, S, KH, hd)
    cache = {"k": ks.reshape(L, B, S_tot, -1),
             "v": vs.reshape(L, B, S_tot, -1),
             "len": jnp.full((B,), S_tot, jnp.int32)}
    return logits, cache


def decode_step(params, cfg, cache, tokens):
    """One token for every sequence. tokens (B,) -> (logits (B,Vp), cache)."""
    B = tokens.shape[0]
    with jax.named_scope("embed"):
        h = cm.embed_tokens(params["emb"], tokens)          # (B, d)
    lengths = cache["len"]
    mrope_pos = None
    if cfg.family == "vlm":
        pos = lengths[None, :, None]                        # (1,B,1)
        mrope_pos = jnp.broadcast_to(pos, (3, B, 1))

    def body(carry, lp):
        h, ks, vs, layer = carry
        h, ks, vs = layer_decode(lp, cfg, h, ks, vs, layer, lengths,
                                 mrope_pos)
        return (h, ks, vs, layer + 1), None

    with jax.named_scope("layers"):
        (h, ks, vs, _), _ = jax.lax.scan(
            body, (h, cache["k"], cache["v"], jnp.int32(0)), params["layers"])
    with jax.named_scope("unembed"):
        h = cm.rmsnorm(h, params["ln_f"], cfg.norm_eps)
        logits = cm.unembed(params["emb"], cfg, h)
    new_cache = {"k": ks, "v": vs, "len": lengths + 1}
    return logits, new_cache
