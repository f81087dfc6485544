"""Whisper-style encoder-decoder (audio family).

The conv audio frontend is a STUB per the assignment: the model consumes
precomputed frame embeddings (B, enc_seq, d_model). Sinusoidal positions
on both sides (simplification noted in DESIGN.md). Decoder layers carry
causal self-attention + cross-attention into the encoder output; decode
caches both the self KV and the (fixed) cross KV, layer-stacked in
decode_attention's (L, B, S, KH*hd) layout and read by layer index.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import constrain, batch_axes
from repro.kernels.decode_attention import ops as da_ops
from repro.models import attention as attn
from repro.models import common as cm
from repro.models import mlp as mlp_mod


def _enc_layer_init(key, cfg, dtype):
    ks = jax.random.split(key, 2)
    p, s = {}, {}
    p["ln1"], s["ln1"] = cm.rmsnorm_init(cfg.d_model, dtype)
    p["attn"], s["attn"] = attn.attn_init(ks[0], cfg, dtype)
    p["ln2"], s["ln2"] = cm.rmsnorm_init(cfg.d_model, dtype)
    p["mlp"], s["mlp"] = mlp_mod.mlp_init(ks[1], cfg, dtype)
    return p, s


def _dec_layer_init(key, cfg, dtype):
    ks = jax.random.split(key, 3)
    p, s = _enc_layer_init(ks[0], cfg, dtype)
    p["ln_x"], s["ln_x"] = cm.rmsnorm_init(cfg.d_model, dtype)
    p["xattn"], s["xattn"] = attn.attn_init(ks[1], cfg, dtype)
    return p, s


def init(key, cfg, max_seq: int = 4096):
    dtype = cm.compute_dtype(cfg)
    ks = jax.random.split(key, 3)
    p, s = {}, {}
    p["emb"], s["emb"] = cm.embedding_init(ks[0], cfg, dtype)
    p["enc_layers"], s["enc_layers"] = cm.stacked(
        lambda k: _enc_layer_init(k, cfg, dtype), ks[1], cfg.n_enc_layers)
    p["dec_layers"], s["dec_layers"] = cm.stacked(
        lambda k: _dec_layer_init(k, cfg, dtype), ks[2], cfg.n_layers)
    p["ln_enc"], s["ln_enc"] = cm.rmsnorm_init(cfg.d_model, dtype)
    p["ln_f"], s["ln_f"] = cm.rmsnorm_init(cfg.d_model, dtype)
    return p, s


def encode(params, cfg, frames):
    """frames: (B, F, d) stub embeddings -> encoder states (B, F, d)."""
    h = frames + cm.sinusoidal_pos(frames.shape[1], cfg.d_model
                                   ).astype(frames.dtype)[None]
    h = constrain(h, batch_axes(), None, None)

    def body(h, lp):
        a = attn.attn_forward(lp["attn"], cfg,
                              cm.rmsnorm(h, lp["ln1"], cfg.norm_eps),
                              causal=False)
        h = h + a
        h = h + mlp_mod.mlp_forward(
            lp["mlp"], cfg, cm.rmsnorm(h, lp["ln2"], cfg.norm_eps))
        return constrain(h, batch_axes(), None, None), None

    h, _ = jax.lax.scan(body, h, params["enc_layers"])
    return cm.rmsnorm(h, params["ln_enc"], cfg.norm_eps)


def _cross_kv(lp, cfg, enc):
    """Per-decoder-layer cross K/V from encoder states."""
    B, F, _ = enc.shape
    KH, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    k = (enc @ lp["xattn"]["wk"] + (lp["xattn"].get("bk", 0))).reshape(B, F, KH, hd)
    v = (enc @ lp["xattn"]["wv"] + (lp["xattn"].get("bv", 0))).reshape(B, F, KH, hd)
    return k, v


def _dec_layer_forward(lp, cfg, h, enc, positions):
    a = attn.attn_forward(lp["attn"], cfg,
                          cm.rmsnorm(h, lp["ln1"], cfg.norm_eps), positions)
    h = h + a
    kx, vx = _cross_kv(lp, cfg, enc)
    x = cm.rmsnorm(h, lp["ln_x"], cfg.norm_eps)
    cx = attn.attn_forward(lp["xattn"], cfg, x, causal=False, kv=(kx, vx))
    h = h + cx
    h = h + mlp_mod.mlp_forward(lp["mlp"], cfg,
                                cm.rmsnorm(h, lp["ln2"], cfg.norm_eps))
    return h


def forward(params, cfg, batch: Dict):
    """batch: frames (B,F,d), tokens (B,S) -> (logits (B,S,Vp), aux=0)."""
    enc = encode(params, cfg, batch["frames"])
    tokens = batch["tokens"]
    B, S = tokens.shape
    h = cm.embed_tokens(params["emb"], tokens)
    h = h + cm.sinusoidal_pos(S, cfg.d_model).astype(h.dtype)[None]
    positions = jnp.arange(S)[None, :]

    def body(h, lp):
        h2 = _dec_layer_forward(lp, cfg, h, enc, positions)
        return constrain(h2, batch_axes(), None, None), None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    h, _ = jax.lax.scan(body_fn, h, params["dec_layers"])
    h = cm.rmsnorm(h, params["ln_f"], cfg.norm_eps)
    logits = cm.unembed(params["emb"], cfg, h)
    return constrain(logits, batch_axes(), None, "model"), 0.0


def init_cache(cfg, batch_size: int, max_len: int, dtype=jnp.bfloat16):
    L, KH, hd = cfg.n_layers, cfg.n_kv_heads, cfg.resolved_head_dim
    F = cfg.enc_seq
    dp = ("data",)
    cache = {
        "k": jnp.zeros((L, batch_size, max_len, KH * hd), dtype),
        "v": jnp.zeros((L, batch_size, max_len, KH * hd), dtype),
        "xk": jnp.zeros((L, batch_size, F, KH * hd), dtype),
        "xv": jnp.zeros((L, batch_size, F, KH * hd), dtype),
        "len": jnp.zeros((batch_size,), jnp.int32),
    }
    kv_spec = P(None, dp, "model", None) if cfg.kv_seq_shard \
        else P(None, dp, None, "model")
    specs = {"k": kv_spec,
             "v": kv_spec,
             "xk": P(None, dp, None, "model"),
             "xv": P(None, dp, None, "model"),
             "len": P(dp)}
    return cache, specs


def prefill(params, cfg, batch: Dict, last_pos=None):
    """Encode + run decoder prompt; returns (last logits, cache)."""
    enc = encode(params, cfg, batch["frames"])
    tokens = batch["tokens"]
    B, S = tokens.shape
    h = cm.embed_tokens(params["emb"], tokens)
    h = h + cm.sinusoidal_pos(S, cfg.d_model).astype(h.dtype)[None]
    positions = jnp.arange(S)[None, :]

    def body(h, lp):
        xn = cm.rmsnorm(h, lp["ln1"], cfg.norm_eps)
        a, (k, v) = attn.attn_prefill(lp["attn"], cfg, xn, positions)
        h = h + a
        kx, vx = _cross_kv(lp, cfg, enc)
        x = cm.rmsnorm(h, lp["ln_x"], cfg.norm_eps)
        h = h + attn.attn_forward(lp["xattn"], cfg, x, causal=False,
                                  kv=(kx, vx))
        h = h + mlp_mod.mlp_forward(lp["mlp"], cfg,
                                    cm.rmsnorm(h, lp["ln2"], cfg.norm_eps))
        return h, (k, v, kx, vx)

    h, (ks, vs, xks, xvs) = jax.lax.scan(body, h, params["dec_layers"])
    hl = h[:, -1] if last_pos is None else \
        jnp.take_along_axis(h, last_pos[:, None, None].astype(jnp.int32)
                            .repeat(h.shape[-1], -1), axis=1)[:, 0]
    hl = cm.rmsnorm(hl, params["ln_f"], cfg.norm_eps)
    logits = cm.unembed(params["emb"], cfg, hl)
    L = ks.shape[0]                             # each (L, B, S, KH, hd)
    cache = {name: c.reshape(L, B, c.shape[2], -1) for name, c in
             (("k", ks), ("v", vs), ("xk", xks), ("xv", xvs))}
    cache["len"] = jnp.full((B,), S, jnp.int32)
    return logits, cache


def decode_step(params, cfg, cache, tokens):
    B = tokens.shape[0]
    lengths = cache["len"]
    h = cm.embed_tokens(params["emb"], tokens)
    # sinusoidal position of the new token (same for all seqs in dry-run;
    # per-seq offsets via lengths)
    d = cfg.d_model
    inv = 1.0 / (10000.0 ** (jnp.arange(d // 2, dtype=jnp.float32) / (d // 2)))
    ang = lengths[:, None].astype(jnp.float32) * inv[None, :]
    pos = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
    h = h + pos.astype(h.dtype)
    F = cfg.enc_seq
    flen = jnp.full((B,), F, jnp.int32)

    def body(carry, lp):
        h, ks, vs, layer = carry
        xn = cm.rmsnorm(h, lp["ln1"], cfg.norm_eps)
        a, ks, vs = attn.attn_decode(lp["attn"], cfg, xn, ks, vs, layer,
                                     lengths)
        h = h + a
        x = cm.rmsnorm(h, lp["ln_x"], cfg.norm_eps)
        q = (x @ lp["xattn"]["wq"] + lp["xattn"].get("bq", 0)).reshape(
            B, cfg.n_heads, cfg.resolved_head_dim)
        cx = da_ops.decode_attention(q, cache["xk"], cache["xv"], flen, layer)
        h = h + cx.reshape(B, -1) @ lp["xattn"]["wo"]
        h = h + mlp_mod.mlp_forward(lp["mlp"], cfg,
                                    cm.rmsnorm(h, lp["ln2"], cfg.norm_eps))
        return (h, ks, vs, layer + 1), None

    (h, ks, vs, _), _ = jax.lax.scan(
        body, (h, cache["k"], cache["v"], jnp.int32(0)), params["dec_layers"])
    h = cm.rmsnorm(h, params["ln_f"], cfg.norm_eps)
    logits = cm.unembed(params["emb"], cfg, h)
    new_cache = dict(cache, k=ks, v=vs, len=lengths + 1)
    return logits, new_cache
