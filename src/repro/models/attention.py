"""GQA attention layer: full-sequence forward (train/prefill) and
single-token cached decode. RoPE / M-RoPE / sinusoidal-free variants.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import constrain, batch_axes
from repro.kernels.decode_attention import ops as da_ops
from repro.kernels.flash_attention import ops as fa_ops
from repro.models import common as cm


def attn_init(key, cfg, dtype, d_in=None):
    d = d_in or cfg.d_model
    hd = cfg.resolved_head_dim
    H, KH = cfg.n_heads, cfg.n_kv_heads
    fsdp = "data" if cfg.weight_sharding == "fsdp" else None
    ks = jax.random.split(key, 4)
    p = {
        "wq": cm.dense_init(ks[0], d, (d, H * hd), dtype),
        "wk": cm.dense_init(ks[1], d, (d, KH * hd), dtype),
        "wv": cm.dense_init(ks[2], d, (d, KH * hd), dtype),
        "wo": cm.dense_init(ks[3], H * hd, (H * hd, cfg.d_model), dtype),
    }
    s = {
        "wq": P(fsdp, "model"), "wk": P(fsdp, "model"), "wv": P(fsdp, "model"),
        "wo": P("model", fsdp),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((KH * hd,), dtype)
        p["bv"] = jnp.zeros((KH * hd,), dtype)
        s["bq"] = s["bk"] = s["bv"] = P("model")
    return p, s


def _project_qkv(p, cfg, x):
    H, KH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = x @ p["wq"] + (p["bq"] if "bq" in p else 0)
    k = x @ p["wk"] + (p["bk"] if "bk" in p else 0)
    v = x @ p["wv"] + (p["bv"] if "bv" in p else 0)
    B = x.shape[:-2]
    S = x.shape[-2]
    q = q.reshape(*B, S, H, hd)
    k = k.reshape(*B, S, KH, hd)
    v = v.reshape(*B, S, KH, hd)
    return q, k, v


def _rope_qk(cfg, q, k, positions, mrope_pos=None):
    if cfg.rope == "rope":
        q = cm.apply_rope(q, positions, cfg.rope_theta)
        k = cm.apply_rope(k, positions, cfg.rope_theta)
    elif cfg.rope == "mrope":
        q = cm.apply_mrope(q, mrope_pos, cfg.rope_theta)
        k = cm.apply_mrope(k, mrope_pos, cfg.rope_theta)
    return q, k


def attn_forward(p, cfg, x, positions=None, mrope_pos=None, causal=True,
                 kv=None):
    """Full-sequence attention. x: (B,S,d). kv: optional (k,v) for
    cross-attention (then no rope/causality on kv)."""
    B, S, _ = x.shape
    dp = batch_axes()
    q, k, v = _project_qkv(p, cfg, x)
    if kv is not None:
        k, v = kv
    else:
        if positions is None:
            positions = jnp.arange(S)[None, :]
        q, k = _rope_qk(cfg, q, k, positions, mrope_pos)
    q = constrain(q, dp, None, "model", None)
    k = constrain(k, dp, None, "model", None)
    out = fa_ops.flash_attention(q, k, v, causal=causal,
                                 window=cfg.sliding_window)
    out = out.reshape(B, S, -1)
    return out @ p["wo"]


def attn_prefill(p, cfg, x, positions=None, mrope_pos=None):
    """Forward + return (out, (k_cache_slice, v_cache_slice))."""
    B, S, _ = x.shape
    with jax.named_scope("attn_qkv"):
        q, k, v = _project_qkv(p, cfg, x)
        if positions is None:
            positions = jnp.arange(S)[None, :]
        q, k = _rope_qk(cfg, q, k, positions, mrope_pos)
    with jax.named_scope("attention"):
        out = fa_ops.flash_attention(q, k, v, causal=True,
                                     window=cfg.sliding_window)
    with jax.named_scope("attn_out"):
        out = out.reshape(B, S, -1)
        return out @ p["wo"], (k, v)


def attn_decode(p, cfg, x, cache_k, cache_v, layer, lengths, mrope_pos=None):
    """One-token decode. x: (B,d). cache_k/v: the layer-stacked caches
    (L,B,Smax,KH*hd), of which ``layer`` is this layer's; lengths (B,) =
    #valid tokens BEFORE this one. Writes the token's K/V row in place at
    (layer, b, lengths[b]); returns (out (B,d), cache_k, cache_v)."""
    B, d = x.shape
    dp = batch_axes()
    with jax.named_scope("attn_qkv"):
        q, k, v = _project_qkv(p, cfg, x[:, None, :])
        pos = lengths[:, None]                   # (B,1) current position
        if cfg.rope == "mrope":
            q, k = _rope_qk(cfg, q, k, None, mrope_pos)
        else:
            q, k = _rope_qk(cfg, q, k, pos)
    with jax.named_scope("kv_write"):            # K/V at position `lengths`
        rows = jnp.arange(B)
        cache_k = cache_k.at[layer, rows, lengths].set(
            k.reshape(B, -1).astype(cache_k.dtype), mode="drop")
        cache_v = cache_v.at[layer, rows, lengths].set(
            v.reshape(B, -1).astype(cache_v.dtype), mode="drop")
    with jax.named_scope("attention"):
        out = da_ops.decode_attention(q[:, 0], cache_k, cache_v, lengths + 1,
                                      layer, window=cfg.sliding_window)
    with jax.named_scope("attn_out"):
        out = constrain(out, dp, "model", None)
        return out.reshape(B, -1) @ p["wo"], cache_k, cache_v
