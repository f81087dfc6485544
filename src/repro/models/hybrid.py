"""Zamba2-style hybrid: a stack of Mamba2 (SSD) layers with one *shared*
attention+MLP block (a single weight set) applied after every
``attn_every``-th SSM layer. The shared block consumes concat(h, emb0)
(2d -> d input projection), following Zamba2's global-residual design.

Decode state is O(1) per sequence (SSM state + conv tail) plus a KV cache
only at the few shared-attention insertion points => long_500k runs.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import constrain, batch_axes
from repro.kernels.mamba_scan import ops as ssd_ops
from repro.kernels.mamba_scan import ref as ssd_ref
from repro.models import attention as attn
from repro.models import common as cm
from repro.models import mlp as mlp_mod


def _conv_dim(cfg) -> int:
    return cfg.d_inner + 2 * cfg.ssm_state


def n_insertions(cfg) -> int:
    return cfg.n_layers // cfg.attn_every if cfg.attn_every else 0


# --------------------------------------------------------------- mamba layer
def mamba_init(key, cfg, dtype):
    d, di, N, H = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_nheads
    cd = _conv_dim(cfg)
    ks = jax.random.split(key, 4)
    p = {
        "ln": cm.rmsnorm_init(d, dtype)[0],
        "in_proj": cm.dense_init(ks[0], d, (d, di + cd + H), dtype),
        "conv_w": cm.dense_init(ks[1], cfg.ssm_conv, (cfg.ssm_conv, cd), dtype),
        "conv_b": jnp.zeros((cd,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H)).astype(jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.full((H,), -2.0, jnp.float32),
        "norm": cm.rmsnorm_init(di, dtype)[0],
        "out_proj": cm.dense_init(ks[2], di, (di, d), dtype),
    }
    fsdp = "data" if cfg.weight_sharding == "fsdp" else None
    s = {
        "ln": {"scale": P(None)},
        "in_proj": P(fsdp, "model"),
        "conv_w": P(None, "model"),
        "conv_b": P("model"),
        "A_log": P(None), "D": P(None), "dt_bias": P(None),
        "norm": {"scale": P("model")},
        "out_proj": P("model", fsdp),
    }
    return p, s


def _mamba_project(p, cfg, x):
    """x (..., d) -> z (..., di), xBC (..., cd), dt (..., H) post-activation."""
    di, H = cfg.d_inner, cfg.ssm_nheads
    cd = _conv_dim(cfg)
    proj = x @ p["in_proj"]
    z = proj[..., :di]
    xBC = proj[..., di:di + cd]
    dt = jax.nn.softplus(proj[..., di + cd:].astype(jnp.float32)
                         + p["dt_bias"])
    return z, xBC, dt


def _split_xbc(cfg, xBC):
    di, N = cfg.d_inner, cfg.ssm_state
    return xBC[..., :di], xBC[..., di:di + N], xBC[..., di + N:]


def mamba_forward(p, cfg, h, return_state=False):
    """Full-sequence Mamba2 layer. h (B,S,d)."""
    B, S, d = h.shape
    H, Pd, N = cfg.ssm_nheads, cfg.ssm_head_dim, cfg.ssm_state
    x_in = cm.rmsnorm(h, p["ln"], cfg.norm_eps)
    z, xBC, dt = _mamba_project(p, cfg, x_in)
    # causal depthwise conv (width ssm_conv) over the sequence
    w = p["conv_w"]
    pad = jnp.pad(xBC, ((0, 0), (cfg.ssm_conv - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + S, :] * w[i][None, None, :]
               for i in range(cfg.ssm_conv)) + p["conv_b"]
    conv = jax.nn.silu(conv)
    x, Bm, Cm = _split_xbc(cfg, conv)
    xh = x.reshape(B, S, H, Pd)
    A = -jnp.exp(p["A_log"])
    out = ssd_ops.ssd_scan(xh, dt, A, Bm, Cm, p["D"],
                           with_state=return_state)
    if return_state:
        y, state = out
    else:
        y, state = out, None
    y = y.reshape(B, S, cfg.d_inner)
    y = cm.rmsnorm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    out_h = h + y @ p["out_proj"]
    if return_state:
        # last (conv-1) raw xBC inputs, needed to continue the conv
        conv_tail = xBC[:, S - (cfg.ssm_conv - 1):, :] if S >= cfg.ssm_conv - 1 \
            else jnp.pad(xBC, ((0, 0), (cfg.ssm_conv - 1 - S, 0), (0, 0)))
        return out_h, (state, conv_tail)
    return out_h


def mamba_decode(p, cfg, h, ssm_state, conv_buf):
    """One-token step. h (B,d); ssm_state (B,H,P,N); conv_buf (B,conv-1,cd)."""
    B, d = h.shape
    H, Pd = cfg.ssm_nheads, cfg.ssm_head_dim
    x_in = cm.rmsnorm(h, p["ln"], cfg.norm_eps)
    z, xBC, dt = _mamba_project(p, cfg, x_in)          # (B,cd),(B,H)
    window = jnp.concatenate([conv_buf, xBC[:, None, :]], axis=1)  # (B,conv,cd)
    conv = jnp.einsum("bkc,kc->bc", window, p["conv_w"]) + p["conv_b"]
    conv = jax.nn.silu(conv)
    x, Bm, Cm = _split_xbc(cfg, conv)
    xh = x.reshape(B, H, Pd)
    A = -jnp.exp(p["A_log"])
    y, new_state = ssd_ref.ssd_decode_step(ssm_state, xh, dt, A, Bm, Cm,
                                           p["D"])
    y = y.reshape(B, cfg.d_inner)
    y = cm.rmsnorm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    return h + y @ p["out_proj"], new_state, window[:, 1:, :]


# ------------------------------------------------------- shared attn block
def shared_init(key, cfg, dtype):
    d = cfg.d_model
    ks = jax.random.split(key, 3)
    p, s = {}, {}
    p["ln"], s["ln"] = cm.rmsnorm_init(2 * d, dtype)
    p["attn"], s["attn"] = attn.attn_init(ks[0], cfg, dtype, d_in=2 * d)
    p["ln2"], s["ln2"] = cm.rmsnorm_init(d, dtype)
    p["mlp"], s["mlp"] = mlp_mod.mlp_init(ks[1], cfg, dtype)
    return p, s


def shared_forward(p, cfg, h, emb0, positions):
    x = jnp.concatenate([h, emb0], axis=-1)
    a = attn.attn_forward(p["attn"], cfg, cm.rmsnorm(x, p["ln"], cfg.norm_eps),
                          positions)
    h = h + a
    h = h + mlp_mod.mlp_forward(p["mlp"], cfg,
                                cm.rmsnorm(h, p["ln2"], cfg.norm_eps))
    return h


def shared_prefill(p, cfg, h, emb0, positions):
    x = jnp.concatenate([h, emb0], axis=-1)
    a, kv = attn.attn_prefill(p["attn"], cfg,
                              cm.rmsnorm(x, p["ln"], cfg.norm_eps), positions)
    h = h + a
    h = h + mlp_mod.mlp_forward(p["mlp"], cfg,
                                cm.rmsnorm(h, p["ln2"], cfg.norm_eps))
    return h, kv


def shared_decode(p, cfg, h, emb0, ck, cv, ins, lengths):
    x = jnp.concatenate([h, emb0], axis=-1)
    a, ck, cv = attn.attn_decode(p["attn"], cfg,
                                 cm.rmsnorm(x, p["ln"], cfg.norm_eps),
                                 ck, cv, ins, lengths)
    h = h + a
    h = h + mlp_mod.mlp_forward(p["mlp"], cfg,
                                cm.rmsnorm(h, p["ln2"], cfg.norm_eps))
    return h, ck, cv


# ------------------------------------------------------------------- model
def init(key, cfg, max_seq: int = 4096):
    dtype = cm.compute_dtype(cfg)
    ks = jax.random.split(key, 3)
    p, s = {}, {}
    p["emb"], s["emb"] = cm.embedding_init(ks[0], cfg, dtype)
    p["mamba"], s["mamba"] = cm.stacked(
        lambda k: mamba_init(k, cfg, dtype), ks[1], cfg.n_layers)
    p["shared"], s["shared"] = shared_init(ks[2], cfg, dtype)
    p["ln_f"], s["ln_f"] = cm.rmsnorm_init(cfg.d_model, dtype)
    return p, s


def _groups(cfg):
    """[(start, stop, attn_after)] covering all layers."""
    out, i = [], 0
    k = cfg.attn_every
    while i < cfg.n_layers:
        j = min(i + k, cfg.n_layers)
        out.append((i, j, (j - i) == k))
        i = j
    return out


def _slice_layers(stacked_params, lo, hi):
    return jax.tree.map(lambda a: a[lo:hi], stacked_params)


def forward(params, cfg, batch: Dict):
    tokens = batch["tokens"]
    h = cm.embed_tokens(params["emb"], tokens)
    emb0 = h
    positions = jnp.arange(tokens.shape[1])[None, :]

    def body(h, lp):
        h2 = mamba_forward(lp, cfg, h)
        return constrain(h2, batch_axes(), None, None), None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    for lo, hi, has_attn in _groups(cfg):
        h, _ = jax.lax.scan(body_fn, h, _slice_layers(params["mamba"], lo, hi))
        if has_attn:
            h = shared_forward(params["shared"], cfg, h, emb0, positions)
    h = cm.rmsnorm(h, params["ln_f"], cfg.norm_eps)
    logits = cm.unembed(params["emb"], cfg, h)
    return constrain(logits, batch_axes(), None, "model"), 0.0


def init_cache(cfg, batch_size: int, max_len: int, dtype=jnp.bfloat16):
    L, H, Pd, N = cfg.n_layers, cfg.ssm_nheads, cfg.ssm_head_dim, cfg.ssm_state
    cd = _conv_dim(cfg)
    KH, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    ni = n_insertions(cfg)
    dp = ("data",)
    cache = {
        "ssm": jnp.zeros((L, batch_size, H, Pd, N), jnp.float32),
        "conv": jnp.zeros((L, batch_size, cfg.ssm_conv - 1, cd), dtype),
        # one per insertion, in decode_attention's (.., S, KH*hd) layout
        "k": jnp.zeros((ni, batch_size, max_len, KH * hd), dtype),
        "v": jnp.zeros((ni, batch_size, max_len, KH * hd), dtype),
        "len": jnp.zeros((batch_size,), jnp.int32),
    }
    specs = {
        "ssm": P(None, dp, "model", None, None),
        "conv": P(None, dp, None, "model"),
        # long-context: shared-attn KV is sequence-sharded over "data"
        # when batch < data axis (DESIGN.md §5)
        "k": P(None, dp, None, "model"),
        "v": P(None, dp, None, "model"),
        "len": P(dp),
    }
    return cache, specs


def prefill(params, cfg, batch: Dict, last_pos=None):
    tokens = batch["tokens"]
    B, S = tokens.shape
    h = cm.embed_tokens(params["emb"], tokens)
    emb0 = h
    positions = jnp.arange(S)[None, :]

    def body(h, lp):
        h2, (state, conv_tail) = mamba_forward(lp, cfg, h, return_state=True)
        return h2, (state, conv_tail)

    kv_width = cfg.n_kv_heads * cfg.resolved_head_dim
    states, convs, ks, vs = [], [], [], []
    for lo, hi, has_attn in _groups(cfg):
        h, (st, cv_) = jax.lax.scan(body, h,
                                    _slice_layers(params["mamba"], lo, hi))
        states.append(st)
        convs.append(cv_)
        if has_attn:
            h, kv = shared_prefill(params["shared"], cfg, h, emb0, positions)
            ks.append(kv[0].reshape(B, S, -1))
            vs.append(kv[1].reshape(B, S, -1))
    hl = h[:, -1] if last_pos is None else \
        jnp.take_along_axis(h, last_pos[:, None, None].astype(jnp.int32)
                            .repeat(h.shape[-1], -1), axis=1)[:, 0]
    hl = cm.rmsnorm(hl, params["ln_f"], cfg.norm_eps)
    logits = cm.unembed(params["emb"], cfg, hl)
    cache = {
        "ssm": jnp.concatenate(states, 0),
        "conv": jnp.concatenate(convs, 0),
        "k": jnp.stack(ks, 0) if ks else jnp.zeros((0, B, S, kv_width)),
        "v": jnp.stack(vs, 0) if vs else jnp.zeros((0, B, S, kv_width)),
        "len": jnp.full((B,), S, jnp.int32),
    }
    return logits, cache


def decode_step(params, cfg, cache, tokens):
    B = tokens.shape[0]
    lengths = cache["len"]
    h = cm.embed_tokens(params["emb"], tokens)
    emb0 = h

    def body(h, xs):
        lp, st, cb = xs
        h2, st, cb = mamba_decode(lp, cfg, h, st, cb)
        return h2, (st, cb)

    new_ssm, new_conv = [], []
    ks, vs = cache["k"], cache["v"]
    ins = 0
    for lo, hi, has_attn in _groups(cfg):
        xs = (_slice_layers(params["mamba"], lo, hi),
              cache["ssm"][lo:hi], cache["conv"][lo:hi])
        h, (st, cb) = jax.lax.scan(body, h, xs)
        new_ssm.append(st)
        new_conv.append(cb)
        if has_attn:
            h, ks, vs = shared_decode(params["shared"], cfg, h, emb0,
                                      ks, vs, ins, lengths)
            ins += 1
    h = cm.rmsnorm(h, params["ln_f"], cfg.norm_eps)
    logits = cm.unembed(params["emb"], cfg, h)
    new_cache = {
        "ssm": jnp.concatenate(new_ssm, 0),
        "conv": jnp.concatenate(new_conv, 0),
        "k": ks,
        "v": vs,
        "len": lengths + 1,
    }
    return logits, new_cache
