"""Pure-jnp oracle for single-token GQA decode attention over a
layer-stacked KV cache, in fp32."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import NEG_INF


def decode_attention_reference(q, k_cache, v_cache, lengths, layer=0, *,
                               scale: float | None = None, window: int = 0):
    """q: (B, H, D); k/v_cache: (L, B, Smax, KH*D); lengths: (B,) int32;
    layer: which of the L caches to read.

    Position of the query token is lengths-1 (the cache already contains
    the current token's K/V at index lengths-1); a sequence of length 0
    attends to nothing and gives zeros. Returns (B, H, D).
    """
    B, H, D = q.shape
    _, _, S, F = k_cache.shape
    KH = F // D
    g = H // KH
    scale = scale if scale is not None else D ** -0.5

    def one_layer(c):
        c = jax.lax.dynamic_index_in_dim(c, layer, 0, keepdims=False)
        return c.reshape(B, S, KH, D).astype(jnp.float32)

    k, v = one_layer(k_cache), one_layer(v_cache)
    qg = q.astype(jnp.float32).reshape(B, KH, g, D) * scale
    logits = jnp.einsum("bkgd,bskd->bkgs", qg, k)
    k_pos = jnp.arange(S)[None, None, None, :]
    mask = k_pos < lengths[:, None, None, None]
    if window and window > 0:
        mask &= k_pos > (lengths[:, None, None, None] - 1 - window)
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jnp.where(mask, jnp.exp(logits - logits.max(-1, keepdims=True)),
                      0.0)
    probs = probs / (probs.sum(-1, keepdims=True) + 1e-30)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, v)
    return out.reshape(B, H, D).astype(q.dtype)
