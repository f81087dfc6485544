"""Pallas TPU decode-attention kernel (single-token GQA over a KV cache),
reading the model's layer-stacked cache in place.

Decode attention is the HBM-bandwidth-bound hot spot of every Coral
decode Serving Instance (paper §2.1): per generated token the KV cache
must stream HBM->VMEM once. The kernel therefore:

  * reads the cache where the model keeps it, (L, B, S, KH*D): token
    major, the kv heads folded into the minor dim, so a block of rows is
    contiguous and lane-dense whatever the head dim. The layer index and
    the lengths are scalar-prefetched and pick the blocks; nothing copies
    or transposes the cache;
  * grid = (B, S/bk) with the KV-block index minor/sequential; each step
    streams a (bk, KH*D) block of K and of V and computes every head. The
    H query rows sit block-diagonally in an (H, KH*D) operand (head h in
    the lanes of its kv head h // G), so one MXU matmul gives all H score
    rows and one more the weighted values, of which each row keeps the
    lanes of its own kv head when the softmax ends;
  * the fp32 online softmax lives in VMEM scratch across KV blocks;
  * blocks beyond the valid cache length short-circuit via pl.when.

Validated on CPU via interpret=True against ref.decode_attention_reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import NEG_INF

# The cache length must be a multiple of min(BLOCK_K, cache length): a
# block is the whole cache, or a multiple of BLOCK_K rows that divides it.
BLOCK_K = 256
# VMEM for the double-buffered K and V blocks beyond BLOCK_K rows; the
# rest of the scoped VMEM holds a block's fp32 temporaries. (On a v5e,
# qwen2-1.5b's cache streamed at the same rate in blocks of 1024 to 4096
# rows, and slower in blocks of 512.)
KV_VMEM_BYTES = 4 << 20


def block_rows(S: int, row_bytes: int) -> int:
    """Cache rows per grid step: the whole cache when it is at most
    BLOCK_K rows or its K and V blocks fit KV_VMEM_BYTES double-buffered,
    else the largest multiple of BLOCK_K that divides S and fits, and
    BLOCK_K where none fits (minicpm-2b's 2304-lane rows)."""
    fits = lambda n: 4 * n * row_bytes <= KV_VMEM_BYTES
    if S <= BLOCK_K or fits(S):
        return S
    assert S % BLOCK_K == 0, f"{S} cache rows cannot be tiled by {BLOCK_K}"
    return max((n for n in range(BLOCK_K, S, BLOCK_K)
                if S % n == 0 and fits(n)), default=BLOCK_K)


def _decode_kernel(len_ref, layer_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *,
                   bk: int, window: int, scale: float, group: int,
                   head_dim: int):
    del layer_ref                          # used by the index maps only
    b = pl.program_id(0)
    ik = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]
    k_lo = ik * bk
    live = k_lo < length
    if window > 0:
        live &= (k_lo + bk - 1) > (length - 1 - window)

    @pl.when(live)
    def _compute():
        q = q_ref[...]                                    # (H, KH*D)
        k = k_ref[...].astype(q.dtype)                    # (bk, KH*D)
        v = v_ref[...].astype(jnp.float32)                # (bk, KH*D)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale                                     # (H, bk)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos < length
        if window > 0:
            mask &= k_pos > (length - 1 - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        acc = acc_ref[...] / (l_ref[...] + 1e-30)         # (H, KH*D)
        H, D = acc.shape[0], head_dim
        kv_head = jax.lax.broadcasted_iota(jnp.int32, (H, D), 0) // group
        out = jnp.zeros((H, D), jnp.float32)
        for h in range(acc.shape[1] // D):                # own kv head's lanes
            out += jnp.where(kv_head == h, acc[:, h * D:(h + 1) * D], 0.0)
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "window", "bk",
                                             "interpret"))
def decode_attention_pallas(q, k_cache, v_cache, lengths, layer, *,
                            scale=None, window=0, bk=None, interpret=False):
    """q: (B, H, D); k/v_cache: (L, B, Smax, KH*D); lengths: (B,);
    layer: int32 scalar, which of the L caches to read -> (B, H, D)."""
    B, H, D = q.shape
    _, _, S, F = k_cache.shape
    KH = F // D
    assert KH * D == F and H % KH == 0, (q.shape, k_cache.shape)
    G = H // KH
    scale_v = scale if scale is not None else D ** -0.5
    bk = bk or block_rows(S, F * k_cache.dtype.itemsize)
    assert S % bk == 0, (S, bk)

    # query heads placed block-diagonally: row h holds q[h] in the lanes
    # of kv head h // G and zeros elsewhere
    dt = jnp.promote_types(q.dtype, k_cache.dtype)
    own = jnp.arange(H)[:, None] // G == jnp.arange(KH)[None, :]   # (H, KH)
    qbd = jnp.where(own[None, :, :, None], q[:, :, None, :], 0)
    qbd = qbd.reshape(B, H, F).astype(dt)
    lengths = lengths.astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    kernel = functools.partial(_decode_kernel, bk=bk, window=window,
                               scale=scale_v, group=G, head_dim=D)
    kv_spec = pl.BlockSpec((None, None, bk, F),
                           lambda b, ik, n, layer: (layer[0], b, ik, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                # lengths, layer
            grid=(B, S // bk),
            in_specs=[
                pl.BlockSpec((None, H, F), lambda b, ik, n, layer: (b, 0, 0)),
                kv_spec,
                kv_spec,
            ],
            out_specs=pl.BlockSpec((None, H, D),
                                   lambda b, ik, n, layer: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, F), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=interpret,
    )(lengths, layer, qbd, k_cache, v_cache)
