"""Pallas TPU decode-attention kernel (single-token GQA over a KV cache).

Decode attention is the HBM-bandwidth-bound hot spot of every Coral
decode Serving Instance (paper §2.1): per generated token the full KV
cache must stream HBM->VMEM once. The kernel therefore:

  * lays KV out as (B, KH, S, D) so the streamed axis S is contiguous,
  * grid = (B, KH, S/bk) with the KV-block index minor/sequential;
    the fp32 (G, D) accumulator for the G = H/KH grouped query heads of
    one KV head lives in VMEM scratch across KV blocks (online softmax),
  * the G query rows share each streamed KV block — GQA turns a
    vector-matrix product into a (G x D) @ (D x bk) MXU matmul,
    raising arithmetic intensity by G without extra HBM traffic,
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

# KV block streamed per grid step; the cache length must be a multiple of
# min(BLOCK_K, cache length)
BLOCK_K = 256


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *,
                   bk: int, window: int, scale: float):
    b = pl.program_id(0)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

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
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)               # (bk, D)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (G, bk)
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
        o_ref[0, 0] = (acc_ref[...] / (l_ref[...] + 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "window", "bk",
                                             "interpret"))
def decode_attention_pallas(q, k_cache, v_cache, lengths, *, scale=None,
                            window=0, bk=BLOCK_K, interpret=False):
    """q: (B, H, D); k/v_cache: (B, Smax, KH, D); lengths: (B,) -> (B, H, D)."""
    B, H, D = q.shape
    _, S, KH, _ = k_cache.shape
    assert H % KH == 0
    G = H // KH
    scale_v = scale if scale is not None else D ** -0.5
    bk = min(bk, S)
    assert S % bk == 0, (S, bk)

    qg = q.reshape(B, KH, G, D)
    kt = k_cache.transpose(0, 2, 1, 3)      # (B, KH, S, D)
    vt = v_cache.transpose(0, 2, 1, 3)
    lengths = lengths.astype(jnp.int32)

    grid = (B, KH, S // bk)
    kernel = functools.partial(_decode_kernel, bk=bk, window=window,
                               scale=scale_v)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # lengths: scalar prefetch
            pl.BlockSpec((1, 1, G, D), lambda b, h, ik: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, ik: (b, h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, ik: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KH, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
        interpret=interpret,
    )(lengths, qg, kt, vt)
    return out.reshape(B, H, D)
