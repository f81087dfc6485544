"""Pallas TPU flash-attention kernel (forward).

Blockwise online-softmax attention with GQA and sliding-window support.

TPU mapping (see DESIGN.md §3):
  * grid = (B, H, Sq/bq, Sk/bk); the KV block index is the minor
    (sequential) grid dimension, so VMEM scratch (acc/m/l) carries across
    KV blocks of one query block — the standard TPU flash pattern.
  * BlockSpecs tile Q (bq, D), K/V (bk, D) into VMEM; bq/bk default 128 to
    align the MXU's 128x128 systolic array; accumulation in fp32.
  * GQA is folded into the K/V index_map (q head h reads kv head h//g),
    so no materialized head replication touches HBM.
  * causal/sliding-window masking is applied in-block; fully-masked KV
    blocks short-circuit via pl.when (causal block pruning).

Validated on CPU via interpret=True against ref.mha_reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import NEG_INF

# query/KV block edge; a sequence must be a multiple of min(BLOCK, its length)
BLOCK = 128


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                      scale: float, causal: bool, window: int,
                      bq: int, bk: int, sq: int, sk: int):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Causal block pruning: skip KV blocks entirely above the diagonal,
    # and (for sliding window) entirely below the window.
    q_lo = iq * bq + (sk - sq)            # absolute position of first query row
    q_hi = q_lo + bq - 1
    k_lo = ik * bk
    k_hi = k_lo + bk - 1
    live = jnp.bool_(True)
    if causal:
        live &= k_lo <= q_hi
    if window > 0:
        live &= k_hi > q_lo - window

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale      # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)              # (bk, D)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (bq, bk)

        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), dtype=jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window > 0:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                              # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                           # (bq, bk)

        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] / (l_ref[...] + 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "bq", "bk", "interpret"))
def flash_attention_fwd(q, k, v, *, causal=True, window=0, scale=None,
                        bq=BLOCK, bk=BLOCK, interpret=False):
    """q: (B, Sq, H, D); k/v: (B, Sk, KH, D) -> (B, Sq, H, D)."""
    B, Sq, H, D = q.shape
    _, Sk, KH, _ = k.shape
    assert H % KH == 0
    g = H // KH
    scale_v = scale if scale is not None else D ** -0.5
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)

    # (B, H, S, D) layout: heads become a grid dimension.
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    grid = (B, H, Sq // bq, Sk // bk)
    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale_v, causal=causal, window=window,
        bq=bq, bk=bk, sq=Sq, sk=Sk)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik: (b, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),   # acc
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running denom
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
