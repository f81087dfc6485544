"""Pallas TPU chunked-SSD (Mamba2) scan kernel.

TPU adaptation of the SSD algorithm (DESIGN.md §3): instead of the GPU
implementation's warp-level scan, the sequence is processed in chunks of
T tokens; each chunk is three MXU matmuls (intra-chunk (T x T) decay-
masked attention-like product, inter-chunk state read, state update) and
the running (P x N) state is carried across the sequential chunk grid
dimension in VMEM scratch — the same carry idiom as flash attention's
online softmax.

grid = (B, H, S/T); per-step VMEM blocks: x (T,P), dt (T,1), B/C (T,N),
state scratch (P,N) fp32. T defaults to 64: (64x64)x(64xN) keeps all
operands resident and the TxT score matrix MXU-aligned for P=N=64.

Validated on CPU via interpret=True against ref.ssd_reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(A_ref, D_ref, x_ref, dt_ref, B_ref, C_ref, s0_ref,
                y_ref, sf_ref, state_ref, *, T: int):
    h = pl.program_id(1)
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    a = A_ref[h]
    d = D_ref[h]
    x = x_ref[0, 0].astype(jnp.float32)            # (T, P)
    dt = dt_ref[0, 0].astype(jnp.float32)          # (T, 1)
    Bm = B_ref[0].astype(jnp.float32)              # (T, N)
    Cm = C_ref[0].astype(jnp.float32)              # (T, N)

    t_idx = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
    u_idx = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
    loglam = dt * a                                # (T, 1)
    # prefix sum as a lower-triangular masked row sum: Mosaic has no
    # cumsum lowering, and a lane reduction keeps it exact in fp32
    cum = jnp.sum(jnp.where(u_idx <= t_idx, loglam.reshape(1, T), 0.0),
                  axis=1, keepdims=True)           # (T, 1) log L_t
    Lt = jnp.exp(cum)                              # (T, 1)

    # intra-chunk score M[t,u] = (C_t.B_u) * dt_u * exp(cum_t - cum_u), u<=t
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (T, T)
    ratio = jnp.exp(cum - cum.reshape(1, T))       # (T, T) exp(cum_t - cum_u)
    M = cb * dt.reshape(1, T) * ratio
    M = jnp.where(u_idx <= t_idx, M, 0.0)
    y = jax.lax.dot_general(M, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (T, P)

    # inter-chunk contribution: L_t * (state @ C_t)
    state = state_ref[...]                          # (P, N)
    y += Lt * jax.lax.dot_general(Cm, state, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y += d * x
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: S <- L_T * S + sum_u exp(cum_T - cum_u) dt_u x_u B_u^T
    Lend = jnp.exp(cum[T - 1:T, :])                 # (1, 1)
    w = jnp.exp(cum[T - 1:T, :] - cum) * dt         # (T, 1)
    upd = jax.lax.dot_general(x * w, Bm, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (P, N)
    state_ref[...] = Lend[0, 0] * state + upd

    @pl.when(ic == nc - 1)
    def _finalize():
        sf_ref[0, 0] = state_ref[...].astype(sf_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_pallas(x, dt, A, Bmat, Cmat, D, init_state=None, *, chunk=64,
               interpret=False):
    """x (B,S,H,P), dt (B,S,H), A (H,), B/C (B,S,N), D (H,) ->
    (y (B,S,H,P), final_state (B,H,P,N))."""
    Bsz, S, H, P = x.shape
    N = Bmat.shape[-1]
    T = min(chunk, S)
    assert S % T == 0, (S, T)
    if init_state is None:
        init_state = jnp.zeros((Bsz, H, P, N), jnp.float32)

    xt = x.transpose(0, 2, 1, 3)                   # (B,H,S,P)
    dtt = dt.transpose(0, 2, 1)[..., None]         # (B,H,S,1)

    grid = (Bsz, H, S // T)
    y, sf = pl.pallas_call(
        functools.partial(_ssd_kernel, T=T),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # A (H,)
            pl.BlockSpec(memory_space=pltpu.SMEM),   # D (H,)
            pl.BlockSpec((1, 1, T, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, T, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, T, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, T, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, T, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((Bsz, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(A.astype(jnp.float32), D.astype(jnp.float32), xt, dtt, Bmat, Cmat,
      init_state)
    return y.transpose(0, 2, 1, 3), sf
