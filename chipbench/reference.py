"""The plain float32 reference's shared parts, and the gap arithmetic.

Each model family's reference (``chipbench/families/<family>.py``,
``reference(conf, mode)``) is straight ``jax.numpy`` at
``Precision.HIGHEST`` built from the pieces here: no kernels, no cache,
no batching, and nothing of the program. ``logits_at`` runs it over one
sequence; ``chunk_readings`` reads the gaps from its logits.

``mode="fp8"`` makes a reference the control, computed one precision
below the bf16 that the configurations state: every matrix product of
the linear layers takes operands rounded to fp8 E4M3 (scaled per row of
activations and per output column of weights) and accumulates in
float32.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def quantize(x, axis: int, mode: Optional[str]):
    if mode is None:
        return x
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    if mode == "fp8":
        s = jnp.maximum(amax, 1e-30) / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    raise ValueError(f"unknown mode {mode!r}")


def linear(x, w, mode):
    """x (T, din) @ w (din, dout), operands rounded per ``mode``."""
    return jnp.matmul(quantize(x, -1, mode), quantize(w, 0, mode),
                      precision=HI)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x (T, heads, hd): rotate-half RoPE at integer positions ``pos``."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = pos[:, None].astype(F32) * inv                    # (T, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _bucket(n: int, least: int, most: int) -> int:
    b = least
    while b < n:
        b *= 2
    return max(n, min(b, most))


CHUNK = 256     # positions whose logits are formed at once


def logits_at(family, params, conf: dict, tokens: np.ndarray,
              sel: np.ndarray, mode: Optional[str] = None):
    """``family``'s reference (or control) logits of the sequence
    ``tokens`` at positions ``sel``, as a list of float32 arrays of
    ``CHUNK`` rows (the last padded with rows of position 0). The
    sequence is padded at its end to a power of two from 256 up, at most
    the engine's ``max_len`` (causal attention leaves earlier positions
    unchanged), and logits are formed ``CHUNK`` positions at a time, so
    that few shapes compile."""
    hidden, logits = family.reference(conf, mode)
    T = len(tokens)
    tok = np.zeros(_bucket(T, 256, conf["engine"]["max_len"]), np.int32)
    tok[:T] = tokens
    hn = hidden(params, tok)
    out = []
    for i in range(0, len(sel), CHUNK):
        s = np.zeros(CHUNK, np.int32)
        part = sel[i:i + CHUNK]
        s[:len(part)] = part
        out.append(logits(params, hn, s))
    return out


def gap_below_best(ref_logits, chosen) -> jax.Array:
    """Per position: how far the reference's logit of the chosen token
    lies below the reference's best logit (0 where it is the best)."""
    picked = jnp.take_along_axis(ref_logits, chosen[:, None], 1)[:, 0]
    return jnp.max(ref_logits, -1) - picked


@jax.jit
def chunk_readings(ref_logits, served, ctrl_logits, valid):
    """Readings of one ``CHUNK`` of positions, of which the first
    ``valid`` are real: the widest gap of the served tokens, the gap
    between the reference's two best logits at each position (-1 past
    ``valid``), and the widest gap of the tokens the control puts
    first. One compiled shape for every chunk."""
    real = jnp.arange(ref_logits.shape[0]) < valid
    gap = jnp.max(jnp.where(real, gap_below_best(ref_logits, served), 0.0))
    top = jax.lax.top_k(ref_logits, 2)[0]
    top2 = jnp.where(real, top[:, 0] - top[:, 1], -1.0)
    ctrl = gap_below_best(ref_logits, jnp.argmax(ctrl_logits, -1))
    return gap, top2, jnp.max(jnp.where(real, ctrl, 0.0))
