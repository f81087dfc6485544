"""Shared kernel utilities: impl selection, padding helpers."""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp

NEG_INF = -1e30  # large-negative instead of -inf: keeps masked softmax NaN-free


@lru_cache(None)
def default_impl() -> str:
    """'pallas' on TPU, 'ref' elsewhere.

    Pallas kernels are authored for the TPU target and validated on CPU in
    interpret mode ('pallas_interpret'); XLA-fused jnp references are the
    fast path on CPU. Only an explicit ``impl=`` argument selects another
    implementation, so a TPU run always takes the Pallas kernels.
    """
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def resolve_impl(impl: str | None) -> str:
    impl = impl or default_impl()
    assert impl in ("ref", "pallas", "pallas_interpret"), impl
    return impl


def pad_to(x: jnp.ndarray, axis: int, multiple: int, value=0.0):
    """Pad ``axis`` of x up to a multiple; returns (padded, original_size)."""
    size = x.shape[axis]
    target = -(-size // multiple) * multiple
    if target == size:
        return x, size
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - size)
    return jnp.pad(x, pad, constant_values=value), size


def cdiv(a: int, b: int) -> int:
    return -(-a // b)
