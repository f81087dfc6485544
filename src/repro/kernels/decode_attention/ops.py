"""jit'd public wrapper for decode attention (inference-only: no vjp)."""
from __future__ import annotations

from repro.kernels.common import resolve_impl
from repro.kernels.decode_attention import kernel as _kernel
from repro.kernels.decode_attention import ref as _ref


def decode_attention(q, k_cache, v_cache, lengths, layer=0, *, scale=None,
                     window=0, impl: str | None = None):
    """q: (B, H, D); k/v_cache: (L, B, Smax, KH*D), the layer-stacked
    cache with the kv heads folded into the last dim (a single layer's is
    L = 1); lengths: (B,); layer: which of the L to read -> (B, H, D)."""
    impl = resolve_impl(impl)
    if impl == "ref":
        return _ref.decode_attention_reference(
            q, k_cache, v_cache, lengths, layer, scale=scale, window=window)
    return _kernel.decode_attention_pallas(
        q, k_cache, v_cache, lengths, layer, scale=scale, window=window,
        interpret=(impl == "pallas_interpret"))
