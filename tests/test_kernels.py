"""Per-kernel correctness sweeps: Pallas (interpret mode) vs ref oracle
across shapes and dtypes, plus gradient checks through the custom vjps."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.decode_attention import kernel as da_kernel
from repro.kernels.decode_attention import ops as da_ops
from repro.kernels.mamba_scan import ops as ms_ops
from repro.kernels.mamba_scan import ref as ms_ref
from repro.kernels.moe_gmm import ops as gmm_ops


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


# ------------------------------------------------------------------ flash
@pytest.mark.parametrize("B,S,H,KH,D", [
    (1, 128, 1, 1, 64), (2, 256, 4, 2, 64), (1, 256, 8, 8, 128),
    (2, 128, 6, 2, 32),
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64),
                                           (False, 0)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, H, KH, D, causal, window, dtype):
    rng = np.random.default_rng(hash((B, S, H, KH, D, causal, window)) % 2**31)
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, S, KH, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, S, KH, D)), dtype)
    ref = fa_ops.flash_attention(q, k, v, causal=causal, window=window,
                                 impl="ref")
    out = fa_ops.flash_attention(q, k, v, causal=causal, window=window,
                                 impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_flash_attention_grad_matches_ref():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 128, 2, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 128, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 128, 2, 32)), jnp.float32)

    def loss(impl):
        return lambda q_, k_, v_: fa_ops.flash_attention(
            q_, k_, v_, impl=impl).sum()

    g1 = jax.grad(loss("pallas_interpret"), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss("ref"), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_chunked_mha_matches_full():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(2, 256, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 256, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 256, 2, 16)), jnp.float32)
    for window in (0, 48):
        full = fa_ref.mha_reference(q, k, v, causal=True, window=window)
        chk = fa_ref.mha_chunked(q, k, v, causal=True, window=window,
                                 chunk=64)
        np.testing.assert_allclose(chk, full, atol=1e-5, rtol=1e-5)


# ----------------------------------------------------------------- decode
# the layer-stacked cache (L, B, S, KH*D) read at its first and last layer;
# hd 64 with G = 1 is minicpm-2b's MHA, hd 128 with G = 6 qwen2-1.5b's GQA
@pytest.mark.parametrize("B,H,KH,D,S", [
    (2, 4, 2, 64, 512), (1, 8, 1, 128, 256), (3, 6, 6, 32, 512),
    (4, 4, 4, 64, 512), (4, 12, 2, 128, 256),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(B, H, KH, D, S, dtype):
    L = 3
    rng = np.random.default_rng(hash((B, H, KH, D, S)) % 2**31)
    q = jnp.asarray(rng.normal(size=(B, H, D)), dtype)
    kc = jnp.asarray(rng.normal(size=(L, B, S, KH * D)), dtype)
    vc = jnp.asarray(rng.normal(size=(L, B, S, KH * D)), dtype)
    drawn = rng.integers(1, S + 1, size=(B,))
    edges = np.resize([0, 1, int(rng.integers(2, S)), S], B)  # empty..full
    for lens, layer, window in itertools.product(
            (drawn, edges), (0, L - 1), (0, 64)):
        lens = jnp.asarray(lens, jnp.int32)
        ref = da_ops.decode_attention(q, kc, vc, lens, layer, window=window,
                                      impl="ref")
        outs = [da_ops.decode_attention(q, kc, vc, lens, layer,
                                        window=window,
                                        impl="pallas_interpret"),
                # several blocks a row, so dead ones are skipped
                da_kernel.decode_attention_pallas(q, kc, vc, lens, layer,
                                                  window=window, bk=128,
                                                  interpret=True)]
        for out in outs:
            np.testing.assert_allclose(np.asarray(out, np.float32),
                                       np.asarray(ref, np.float32),
                                       **_tol(dtype))


# -------------------------------------------------------------------- ssd
@pytest.mark.parametrize("B,S,H,P,N", [
    (1, 64, 2, 16, 16), (2, 128, 3, 16, 32), (1, 128, 1, 64, 64),
])
def test_ssd_sweep(B, S, H, P, N):
    rng = np.random.default_rng(hash((B, S, H, P, N)) % 2**31)
    x = jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, size=(B, S, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    D = jnp.asarray(rng.normal(size=(H,)), jnp.float32)
    y_ref, s_ref = ms_ref.ssd_reference(x, dt, A, Bm, Cm, D)
    y_chk, s_chk = ms_ref.ssd_chunked_reference(x, dt, A, Bm, Cm, D, chunk=32)
    y_pl, s_pl = ms_ops.ssd_scan(x, dt, A, Bm, Cm, D,
                                 impl="pallas_interpret", with_state=True)
    np.testing.assert_allclose(y_chk, y_ref, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(y_pl, y_ref, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(s_pl, s_ref, atol=1e-4, rtol=1e-4)


def test_ssd_decode_continuation():
    """prefill final state + decode step == full-sequence scan."""
    rng = np.random.default_rng(5)
    B, S, H, P, N = 1, 33, 2, 8, 8
    x = jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.1, size=(B, S, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    D = jnp.asarray(rng.normal(size=(H,)), jnp.float32)
    y_all, _ = ms_ref.ssd_reference(x, dt, A, Bm, Cm, D)
    _, s_pre = ms_ref.ssd_reference(x[:, :-1], dt[:, :-1], A, Bm[:, :-1],
                                    Cm[:, :-1], D)
    y_step, _ = ms_ref.ssd_decode_step(s_pre, x[:, -1], dt[:, -1], A,
                                       Bm[:, -1], Cm[:, -1], D)
    np.testing.assert_allclose(y_step, y_all[:, -1], atol=1e-5, rtol=1e-5)


# -------------------------------------------------------------------- gmm
@pytest.mark.parametrize("E,C,d,f", [(2, 32, 16, 16), (4, 64, 96, 160),
                                     (8, 128, 128, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gmm_sweep(E, C, d, f, dtype):
    rng = np.random.default_rng(hash((E, C, d, f)) % 2**31)
    x = jnp.asarray(rng.normal(size=(E, C, d)), dtype)
    w = jnp.asarray(rng.normal(size=(E, d, f)), dtype)
    ref = gmm_ops.grouped_matmul(x, w, impl="ref")
    out = gmm_ops.grouped_matmul(x, w, impl="pallas_interpret")
    tol = dict(atol=1e-1, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol)


def test_gmm_grad():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 16, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(2, 8, 12)), jnp.float32)
    g1 = jax.grad(lambda a, b: gmm_ops.grouped_matmul(
        a, b, impl="pallas_interpret").sum(), argnums=(0, 1))(x, w)
    g2 = jax.grad(lambda a, b: gmm_ops.grouped_matmul(
        a, b, impl="ref").sum(), argnums=(0, 1))(x, w)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=1e-5)
