"""Ahead-of-time compiles of the four Pallas kernels for a described TPU
v5e chip, at the real widths of the configs that use them.

Nothing runs: the chip's compiler is handed shapes and refuses what
interpret mode cannot show (a primitive Mosaic cannot lower, a block not
aligned to the tiling, too much VMEM). The topology is described inside
a module fixture, never at import, so every pytest worker collects the
same tests and only the worker that runs this file loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels.decode_attention import ops as da_ops
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.mamba_scan import ops as ms_ops
from repro.kernels.moe_gmm import ops as gmm_ops

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a persistent-cache entry for a described chip cannot be read back
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        cc.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


QWEN = get_config("qwen2-1.5b")
ZAMBA = get_config("zamba2-1.2b")
GRANITE = get_config("granite-moe-3b-a800m")


@pytest.mark.parametrize("S", [16, 512])
def test_flash_attention_qwen2(one_chip, S):
    H, KH, D = QWEN.n_heads, QWEN.n_kv_heads, QWEN.resolved_head_dim
    _compile(lambda q, k, v: fa_ops.flash_attention(q, k, v, impl="pallas"),
             one_chip, ((1, S, H, D), BF16), ((1, S, KH, D), BF16),
             ((1, S, KH, D), BF16))


@pytest.mark.parametrize("B,S", [(8, 256), (8, 512)])
def test_decode_attention_qwen2(one_chip, B, S):
    H, KH, D = QWEN.n_heads, QWEN.n_kv_heads, QWEN.resolved_head_dim
    _compile(lambda q, k, v, n: da_ops.decode_attention(q, k, v, n,
                                                         impl="pallas"),
             one_chip, ((B, H, D), BF16), ((B, S, KH, D), BF16),
             ((B, S, KH, D), BF16), ((B,), I32))


def test_mamba_scan_zamba2(one_chip):
    """The prefill path (final state returned) at zamba2-1.2b's widths."""
    B, S, H = 1, 256, ZAMBA.ssm_nheads
    P, N = ZAMBA.ssm_head_dim, ZAMBA.ssm_state
    _compile(lambda *a: ms_ops.ssd_scan(*a, impl="pallas", with_state=True),
             one_chip, ((B, S, H, P), BF16), ((B, S, H), F32), ((H,), F32),
             ((B, S, N), BF16), ((B, S, N), BF16), ((H,), F32),
             ((B, H, P, N), F32))


# expert capacity of a 512-token prefill (128) and of an 8-slot decode
# step (the floor of 4), as models/mlp.py sizes it
@pytest.mark.parametrize("C", [4, 128])
def test_moe_gmm_granite(one_chip, C):
    E, d, f = GRANITE.n_experts, GRANITE.d_model, GRANITE.d_ff

    def ffn(x, wg, wd):
        h = gmm_ops.grouped_matmul(x, wg, impl="pallas")
        return gmm_ops.grouped_matmul(h, wd, impl="pallas")

    _compile(ffn, one_chip, ((E, C, d), BF16), ((E, d, f), BF16),
             ((E, f, d), BF16))
