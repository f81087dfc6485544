"""Ahead-of-time compiles of the four Pallas kernels for a described TPU
v5e chip, at the real widths of the configs that use them, and of the
decode step, whose KV cache must stay in place.

Nothing runs: the chip's compiler is handed shapes and refuses what
interpret mode cannot show (a primitive Mosaic cannot lower, a block not
aligned to the tiling, too much VMEM). The topology is described inside
a module fixture, never at import, so every pytest worker collects the
same tests and only the worker that runs this file loads the TPU library.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels import common as kernels_common
from repro.kernels.decode_attention import ops as da_ops
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.mamba_scan import ops as ms_ops
from repro.kernels.moe_gmm import ops as gmm_ops
from repro.models import api as mapi
from repro.train import steps

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
    """The layer-stacked cache (L, B, S, KH*D), the layer a traced int."""
    L, H, KH, D = QWEN.n_layers, QWEN.n_heads, QWEN.n_kv_heads, \
        QWEN.resolved_head_dim
    _compile(lambda q, k, v, n, layer: da_ops.decode_attention(
                 q, k, v, n, layer, impl="pallas"),
             one_chip, ((B, H, D), BF16), ((L, B, S, KH * D), BF16),
             ((L, B, S, KH * D), BF16), ((B,), I32), ((), I32))


# what a cache-sized relayout would be: a copy, transpose or slice of it
_MOVES = ("copy", "copy-start", "transpose", "dynamic-slice")
_INSTR = re.compile(r"^\s*(?:ROOT )?%\S+ = (.*?) ([\w-]+)\(")


def _cache_moves(hlo: str, B: int, S: int):
    """Instructions of ``_MOVES`` whose result has the cache's (B, S) dims."""
    found = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m or m.group(2) not in _MOVES:
            continue
        for dims in re.findall(r"\[([\d,]+)\]", m.group(1)):
            d = [int(x) for x in dims.split(",")]
            if any(d[i:i + 2] == [B, S] for i in range(len(d) - 1)):
                found.append(line.strip()[:160])
    return found


# serve_step at published widths (2 layers: depth repeats the pattern) and
# the cells' slots x max_len, the cache donated as JaxEngine donates it
@pytest.mark.parametrize("name,B,S", [("qwen2-1.5b", 16, 4096),
                                      ("minicpm-2b", 8, 1280)])
def test_serve_step_keeps_cache_in_place(one_chip, monkeypatch, name, B, S):
    monkeypatch.setattr(kernels_common, "default_impl", lambda: "pallas")
    cfg = get_config(name).with_(n_layers=2)
    model = mapi.get_model(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), cfg)[0]))
    cache = on_chip(jax.eval_shape(
        lambda: model.init_cache(cfg, B, S, jnp.dtype(cfg.dtype))[0]))
    tokens = jax.ShapeDtypeStruct((B,), I32, sharding=one_chip)
    compiled = jax.jit(steps.make_serve_step(cfg), donate_argnums=(1,)) \
        .lower(params, cache, tokens).compile()
    hlo = compiled.as_text()
    assert "decode_attention_pallas" in hlo
    kv_bytes = sum(cache[n].size * cache[n].dtype.itemsize for n in "kv")
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 0.01 * kv_bytes, mem
    assert mem.alias_size_in_bytes >= kv_bytes, mem
    assert not _cache_moves(hlo, B, S)


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
