"""Per-architecture smoke tests (reduced configs, deliverable f) and
family-level prefill/decode consistency properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCH_IDS, get_config, get_smoke_config
from repro.configs.base import SHAPES, cell_is_runnable
from repro.models import api as mapi
from repro.train import optimizer as opt
from repro.train import steps


def _batch_for(cfg, B, S, seed=1):
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(seed),
                                          (B, S), 0, cfg.vocab_size)}
    if cfg.family == "audio":
        batch["frames"] = jax.random.normal(
            jax.random.PRNGKey(seed + 1), (B, cfg.enc_seq, cfg.d_model))
    if cfg.family == "vlm":
        batch["vision_embeds"] = jax.random.normal(
            jax.random.PRNGKey(seed + 1), (B, cfg.n_vision_tokens,
                                           cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch):
    """One forward + one train step on CPU: output shapes + no NaNs."""
    cfg = get_smoke_config(arch)
    model = mapi.get_model(cfg)
    params, specs = model.init(jax.random.PRNGKey(0), cfg)
    B, S = 2, 16
    batch = _batch_for(cfg, B, S)
    logits, aux = model.forward(params, cfg, batch)
    S_total = S + (cfg.n_vision_tokens if cfg.family == "vlm" else 0)
    assert logits.shape[0] == B and logits.shape[1] == S_total
    assert logits.shape[2] >= cfg.vocab_size
    assert not bool(jnp.isnan(logits).any())

    batch["labels"] = batch["tokens"]
    oc = opt.OptConfig(total_steps=4, warmup_steps=1)
    ts = steps.make_train_step(cfg, oc)
    p2, o2, m = ts(params, opt.init_opt_state(params), batch)
    assert np.isfinite(float(m["loss"]))
    assert np.isfinite(float(m["grad_norm"]))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_decode_matches_forward(arch):
    """decode_step(prefill(prompt)) must agree with teacher forcing."""
    cfg = get_smoke_config(arch)
    if cfg.family == "vlm":
        pytest.skip("vlm decode positions differ from fused fwd (M-RoPE)")
    if cfg.family == "moe":
        # capacity-dropping differs between teacher-forcing and decode by
        # construction; disable drops for the consistency check
        cfg = cfg.with_(moe_capacity_factor=100.0)
    model = mapi.get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0), cfg)
    B, S = 2, 12
    batch = _batch_for(cfg, B, S)
    lp, cache = model.prefill(params, cfg, batch)
    nxt = jnp.argmax(lp[:, :cfg.vocab_size], -1)
    if "k" in cache:                    # (L, B, S, KH*hd): room to decode
        pad = ((0, 0), (0, 0), (0, 4), (0, 0))
        cache = dict(cache, k=jnp.pad(cache["k"], pad),
                     v=jnp.pad(cache["v"], pad))
    ld, cache = model.decode_step(params, cfg, cache, nxt)
    batch2 = dict(batch, tokens=jnp.concatenate(
        [batch["tokens"], nxt[:, None]], 1))
    logits2, _ = model.forward(params, cfg, batch2)
    np.testing.assert_allclose(ld, logits2[:, -1], atol=2e-4, rtol=2e-3)


def test_full_configs_match_assignment():
    """The full (dry-run) configs carry the exact assigned shapes."""
    spec = {
        "zamba2-1.2b": (38, 2048, 32, 32, 8192, 32000),
        "xlstm-350m": (24, 1024, 4, 4, 0, 50304),
        "whisper-base": (6, 512, 8, 8, 2048, 51865),
        "granite-moe-3b-a800m": (32, 1536, 24, 8, 512, 49155),
        "dbrx-132b": (40, 6144, 48, 8, 10752, 100352),
        "minicpm-2b": (40, 2304, 36, 36, 5760, 122753),
        "glm4-9b": (40, 4096, 32, 2, 13696, 151552),
        "mistral-nemo-12b": (40, 5120, 32, 8, 14336, 131072),
        "qwen2-1.5b": (28, 1536, 12, 2, 8960, 151936),
        "qwen2-vl-2b": (28, 1536, 12, 2, 8960, 151936),
    }
    for arch, (L, d, H, KV, f, V) in spec.items():
        cfg = get_config(arch)
        assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                cfg.d_ff, cfg.vocab_size) == (L, d, H, KV, f, V), arch
    assert get_config("zamba2-1.2b").ssm_state == 64
    assert get_config("granite-moe-3b-a800m").n_experts == 40
    assert get_config("granite-moe-3b-a800m").top_k == 8
    assert get_config("dbrx-132b").n_experts == 16
    assert get_config("dbrx-132b").top_k == 4


def test_long_context_skip_policy():
    """long_500k runs only for recurrent (SSM/hybrid) archs."""
    runnable = {}
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        shape = [s for s in SHAPES if s.name == "long_500k"][0]
        runnable[arch] = cell_is_runnable(cfg, shape)[0]
    assert runnable["zamba2-1.2b"] and runnable["xlstm-350m"]
    for arch in ("qwen2-1.5b", "glm4-9b", "dbrx-132b", "whisper-base"):
        assert not runnable[arch]


def test_input_specs_no_allocation():
    """input_specs must yield ShapeDtypeStructs only (no device arrays)."""
    for arch in ("qwen2-1.5b", "zamba2-1.2b", "whisper-base"):
        cfg = get_config(arch)
        for shape in SHAPES:
            if not cell_is_runnable(cfg, shape)[0]:
                continue
            inputs, specs = mapi.input_specs(cfg, shape)
            for leaf in jax.tree.leaves(inputs):
                assert isinstance(leaf, jax.ShapeDtypeStruct), type(leaf)


def test_mlstm_chunk_boundary_property():
    """Chunked mLSTM == token recurrence across chunk boundaries."""
    from repro.models.xlstm import _mlstm_chunked, CHUNK
    rng = np.random.default_rng(3)
    B, S, H, dh = 1, 2 * CHUNK, 2, 8
    qh = jnp.asarray(rng.normal(size=(B, S, H, dh)), jnp.float32)
    kh = jnp.asarray(rng.normal(size=(B, S, H, dh)), jnp.float32)
    vh = jnp.asarray(rng.normal(size=(B, S, H, dh)), jnp.float32)
    li = jnp.asarray(rng.normal(size=(B, S, H)), jnp.float32)
    lf = jnp.asarray(
        jax.nn.log_sigmoid(jnp.asarray(rng.normal(size=(B, S, H)),
                                       jnp.float32) + 2))
    h1, _ = _mlstm_chunked(qh, kh, vh, li, lf)
    C = jnp.zeros((B, H, dh, dh))
    n = jnp.zeros((B, H, dh))
    m = jnp.full((B, H), -1e30)
    outs = []
    for t in range(S):
        m_new = jnp.maximum(lf[:, t] + m, li[:, t])
        fs = jnp.exp(lf[:, t] + m - m_new)
        i_s = jnp.exp(li[:, t] - m_new)
        C = fs[..., None, None] * C + i_s[..., None, None] \
            * jnp.einsum("bhd,bhe->bhde", vh[:, t], kh[:, t])
        n = fs[..., None] * n + i_s[..., None] * kh[:, t]
        b = jnp.einsum("bhd,bhd->bh", n, qh[:, t])
        den = jnp.maximum(jnp.abs(b), jnp.exp(-m_new))
        outs.append(jnp.einsum("bhde,bhe->bhd", C, qh[:, t]) / den[..., None])
        m = m_new
    np.testing.assert_allclose(h1, jnp.stack(outs, 1), atol=5e-4, rtol=5e-4)
