"""Real JAX serving engine: continuous batching must reproduce the
model's own greedy decoding exactly."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.models import api as mapi
from repro.serving.engine import JaxEngine


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("qwen2-1.5b")
    model = mapi.get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0), cfg)
    return cfg, model, params


def _direct_greedy(model, cfg, params, prompt, n_new):
    batch = {"tokens": jnp.asarray(prompt)[None, :]}
    logits, cache = model.prefill(params, cfg, batch)
    pad = ((0, 0), (0, 0), (0, n_new + 1), (0, 0))     # (L, B, S, KH*hd)
    cache = dict(cache, k=jnp.pad(cache["k"], pad),
                 v=jnp.pad(cache["v"], pad))
    toks = [int(jnp.argmax(logits[0, :cfg.vocab_size]))]
    for _ in range(n_new):
        lg, cache = model.decode_step(params, cfg, cache,
                                      jnp.asarray(toks[-1:]))
        toks.append(int(jnp.argmax(lg[0, :cfg.vocab_size])))
    return toks


def test_engine_matches_direct_greedy(setup):
    """Bucket padding must be invisible: the engine's outputs equal
    greedy decoding of the exact (unpadded) prompt."""
    cfg, model, params = setup
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=(int(n),))
               for n in (5, 9, 16)]
    n_new = 6
    eng = JaxEngine(cfg, params, max_batch=4, max_len=64)
    for i, p in enumerate(prompts):
        eng.submit(i, p, n_new)
    finished = eng.drain()
    assert set(finished) == {0, 1, 2}
    for i, p in enumerate(prompts):
        want = _direct_greedy(model, cfg, params, p, n_new)
        got = finished[i].out_tokens
        assert got == want, (i, got, want)


def test_engine_matches_direct_greedy_mha_hd64():
    """minicpm-2b's cache shape at a tiny size: MHA with head dim 64, so
    the folded (.., S, KH*hd) rows hold whole 64-lane heads."""
    cfg = get_smoke_config("minicpm-2b").with_(d_model=128, n_heads=2,
                                                n_kv_heads=2)
    assert cfg.resolved_head_dim == 64
    model = mapi.get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, size=(int(n),))
               for n in (3, 12, 17)]
    eng = JaxEngine(cfg, params, max_batch=2, max_len=64)
    for i, p in enumerate(prompts):
        eng.submit(i, p, 5)
    finished = eng.drain()
    for i, p in enumerate(prompts):
        assert finished[i].out_tokens == \
            _direct_greedy(model, cfg, params, p, 5), i


def test_engine_slot_reuse(setup):
    cfg, model, params = setup
    rng = np.random.default_rng(1)
    eng = JaxEngine(cfg, params, max_batch=2, max_len=64)
    for i in range(5):                      # more requests than slots
        eng.submit(i, rng.integers(0, cfg.vocab_size, size=(6,)), 3)
    finished = eng.drain()
    assert set(finished) == set(range(5))
    for r in finished.values():
        assert len(r.out_tokens) == 4       # first + 3 generated


@pytest.mark.parametrize("max_len,kernel", [(200, "flash_attention"),
                                            (384, "decode_attention")])
def test_engine_rejects_untileable_max_len(setup, max_len, kernel):
    """200 leaves a partial 128-row prefill block at the max_len bucket;
    384 leaves a partial 256-row decode block."""
    cfg, _, params = setup
    with pytest.raises(ValueError, match=f"cannot be tiled by {kernel}"):
        JaxEngine(cfg, params, max_batch=2, max_len=max_len)


@pytest.mark.parametrize("n_prompt,max_new", [(65, 1), (60, 5), (0, 3)])
def test_submit_rejects_request_beyond_max_len(setup, n_prompt, max_new):
    cfg, _, params = setup
    eng = JaxEngine(cfg, params, max_batch=2, max_len=64)
    with pytest.raises(ValueError, match="does not fit max_len=64"):
        eng.submit(0, np.zeros((n_prompt,), np.int32), max_new)
    assert not eng.queue
    eng.submit(1, np.zeros((60,), np.int32), 4)     # exactly fills the cache
    assert len(eng.drain()[1].out_tokens) == 5
