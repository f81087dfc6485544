"""Bring-up check: the serving main path on a TPU, at full model width.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # four one-chip replicas behind the router

One chip: the Pallas attention kernels against their jnp references at
qwen2-1.5b's shapes; the engine's compiled decode step must contain the
Pallas kernel (``tpu_custom_call``); then qwen2-1.5b at its published
widths (28 layers, d 1536, vocab 151936), with bf16 weights drawn from
``--seed``, serves 16 requests through ``JaxEngine`` and the launcher's
request loop. Its greedy tokens must equal a direct prefill + decode_step
greedy decode of the same prompts.

Four chips (``--chips 4``, and nothing else): qwen2-1.5b on chips 0 and 1
and minicpm-2b on chips 2 and 3, one engine per chip, behind the
round-robin router that ``examples/serve_multi_llm.py`` uses, in this
one process. Each engine's arrays must sit on its own chip, and the two
replicas of a model must agree token for token.

Without a TPU it exits non-zero before doing any work. Latencies it
prints are bring-up readings, not benchmark results. The last line of
stdout is ``{"ok": true, "device": {...}}`` as JAX reports the device.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.registry import get_config  # noqa: E402
from repro.kernels.decode_attention import ops as da_ops  # noqa: E402
from repro.kernels.flash_attention import ops as fa_ops  # noqa: E402
from repro.launch import serve as launch  # noqa: E402
from repro.models import api as mapi  # noqa: E402

MAX_BATCH, MAX_LEN, MAX_PROMPT = 8, 512, 400
EXACT_BUCKETS = (16, 32, 64, 128)   # prompts whose length is their bucket
NEW_TOKENS = (16, 32)               # max_new drawn from [16, 32]
N_REQUESTS = 16
# Both sides start from the same bf16 inputs, compute in fp32 and round
# the output to bf16, whose step is 2^-7 relative; the kernel may also
# feed its fp32 operands to the MXU in bf16 passes, which moves a softmax
# weight by about 1e-2 at qwen2's head dim 128. Outputs are O(1), so 2e-2
# absolute and relative (the bf16 bound of tests/test_kernels.py) allows
# that with about a factor of two to spare.
BF16_TOL = dict(atol=2e-2, rtol=2e-2)


def check_device(platform: str = "tpu") -> dict:
    dev = launch.device_info()
    print(f"[device] platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != platform:
        raise SystemExit(f"chip_smoke: needs a {platform} backend, JAX found "
                         f"{dev['platform']}; there is no fallback")
    return dev


class CacheCounter:
    """Counts JAX's persistent compilation cache lookups and hits."""

    def __init__(self):
        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def kernel_parity(impl: str = "pallas", buckets=(16, 128, MAX_LEN),
                  batch: int = MAX_BATCH, max_len: int = MAX_LEN,
                  seed: int = 0):
    """Flash (prefill) and decode attention, ``impl`` against ``ref``, in
    bf16 at qwen2-1.5b's attention shapes."""
    cfg = get_config("qwen2-1.5b")
    H, KH, D = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    rng = np.random.default_rng(seed)
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.bfloat16)

    def compare(name, got, want):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        assert np.isfinite(got).all(), f"{name}: non-finite output"
        err = float(np.abs(got - want).max())
        np.testing.assert_allclose(got, want, err_msg=name, **BF16_TOL)
        print(f"[kernels] {name}: {impl} vs ref max|diff|={err:.3e} ok",
              flush=True)

    for S in buckets:
        q, k, v = normal(1, S, H, D), normal(1, S, KH, D), normal(1, S, KH, D)
        compare(f"flash_attention S={S}",
                fa_ops.flash_attention(q, k, v, impl=impl),
                fa_ops.flash_attention(q, k, v, impl="ref"))
    # the model's layer-stacked cache (L, B, S, KH*D), read at its last layer
    L = 2
    q = normal(batch, H, D)
    kc, vc = (normal(L, batch, max_len, KH * D) for _ in range(2))
    lens = jnp.asarray(rng.integers(1, max_len + 1, size=batch), jnp.int32)
    compare(f"decode_attention L={L} B={batch} S={max_len}",
            da_ops.decode_attention(q, kc, vc, lens, L - 1, impl=impl),
            da_ops.decode_attention(q, kc, vc, lens, L - 1, impl="ref"))


def decode_step_hlo(eng) -> str:
    """Compiled HLO of the engine's own decode step."""
    toks = np.zeros((eng.max_batch,), np.int32)
    return eng._serve.lower(eng.params, eng.cache, toks).compile().as_text()


def check_placement(eng):
    for leaf in jax.tree.leaves((eng.params, eng.cache)):
        assert leaf.devices() == {eng.device}, (leaf.devices(), eng.device)


def warm_up(eng, prompt_lens):
    """Compile every prefill bucket and the decode step before timing."""
    for i, n in enumerate(sorted(set(prompt_lens))):
        eng.submit(-1 - i, np.zeros((n,), np.int32), 2)
    eng.drain()


def check_outputs(finished, rids, vocab_size: int):
    """Each request finished with max_new + 1 tokens, all below vocab."""
    for rid in rids:
        req = finished[rid]
        assert len(req.out_tokens) == req.max_new + 1, (rid, req.out_tokens)
        assert all(0 <= t < vocab_size for t in req.out_tokens), rid


@functools.partial(jax.jit, static_argnums=1)
def _prefill(params, cfg, tokens):
    return mapi.get_model(cfg).prefill(params, cfg, {"tokens": tokens})


@functools.partial(jax.jit, static_argnums=1, donate_argnums=2)
def _decode_step(params, cfg, cache, tokens):
    return mapi.get_model(cfg).decode_step(params, cfg, cache, tokens)


def direct_greedy(cfg, params, prompt, n_new: int, max_len: int,
                  batch: int):
    """Greedy decode by the model's own prefill and decode_step, with the
    cache padded to the engine's max_len and the prompt copied into every
    row of the engine's batch width. The same shapes give the same
    compiled arithmetic as the engine's: at batch 1 XLA orders the matmul
    sums differently, and random weights leave near-tied logits that
    then flip (seen on a v5e at the 18th token of a 16-token prompt)."""
    logits, cache = _prefill(params, cfg, np.asarray(prompt, np.int32)[None])
    pad = ((0, 0), (0, 0), (0, max_len - len(prompt)), (0, 0))
    cache = {"k": jnp.repeat(jnp.pad(cache["k"], pad), batch, axis=1),
             "v": jnp.repeat(jnp.pad(cache["v"], pad), batch, axis=1),
             "len": jnp.repeat(cache["len"], batch)}
    toks = [int(jnp.argmax(logits[0, :cfg.vocab_size]))]
    for _ in range(n_new):
        logits, cache = _decode_step(params, cfg, cache,
                                     np.full((batch,), toks[-1], np.int32))
        toks.append(int(jnp.argmax(logits[0, :cfg.vocab_size])))
    return toks


def serving_phase(cfg, seed: int, max_len: int = MAX_LEN,
                  max_prompt: int = MAX_PROMPT):
    """Serve N_REQUESTS, arriving at 8/s, through the launcher's router
    and check them."""
    eng = launch.build_engine(cfg, MAX_BATCH, max_len, seed)
    print(f"[serve] {cfg.name}: {cfg.n_layers}L d={cfg.d_model} "
          f"vocab={cfg.vocab_size} {cfg.dtype} max_batch={MAX_BATCH} "
          f"max_len={max_len}", flush=True)
    rng = np.random.default_rng(seed)
    exact = [n for n in EXACT_BUCKETS if n <= max_prompt]
    lens = exact + [int(n) for n in
                    rng.integers(16, max_prompt + 1, N_REQUESTS - len(exact))]
    max_news = rng.integers(NEW_TOKENS[0], NEW_TOKENS[1] + 1, N_REQUESTS)
    arrivals = np.cumsum(rng.exponential(1 / 8.0, N_REQUESTS))
    trace = [(t, cfg.name, rid, rng.integers(0, cfg.vocab_size, (n,)), int(m))
             for rid, (t, n, m) in enumerate(zip(arrivals, lens, max_news))]
    warm_up(eng, lens)
    finished, arrived, _ = launch.route({cfg.name: [eng]}, trace)
    check_outputs(finished, range(N_REQUESTS), cfg.vocab_size)
    print(f"[serve] prompt lengths {sorted(lens)}; every request finished "
          f"with max_new+1 tokens below vocab_size", flush=True)
    launch.report(launch.latency_stats(finished, arrived,
                                       range(N_REQUESTS)),
                  "serve, bring-up reading, not a benchmark")
    for rid, n in enumerate(exact):
        want = direct_greedy(cfg, eng.params, trace[rid][3], trace[rid][4],
                             max_len, MAX_BATCH)
        got = finished[rid].out_tokens
        assert got == want, (n, got, want)
        print(f"[serve] prompt {n} tokens: engine == direct greedy "
              f"({len(got)} tokens)", flush=True)
    return eng


def replicas_phase(devices, seed: int, max_len: int = 256):
    """qwen2-1.5b on chips 0-1, minicpm-2b on chips 2-3, behind the
    round-robin router; each prompt is sent twice in a row, so the router
    gives its two copies to the model's two replicas."""
    placement = {"qwen2-1.5b": devices[0:2], "minicpm-2b": devices[2:4]}
    cfgs = {arch: get_config(arch) for arch in placement}
    replicas = {}
    for arch, devs in placement.items():
        cfg = cfgs[arch]
        replicas[arch] = [launch.build_engine(cfg, MAX_BATCH, max_len, seed, d)
                          for d in devs]
        for eng in replicas[arch]:
            check_placement(eng)
            print(f"[replicas] {arch} ({cfg.n_layers}L d={cfg.d_model}) "
                  f"params and cache on {eng.device}", flush=True)
    rng = np.random.default_rng(seed)
    trace, t = [], 0.0
    for _ in range(8):
        for arch, cfg in cfgs.items():
            prompt = rng.integers(0, cfg.vocab_size, (int(rng.integers(17, 65)),))
            max_new = int(rng.integers(NEW_TOKENS[0], NEW_TOKENS[1] + 1))
            for _copy in range(2):
                t += rng.exponential(1 / 16.0)
                trace.append((t, arch, len(trace), prompt, max_new))
    for group in replicas.values():
        for eng in group:
            warm_up(eng, [len(p) for _, _, _, p, _ in trace])
    finished, arrived, served_by = launch.route(replicas, trace)
    for arch, cfg in cfgs.items():
        rids = [rid for _, a, rid, _, _ in trace if a == arch]
        check_outputs(finished, rids, cfg.vocab_size)
        for a, b in zip(rids[::2], rids[1::2]):
            assert {served_by[a], served_by[b]} == {0, 1}, (a, b)
            assert finished[a].out_tokens == finished[b].out_tokens, (
                arch, a, b, finished[a].out_tokens, finished[b].out_tokens)
        print(f"[replicas] {arch}: {len(rids) // 2} prompt pairs, the two "
              f"replicas agree token for token", flush=True)
        launch.report(launch.latency_stats(finished, arrived, rids),
                      f"{arch}, bring-up reading, not a benchmark")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    cache_dir = launch.use_compile_cache()
    dev = check_device("tpu")
    if dev["count"] < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} devices, JAX found {dev['count']}")
    cache = CacheCounter()
    if args.chips == 4:
        replicas_phase(jax.devices()[:4], args.seed)
    else:
        kernel_parity()
        eng = serving_phase(get_config("qwen2-1.5b"), args.seed)
        assert "tpu_custom_call" in decode_step_hlo(eng), \
            "the engine's decode step holds no Pallas kernel"
        print("[kernels] tpu_custom_call found in the engine's compiled "
              "decode step", flush=True)
    print(f"[cache] {cache_dir}: {cache.hits} of {cache.requests} compiles "
          f"found in the persistent cache", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": args.chips}}))


if __name__ == "__main__":
    main()
