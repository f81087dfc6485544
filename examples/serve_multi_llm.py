"""End-to-end driver (paper kind = serving): serve TWO small models with
batched requests through real JAX engines behind a Coral-style
round-robin router (``repro.launch.serve.route``, which also spreads a
model's requests over its replicas), and report per-model latency and
throughput.

Run:  PYTHONPATH=src python examples/serve_multi_llm.py
"""
from typing import Dict

import numpy as np

from repro.configs.registry import get_smoke_config
from repro.launch.serve import build_engine, latency_stats, report, route

ARCHS = ["qwen2-1.5b", "glm4-9b"]
N_REQ, RATE = 16, 4.0


def make_trace(vocab: Dict[str, int], n_per_model: int, rate: float, rng):
    """Poisson arrivals alternating over the models: a list of
    (arrival_s, arch, rid, prompt, max_new)."""
    archs = list(vocab)
    trace, t = [], 0.0
    for rid in range(n_per_model * len(archs)):
        arch = archs[rid % len(archs)]
        t += rng.exponential(1.0 / (rate * len(archs)))
        prompt = rng.integers(0, vocab[arch], size=(int(rng.integers(8, 48)),))
        trace.append((t, arch, rid, prompt, int(rng.integers(8, 24))))
    return trace


def main():
    replicas = {}
    for arch in ARCHS:
        cfg = get_smoke_config(arch)
        replicas[arch] = [build_engine(cfg, max_batch=4, max_len=128)]
        print(f"[init] {arch}: {cfg.n_layers}L d={cfg.d_model} (reduced)")
    vocab = {a: g[0].cfg.vocab_size for a, g in replicas.items()}
    trace = make_trace(vocab, N_REQ, RATE, np.random.default_rng(0))

    finished, arrived, _ = route(replicas, trace)
    print(f"\nserved {len(finished)} requests across {len(ARCHS)} models")
    for arch in ARCHS:
        rids = [rid for _, a, rid, _, _ in trace if a == arch]
        report(latency_stats(finished, arrived, rids), arch)


if __name__ == "__main__":
    main()
