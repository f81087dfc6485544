"""Serving launcher: run a model behind the JAX serving engine with
batched synthetic requests (the paper-kind end-to-end driver).

    PYTHONPATH=src python -m repro.launch.serve            # smoke config
    PYTHONPATH=src python -m repro.launch.serve --full     # published config, TPU only

It prints the platform, device kind and device count it serves on. The
published (``--full``) config runs only on a TPU backend; the reduced
smoke config runs anywhere.
"""
from __future__ import annotations

import argparse
import os
import time
from pathlib import Path
from typing import Dict, List

import jax
import numpy as np

from repro.configs.registry import get_config, get_smoke_config
from repro.models import api as mapi
from repro.obs.percentiles import percentiles
from repro.serving.engine import JaxEngine

REPO_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; entry points only.

    ``JAX_COMPILATION_CACHE_DIR``, when set, places it (JAX reads the
    variable itself). Otherwise it lives at the fixed ``<repo>/.jax_cache``:
    the path is part of what makes a later run find its entries.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO_ROOT / ".jax_cache"))
    return jax.config.jax_compilation_cache_dir


def device_info() -> dict:
    """The backend as JAX reports it: platform, device kind, count."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def build_engine(cfg, max_batch: int = 8, max_len: int = 256, seed: int = 0,
                 device=None) -> JaxEngine:
    """Engine over weights drawn from ``seed``, created on ``device``."""
    device = device or jax.devices()[0]
    model = mapi.get_model(cfg)
    with jax.default_device(device):
        params = jax.jit(lambda k: model.init(k, cfg)[0])(
            jax.random.PRNGKey(seed))
    return JaxEngine(cfg, params, max_batch=max_batch, max_len=max_len,
                     device=device)


def route(replicas: Dict[str, List[JaxEngine]], trace):
    """Open loop behind a round-robin router: replay ``trace``, a list of
    (arrival_s, model, rid, prompt, max_new) in arrival order, send each
    request to the next of its model's replicas (one engine each, e.g.
    one per chip) and step every engine until all requests finish.
    Returns (finished requests by rid, arrival host time by rid, replica
    index by rid)."""
    turn = {model: 0 for model in replicas}
    engines = [e for group in replicas.values() for e in group]
    t0 = time.time()
    arrived = {rid: t0 + t for t, _, rid, _, _ in trace}
    submitted, finished, served_by = 0, {}, {}
    while len(finished) < len(trace):
        now = time.time() - t0
        while submitted < len(trace) and trace[submitted][0] <= now:
            _, model, rid, prompt, max_new = trace[submitted]
            i = turn[model] % len(replicas[model])
            turn[model] += 1
            replicas[model][i].submit(rid, prompt, max_new)
            served_by[rid] = i
            submitted += 1
        progressed = False
        for eng in engines:
            if any(eng.slots) or eng.queue:
                # a request admitted in this step may also finish in it
                reqs = eng.in_flight()
                for rid, _tok, done in eng.step():
                    if done:
                        finished[rid] = reqs[rid]
                progressed = True
        if not progressed:
            time.sleep(0.004)
    return finished, arrived, served_by


def latency_stats(finished, arrived, rids) -> dict:
    """Samples (seconds) of requests ``rids`` from the engine's stamps:
    TTFT from each request's arrival (``arrived``, host time by rid), so
    the loop's lateness and a blocking prefill's queueing count; the
    wait in the engine's queue (admitted less submitted); the gaps
    between tokens. Also wall seconds from the first arrival to the last
    token, and tokens."""
    reqs = [finished[r] for r in rids]
    end = max(r.token_times[-1] if r.token_times else r.prefill_done
              for r in reqs)
    return {"wall_s": end - min(arrived[r] for r in rids),
            "tokens": sum(len(r.out_tokens) for r in reqs),
            "ttft_s": [finished[r].prefill_done - arrived[r] for r in rids],
            "queue_s": [r.admitted - r.submitted for r in reqs],
            "tpot_s": [float(g) for r in reqs for g in np.diff(r.token_times)]}


def report(stats, tag: str = "serve"):
    n, wall = len(stats["ttft_s"]), stats["wall_s"]
    print(f"[{tag}] {n} requests, {stats['tokens']} tokens "
          f"in {wall:.1f}s -> {stats['tokens'] / wall:.1f} tok/s")
    # repro.obs nearest-rank percentiles: the same semantics the
    # simulator's SLOReport uses, so engine and sim numbers line up
    f50, f95 = percentiles(stats["ttft_s"], (0.50, 0.95))
    print(f"[{tag}] TTFT   p50={f50*1e3:.1f}ms p95={f95*1e3:.1f}ms")
    q50, q95 = percentiles(stats["queue_s"], (0.50, 0.95))
    print(f"[{tag}] queue  p50={q50*1e3:.1f}ms p95={q95*1e3:.1f}ms")
    if stats["tpot_s"]:
        t50, t95 = percentiles(stats["tpot_s"], (0.50, 0.95))
        print(f"[{tag}] TPOT   p50={t50*1e3:.1f}ms p95={t95*1e3:.1f}ms")


def serve(cfg, n_requests: int = 32, rate: float = 5.0, max_batch: int = 8,
          max_len: int = 256, seed: int = 0):
    eng = build_engine(cfg, max_batch=max_batch, max_len=max_len, seed=seed)
    rng = np.random.default_rng(seed)
    lens = rng.integers(8, 64, size=n_requests)
    outs = rng.integers(8, 32, size=n_requests)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n_requests))
    trace = [(t, cfg.name, rid, rng.integers(0, cfg.vocab_size, size=(int(n),)),
              int(m)) for rid, (t, n, m) in enumerate(zip(arrivals, lens, outs))]
    finished, arrived, _ = route({cfg.name: [eng]}, trace)
    report(latency_stats(finished, arrived, sorted(finished)))
    return finished


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--full", action="store_true",
                    help="serve the published config (TPU backend only)")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=5.0)
    ap.add_argument("--max-batch", type=int, default=8)
    args = ap.parse_args()
    cache_dir = use_compile_cache()
    dev = device_info()
    print(f"[serve] platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']} compile_cache={cache_dir}")
    if args.full and dev["platform"] != "tpu":
        ap.error(f"--full needs a TPU backend; JAX found {dev['platform']}")
    cfg = get_config(args.arch) if args.full else get_smoke_config(args.arch)
    serve(cfg, n_requests=args.requests, rate=args.rate,
          max_batch=args.max_batch)


if __name__ == "__main__":
    main()
