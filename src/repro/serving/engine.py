"""Real JAX serving engine: slot-based continuous batching.

This is the per-node execution engine of a Serving Instance (the role
vLLM plays in the paper's runtime, §5.2) — implemented in pure JAX so
the whole serving path runs on this container with small models, and on
TPU unchanged. It is the "real system" against which the event
simulator's latency CDFs are validated (benchmarks/fig6_fidelity.py).

Design: a fixed pool of B decode slots with a pre-allocated KV/state
cache. Prefill runs per-request (bucketed padding), its cache is
inserted into a free slot, and one ``serve_step`` advances every active
slot by a token (inactive slots compute garbage that is masked out —
the standard static-shape TPU serving pattern).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.decode_attention.kernel import BLOCK_K as DECODE_BLOCK
from repro.kernels.flash_attention.kernel import BLOCK as PREFILL_BLOCK
from repro.models import api as mapi
from repro.train import steps


def _bucket(n: int) -> int:
    b = 16
    while b < n:
        b *= 2
    return b


def check_max_len(max_len: int) -> None:
    """Reject a cache length the attention kernels cannot tile: decode
    streams the cache in blocks of min(DECODE_BLOCK, max_len), and the
    largest prefill bucket (max_len itself) is tiled in blocks of
    min(PREFILL_BLOCK, max_len)."""
    if max_len < 1:
        raise ValueError(f"max_len={max_len} must be positive")
    for kernel, block in (("decode_attention", DECODE_BLOCK),
                          ("flash_attention", PREFILL_BLOCK)):
        if max_len % min(block, max_len):
            raise ValueError(
                f"max_len={max_len} cannot be tiled by {kernel}: it needs "
                f"max_len % min({block}, max_len) == 0")


@dataclass
class EngineRequest:
    rid: int
    prompt: np.ndarray
    max_new: int
    out_tokens: List[int] = field(default_factory=list)
    submitted: float = 0.0
    prefill_done: float = 0.0
    token_times: List[float] = field(default_factory=list)


class JaxEngine:
    """``device`` holds the params and the cache, and so runs every step
    (default ``jax.devices()[0]``); one engine per chip serves replicas."""

    def __init__(self, cfg, params, max_batch: int = 8, max_len: int = 512,
                 greedy: bool = True, device=None):
        check_max_len(max_len)
        self.cfg = cfg
        self.device = device or jax.devices()[0]
        self.params = jax.device_put(params, self.device)
        self.model = mapi.get_model(cfg)
        self.max_batch = max_batch
        self.max_len = max_len
        dt = jnp.dtype(cfg.dtype)
        with jax.default_device(self.device):
            cache, _ = self.model.init_cache(cfg, max_batch, max_len, dt)
        self.cache = jax.device_put(cache, self.device)
        self._serve = jax.jit(steps.make_serve_step(cfg), donate_argnums=(1,))
        self._prefill = jax.jit(
            lambda p, b, lp: self.model.prefill(p, cfg, b, lp))
        self._insert = jax.jit(self._insert_impl, donate_argnums=(0,))
        self.slots: List[Optional[EngineRequest]] = [None] * max_batch
        self.queue: List[EngineRequest] = []
        self.greedy = greedy
        self.iteration_log: List[Tuple[str, int, float]] = []

    # ------------------------------------------------------------ plumbing
    def _insert_impl(self, cache, pre_cache, slot, length):
        def upd(c, p):
            if c.ndim == 1:                     # per-slot lengths
                return c.at[slot].set(length)
            # batch axis is 1; zero-pad trailing dims (kv seq) up to cache
            pads = [(0, 0), (0, 0)]
            for i in range(2, c.ndim):
                pads.append((0, c.shape[i] - p.shape[i]))
            p = jnp.pad(p, pads).astype(c.dtype)
            return jax.lax.dynamic_update_slice_in_dim(c, p, slot, axis=1)
        return jax.tree.map(upd, cache, pre_cache)

    def submit(self, rid: int, prompt: np.ndarray, max_new: int):
        prompt = np.asarray(prompt)
        # the cache holds the prompt and every decoded token but the last
        if len(prompt) < 1 or len(prompt) + max_new > self.max_len:
            raise ValueError(
                f"request {rid}: a prompt of {len(prompt)} tokens plus "
                f"{max_new} new tokens does not fit max_len={self.max_len}")
        self.queue.append(EngineRequest(rid, prompt, max_new,
                                        submitted=time.time()))

    def _admit(self):
        for i in range(self.max_batch):
            if self.slots[i] is None and self.queue:
                req = self.queue.pop(0)
                S = len(req.prompt)
                # recurrent state absorbs trailing pads, so SSM/xLSTM
                # prefill must run at the exact prompt length; attention
                # families bucket-pad (pads masked via cache len = S).
                bucket = S if self.cfg.is_recurrent \
                    else min(_bucket(S), self.max_len)
                toks = np.zeros((1, bucket), np.int32)
                toks[0, :S] = req.prompt
                t0 = time.time()
                # host arrays go straight to the engine's device under jit
                logits, pre_cache = self._prefill(
                    self.params, {"tokens": toks},
                    np.full((1,), S - 1, np.int32))
                first = int(jnp.argmax(logits[0, :self.cfg.vocab_size])) \
                    if self.greedy else 0
                self.cache = self._insert(self.cache, pre_cache,
                                          np.int32(i), np.int32(S))
                jax.block_until_ready(self.cache["len"])
                req.prefill_done = time.time()
                req.out_tokens.append(first)
                self.iteration_log.append(("prefill", bucket,
                                           req.prefill_done - t0))
                self.slots[i] = req

    # ---------------------------------------------------------------- step
    def step(self) -> List[Tuple[int, int, bool]]:
        """Admit + advance every active slot one token.
        Returns [(rid, token, done)]."""
        self._admit()
        active = [i for i, s in enumerate(self.slots) if s is not None]
        if not active:
            return []
        toks = np.zeros((self.max_batch,), np.int32)
        for i in active:
            toks[i] = self.slots[i].out_tokens[-1]
        t0 = time.time()
        logits, self.cache = self._serve(self.params, self.cache, toks)
        nxt = np.asarray(jnp.argmax(logits[:, :self.cfg.vocab_size], -1))
        jax.block_until_ready(nxt)
        dt = time.time() - t0
        self.iteration_log.append(("decode", len(active), dt))
        out = []
        now = time.time()
        for i in active:
            req = self.slots[i]
            req.out_tokens.append(int(nxt[i]))
            req.token_times.append(now)
            done = len(req.out_tokens) - 1 >= req.max_new
            out.append((req.rid, int(nxt[i]), done))
            if done:
                self.slots[i] = None
        return out

    def drain(self) -> Dict[int, EngineRequest]:
        """Run to completion; returns finished requests by rid."""
        finished: Dict[int, EngineRequest] = {}
        while any(s is not None for s in self.slots) or self.queue:
            reqs = {s.rid: s for s in self.slots if s is not None}
            for rid, _tok, done in self.step():
                if done:
                    finished[rid] = reqs[rid]
        return finished
