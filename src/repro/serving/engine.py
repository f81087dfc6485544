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

Every step records its host phases as spans (``repro.obs.SpanLog``, kept
in ``engine.spans`` and, while the profiler runs, on the device trace's
clock): ``engine.step`` around the whole step, ``engine.admit`` around
the admission loop and, per admitted request, ``engine.prefill`` (its
tokens and the prefill dispatch), ``engine.first_token`` (the argmax that
waits for the prefill) and ``engine.insert`` (the insert and its sync);
then ``engine.decode`` (the token vector and the decode dispatch),
``engine.sample`` (the argmax copied to the host, which waits for the
step) and ``engine.emit`` (the per-slot loop). A request's stamps
(``admitted``, ``prefill_done``, ``token_times``) and ``iteration_log``
are read from the same clock reads.
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
from repro.obs.spans import SpanLog
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


def engine_insert(cache, pre_cache, slot, length):
    """Writes one prefilled request's cache into batch slot ``slot``."""
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


@dataclass
class EngineRequest:
    rid: int
    prompt: np.ndarray
    max_new: int
    out_tokens: List[int] = field(default_factory=list)
    submitted: float = 0.0          # host clock (s): when submitted
    admitted: float = 0.0           # taken off the queue to prefill
    prefill_done: float = 0.0       # first token on the host, cache in
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
        model = self.model

        def engine_prefill(p, b, lp):
            return model.prefill(p, cfg, b, lp)

        self._prefill = jax.jit(engine_prefill)
        self._insert = jax.jit(engine_insert, donate_argnums=(0,))
        self.slots: List[Optional[EngineRequest]] = [None] * max_batch
        self.queue: List[EngineRequest] = []
        self.greedy = greedy
        self.iteration_log: List[Tuple[str, int, float]] = []
        self.spans = SpanLog()
        self.n_steps = 0

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
        span = self.spans.span
        for i in range(self.max_batch):
            if self.slots[i] is None and self.queue:
                req = self.queue.pop(0)
                S = len(req.prompt)
                # recurrent state absorbs trailing pads, so SSM/xLSTM
                # prefill must run at the exact prompt length; attention
                # families bucket-pad (pads masked via cache len = S).
                bucket = S if self.cfg.is_recurrent \
                    else min(_bucket(S), self.max_len)
                with span("engine.prefill", req.rid, bucket=bucket,
                          prompt_len=S) as pre:
                    toks = np.zeros((1, bucket), np.int32)
                    toks[0, :S] = req.prompt
                    # host arrays go straight to the engine's device under jit
                    logits, pre_cache = self._prefill(
                        self.params, {"tokens": toks},
                        np.full((1,), S - 1, np.int32))
                with span("engine.first_token", req.rid):
                    first = int(jnp.argmax(logits[0, :self.cfg.vocab_size])) \
                        if self.greedy else 0
                with span("engine.insert", req.rid) as ins:
                    self.cache = self._insert(self.cache, pre_cache,
                                              np.int32(i), np.int32(S))
                    jax.block_until_ready(self.cache["len"])
                req.admitted = pre.t0_ns * 1e-9
                req.prefill_done = ins.t1_ns * 1e-9
                req.out_tokens.append(first)
                self.iteration_log.append(("prefill", bucket,
                                           (ins.t1_ns - pre.t0_ns) * 1e-9))
                self.slots[i] = req

    # ---------------------------------------------------------------- step
    def step(self) -> List[Tuple[int, int, bool]]:
        """Admit + advance every active slot one token.
        Returns [(rid, token, done)]."""
        self.n_steps += 1
        active = sum(s is not None for s in self.slots)
        with self.spans.span("engine.step", step=self.n_steps, active=active,
                             queue=len(self.queue)):
            return self._step()

    def _step(self) -> List[Tuple[int, int, bool]]:
        span = self.spans.span
        with span("engine.admit"):
            self._admit()
        active = [i for i, s in enumerate(self.slots) if s is not None]
        if not active:
            return []
        with span("engine.decode") as dec:
            toks = np.zeros((self.max_batch,), np.int32)
            for i in active:
                toks[i] = self.slots[i].out_tokens[-1]
            logits, self.cache = self._serve(self.params, self.cache, toks)
        with span("engine.sample") as smp:
            nxt = np.asarray(jnp.argmax(logits[:, :self.cfg.vocab_size], -1))
        self.iteration_log.append(("decode", len(active),
                                   (smp.t1_ns - dec.t0_ns) * 1e-9))
        now = smp.t1_ns * 1e-9
        out = []
        with span("engine.emit"):
            for i in active:
                req = self.slots[i]
                req.out_tokens.append(int(nxt[i]))
                req.token_times.append(now)
                done = len(req.out_tokens) - 1 >= req.max_new
                out.append((req.rid, int(nxt[i]), done))
                if done:
                    self.slots[i] = None
        return out

    def in_flight(self) -> Dict[int, EngineRequest]:
        """Every request queued or in a slot, by rid."""
        reqs = {r.rid: r for r in self.queue}
        reqs.update((s.rid, s) for s in self.slots if s is not None)
        return reqs

    def drain(self) -> Dict[int, EngineRequest]:
        """Run to completion; returns finished requests by rid."""
        finished: Dict[int, EngineRequest] = {}
        while any(s is not None for s in self.slots) or self.queue:
            # a request admitted in this step may also finish in it
            reqs = self.in_flight()
            for rid, _tok, done in self.step():
                if done:
                    finished[rid] = reqs[rid]
        return finished
