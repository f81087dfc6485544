"""Operations and bytes that the algorithm needs, from a configuration file.

Counted from the work each call needs (the live key/value length of each
active slot, the real prompt length), never from the shapes that the
implementation happens to pad to or stream. A multiply-add is 2
operations. Softmax, norms and RoPE are left out: they are a small
share of the work and of no matrix unit.

What depends on the model family (its linear layers, which layers
attend, what else a step computes) comes from the family's module
(``chipbench/families/<family>.py``); attention, the logits and the
roofline are shared here.
"""
from __future__ import annotations

from typing import Iterable, Tuple


def elem_bytes(conf: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[conf["torch_dtype"]]


def unembed_flops(conf: dict) -> int:
    """Logits of one position over the real vocabulary."""
    return 2 * conf["hidden_size"] * conf["vocab_size"]


def decode_attention_cost(dims, conf: dict,
                          live: Iterable[int]) -> Tuple[int, int]:
    """(operations, bytes) of single-token attention over every layer
    that attends (``dims``: layers, query heads, key/value heads, head
    size; a family's ``attention_dims``), for slots that attend over
    ``live`` keys each: q.K and p.V over the live keys; the live K and V
    read once, q read and the output written."""
    L, H, KH, hd = dims
    b = elem_bytes(conf)
    flops = nbytes = 0
    for n in live:
        flops += L * 4 * H * hd * n
        nbytes += L * b * (2 * n * KH * hd + 2 * H * hd)
    return flops, nbytes


def flash_attention_cost(dims, conf: dict, s: int) -> Tuple[int, int]:
    """(operations, bytes) of causal attention over a prompt of ``s`` real
    tokens, every layer that attends (``dims`` as above): s(s+1)/2
    query-key pairs, each 2*hd for q.k and 2*hd for p.v, per head; q, k,
    v read and the output written once."""
    L, H, KH, hd = dims
    flops = L * 2 * H * hd * s * (s + 1)
    nbytes = L * elem_bytes(conf) * s * hd * (2 * H + 2 * KH)
    return flops, nbytes


def decode_step_flops(family, conf: dict, live: Iterable[int]) -> int:
    """One decode step: linear layers and logits per active slot, and
    what the family adds (attention over the live keys)."""
    live = list(live)
    per_slot = family.linear_flops(conf) + unembed_flops(conf)
    return len(live) * per_slot + family.decode_flops(conf, live)


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak bf16 rate and bytes over HBM bandwidth."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
