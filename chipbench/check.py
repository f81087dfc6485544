"""Decides ``correct``: the served tokens against the plain reference.

Once the window has closed, a sample drawn from the seed of the requests
that finished (always with the one that served the most tokens, and the
one with the longest prompt) is run through the reference once, prompt
and served tokens together. At every position where a token was served
(the prefill's first and each decoded one), the gap by which the
reference's logit of the served token lies below the reference's best
is read; the widest gap must stay within the cell's limit. Greedy
decoding serves the program's best token, so a sound program leaves
gaps of its rounding only; a token altered where it is produced, a step
that loses its cache or a lower precision widens them.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Sequence

import numpy as np

from chipbench import reference


@dataclass
class Compared:
    tokens: np.ndarray      # prompt, then every served token but the last
    sel: np.ndarray         # positions whose next token was served
    served: np.ndarray      # the served tokens


def sample(finished: Sequence, n: int, seed: int) -> list:
    """``n`` of the finished requests (objects with ``.er``), drawn from
    ``seed``, always with the one that served most tokens and the one
    with the longest prompt."""
    if not finished:
        return []
    by_out = max(finished, key=lambda s: (len(s.er.out_tokens), len(s.er.prompt)))
    by_prompt = max(finished, key=lambda s: (len(s.er.prompt), len(s.er.out_tokens)))
    chosen = [by_out] + ([by_prompt] if by_prompt is not by_out else [])
    rest = [s for s in finished if all(s is not c for c in chosen)]
    rng = np.random.default_rng([seed, 7])
    k = max(0, min(n - len(chosen), len(rest)))
    idx = rng.choice(len(rest), size=k, replace=False) if k else []
    return chosen + [rest[i] for i in sorted(idx)]


def sequence_of(prompt, out_tokens) -> Compared:
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(out_tokens, np.int32)
    tokens = np.concatenate([prompt, served[:-1]])
    sel = np.arange(len(prompt) - 1, len(tokens))
    return Compared(tokens, sel, served)


def gaps(family, params, conf: dict, seqs: List[Compared],
         controls: Sequence[str] = ()) -> dict:
    """Against ``family``'s reference: the widest gap of the served
    tokens (``max_logit_gap``), the number of positions compared and the
    median gap between the reference's two best logits there
    (``top2_gap_median``: how near the ties are), how many served tokens
    repeat the token before them (``repeats``); for each control mode in
    ``controls`` (``"fp8"``) also the widest gap of the tokens that the
    control puts first at the same positions (``control_gap.<mode>``)."""
    out = {"max_logit_gap": 0.0, "positions": 0}
    out.update({f"control_gap.{m}": 0.0 for m in controls})
    top2 = []
    C = reference.CHUNK
    for s in seqs:
        refs = reference.logits_at(family, params, conf, s.tokens, s.sel)
        ctrl = {m: reference.logits_at(family, params, conf, s.tokens,
                                       s.sel, m) for m in controls}
        for i, ref in enumerate(refs):
            served = np.zeros(C, np.int32)
            part = s.served[i * C:(i + 1) * C]
            served[:len(part)] = part
            for m in controls or [None]:
                g, t2, gc = reference.chunk_readings(
                    ref, served, ctrl[m][i] if m else ref, len(part))
                if m:
                    key = f"control_gap.{m}"
                    out[key] = max(out[key], float(gc))
            out["max_logit_gap"] = max(out["max_logit_gap"], float(g))
            t2 = np.asarray(t2)
            top2.append(t2[:len(part)])
        out["positions"] += len(s.sel)
        out["repeats"] = out.get("repeats", 0) + int(
            np.sum(s.served == s.tokens[s.sel]))
    if top2:
        out["top2_gap_median"] = float(np.median(np.concatenate(top2)))
    return out
