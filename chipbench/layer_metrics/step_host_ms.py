"""Engine step (host): device idle inside the engine's ``engine.step``
spans of the trace, summed and divided by the number of steps, ms. Idle
is where no op of a TPU's ``XLA Ops`` line runs (the union of their
intervals, as ``devtrace`` computes busy time; mean over chips): the
host work of a step that the device waits out. A program whose steps
emit no ``engine.step`` span, and a trace with no TPU plane, read
nothing.

``idle_by_span`` splits the same idle time by the innermost ``engine.*``
span at each moment, for the breakdown of a step's host time."""
from bisect import bisect_left, bisect_right
from collections import defaultdict
from typing import Dict, List, Tuple

from chipbench import devtrace

STEP, PREFIX = "engine.step", "engine."


def _end(e: dict) -> float:
    return e["start_ns"] + e["dur_ns"]


def _idle(busy: List[Tuple[float, float]], starts: List[float], a: float,
          b: float) -> List[Tuple[float, float]]:
    """The parts of [a, b] that no interval of ``busy`` (merged, sorted;
    ``starts`` their starts) covers."""
    out, prev = [], a
    for lo, hi in busy[max(bisect_left(starts, a) - 1, 0):
                       bisect_right(starts, b)]:
        if hi <= prev:
            continue
        if lo > prev:
            out.append((prev, min(lo, b)))
        prev = max(prev, hi)
        if prev >= b:
            break
    if prev < b:
        out.append((prev, b))
    return out


def idle_by_span(events: List[dict]) -> Tuple[Dict[str, float], int]:
    """Device idle seconds inside ``engine.step`` spans by the innermost
    ``engine.*`` span over each moment (mean over chips), and the number
    of steps."""
    host = sorted((e for e in events if e["name"].startswith(PREFIX)
                   and not e["plane"].startswith(devtrace.DEVICE_PREFIX)),
                  key=lambda e: e["start_ns"])
    steps = [e for e in host if e["name"] == STEP]
    chips = []
    for evs in devtrace._device_ops(events).values():
        busy = devtrace._union((e["start_ns"], _end(e)) for e in evs)
        chips.append((busy, [lo for lo, _ in busy]))
    out: Dict[str, float] = defaultdict(float)
    if not steps or not chips:
        return {}, 0
    starts = [e["start_ns"] for e in host]
    for step in steps:
        a, b = step["start_ns"], _end(step)
        inner = [e for e in host[bisect_left(starts, a):
                                 bisect_right(starts, b)] if _end(e) <= b]
        cuts = sorted({x for e in inner for x in (e["start_ns"], _end(e))})
        for busy, busy_starts in chips:
            for lo, hi in _idle(busy, busy_starts, a, b):
                edges = [lo] + [x for x in cuts if lo < x < hi] + [hi]
                for x, y in zip(edges, edges[1:]):
                    mid = (x + y) / 2
                    owner = max((e for e in inner
                                 if e["start_ns"] <= mid <= _end(e)),
                                key=lambda e: (e["start_ns"], -e["dur_ns"]))
                    out[owner["name"]] += (y - x) * 1e-9 / len(chips)
    return dict(out), len(steps)


def read(ctx):
    if not ctx.events:
        return None
    idle, steps = idle_by_span(ctx.events)
    return 1e3 * sum(idle.values()) / steps if steps else None
