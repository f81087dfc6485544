"""Runs one cell of the chip benchmark once and prints one JSON line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It drives the program's normal serving path in this one process:
weights drawn from ``--seed`` on the device, ``JaxEngine.submit`` and
``JaxEngine.step`` under the benchmark's own traffic. Set-up (``setup_s``)
runs from process start until the window opens: weights, cache, and one
run of every prefill bucket, insert and decode step that the cell's
traffic uses. Then the window lasts ``--seconds``; with ``--trace 1`` a
few seconds inside it are traced and the per-layer metrics are printed
instead of the end-to-end ones. After the window the served tokens are
compared with the plain reference (``check.py``).

It refuses, before any work, a backend that is not a TPU or that has
fewer chips than the cell asks for. JAX's persistent compilation cache
lives at the fixed ``<checkout>/.chipbench_cache/jax``. The last line of
stdout is the result; the numbers compared, each beside its limit, are
the last lines of stderr.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parent.parent
CACHE = CHECKOUT / ".chipbench_cache"
# the TPU library writes its logs to /tmp unless told where
os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(),
                                                  "tpu_logs"))
for p in (str(CHECKOUT / "src"), str(CHECKOUT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

from chipbench import spec  # noqa: E402

TRACE_AT, TRACE_S = 0.6, 3.0     # traced: from 60% into the window, 3 s
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def use_compile_cache():
    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class Compiles:
    """Counts compile requests (persistent-cache hits included) in each
    phase of a run (``setup``, ``window``, ``check``), and the persistent
    cache's hits."""

    def __init__(self):
        self.counts = {"setup": 0, "window": 0, "check": 0}
        self.phase, self.hits = "setup", 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.counts[self.phase] += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def check_devices(cell) -> dict:
    dev = device_info()
    print(f"[chipbench] platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", file=sys.stderr, flush=True)
    if dev["platform"] != "tpu":
        raise SystemExit(f"chipbench: needs a TPU, JAX found {dev['platform']}")
    if dev["count"] < cell.chips:
        raise SystemExit(f"chipbench: cell {cell.name} needs {cell.chips} "
                         f"chips, JAX found {dev['count']}")
    spec.load_peaks(dev["kind"], CHECKOUT)
    return dev


def build(cell, seed: int):
    """The weights drawn from ``seed`` and the engine serving them."""
    from chipbench import system
    family, conf, dev = cell.family, cell.config, jax.devices()[0]
    cfg = family.program_config(conf)
    params = system.make_params(family, conf, cfg, seed, dev)
    return params, system.make_engine(conf, cfg, params, dev)


def run_cell(cell, seed: int, seconds: float, trace: bool,
             root: Path = CHECKOUT, t_start: float = T_START,
             fault=None, controls=()) -> dict:
    """One run of ``cell``; returns the result object. ``fault``, for
    tests, breaks the engine after set-up (``fault(engine)``);
    ``controls`` (``("fp8",)``) adds the control's readings on the same
    sample to the numbers compared."""
    from chipbench import loop, metrics, traffic

    compiles = Compiles()
    conf, dev = cell.config, jax.devices()[0]
    params, engine = build(cell, seed)
    max_len = conf["engine"]["max_len"]
    reqs = traffic.make_requests(cell.traffic, cell.cell, max_len,
                                 conf["vocab_size"], seed, seconds)
    loop.warm_up(engine, [loop.prefill_bucket(len(r.prompt), max_len)
                          for r in reqs])
    if fault:
        fault(engine)
    setup_s = time.time() - t_start

    logdir = None
    if trace:
        logdir = str(CACHE / "trace")
        shutil.rmtree(logdir, ignore_errors=True)

    def tracer(w0, w1):
        a = w0 + TRACE_AT * (w1 - w0)
        return loop.Window(a, min(a + TRACE_S, w1), logdir)

    compiles.phase = "window"
    if cell.traffic["arrivals"]["process"] == "backlog":
        run = loop.backlog(engine, reqs, seconds, cell.traffic["drain_s"],
                           tracer if trace else None)
    else:
        run = loop.open_loop(engine, reqs, seconds, cell.traffic["drain_s"],
                             tracer if trace else None)
    compiles.phase = "check"
    stats = dev.memory_stats() or {}
    device = dict(device_info(),
                  memory_peak_bytes=int(stats.get("peak_bytes_in_use", 0)))

    ctx = metrics.Context(conf=conf, cell=cell, run=run, setup_s=setup_s,
                          peaks=spec.load_peaks(device["kind"], root)
                          if device["platform"] == "tpu" else None)
    if trace:
        ctx.load_trace(logdir)
        shutil.rmtree(logdir, ignore_errors=True)
        device.update(busy_s=ctx.trace["busy_s"], window_s=ctx.trace["window_s"])
        values = metrics.per_layer(ctx, cell.per_layer, root)
    else:
        values = metrics.end_to_end(ctx, cell.end_to_end)
    attempted, failed = metrics.attempted_failed(ctx)

    engine.cache = None                 # free the cache for the reference
    del engine
    t_check = time.time()
    got, compared, correct = compare(cell, params, run, seed, controls)
    compiles.close()

    info = {"setup_s": setup_s,
            **{f"{k}_compiles": n for k, n in compiles.counts.items()},
            "cache_hits": compiles.hits, "requests": len(reqs),
            "steps": len(run.steps), "check_s": time.time() - t_check}
    if run.min_queue is not None:
        info["min_queue_in_window"] = run.min_queue
    for k in ("top2_gap_median", "repeats"):
        if k in got:
            info[k] = got[k]
    result = {"correct": correct, "attempted": attempted,
              "failed": failed,
              "metrics": {k: {"value": v, "unit": u}
                          for k, (v, u) in values.items()},
              "device": device}
    if trace:
        result["breakdown"] = {"device_ops": ctx.trace["device_ops"],
                               "idle_gaps": ctx.trace["idle_gaps"]}
    result["info"] = info
    result["check"] = compared
    return result


def compare(cell, params, run, seed: int, controls=()):
    """The output check over a sample of the run's finished requests:
    (readings, the numbers compared with their limits, correct)."""
    from chipbench import check
    finished = [s for s in run.served if s.finished]
    chosen = check.sample(finished, cell.cell["check"]["requests"], seed)
    got = check.gaps(cell.family, params, cell.config,
                     [check.sequence_of(s.er.prompt, s.er.out_tokens)
                      for s in chosen], controls)
    limit = cell.cell["check"]["max_logit_gap"]
    compared = {"max_logit_gap": {"value": got["max_logit_gap"],
                                  "rule": "<=", "limit": limit},
                "positions": {"value": got["positions"], "rule": ">=",
                              "limit": 1}}
    compared.update({k: {"value": v, "rule": ">", "limit": limit}
                     for k, v in got.items() if k.startswith("control_gap")})
    correct = got["positions"] >= 1 and got["max_logit_gap"] <= limit
    return got, compared, bool(correct)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload, CHECKOUT)
    check_devices(cell)
    use_compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    print(f"[chipbench] {json.dumps(result['info'])}", file=sys.stderr)
    for name, c in result["check"].items():
        print(f"[check] {name} {c['value']!r} {c['rule']} {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
