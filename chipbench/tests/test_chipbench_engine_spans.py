"""The readers of the engine's spans and stamps: ``step_host_ms`` (device
idle inside ``engine.step`` host spans, split by the innermost engine
span) and ``admit_wait_p90_ms`` (the engine's own queue wait), on
synthetic events and requests and on a real engine on the CPU; and the
engine's spans in a CPU profiler trace, on the record's clock."""
import glob
import os
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import devtrace, loop, metrics, spec
from chipbench.percentiles import percentile
from chipbench.traffic import Request
from repro.configs.registry import get_smoke_config
from repro.launch.serve import build_engine
from repro.serving.engine import EngineRequest

from _chipbench_tiny import CHECKOUT

STEP_HOST = spec.load_metric_reader("step_host_ms", CHECKOUT)
ADMIT = spec.load_metric_reader("admit_wait_p90_ms", CHECKOUT)
QUEUE = spec.load_metric_reader("queue_wait_p90_ms", CHECKOUT)
ENGINE_SPANS = {"engine.step", "engine.admit", "engine.prefill",
                "engine.first_token", "engine.insert", "engine.decode",
                "engine.sample", "engine.emit"}


def _ev(plane, line, name, start, end):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": float(start), "dur_ns": float(end - start)}


DEV, HOST = "/device:TPU:0", "/host:CPU"
EVENTS = [
    _ev(DEV, "XLA Ops", "%fusion.1 = bf16[8]{0} fusion(...)", 0, 10),
    _ev(DEV, "XLA Ops", "%fusion.2 = bf16[8]{0} fusion(...)", 30, 50),
    _ev(DEV, "XLA Ops", "%fusion.3 = bf16[8]{0} fusion(...)", 80, 90),
    _ev(DEV, "XLA Ops", "%fusion.4 = bf16[8]{0} fusion(...)", 115, 120),
    _ev(HOST, "python", "chipbench.step", 4, 101),
    _ev(HOST, "python", "engine.step", 5, 100),
    _ev(HOST, "python", "engine.admit", 6, 20),
    _ev(HOST, "python", "engine.prefill", 7, 15),
    _ev(HOST, "python", "engine.decode", 20, 40),
    _ev(HOST, "python", "engine.sample", 40, 85),
    _ev(HOST, "python", "engine.emit", 86, 95),
    _ev(HOST, "python", "chipbench.step", 109, 131),
    _ev(HOST, "python", "engine.step", 110, 130),
]
# idle in the first step: [10,30] (prefill 5, admit 5, decode 10), [50,80]
# (sample 30), [90,100] (emit 5, the step itself 5); in the second step
# [110,115] and [120,130]: 15; [100,110] lies between steps
SPLIT = {"engine.prefill": 5e-9, "engine.admit": 5e-9,
         "engine.decode": 10e-9, "engine.sample": 30e-9, "engine.emit": 5e-9,
         "engine.step": 20e-9}


def test_step_host_splits_idle_by_the_innermost_engine_span():
    idle, steps = STEP_HOST.idle_by_span(EVENTS)
    assert steps == 2
    assert idle == pytest.approx(SPLIT)
    ctx = SimpleNamespace(events=EVENTS)
    assert STEP_HOST.read(ctx) == pytest.approx(1e3 * 75e-9 / 2)
    # the steps' idle is part of the device's idle over the traced span
    t = devtrace.reduce_events(EVENTS)
    assert STEP_HOST.read(ctx) * 1e-3 * steps <= \
        t["window_s"] - t["busy_s"] + 1e-15


def test_step_host_takes_the_mean_over_chips():
    busy = [_ev("/device:TPU:1", "XLA Ops", "%fusion.9 = f()", 0, 130)]
    idle, _ = STEP_HOST.idle_by_span(EVENTS + busy)
    assert idle == pytest.approx({k: v / 2 for k, v in SPLIT.items()})


@pytest.mark.parametrize("events", [
    [e for e in EVENTS if not e["plane"].startswith(DEV)],   # CPU trace
    [e for e in EVENTS if not e["name"].startswith("engine.")],  # no spans
    [], None])
def test_step_host_reads_nothing_without_a_tpu_or_engine_spans(events):
    assert STEP_HOST.read(SimpleNamespace(events=events)) is None


def _ctx(served, trace_span=(50.0, 53.0), process="poisson"):
    cell = SimpleNamespace(traffic={"arrivals": {"process": process}})
    run = loop.Run(served, window=(0.0, 100.0), trace_span=trace_span)
    return metrics.Context(conf={}, cell=cell, run=run, setup_s=0.0)


def _served(rid, due, late, wait, prefill):
    req = Request(rid, due, np.zeros((4,), np.int32), 2)
    er = EngineRequest(rid, req.prompt, 2, submitted=due + late)
    er.admitted = er.submitted + wait
    er.prefill_done = er.admitted + prefill
    return loop.Served(req, due, submitted=er.submitted, er=er,
                       prefill_s=prefill)


def test_admit_wait_is_the_queue_wait_less_the_generators_lateness():
    rng = np.random.default_rng(7)
    served = [_served(i, float(i), *rng.uniform(0.0, 0.5, 3))
              for i in range(60)]
    ctx = _ctx(served)
    due = ctx.host_due()
    assert len(due) == 50                       # due before the trace
    waits = [s.er.admitted - s.er.submitted for s in due]
    assert ADMIT.read(ctx) == pytest.approx(1e3 * percentile(waits, 0.9))
    for s in due:                               # per request, exactly
        late = s.er.submitted - s.due
        wait = s.er.admitted - s.er.submitted
        assert s.first_token - s.due - s.prefill_s == \
            pytest.approx(late + wait, abs=1e-9)
    assert ADMIT.read(ctx) <= QUEUE.read(ctx)


def test_admit_wait_reads_nothing_without_the_stamp_or_in_a_backlog():
    served = [_served(i, float(i), 0.1, 0.2, 0.3) for i in range(5)]
    assert ADMIT.read(_ctx(served, process="backlog")) is None
    for s in served:    # a program that stamps no admission
        s.er = SimpleNamespace(prompt=s.er.prompt, out_tokens=[1],
                               submitted=s.er.submitted,
                               prefill_done=s.er.prefill_done)
    assert ADMIT.read(_ctx(served)) is None
    assert QUEUE.read(_ctx(served)) is not None


@pytest.fixture(scope="module")
def engine():
    return build_engine(get_smoke_config("qwen2-1.5b"), max_batch=2,
                        max_len=64)


def test_a_real_open_loop_splits_ttft_into_lateness_wait_and_prefill(engine):
    """The engine's stamps and its ``iteration_log`` prefill time add up
    to the time to first token from when each request was due."""
    rng = np.random.default_rng(0)
    reqs = [Request(i, 0.01 * i, rng.integers(0, 100, (6 + i,)), 1 + i % 3)
            for i in range(8)]
    run = loop.open_loop(engine, reqs, 0.1, 30.0)
    assert all(s.finished for s in run.served)
    for s in run.served:
        er = s.er
        assert s.due <= er.submitted <= er.admitted <= er.prefill_done
        assert s.first_token - s.due == pytest.approx(
            (er.submitted - s.due) + (er.admitted - er.submitted)
            + s.prefill_s, abs=1e-6)
    cell = SimpleNamespace(traffic={"arrivals": {"process": "poisson"}})
    ctx = metrics.Context(conf={}, cell=cell, run=run, setup_s=0.0)
    assert len(ctx.host_due()) == 8
    assert 0.0 <= ADMIT.read(ctx) <= QUEUE.read(ctx)


def test_cpu_profiler_trace_holds_the_engine_spans_on_the_records_clock(
        engine, tmp_path):
    import jax
    from jax.profiler import ProfileData
    rng = np.random.default_rng(1)
    for i in range(3):
        engine.submit(100 + i, rng.integers(0, 100, (5,)), 2)
    n0 = engine.n_steps
    with jax.profiler.trace(str(tmp_path)):
        engine.drain()
    events = devtrace.read_xplane(str(tmp_path))
    host = [e for e in events if e["name"].startswith("engine.")]
    assert {e["name"] for e in host} == ENGINE_SPANS
    assert all(not e["plane"].startswith(devtrace.DEVICE_PREFIX)
               for e in host)
    # a trace's times count from the profile's start
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    env = ProfileData.from_file(path).find_plane_with_name("Task Environment")
    base = dict(env.stats)["profile_start_time"]
    traced = sorted((e for e in host if e["name"] == "engine.step"),
                    key=lambda e: e["start_ns"])
    recs = [r for r in engine.spans.records
            if r.name == "engine.step" and r.meta["step"] > n0]
    assert len(traced) == len(recs) == engine.n_steps - n0 >= 2
    for e, r in zip(traced, recs):
        assert abs(base + e["start_ns"] - r.t0_ns) < 1e6
