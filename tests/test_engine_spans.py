"""The serving engine's spans and request stamps (``repro.obs.spans``):
records nest and carry request ids, stay bounded, and give the stamps
and ``iteration_log`` from the same clock reads; a request admitted and
finished in one step is returned by ``drain`` and the router; the
launcher reads TTFT from arrival and the queue wait from the stamps; the
compiled programs carry the model's scope names."""
import re

import jax
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.launch.serve import latency_stats, route
from repro.models import api as mapi
from repro.obs import MAX_SPANS, SpanLog
from repro.serving.engine import JaxEngine

NAMES = ("engine.step", "engine.admit", "engine.prefill",
         "engine.first_token", "engine.insert", "engine.decode",
         "engine.sample", "engine.emit")


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("qwen2-1.5b")
    params, _ = mapi.get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def served(setup):
    """An engine of 2 slots that served 5 requests of 1-4 new tokens."""
    cfg, params = setup
    rng = np.random.default_rng(0)
    eng = JaxEngine(cfg, params, max_batch=2, max_len=64)
    for rid in range(5):
        eng.submit(rid, rng.integers(0, cfg.vocab_size, (5 + 3 * rid,)),
                   1 + rid % 4)
    return eng, eng.drain()


def test_spans_nest_and_carry_request_ids(served):
    eng, finished = served
    recs = list(eng.spans.records)
    by_id = {r.id: r for r in recs}
    assert {r.name for r in recs} == set(NAMES)
    parent = {"engine.step": None, "engine.admit": "engine.step",
              "engine.prefill": "engine.admit",
              "engine.first_token": "engine.admit",
              "engine.insert": "engine.admit", "engine.decode": "engine.step",
              "engine.sample": "engine.step", "engine.emit": "engine.step"}
    for r in recs:
        want = parent[r.name]
        assert (by_id[r.parent].name if r.parent is not None else None) == want
        if r.parent is not None:
            p = by_id[r.parent]
            assert p.t0_ns <= r.t0_ns <= r.t1_ns <= p.t1_ns
    per_request = [r for r in recs if r.name in
                   ("engine.prefill", "engine.first_token", "engine.insert")]
    assert sorted({r.rid for r in per_request}) == sorted(finished)
    assert all(r.rid is None for r in recs if r not in per_request)
    steps = [r.meta for r in recs if r.name == "engine.step"]
    assert [m["step"] for m in steps] == list(range(1, eng.n_steps + 1))
    assert steps[0] == {"step": 1, "active": 0, "queue": 5}
    pre = [r.meta for r in recs if r.name == "engine.prefill"]
    assert all(m["bucket"] >= m["prompt_len"] for m in pre)


def test_span_log_stays_bounded(served):
    log = SpanLog()
    n = MAX_SPANS // 2 + 5
    for i in range(n):
        with log.span("outer", rid=i):
            with log.span("inner"):
                pass
    assert len(log.records) == MAX_SPANS
    # a span is recorded when it closes, inner before outer; the oldest go
    assert [r.id for r in list(log.records)[:4]] == [11, 10, 13, 12]
    assert log.records[-1].id == 2 * n - 2 and log.records[-1].rid == n - 1
    assert all(r.parent == r.id - 1 for r in log.records
               if r.name == "inner")
    assert served[0].spans.records.maxlen == MAX_SPANS


def test_request_stamps_are_ordered(served):
    _, finished = served
    assert sorted(finished) == list(range(5))
    for r in finished.values():
        assert 0 < r.submitted <= r.admitted <= r.prefill_done
        assert r.prefill_done <= r.token_times[0]
        assert r.token_times == sorted(r.token_times)


def test_iteration_log_is_the_span_intervals(served):
    eng, finished = served
    recs = list(eng.spans.records)
    pre = [r for r in recs if r.name == "engine.prefill"]
    ins = [r for r in recs if r.name == "engine.insert"]
    dec = [r for r in recs if r.name == "engine.decode"]
    smp = [r for r in recs if r.name == "engine.sample"]
    log = eng.iteration_log
    assert [e[1] for e in log if e[0] == "prefill"] == \
        [p.meta["bucket"] for p in pre]
    assert [e[2] for e in log if e[0] == "prefill"] == \
        [(i.t1_ns - p.t0_ns) * 1e-9 for p, i in zip(pre, ins)]
    assert [e[2] for e in log if e[0] == "decode"] == \
        [(s.t1_ns - d.t0_ns) * 1e-9 for d, s in zip(dec, smp)]
    for p, i in zip(pre, ins):
        assert finished[p.rid].admitted == p.t0_ns * 1e-9
        assert finished[p.rid].prefill_done == i.t1_ns * 1e-9


def test_drain_returns_a_request_admitted_and_finished_in_one_step(setup):
    cfg, params = setup
    eng = JaxEngine(cfg, params, max_batch=2, max_len=64)
    eng.submit(0, np.arange(6), 1)
    eng.submit(1, np.arange(9), 3)
    eng.submit(2, np.arange(4), 1)          # admitted once a slot frees
    finished = eng.drain()
    assert sorted(finished) == [0, 1, 2]
    assert [len(finished[r].out_tokens) for r in range(3)] == [2, 4, 2]


def test_router_returns_one_step_requests_and_times_ttft_from_arrival(setup):
    cfg, params = setup
    eng = JaxEngine(cfg, params, max_batch=2, max_len=64)
    rng = np.random.default_rng(3)
    trace = [(0.01 * i, cfg.name, i, rng.integers(0, cfg.vocab_size, (8,)),
              1 + i % 2) for i in range(6)]
    finished, arrived, served_by = route({cfg.name: [eng]}, trace)
    assert sorted(finished) == list(range(6))
    assert set(served_by.values()) == {0}
    stats = latency_stats(finished, arrived, range(6))
    for i in range(6):
        r = finished[i]
        assert stats["ttft_s"][i] == r.prefill_done - arrived[i]
        assert stats["queue_s"][i] == r.admitted - r.submitted
        assert arrived[i] <= r.submitted + 1e-6
        assert stats["ttft_s"][i] >= stats["queue_s"][i]
    assert stats["tokens"] == sum(len(r.out_tokens) for r in finished.values())


def test_compiled_programs_carry_scope_names(setup):
    cfg, params = setup
    eng = JaxEngine(cfg, params, max_batch=2, max_len=64)
    dec = eng._serve.lower(eng.params, eng.cache,
                           np.zeros((2,), np.int32)).compile().as_text()
    pre = eng._prefill.lower(eng.params,
                             {"tokens": np.zeros((1, 16), np.int32)},
                             np.zeros((1,), np.int32)).compile().as_text()

    def scopes(text):
        return {part for name in re.findall(r'op_name="([^"]*)"', text)
                for part in name.split("/")}
    layer = {"attn_qkv", "attention", "attn_out", "mlp"}
    assert {"embed", "layers", "unembed", "kv_write"} | layer <= scopes(dec)
    assert {"embed", "layers", "unembed"} | layer <= scopes(pre)
    assert "kv_write" not in scopes(pre)
    assert pre.startswith("HloModule jit_engine_prefill")
    # the cache write sits inside the layer scan
    in_scan = r"jit\(serve_step\)/layers/while/body/.*kv_write/.*"
    assert any(re.fullmatch(in_scan, n)
               for n in re.findall(r'op_name="([^"]*)"', dec))
