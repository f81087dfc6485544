"""Model families: the dense family's code, moved into
``chipbench/families/dense.py``, draws, references and counts exactly as
before (``fixtures/dense_family.json``, recorded from the tree before
the move); a family added as one new file, with its configuration, cell
and mix, runs through the harness with no other file changed; and a
configuration that states no family, or one with no file, is refused."""
import base64
import dataclasses
import hashlib
import json
import shutil
import time

import jax
import numpy as np
import pytest

from chipbench import check, counts, loop, metrics, spec, system, traffic
from _chipbench_tiny import CHECKOUT, RUN

FIXTURE = json.loads((CHECKOUT / "chipbench" / "tests" / "fixtures" /
                      "dense_family.json").read_text())
DENSE = spec.load_family({"family": "dense"}, CHECKOUT)
CPU = jax.devices("cpu")[0]


def _tiny(key):
    rec = FIXTURE["tiny"][key]
    return rec, dict(rec["conf"], family="dense")


@pytest.mark.parametrize("key", sorted(FIXTURE["tiny"]))
def test_dense_weights_are_drawn_as_before(key):
    rec, conf = _tiny(key)
    params = system.make_params(DENSE, conf, DENSE.program_config(conf),
                                FIXTURE["seed"], CPU)
    got = {jax.tree_util.keystr(p): [list(v.shape), str(v.dtype),
           hashlib.sha256(np.asarray(v).tobytes()).hexdigest()]
           for p, v in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert got == rec["params"]


@pytest.mark.parametrize("mode", [None, "fp8"])
@pytest.mark.parametrize("key", sorted(FIXTURE["tiny"]))
def test_dense_reference_logits_are_as_before(key, mode):
    from chipbench import reference
    rec, conf = _tiny(key)
    params = system.make_params(DENSE, conf, DENSE.program_config(conf),
                                FIXTURE["seed"], CPU)
    sel = np.asarray(rec["sel"], np.int32)
    parts = reference.logits_at(DENSE, params, conf,
                                np.asarray(rec["tokens"], np.int32), sel, mode)
    got = np.concatenate([np.asarray(p) for p in parts])[:len(sel)]
    want = np.frombuffer(base64.b64decode(rec["logits"][mode or "plain"]),
                         np.float32).reshape(got.shape)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("name", sorted(FIXTURE["counts"]))
def test_dense_counts_are_as_before(name):
    rec = FIXTURE["counts"][name]
    conf = json.loads((CHECKOUT / "chipbench" / "configs" /
                       f"{name}.json").read_text())
    family = spec.load_family(conf, CHECKOUT)
    dims = family.attention_dims(conf)
    for live, n in rec["decode_step_flops"]:
        assert counts.decode_step_flops(family, conf, live) == n
    for s, n in rec["prefill_flops"]:
        assert family.prefill_flops(conf, s) == n
    for live, fb in rec["decode_attention_cost"]:
        assert list(counts.decode_attention_cost(dims, conf, live)) == fb
    for s, fb in rec["flash_attention_cost"]:
        assert list(counts.flash_attention_cost(dims, conf, s)) == fb


# A family that only its own file defines: a one-layer grouped-query
# decoder without biases, with an untied unembedding, as the program's
# dense transformer runs it.
TOY_FAMILY = '''
import math

import jax
import jax.numpy as jnp

from chipbench import counts
from chipbench.reference import F32, HI, linear, rmsnorm, rope


def program_config(conf):
    from repro.configs.registry import get_config
    return get_config(conf["arch"]).with_(
        n_layers=1, d_model=conf["d"], n_heads=conf["heads"],
        n_kv_heads=conf["kv_heads"], d_ff=conf["ffn"],
        vocab_size=conf["vocab_size"], qkv_bias=False, tie_embeddings=False,
        rope_theta=conf["theta"], norm_eps=conf["eps"],
        dtype=conf["torch_dtype"])


def draw_params(conf, cfg, key):
    from repro.models import api
    shapes = jax.eval_shape(lambda k: api.get_model(cfg).init(k, cfg)[0],
                            jax.random.PRNGKey(0))
    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (path, sd) in enumerate(leaves):
        z = jax.random.normal(jax.random.fold_in(key, i), sd.shape)
        name = path[-1].key
        if name == "scale":
            w = 1.0 + 0.1 * z
        elif name == "embed":
            w = z / math.sqrt(sd.shape[-1])
        else:
            w = z / math.sqrt(sd.shape[-2])
        out.append(w.astype(conf["torch_dtype"]))
    return jax.tree_util.tree_unflatten(tree, out)


def reference(conf, mode):
    H, KH, V = conf["heads"], conf["kv_heads"], conf["vocab_size"]
    hd, eps, theta = conf["d"] // H, conf["eps"], conf["theta"]

    def hidden(params, tokens):
        T = tokens.shape[0]
        lp = jax.tree.map(lambda a: a[0].astype(F32), params["layers"])
        at, ml = lp["attn"], lp["mlp"]
        h = params["emb"]["embed"][:V].astype(F32)[tokens]
        pos = jnp.arange(T)
        x = rmsnorm(h, lp["ln1"]["scale"], eps)
        q = rope(linear(x, at["wq"], mode).reshape(T, H, hd), pos, theta)
        k = rope(linear(x, at["wk"], mode).reshape(T, KH, hd), pos, theta)
        v = linear(x, at["wv"], mode).reshape(T, KH, hd)
        k, v = jnp.repeat(k, H // KH, 1), jnp.repeat(v, H // KH, 1)
        s = jnp.einsum("thd,shd->hts", q, k, precision=HI) * hd ** -0.5
        s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
        o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v, precision=HI)
        h = h + linear(o.reshape(T, H * hd), at["wo"], mode)
        x = rmsnorm(h, lp["ln2"]["scale"], eps)
        g = jax.nn.silu(linear(x, ml["wg"], mode)) * linear(x, ml["wu"], mode)
        h = h + linear(g, ml["wd"], mode)
        return rmsnorm(h, params["ln_f"]["scale"].astype(F32), eps)

    def logits(params, hn, sel):
        W = params["emb"]["unembed"][:, :V].astype(F32)
        return linear(hn[sel], W, mode)

    return jax.jit(hidden), jax.jit(logits)


def linear_flops(conf):
    d, H, KH, f = conf["d"], conf["heads"], conf["kv_heads"], conf["ffn"]
    hd = d // H
    return 2 * (d * (H + 2 * KH) * hd + H * hd * d + 3 * d * f)


def attention_dims(conf):
    return 1, conf["heads"], conf["kv_heads"], conf["d"] // conf["heads"]


def prefill_flops(conf, s):
    return s * linear_flops(conf) + counts.unembed_flops(conf) + \\
        counts.flash_attention_cost(attention_dims(conf), conf, s)[0]


def decode_flops(conf, live):
    return counts.decode_attention_cost(attention_dims(conf), conf, live)[0]
'''
TOY_CONFIG = {"arch": "qwen2-1.5b", "family": "toy", "d": 64, "heads": 4,
              "kv_heads": 2, "ffn": 128, "vocab_size": 300, "hidden_size": 64,
              "theta": 10000.0, "eps": 1e-6, "torch_dtype": "bfloat16",
              "engine": {"max_batch": 4, "max_len": 256}}
TOY_MIX = {"prompt": {"mean": 60, "cv": 1.0, "min": 8},
           "output": {"mean": 12, "cv": 0.8, "min": 4},
           "arrivals": {"process": "poisson"}, "drain_s": 10}
# the limit lies between the sound engine's widest gap and the fp8
# control's (CPU, seeds 1, 5, 99, 2**33 + 3, 2**33 + 7: up to 0.029
# against 0.24 and more)
TOY_CELL = {"rate_per_s": 40.0,
            "check": {"requests": 6, "max_logit_gap": 0.05}}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def _root_with_toy(tmp_path, config=TOY_CONFIG, family=TOY_FAMILY):
    """A copy of the benchmark with the toy family, its configuration,
    mix and cell added as new files and entries, and the paths of the
    files it shares with the checkout."""
    shutil.copytree(CHECKOUT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shared = sorted(p.relative_to(tmp_path) for p in
                    (tmp_path / "chipbench").rglob("*") if p.is_file())
    b = tmp_path / "chipbench"
    if family is not None:
        (b / "families" / "toy.py").write_text(family)
    (b / "configs" / "toy-1l.json").write_text(json.dumps(config))
    (b / "traffic" / "toy-short.json").write_text(json.dumps(TOY_MIX))
    (b / "cells" / "toy-1l.toy-short.json").write_text(json.dumps(TOY_CELL))
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy-1l",
                             "file": "chipbench/configs/toy-1l.json"})
    bench["workloads"].append({"name": "toy-1l.toy-short", "config": "toy-1l",
                               "traffic": "toy-short", "chips": 1})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("tpot_p50_ms", "mfu.decode", "mfu.prefill"):
            m.setdefault("workloads", []).append("toy-1l.toy-short")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return shared


def test_a_family_enters_as_new_files(tmp_path):
    shared = _root_with_toy(tmp_path)
    for rel in shared:          # the harness's own files are untouched
        assert (tmp_path / rel).read_bytes() == (CHECKOUT / rel).read_bytes()
    cell = spec.load_cell("toy-1l.toy-short", tmp_path)
    assert cell.family.__file__ == str(tmp_path / "chipbench" / "families" /
                                       "toy.py")
    seed = 2**33 + 3
    params, engine = RUN.build(cell, seed)
    assert "unembed" in params["emb"]
    reqs = traffic.make_requests(cell.traffic, cell.cell, 256, 300, seed, 1.0)
    loop.warm_up(engine, [loop.prefill_bucket(len(r.prompt), 256)
                          for r in reqs])
    run = loop.open_loop(engine, reqs, 1.0, 10)

    finished = [s for s in run.served if s.finished]
    seqs = [check.sequence_of(s.er.prompt, s.er.out_tokens)
            for s in check.sample(finished, 6, seed)]
    got = check.gaps(cell.family, params, cell.config, seqs, ("fp8",))
    limit = TOY_CELL["check"]["max_logit_gap"]
    assert got["positions"] > 20
    assert got["max_logit_gap"] <= limit < got["control_gap.fp8"], got

    ctx = metrics.Context(conf=cell.config, cell=cell, run=run, setup_s=0.0,
                          peaks=PEAKS)
    # per slot: q, k, v, o and SwiGLU, 2 x (64 x 8 x 16 + 64 x 64 + 3 x
    # 64 x 128), and logits over 300 ids, 2 x 64 x 300; attention over n
    # live keys: 4 x 4 heads x 16 x n
    steps = [s for s in ctx.host_steps() if s.decode_s > 0]
    flops = sum(len(s.live) * (73_728 + 38_400) + 256 * sum(s.live)
                for s in steps)
    want = 100 * flops / sum(s.decode_s for s in steps) \
        / PEAKS["bf16_flops_per_s"]
    got_mfu = {m: spec.load_metric_reader(m, tmp_path).read(ctx)
               for m in ("mfu.decode", "mfu.prefill")}
    assert got_mfu["mfu.decode"] == pytest.approx(want, rel=1e-12)
    assert got_mfu["mfu.prefill"] > 0


def test_a_whole_run_of_the_new_family_is_correct(tmp_path):
    _root_with_toy(tmp_path)
    cell = spec.load_cell("toy-1l.toy-short", tmp_path)
    res = RUN.run_cell(cell, 2**33 + 7, 1.0, False, root=tmp_path,
                       t_start=time.time())
    assert res["correct"], res["check"]
    assert set(res["metrics"]) == {"tpot_p50_ms", "setup_s"}


@pytest.mark.parametrize("case", ["no family key", "no family file",
                                  "not a name", "incomplete family"])
def test_a_family_must_be_stated_and_have_its_file(tmp_path, case):
    config, family = dict(TOY_CONFIG), TOY_FAMILY
    if case == "no family key":
        del config["family"]
    elif case == "no family file":
        family = None
    elif case == "not a name":
        config["family"] = "../dense"
    else:
        family = TOY_FAMILY.replace("def decode_flops", "def _decode_flops")
    _root_with_toy(tmp_path, config, family)
    with pytest.raises(spec.SpecError):
        spec.load_cell("toy-1l.toy-short", tmp_path)


def test_the_committed_configs_state_their_family():
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        conf = json.loads((CHECKOUT / c["file"]).read_text())
        assert spec.load_family(conf, CHECKOUT) is DENSE
