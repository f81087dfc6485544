"""Tiny cells for the CPU tests: the committed cells with their models cut
to a tiny width and their traffic to short requests."""
import dataclasses
import importlib.util
import json
import time
from pathlib import Path

from chipbench import spec

CHECKOUT = Path(__file__).resolve().parents[2]
# Limits of the output check at the tiny sizes, set as the cells' are at
# theirs: between the sound engine's widest served-token gap and the fp8
# control's (CPU, seeds 1 and 2**33 + 1: qwen2 0.008 against 0.18;
# minicpm, whose muP divisor shrinks the logits, 0.0001 against 0.004).
TINY_LIMIT = {"qwen2-1.5b.code": 0.05, "minicpm-2b.chat-backlog": 0.002}


def _run_module():
    s = importlib.util.spec_from_file_location(
        "chipbench_run", CHECKOUT / "chipbench" / "run.py")
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


RUN = _run_module()


def tiny_cell(name: str) -> spec.Cell:
    """The cell ``name`` with its configuration cut to a tiny width and
    its traffic to short requests; everything else as committed."""
    c = spec.load_cell(name, CHECKOUT)
    conf = dict(c.config, num_hidden_layers=4, intermediate_size=128,
                vocab_size=512, engine={"max_batch": 4, "max_len": 256})
    if conf["num_key_value_heads"] == conf["num_attention_heads"]:
        conf.update(hidden_size=72, num_attention_heads=4, num_key_value_heads=4)
    else:
        conf.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2)
    if conf.get("dim_model_base"):     # keep the published logit divisor
        full = c.config
        conf["dim_model_base"] = conf["hidden_size"] * full["dim_model_base"] \
            / full["hidden_size"]
    traffic = json.loads(json.dumps(c.traffic))
    traffic["prompt"]["mean"], traffic["output"]["mean"] = 60, 12
    traffic["drain_s"] = min(traffic["drain_s"], 10)
    cell = dict(c.cell, check={"requests": 6, "max_logit_gap": TINY_LIMIT[name]})
    if "rate_per_s" in cell:
        cell["rate_per_s"] = 40.0      # enough to keep every slot busy
    return dataclasses.replace(c, config=conf, traffic=traffic, cell=cell)


CELLS = [w["name"] for w in json.loads(
    (CHECKOUT / "BENCHMARK.json").read_text())["workloads"]]


def run_tiny(cell, seed=2**33 + 1, trace=False, **kw):
    return RUN.run_cell(cell, seed, 2.0, trace, t_start=time.time(), **kw)


