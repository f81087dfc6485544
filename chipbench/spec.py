"""Finds a cell and everything it names, by name, under the checkout.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``. It names a
configuration (``chipbench/configs/<config>.json``) and a traffic mix
(``chipbench/traffic/<traffic>.json``); what belongs to the cell alone
(its rate, the size of its output check and that check's limit) is in
``chipbench/cells/<cell>.json``. Each per-layer metric is a reader in
``chipbench/layer_metrics/<metric>.py``. The configuration states its
model family (``"family"``), whose code is
``chipbench/families/<family>.py``. Adding a cell, a configuration, a
mix, a metric or a family adds files and entries; no file here changes.

A family's module defines:

* ``program_config(conf)``: the program's model configuration for the
  configuration file ``conf``; it raises where the family's reference
  does not cover what the program would run;
* ``draw_params(conf, cfg, key)``: every leaf of the served weights,
  drawn from ``key`` in the dtype served (traced in one jitted call);
* ``reference(conf, mode)``: the jitted ``(hidden, logits)`` pair of its
  plain float32 reference, ``hidden(params, tokens (T,))`` -> (T, d) and
  ``logits(params, hidden, rows (P,))`` -> float32 (P, vocab); with
  ``mode="fp8"`` the control (``chipbench/reference.py``);
* ``linear_flops(conf)``: operations of the linear layers for one token
  through every layer;
* ``attention_dims(conf)``: (layers that attend, query heads, key/value
  heads, head size), for the shared attention counts;
* ``prefill_flops(conf, s)``: operations of a prompt of ``s`` real
  tokens, logits at its last position included;
* ``decode_flops(conf, live)``: what one decode step computes beyond each
  slot's linear layers and logits, for slots at ``live`` keys.
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH_DIR = Path(__file__).resolve().parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
FAMILY_API = ("program_config", "draw_params", "reference", "linear_flops",
              "attention_dims", "prefill_flops", "decode_flops")
_modules: Dict[Path, ModuleType] = {}


class SpecError(ValueError):
    """A cell, configuration, mix or metric that is missing or malformed."""


def _read(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"{path} does not exist")
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict          # the configuration as it is run
    family: ModuleType    # chipbench/families/<config's family>.py
    traffic: dict         # the traffic mix's parameters
    cell: dict            # what belongs to this cell alone
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path | None = None) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` (root: the checkout)."""
    root = Path(root) if root is not None else BENCH_DIR.parent
    bench = _read(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no cell {name!r}; cells: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"cell {name!r} names unknown config {w['config']!r}")
    bdir = root / "chipbench"
    config = _read(root / configs[w["config"]]["file"])
    return Cell(
        name=name, chips=int(w["chips"]),
        config_name=w["config"], traffic_name=w["traffic"],
        config=config, family=load_family(config, root),
        traffic=_read(bdir / "traffic" / f"{w['traffic']}.json"),
        cell=_read(bdir / "cells" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


def _load_module(path: Path, prefix: str, name: str) -> ModuleType:
    """The module at ``path``, executed once per process."""
    path = path.resolve()
    if path not in _modules:
        spec = importlib.util.spec_from_file_location(
            f"{prefix}_{name.replace('.', '_').replace('-', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _modules[path] = mod
    return _modules[path]


def load_family(conf: dict, root: Path | None = None) -> ModuleType:
    """The module of the configuration's ``"family"``,
    ``chipbench/families/<family>.py``, with every function of the
    family interface (above)."""
    root = Path(root) if root is not None else BENCH_DIR.parent
    name = conf.get("family")
    if not isinstance(name, str) or not NAME.match(name):
        raise SpecError(f"configuration {conf.get('arch')!r} states no "
                        f"model family (\"family\": {name!r})")
    path = root / "chipbench" / "families" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no code for model family {name!r} at {path}")
    mod = _load_module(path, "chipbench_family", name)
    missing = [f for f in FAMILY_API if not callable(getattr(mod, f, None))]
    if missing:
        raise SpecError(f"{path} does not define {', '.join(missing)}")
    return mod


def load_metric_reader(name: str, root: Path | None = None) -> ModuleType:
    """The module ``chipbench/layer_metrics/<name>.py``; it defines
    ``read(ctx) -> float | None``."""
    root = Path(root) if root is not None else BENCH_DIR.parent
    path = root / "chipbench" / "layer_metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no reader for per-layer metric {name!r} at {path}")
    mod = _load_module(path, "chipbench_metric", name)
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"{path} defines no read(ctx)")
    return mod


def load_peaks(device_kind: str, root: Path | None = None) -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind``; an unknown kind is
    an error, never a default."""
    root = Path(root) if root is not None else BENCH_DIR.parent
    table = _read(root / "chipbench" / "peaks.json")
    if device_kind not in table["kinds"]:
        raise SpecError(f"no peaks for device kind {device_kind!r}; "
                        f"known: {sorted(table['kinds'])}")
    return table["kinds"][device_kind]
