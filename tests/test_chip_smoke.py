"""CPU rehearsal of chip_smoke.py: it refuses to run without a TPU, and
its kernel and serving phases pass their own checks at a small size."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs.registry import get_smoke_config

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_exits_nonzero_without_tpu(where, tmp_path):
    """On the CPU backend, or copied away from the repo, the script fails
    and prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    script = SCRIPT
    if where == "alone":
        script = tmp_path / "chip_smoke.py"
        shutil.copy(SCRIPT, script)
        env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0, out.stdout
    assert '"ok"' not in out.stdout


def test_device_check_refuses_cpu(smoke):
    with pytest.raises(SystemExit, match="needs a tpu backend"):
        smoke.check_device("tpu")


def test_kernel_parity_interpret(smoke):
    smoke.kernel_parity(impl="pallas_interpret", buckets=(16, 128), batch=2,
                        max_len=256)


def test_serving_phase_smoke_config(smoke):
    """The serving phase on the qwen2 smoke config (jnp reference
    kernels): every request finishes and the engine matches direct greedy
    decoding on the exact-bucket prompts."""
    eng = smoke.serving_phase(get_smoke_config("qwen2-1.5b"), seed=0,
                              max_len=128, max_prompt=96)
    assert eng.device.platform == "cpu"
    smoke.check_placement(eng)


def test_replicas_phase_smoke_configs(smoke, monkeypatch):
    """The four-replica phase on smoke configs, all four engines on the
    one CPU device: the router spreads each prompt pair over a model's two
    replicas and they agree token for token."""
    import jax
    monkeypatch.setattr(smoke, "get_config", get_smoke_config)
    smoke.replicas_phase([jax.devices()[0]] * 4, seed=0)
