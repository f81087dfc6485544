"""Shared fixtures. NOTE: no XLA_FLAGS here — smoke tests must see the
real single CPU device; only launch/dryrun.py forces 512 host devices.
The tests run on the CPU backend even where a TPU is attached."""
import os
import tempfile

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the TPU library, where installed, logs to /tmp unless given a directory
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(tempfile.gettempdir(), "tpu_logs"))


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def phi4_runtime_library():
    """Session-scoped template library for the epoch-runtime tests,
    served from the ``artifacts/lib_test_*.pkl`` disk cache (see
    tests/_libcache.py) instead of being rebuilt per run."""
    from _libcache import cached_test_library
    from repro.core.hardware import make_node_configs
    from repro.core.modelspec import PAPER_MODELS
    from repro.traces.workloads import workload_stats

    model = PAPER_MODELS["phi4-14b"]
    configs = make_node_configs(["L40S", "L4"], sizes=(1, 2))
    wls = {model.name: workload_stats(model.trace)}
    return cached_test_library("runtime", [model], configs, wls,
                               n_max=3, rho=8.0)
