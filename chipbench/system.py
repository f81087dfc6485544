"""The system under test, as the benchmark sets it up: the weights drawn
from the seed, and the engine that serves them. What the weights are,
and the program's model configuration they fit, belong to the model's
family (``chipbench/families/<family>.py``).

The weights are the benchmark's own: one jitted call draws every leaf on
the device, in the dtype served, in the tree layout that the program's
``model.init`` declares (its shapes only are taken from the program). So
the plain reference can use the same weights without taking anything the
program made.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.serving.engine import JaxEngine


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (also past 32 bits)."""
    words = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.wrap_key_data(words.astype(np.uint32),
                                    impl="threefry2x32")


def make_params(family, conf: dict, cfg, seed: int, device):
    """``family``'s weights (``draw_params``) for configuration ``conf``,
    drawn from ``seed`` on ``device`` in one jitted call."""
    sharding = jax.sharding.SingleDeviceSharding(device)
    return jax.jit(lambda key: family.draw_params(conf, cfg, key),
                   out_shardings=sharding)(seed_key(seed))


def make_engine(conf: dict, cfg, params, device) -> JaxEngine:
    eng = conf["engine"]
    return JaxEngine(cfg, params, max_batch=eng["max_batch"],
                     max_len=eng["max_len"], device=device)
