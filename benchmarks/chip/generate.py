"""Inputs of each configuration, made on the device from the seed.

``inputs`` of the configuration file names the generator,
``generators/<inputs>.py``, whose ``make(config, key)`` returns the raw
inputs that ``solve`` takes, in one jitted call.
"""
from __future__ import annotations

import jax

import cells


def seed_key(seed: int):
    """A PRNG key for any whole seed, also beyond 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be a whole number >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                              seed >> 31)


def make(config: dict, key):
    return cells.component("generators", config["inputs"]).make(config, key)
