"""Random weights from the seed, made by the benchmark, in the served tree.

The benchmark, not the program, makes the weights, so the reference can
use the very same numbers without taking anything the program made.  One
jitted call builds the whole tree on the device, in float32 (the type the
program serves its weights in; it casts to bfloat16 inside the step).

The configuration's family module (``bench/core/models.py``) lays the
tree out as the program's serve step reads it, with its matrices drawn by
``_mat`` and its norm gains and biases at the spreads below.  Gains,
biases and padding are random too, so the comparison with the reference
covers every term, padding masks included.
"""
from __future__ import annotations

import functools
import json
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from . import models

# spread of the norm gains around one, and of the biases
GAIN_SD = 0.1
BIAS_SD = 0.1


def as_run(cfg: dict) -> dict:
    """The configuration as the program computes it: the published values
    with each departure the program cannot help (``departures`` entries
    that name a ``key``) set to its ``as_run`` value."""
    out = dict(cfg)
    for d in cfg.get("departures", []):
        if "key" in d:
            out[d["key"]] = d["as_run"]
    return out


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def dims(cfg: dict) -> Dict:
    """The sizes the served tree is laid out in, from a config file (the
    family's ``dims``), with its ``model_type``: the counts find the
    family from the sizes alone."""
    return dict(models.load(cfg).dims(cfg), model_type=cfg["model_type"])


def _mat(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(
        jnp.float32(fan_in))


def _init(cfg: dict, key) -> Dict:
    cfg = as_run(cfg)
    return models.load(cfg).init(cfg, key)


@functools.lru_cache(maxsize=None)
def _jitted(cfg_json: str):
    return jax.jit(functools.partial(_init, json.loads(cfg_json)))


def make(cfg: dict, seed: int):
    """The whole tree for ``seed``, built on the default device."""
    return _jitted(json.dumps(cfg, sort_keys=True))(jax_key(seed))


def jax_key(seed: int):
    """A JAX key from any whole-number seed (JAX keys take 32 bits)."""
    word = np.random.default_rng([int(seed) % 2**64, 0x5EED]).integers(
        0, 2**31)
    return jax.random.PRNGKey(int(word))
