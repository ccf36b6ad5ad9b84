"""bench/reference.py against the program at the smoke size, on the CPU.

The program computes in bfloat16; with its compute type switched to
float32 for the test, the two must agree to float32 rounding, which pins
every convention (rotary halves, norm gains, biases, head grouping, expert
routing and renormalisation, vocabulary masking).  As served (bfloat16)
they agree to bfloat16 rounding.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference as R
from bench.core import driver, weights
from repro.arch import model as M

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _cfg(name):
    with open(os.path.join(DATA, f"tiny-{name}.json")) as f:
        return json.load(f)


def _program_logits(cfg, params, tokens, compute):
    arch = driver.program_arch(driver.import_program(), cfg)
    old = M.COMPUTE_DTYPE
    M.COMPUTE_DTYPE = compute
    try:
        with jax.default_matmul_precision("highest"):
            logits, _ = M.forward(params, {"tokens": jnp.asarray(tokens)[None]},
                                  arch)
    finally:
        M.COMPUTE_DTYPE = old
    return np.asarray(logits[0, :, : cfg["vocab_size"]], np.float32)


@pytest.mark.parametrize("name", ["dense", "moe"])
def test_reference_matches_program_in_float32(name):
    cfg = _cfg(name)
    params = weights.make(cfg, 3)
    tokens = np.random.default_rng(0).integers(0, 256, 40).astype(np.int32)
    want = _program_logits(cfg, params, tokens, jnp.float32)
    got = R.logits(cfg, params, tokens, np.arange(40, dtype=np.int32))
    # float32 throughout: only summation order differs
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


@pytest.mark.parametrize("name", ["dense", "moe"])
def test_reference_matches_program_as_served(name):
    cfg = _cfg(name)
    params = weights.make(cfg, 4)
    tokens = np.random.default_rng(1).integers(0, 256, 40).astype(np.int32)
    want = _program_logits(cfg, params, tokens, jnp.bfloat16)
    got = R.logits(cfg, params, tokens, np.arange(40, dtype=np.int32))
    # bfloat16 activations: a relative step of 2**-8 per rounding, a few
    # roundings per layer, two layers
    assert np.abs(got - want).max() <= 2**-4 * np.abs(want).max()


@pytest.mark.parametrize("name", ["dense", "moe"])
def test_control_is_lower_precision(name):
    cfg = _cfg(name)
    params = weights.make(cfg, 5)
    tokens = np.random.default_rng(2).integers(0, 256, 64).astype(np.int32)
    where = np.arange(64, dtype=np.int32)
    ref = R.logits(cfg, params, tokens, where)
    f8 = R.logits(cfg, params, tokens, where, "fp8")
    bf = _program_logits(cfg, params, tokens, jnp.bfloat16)
    # the typical position: a bfloat16 router flip of the MoE moves a
    # single position as far as fp8 does, and the widest gap with it
    f8_gap = np.median(np.abs(f8 - ref).max(-1))
    bf_gap = np.median(np.abs(bf - ref).max(-1))
    assert f8_gap > 4 * bf_gap


def test_padding_changes_nothing_before_it():
    cfg = _cfg("dense")
    params = weights.make(cfg, 6)
    tokens = np.random.default_rng(3).integers(0, 256, 30).astype(np.int32)
    a = R.logits(cfg, params, tokens, np.array([29], np.int32))
    b = R.logits(cfg, params, tokens[:30], np.array([29], np.int32))
    longer = np.concatenate([tokens, tokens])
    c = R.logits(cfg, params, longer, np.array([29], np.int32))
    assert np.allclose(a, b) and np.allclose(a, c, atol=1e-5)


def test_tied_head_is_the_embedding():
    cfg = _cfg("dense")
    assert cfg["tie_word_embeddings"]
    params = weights.make(cfg, 7)
    assert np.array_equal(np.asarray(params["head"]),
                          np.asarray(params["embed"]).T)


def test_departures_are_read_as_run():
    cfg = _cfg("moe")
    assert not cfg["norm_topk_prob"] and cfg["shared_expert_gate"]
    run = weights.as_run(cfg)
    assert run["norm_topk_prob"] and not run["shared_expert_gate"]
