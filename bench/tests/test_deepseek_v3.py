"""The ``deepseek_v3`` family (``bench/models/deepseek_v3.py``) at the
smoke size on the CPU: its reference against the program's
moonlight-16b-a3b smoke preset (``data/tiny-mla.json``: latent attention,
a leading dense layer, sigmoid routing with a correction bias, rank 0 of
4-way expert parallelism), its work counts by hand, and a whole run
through the harness.
"""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as R
from bench.core import counts as C
from bench.core import driver, harness, models, weights
from repro.arch import model as M

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BENCH = os.path.dirname(os.path.dirname(DATA))


def _load(path):
    with open(path) as f:
        return json.load(f)


def _tiny():
    return _load(os.path.join(DATA, "tiny-mla.json"))


def _program_logits(cfg, params, tokens, compute):
    arch = driver.program_arch(driver.import_program(), cfg)
    old = M.COMPUTE_DTYPE
    M.COMPUTE_DTYPE = compute
    try:
        with jax.default_matmul_precision("highest"):
            logits, _ = M.forward(params,
                                  {"tokens": jnp.asarray(tokens)[None]}, arch)
    finally:
        M.COMPUTE_DTYPE = old
    return np.asarray(logits[0, :, : cfg["vocab_size"]], np.float32)


def test_program_runs_the_share_the_file_states():
    cfg = _tiny()
    arch = driver.program_arch(driver.import_program(), cfg)
    assert (arch.expert_ranks, arch.expert_rank, arch.n_experts_local) == (
        4, 0, 4)
    moe = weights.make(cfg, 3)["layers"]["moe"]
    assert moe["w_gate"].shape[1] == 4 and moe["router"].shape[-1] == 16


def test_reference_matches_program_in_float32():
    cfg = _tiny()
    params = weights.make(cfg, 3)
    tokens = np.random.default_rng(0).integers(0, 256, 40).astype(np.int32)
    want = _program_logits(cfg, params, tokens, jnp.float32)
    got = R.logits(cfg, params, tokens, np.arange(40, dtype=np.int32))
    # float32 throughout: only summation order differs
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


def test_reference_matches_program_as_served():
    cfg = _tiny()
    params = weights.make(cfg, 4)
    tokens = np.random.default_rng(1).integers(0, 256, 40).astype(np.int32)
    want = _program_logits(cfg, params, tokens, jnp.bfloat16)
    got = R.logits(cfg, params, tokens, np.arange(40, dtype=np.int32))
    # the typical position agrees to bfloat16 rounding (a few roundings
    # a layer, three layers); a near-tie of the sigmoid router's top-4
    # may flip at a single position, so the median is held
    assert np.median(np.abs(got - want).max(-1)) <= 2**-5 * np.abs(
        want).max()


def test_control_is_lower_precision():
    cfg = _tiny()
    params = weights.make(cfg, 5)
    tokens = np.random.default_rng(2).integers(0, 256, 64).astype(np.int32)
    where = np.arange(64, dtype=np.int32)
    ref = R.logits(cfg, params, tokens, where)
    f8 = R.logits(cfg, params, tokens, where, "fp8")
    bf = _program_logits(cfg, params, tokens, jnp.bfloat16)
    f8_gap = np.median(np.abs(f8 - ref).max(-1))
    bf_gap = np.median(np.abs(bf - ref).max(-1))
    assert f8_gap > 4 * bf_gap


def test_work_counts_by_hand():
    d = weights.dims(_tiny())
    fam = models.load(d)
    D, H, r, dn, dr, dv = 32, 2, 32, 16, 8, 16
    mla = 2 * (D * H * (dn + dr) + D * (r + dr) + r * H * (dn + dv)
               + H * dv * D)
    dense = mla + 6 * D * 64
    # router over 16, 4 chosen of which this rank holds a quarter, the
    # shared experts' 2 x 16 width
    moe = mla + 2 * D * 16 + 4 * 4 / 16 * 6 * D * 16 + 6 * D * 32
    assert fam.linear_flops_per_token(d) == dense + 2 * moe
    assert fam.attn_flops(d, 10) == 3 * 2 * H * (dn + dr + dv) * 10
    # 5 rows of the latent and the rotary key, 2 queries and outputs at
    # the published head widths, 2 B each, 3 layers
    assert fam.attn_bytes(d, 5, 2) == 3 * (5 * (r + dr) * 2
                                           + 2 * H * (dn + dr + dv) * 2)


def test_published_counts_of_the_benchmark_configuration():
    d = weights.dims(_load(os.path.join(BENCH, "configs",
                                        "moonlight-16b-a3b.json")))
    fam = models.load(d)
    # 1,152 B of pool a token and layer; 1.5 routed experts a token here
    assert fam.attn_bytes(d, 1, 0) == d["L"] * 1152
    assert d["El"] == 16 and d["k"] * d["El"] / d["E"] == 1.5
    w = C.StepWork(d)
    w.add_request(0, 100, 4, 16)
    assert w.totals(0, 20)["model_flops"] > 0


def test_sound_run_is_correct():
    cfg = _tiny()
    cell = _load(os.path.join(DATA, "tiny-cell.json"))
    cell = dict(cell, correct={"sample": 3,
                               "limits": {"token_mismatch": 0.12}})
    mix = {"arrivals": "closed", "outstanding": 8, "output_tokens": 8,
           "prompt": {"dist": "lognormal", "median": 24, "sigma": 1.0,
                      "min": 4, "max": 64}, "pre_window_s": 0.5}
    res = harness.run_cell("qwen2-1.5b.chat-rate", 2**31 + 13, 1.5, False,
                           time.perf_counter(), require_chip=False,
                           overrides=dict(config=cfg, cell=cell, mix=mix),
                           log=lambda s: None)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
