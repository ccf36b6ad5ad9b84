"""The control, at a size a test run holds, through the comparison that
decides ``correct``: the reference computed in float8 in the program's
place comes out not correct against the smoke-size cell's limits, where
the program itself comes out correct.

For each seed the program (bfloat16, as served) decodes two prompts
greedily; ``check.checks`` then reads the gaps of its tokens, and with
``control`` the gaps of the tokens the fp8 forward puts first at the same
positions, and ``check.verdict`` judges both against
``data/tiny-cell.json``.  The smoke-size dense model carries this test:
on the smoke-size MoE a bfloat16 router flip can move the program as far
as the control on one seed (the chip readings of both cells are in
PERF.md).
"""
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.core import check, driver, traffic, weights
from repro.arch import model as M

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PROMPT, OUT, PAD = 32, 96, 128


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def _greedy(arch, params, prompt, n, vocab):
    fwd = jax.jit(functools.partial(M.forward, cfg=arch))
    seq = np.zeros(PAD, np.int32)
    seq[: len(prompt)] = prompt
    out = []
    for i in range(n):  # causal: what lies past the read position is unseen
        logits, _ = fwd(params, {"tokens": jnp.asarray(seq)[None]})
        t = int(np.asarray(logits[0, len(prompt) + i - 1, :vocab]).argmax())
        out.append(t)
        if len(prompt) + i < PAD:
            seq[len(prompt) + i] = t
    return np.asarray(out, np.int32)


def _verdicts(seed):
    cfg, cell = _load("tiny-dense.json"), _load("tiny-cell.json")
    mix = {"output_tokens": OUT}
    arch = driver.program_arch(driver.import_program(), cfg)
    params = weights.make(cfg, seed)
    rng = np.random.default_rng(seed)
    outs, reqs = [], {}
    for rid in range(2):
        prompt = rng.integers(0, cfg["vocab_size"], PROMPT).astype(np.int32)
        toks = _greedy(arch, params, prompt, OUT, cfg["vocab_size"])
        reqs[rid] = traffic.Request(rid, "win", 0.0, prompt, None, False)
        outs.append(driver.Outcome(rid, "win", 0.0, PROMPT, False,
                                   end=0.0, tokens=toks))
    return [check.checks(cfg, params, cell, mix, outs, reqs, seed,
                         control=control, log=lambda s: None)
            for control in (False, True)]


@pytest.mark.parametrize("seed", range(4))
def test_control_fails_where_the_program_passes(seed):
    prog, ctrl = _verdicts(seed)
    assert check.verdict(prog), prog
    assert not check.verdict(ctrl), ctrl
    assert ctrl["logit_gap"]["value"] >= 3 * prog["logit_gap"]["value"]
