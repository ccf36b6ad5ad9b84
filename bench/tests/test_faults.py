"""A whole run on the CPU at the smoke size, with the look for a chip
skipped: sound, it comes out correct; with the timed path broken
underneath, ``correct`` comes out false.

The faults a serve cell can have: a token altered where it is produced,
a step that returns its state (the KV cache) unchanged, and the gate's
verdict altered.  (The training faults, and the exchange between chips,
do not arise on a one-chip serve path.)
"""
import json
import os
import time

import jax.numpy as jnp
import pytest

from bench.core import harness
from repro.arch import model as M
from repro.core import pipeline

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


MIX_OPEN = {"arrivals": "open", "rate_rps": 20.0, "output_tokens": 8,
            "prompt": {"dist": "lognormal", "median": 24, "sigma": 1.0,
                       "min": 4, "max": 64}, "pre_window_s": 0.5}
MIX_CLOSED = dict(MIX_OPEN, arrivals="closed", outstanding=8)
# the smoke-size cell, whose limit test_control.py holds the control to
with open(os.path.join(DATA, "tiny-cell.json")) as f:
    CELL = json.load(f)


def _run(name, mix, workload="qwen2-1.5b.chat-rate"):
    with open(os.path.join(DATA, f"tiny-{name}.json")) as f:
        cfg = json.load(f)
    return harness.run_cell(workload, 2**31 + 11, 1.5, False,
                            time.perf_counter(), require_chip=False,
                            overrides=dict(config=cfg, cell=CELL, mix=mix),
                            log=lambda s: None)


@pytest.mark.parametrize("name,mix", [("dense", MIX_OPEN),
                                      ("moe", MIX_CLOSED)])
def test_sound_run_is_correct(name, mix):
    res = _run(name, mix)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"


def test_altered_token_is_caught(monkeypatch):
    real = M.paged_decode_step

    def altered(*a, **k):
        out, kv = real(*a, **k)
        if k.get("sample_greedy"):
            out = (out + 1) % 256
        return out, kv
    monkeypatch.setattr(M, "paged_decode_step", altered)
    res = _run("dense", MIX_OPEN)
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > 0.5


def test_unchanged_state_is_caught(monkeypatch):
    real = M.paged_decode_step

    def stale(params, kv, *a, **k):
        out, _ = real(params, kv, *a, **k)
        return out, kv  # the cache is never written
    monkeypatch.setattr(M, "paged_decode_step", stale)
    res = _run("dense", MIX_OPEN)
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > 0.5


def test_altered_gate_verdict_is_caught(monkeypatch):
    real = pipeline.MappedModel.jax_predict

    def flipped(self, backend="jnp"):
        fn = real(self, backend)
        return lambda x: jnp.asarray(1 - fn(x), jnp.int32)
    monkeypatch.setattr(pipeline.MappedModel, "jax_predict", flipped)
    res = _run("dense", MIX_OPEN)
    assert not res["correct"]
    assert res["checks"]["gate_mismatches"]["value"] > 0
