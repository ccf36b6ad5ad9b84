"""Model families (``bench/core/models.py``, ``bench/models/``).

The digests in ``data/digests.json`` were recorded on the CPU before the
family code moved out of the shared harness: the weights, the reference's
logits and the work counts must come out bitwise as they did then.
"""
import glob
import hashlib
import json
import os

import jax
import numpy as np
import pytest

from bench import reference as R
from bench.core import counts as C
from bench.core import models, weights

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BENCH = os.path.dirname(os.path.dirname(DATA))
# (admission step, prompt, output tokens, prefill chunk)
REQUESTS = [(0, 35, 4, 16), (3, 100, 8, 16), (7, 1, 5, 16), (9, 64, 1, 16)]


def _load(path):
    with open(path) as f:
        return json.load(f)


def _sha(a):
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()


def _work(cfg):
    w = C.StepWork(weights.dims(cfg))
    for r in REQUESTS:
        w.add_request(*r)
    return w


@pytest.fixture(scope="module")
def digests():
    return _load(os.path.join(DATA, "digests.json"))


@pytest.mark.parametrize("name", ["dense", "moe"])
def test_weights_and_logits_are_bitwise_as_recorded(name, digests):
    cfg = _load(os.path.join(DATA, f"tiny-{name}.json"))
    tokens = np.random.default_rng(0).integers(0, 256, 40).astype(np.int32)
    where = np.arange(40, dtype=np.int32)
    for seed in (3, 4):
        params = weights.make(cfg, seed)
        got = {jax.tree_util.keystr(k): _sha(v) for k, v in
               jax.tree_util.tree_leaves_with_path(params)}
        assert got == digests[f"{name}.weights.{seed}"]
        for prec in ("f32", "fp8"):
            assert _sha(R.logits(cfg, params, tokens, where, prec)) == \
                digests[f"{name}.logits.{seed}.{prec}"]


@pytest.mark.parametrize("name", ["dense", "moe"])
def test_tiny_work_counts_are_as_recorded(name, digests):
    w = _work(_load(os.path.join(DATA, f"tiny-{name}.json")))
    got = [repr(v) for v in w.totals(0, 200).values()] + [
        repr(v) for v in w.totals(5, 12).values()] + [
        repr(x) for x in w.attn_roofline_s(0, 200, 1e12, 1e9)]
    assert got == digests[f"{name}.totals"]


@pytest.mark.parametrize("name", ["qwen2-1.5b", "qwen2-moe-a2.7b"])
def test_benchmark_work_counts_are_as_recorded(name, digests):
    w = _work(_load(os.path.join(BENCH, "configs", name + ".json")))
    got = [repr(v) for v in w.totals(0, 200).values()] + [
        repr(x) for x in w.attn_roofline_s(0, 200, 197e12, 819e9)]
    assert got == digests[f"{name}.totals"]


def _config_files():
    paths = sorted(glob.glob(os.path.join(BENCH, "configs", "*.json"))
                   + glob.glob(os.path.join(DATA, "*.json")))
    # a configuration names the program's config it runs
    return [p for p in paths if "program_arch" in _load(p)]


@pytest.mark.parametrize("path", _config_files(), ids=os.path.basename)
def test_every_config_has_a_family_with_the_whole_interface(path):
    cfg = _load(path)
    fam = models.load(cfg)
    assert os.path.basename(fam.__file__) == cfg["model_type"] + ".py"
    for name in models.API:
        assert callable(getattr(fam, name)), name
    d = weights.dims(cfg)
    assert models.load(d) is fam
    assert fam.linear_flops_per_token(d) > 0
    assert fam.attn_flops(d, 1) > 0 and fam.attn_bytes(d, 1, 1) > 0


def test_an_unknown_family_names_the_file_to_add():
    with pytest.raises(SystemExit, match="bench/models/no_such_family.py"):
        models.load({"model_type": "no_such_family"})


TOY = '''
"""A family that is qwen2 with its logits doubled and no linear work."""
from bench.models import qwen2

dims = qwen2.dims
init = qwen2.init
program_settings = qwen2.program_settings
program_widths = qwen2.program_widths
attn_flops = qwen2.attn_flops
attn_bytes = qwen2.attn_bytes


def forward(cfg, fp8, params, tokens, where):
    return 2.0 * qwen2.forward(cfg, fp8, params, tokens, where)


def linear_flops_per_token(d):
    return 0
'''


def test_a_new_family_is_a_new_file(tmp_path, monkeypatch):
    cfg = _load(os.path.join(DATA, "tiny-dense.json"))
    tokens = np.arange(20, dtype=np.int32)
    where = np.arange(20, dtype=np.int32)
    base = R.logits(cfg, weights.make(cfg, 3), tokens, where)
    (tmp_path / "toy_family.py").write_text(TOY)
    monkeypatch.setattr(models, "DIR", str(tmp_path))
    toy = dict(cfg, model_type="toy_family")
    assert models.load(toy).__file__ == str(tmp_path / "toy_family.py")
    # weights, reference and counts all reach it through the loader
    got = R.logits(toy, weights.make(toy, 3), tokens, where)
    np.testing.assert_array_equal(got, 2 * base)
    d = weights.dims(toy)
    w = C.StepWork(d)
    w.add_request(0, 3, 1, 16)
    tot = w.totals(0, 1)
    assert tot["model_flops"] == tot["attn_flops"] + C.head_flops(d)


def test_a_family_without_the_whole_interface_is_refused(tmp_path,
                                                         monkeypatch):
    (tmp_path / "half.py").write_text("def dims(cfg):\n    return {}\n")
    monkeypatch.setattr(models, "DIR", str(tmp_path))
    with pytest.raises(SystemExit, match="half.py lacks"):
        models.load({"model_type": "half"})
