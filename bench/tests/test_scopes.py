"""The program's trace marks, read from traces recorded on the chip.

``data/chat-rate.xplane.pb`` is two run() calls of the qwen2-1.5b
chat-rate cell on one TPU v5e, by a program with no marks.
``data/chat-rate-scoped.xplane.pb`` is two calls of the same cell by a
program with its named scopes, ``serve.*`` spans and step stamps
(``python3 bench/trace_probe.py record``).  The checks read each trace
a second way, and pin the readings so a change to the reduction shows.
"""
import json
import os
import time

import pytest

from bench.core import harness, readers, scopes
from bench.core.trace import Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRACES = {"unmarked": os.path.join(DATA, "chat-rate.xplane.pb"),
          "scoped": os.path.join(DATA, "chat-rate-scoped.xplane.pb")}
READERS = {
    "kv_share": lambda c: scopes.scope_share(c, ("kv",), "kv_share"),
    "mlp_share": lambda c: scopes.scope_share(c, ("mlp",), "mlp_share"),
    "moe_share": lambda c: scopes.scope_share(c, ("moe",), "moe_share"),
    "head_share": lambda c: scopes.scope_share(c, ("lm_head", "sample"),
                                               "head_share"),
    "wave_gap_ms": scopes.wave_gap_ms,
}


@pytest.fixture(scope="module", params=sorted(TRACES))
def name(request):
    return request.param


@pytest.fixture(scope="module")
def traces():
    return {k: Trace.load(p) for k, p in TRACES.items()}


def _ctx(tr, path, log=None):
    win = tr.window()
    return dict(trace=tr, window=win, dev=0, busy=tr.busy(0, win),
                peaks=None, xplane=path,
                log=log if log is not None else (lambda s: None))


def test_wire_reader_matches_profile_data(name, traces):
    tr = traces[name]
    mine = scopes.read_device_ops(TRACES[name])
    assert set(mine) == set(tr.ops) and mine[0]
    for dev, ops in mine.items():
        assert [(n, a, b) for n, a, b, _ in ops] == tr.ops[dev]
    # a window keeps exactly the operations that overlap it
    lo, hi = tr.window()
    cut = scopes.read_device_ops(TRACES[name], (lo, hi))[0]
    assert cut == [o for o in mine[0] if o[2] > lo and o[1] < hi]


def test_scope_times_sum_to_busy(name, traces):
    tr = traces[name]
    win = tr.window()
    t = scopes.scope_times(scopes.read_device_ops(TRACES[name])[0], win)
    assert sum(t.values()) == pytest.approx(tr.busy(0, win), rel=1e-3)
    assert set(t) <= set(scopes.SCOPES) | {scopes.UNSCOPED}


def test_unmarked_trace_reads_nothing(traces):
    m = scopes.Marks.load(TRACES["unmarked"])
    tr = traces["unmarked"]
    assert not m.scoped(0, tr.window()) and not m.spans
    ctx = _ctx(tr, TRACES["unmarked"])
    assert all(r(ctx) is None for r in READERS.values())


def test_scoped_trace_holds_every_mark(traces):
    m = scopes.Marks.load(TRACES["scoped"])
    found = {scopes.scope_of(o[3]) for o in m.ops[0]}
    # greedy ``sample`` (the argmax) is fused into the head's matmul on
    # the TPU, and that fusion carries the ``lm_head`` name
    assert found == {"gate", "kv", "attention", "mlp", "lm_head",
                     scopes.UNSCOPED}
    assert {s[0] for s in m.spans} == {
        "serve.admit", "serve.build", "serve.upload", "serve.launch",
        "serve.sync", "serve.drain"}
    # each traced call holds the six, nested in its bench.run span
    runs = [s for s in traces["scoped"].spans if s[0] == "bench.run"]
    for _, lo, hi in runs:
        assert {n for n, a, b in m.spans if lo <= a and b <= hi} == {
            s[0] for s in m.spans}


def test_wave_gap_within_host_gap(traces):
    ctx = _ctx(traces["scoped"], TRACES["scoped"])
    assert 0 < scopes.wave_gap_ms(ctx) <= readers.host_gap_ms(ctx)


def test_innermost_attribution_covers_the_gaps(traces):
    tr = traces["scoped"]
    win = tr.window()
    spans = tr.spans + scopes.Marks.load(TRACES["scoped"]).spans
    gs = tr.idle_gaps(0, win)
    att = scopes.attribute_innermost(gs, spans)
    assert sum(att.values()) == pytest.approx(sum(b - a for a, b in gs),
                                              rel=1e-9)
    # bench.run holds the serve.* spans: little of its idle time is left
    # to it once they take theirs
    serve = sum(v for k, v in att.items() if k.startswith("serve."))
    assert serve > att.get("bench.run", 0.0)


def test_readings_pinned(traces):
    lines = []
    ctx = _ctx(traces["scoped"], TRACES["scoped"], log=lines.append)
    got = {k: r(ctx) for k, r in READERS.items()}
    assert got.pop("moe_share") is None  # a dense model
    assert got == pytest.approx(EXPECTED, rel=1e-9)
    assert all(0 < v < 100 for v in got.values())
    # the split, unscoped included, and the host spans are logged
    assert any("unscoped" in s for s in lines)
    assert any("serve.build" in s for s in lines)


def test_marks_of_another_trace_are_refused(traces):
    lines = []
    ctx = _ctx(traces["unmarked"], TRACES["scoped"], log=lines.append)
    assert scopes.for_run(ctx) is None and "not read" in lines[0]


def test_nesting_rules():
    items = [("outer", 0.0, 10.0), ("inner", 2.0, 4.0), ("late", 3.0, 6.0)]
    assert scopes._innermost(items, (0.0, 10.0)) == {
        "outer": 6.0, "inner": 1.0, "late": 3.0}
    assert scopes._innermost(items, (3.5, 5.0)) == {"late": 1.5}
    spans = [("bench.run", 0.0, 10.0), ("serve.build", 1.0, 3.0)]
    assert scopes.attribute_innermost([(0.5, 2.0), (9.0, 11.0)], spans) == {
        "bench.run": 1.5, "serve.build": 1.0, "unattributed": 1.0}
    assert scopes.scope_of("jit(run_k)/while/body/kv/scatter") == "kv"
    assert scopes.scope_of("jit(run_k)/mlp/jit(f)/attention/dot") == (
        "attention")
    assert scopes.scope_of("jit(run_k)/while/body/copy") == "unscoped"
    assert scopes.scope_of("") == "unscoped"


def test_traced_cpu_run_reads_no_marks():
    """A ``--trace 1`` run on the CPU at the smoke size: the host spans
    are there but no device operation, so no new reader reads, and none
    raises."""
    with open(os.path.join(DATA, "tiny-dense.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(DATA, "tiny-cell.json")) as f:
        cell = json.load(f)
    mix = {"arrivals": "open", "rate_rps": 20.0, "output_tokens": 8,
           "prompt": {"dist": "lognormal", "median": 24, "sigma": 1.0,
                      "min": 4, "max": 64}, "pre_window_s": 0.5}
    res = harness.run_cell("qwen2-1.5b.chat-rate", 2**31 + 7, 1.5, True,
                           time.perf_counter(), require_chip=False,
                           overrides=dict(config=cfg, cell=cell, mix=mix),
                           log=lambda s: None)
    assert res["correct"] and res["metrics"] == {}


# kv_share, mlp_share, head_share (%), wave_gap_ms of the scoped trace
EXPECTED = dict(kv_share=20.42207618063053, mlp_share=18.906395402321962,
                head_share=1.763184799110502, wave_gap_ms=15.243108500000185)
