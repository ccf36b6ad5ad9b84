"""The trace reduction, on a small trace recorded on the chip.

``data/chat-rate.xplane.pb`` is two run() calls of the qwen2-1.5b
chat-rate cell traced on one TPU v5e (``--trace 1`` with the traced span
cut to two calls).  The checks recompute each number a second way, and
pin the readings so a change to the reduction shows.
"""
import os

import numpy as np
import pytest

from bench.core import readers
from bench.core.trace import (Trace, attribute, gaps, merge, op_base,
                              self_times, short_name, union_length)

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "chat-rate.xplane.pb")


@pytest.fixture(scope="module")
def tr():
    return Trace.load(PATH)


def test_planes_found(tr):
    assert tr.ops[0] and tr.modules[0]
    assert any("run_k" in n for n, _, _ in tr.modules[0])
    assert {s[0] for s in tr.spans} >= {"bench.run", "bench.collect"}
    bases = {op_base(n) for n, _, _ in tr.ops[0]}
    assert readers.ATTN_OP in bases and readers.GATE_OP in bases


def test_busy_and_gaps_partition_the_window(tr):
    win = tr.window()
    busy = tr.busy(0, win)
    idle = sum(b - a for a, b in tr.idle_gaps(0, win))
    assert 0 < busy <= win[1] - win[0]
    assert busy + idle == pytest.approx(win[1] - win[0], rel=1e-9)


def test_busy_by_a_second_route(tr):
    # sample the window on a 10 us grid: the share of points inside some
    # operation is the busy share
    win = tr.window()
    t = np.arange(win[0], win[1], 1e-5)
    inside = np.zeros(len(t), bool)
    for _, a, b in tr.ops[0]:
        inside[np.searchsorted(t, a):np.searchsorted(t, b)] = True
    assert inside.mean() == pytest.approx(
        tr.busy(0, win) / (win[1] - win[0]), abs=2e-3)


def test_self_times_add_up_to_busy(tr):
    win = tr.window()
    own = self_times(tr.ops[0], win)
    assert sum(own.values()) == pytest.approx(tr.busy(0, win), rel=1e-3)
    assert all(v >= 0 for v in own.values())


def test_readers_on_the_recorded_trace(tr):
    win = tr.window()
    ctx = dict(trace=tr, window=win, dev=0, busy=tr.busy(0, win),
               peaks=None, log=lambda s: None)
    idle = readers.idle_share(ctx)
    gate = readers.gate_share(ctx)
    gap = readers.host_gap_ms(ctx)
    assert 0 <= idle < 100 and 0 < gate < 100 and gap > 0
    assert (idle, gate, gap) == pytest.approx(EXPECTED, rel=1e-9)


def test_attribution_covers_every_gap(tr):
    win = tr.window()
    gs = tr.idle_gaps(0, win)
    att = attribute(gs, tr.spans)
    assert sum(att.values()) >= sum(b - a for a, b in gs) - 1e-12


def test_interval_arithmetic():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0)]
    assert merge(iv) == [(0.0, 2.0), (3.0, 4.0)]
    assert union_length(iv, (0.0, 10.0)) == 3.0
    assert union_length(iv, (1.5, 3.5)) == 1.0
    assert gaps(iv, (0.0, 5.0)) == [(2.0, 3.0), (4.0, 5.0)]
    ev = [("while.1", 0.0, 10.0), ("fusion.2", 1.0, 3.0),
          ("paged_attention.3", 4.0, 5.0)]
    assert self_times(ev, (0.0, 10.0)) == {
        "while.1": 7.0, "fusion.2": 2.0, "paged_attention.3": 1.0}


def test_names():
    n = "%copy.106 = bf16[28,4352,16,2,128]{4,3,2,1,0:T(2,128)} copy(x)"
    assert short_name(n) == "copy.106 = bf16[28,4352,16,2,128]"
    assert op_base(short_name(n)) == "copy"
    assert op_base("fused_eb_pallas.8 = s32[1,128]") == "fused_eb_pallas"


# idle_share (%), gate_share (%), host_gap_ms of the recorded trace
EXPECTED = (9.010905510795485, 0.003058300645634745, 27.448309999999974)
