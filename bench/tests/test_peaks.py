import pytest

from bench.core import harness, readers


def test_known_device():
    p = harness.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_unknown_device_is_refused():
    with pytest.raises(SystemExit):
        harness.peaks_for("TPU v9 imaginary")


def test_every_per_layer_metric_has_a_reader():
    spec = harness.load_json(f"{harness.ROOT}/BENCHMARK.json")
    for m in spec["per_layer"]:
        assert callable(harness.load_reader(m["name"]))


class _Trace:
    def __init__(self, ops):
        self.ops = {0: ops}
        self.modules = {0: []}

    def op_time(self, dev, win, base):
        from bench.core.trace import op_base, union_length
        return union_length([(a, b) for n, a, b in self.ops[dev]
                             if op_base(n) == base], win)


def test_share_over_100_is_refused():
    # an attention kernel that ran 1 ms for work whose roofline is 2 ms:
    # the work is counted too high, and the reader says so
    class W:
        def attn_roofline_s(self, lo, hi, pf, pb):
            return 2e-3, 1
    ctx = dict(trace=_Trace([(readers.ATTN_OP + ".3", 0.0, 1e-3)]),
               window=(0.0, 1.0), dev=0, busy=1e-3,
               peaks={"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0},
               work=W(), steps=(0, 1), log=lambda s: None, chips=1)
    with pytest.raises(ValueError):
        readers.attn_roofline(ctx)


def test_nothing_to_read_reads_nothing():
    ctx = dict(trace=_Trace([]), window=(0.0, 1.0), dev=0, busy=0.0,
               peaks=None, work=None, steps=(0, 0), log=print, chips=1)
    assert readers.gate_share(ctx) is None
    assert readers.attn_roofline(ctx) is None
    assert readers.idle_share(ctx) is None
    assert readers.mfu(ctx) is None
