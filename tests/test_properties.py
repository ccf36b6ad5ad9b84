"""Hypothesis property tests on the system's table invariants."""
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.tables import (pack_codes, range_to_ternary)
from repro.core import encode_based as EB
from repro.ml.tree import DecisionTreeClassifier


@settings(max_examples=200, deadline=None)
@given(st.integers(0, 255), st.integers(0, 255), st.integers(1, 8))
def test_range_to_ternary_exact_cover(a, b, bits):
    """Prefix cover == the range, nothing more, nothing less, disjoint."""
    lo, hi = min(a, b), max(a, b)
    lo &= (1 << bits) - 1
    hi &= (1 << bits) - 1
    lo, hi = min(lo, hi), max(lo, hi)
    entries = range_to_ternary(lo, hi, bits)
    covered = np.zeros(1 << bits, int)
    for v, m in entries:
        for x in range(1 << bits):
            if (x & m) == v:
                covered[x] += 1
    inside = np.arange(1 << bits)
    expect = ((inside >= lo) & (inside <= hi)).astype(int)
    np.testing.assert_array_equal(covered, expect)  # exact & disjoint


@settings(max_examples=50, deadline=None)
@given(st.lists(st.integers(1, 16), min_size=1, max_size=8),
       st.integers(0, 2**31 - 1))
def test_pack_codes_fields_recoverable(widths, seed):
    rng = np.random.default_rng(seed)
    codes = np.stack([rng.integers(0, 2**w, 16) for w in widths], axis=1)
    packed = pack_codes(codes, widths)
    from repro.core.tables import key_layout
    for f, (word, off, w) in enumerate(key_layout(widths)):
        field = (packed[:, word] >> off) & ((1 << w) - 1)
        np.testing.assert_array_equal(field, codes[:, f].astype(np.uint32))


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000))
def test_eb_tree_mapping_is_exact(seed):
    """EB-mapped DT == native DT on every input (paper's parity claim)."""
    rng = np.random.default_rng(seed)
    X = rng.integers(0, 256, (300, 3))
    y = ((X[:, 0] > 97) & (X[:, 1] < 200)).astype(np.int64)
    dt = DecisionTreeClassifier(max_depth=4).fit(X, y)
    mapped = EB.map_dt_eb(dt, 3, 8)
    Xt = rng.integers(0, 256, (200, 3))
    np.testing.assert_array_equal(mapped.predict(Xt), dt.predict(Xt))


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 6))
def test_bucketize_codes_monotone(seed, T):
    """Feature codes are monotone in the raw value (order preservation)."""
    rng = np.random.default_rng(seed)
    from repro.core.tables import FeatureTable
    thr = np.unique(rng.integers(1, 255, T))
    ft = FeatureTable(thr.astype(np.int64), 8)
    vals = np.arange(256)
    codes = ft.encode(vals)
    assert (np.diff(codes) >= 0).all()
    assert codes[0] == 0 and codes[-1] == len(thr)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000))
def test_lb_quantization_error_bounded(seed):
    """LB sums live within the action_bits budget (no overflow by design)."""
    rng = np.random.default_rng(seed)
    from repro.core.lookup_based import _quantize_tables
    raw = rng.normal(0, 10, (5, 64, 4))
    for bits in (8, 16):
        luts, scale = _quantize_tables(raw, bits)
        worst = np.abs(luts).max(axis=(1, 2)).sum()
        assert worst <= 2 ** (bits - 1) + 5 * 0.5  # rounding slack
