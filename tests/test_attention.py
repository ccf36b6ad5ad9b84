"""Attention-level invariants the serve path leans on: int8 KV
round-trip error bounds, blocked-mask correctness at page-boundary
positions, and the paged gather/scatter primitives."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.nn import attention as A
from repro.nn import attn_backend as AB

PAGE = 8


# ------------------------------------------------------------- int8 KV
@pytest.mark.parametrize("shape", [(2, 4, 3, 16), (1, 1, 1, 64), (5, 8)])
def test_quantize_kv_int8_round_trip_bound(shape):
    """Dequantized values are within half a quantization step of the
    original: |x - q*scale| <= scale/2, with scale = max|x|/127 per
    vector (the paper's action-bits quantization, serving-side)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 3.0, shape).astype(np.float32),
                    jnp.bfloat16)
    q, scale = A.quantize_kv_int8(x)
    assert q.dtype == jnp.int8 and scale.shape == (*shape[:-1], 1)
    xf = np.asarray(x, np.float32)
    err = np.abs(xf - np.asarray(q, np.float32) * np.asarray(scale))
    bound = np.asarray(scale) / 2 + 1e-6
    assert (err <= bound).all(), float((err - bound).max())
    # the per-vector max is representable exactly up to rounding
    assert (np.abs(np.asarray(q)).max(axis=-1) >= 126).all()


def test_quantize_kv_int8_zero_vector_safe():
    q, scale = A.quantize_kv_int8(jnp.zeros((3, 8), jnp.bfloat16))
    assert (np.asarray(q) == 0).all()
    assert np.isfinite(np.asarray(scale)).all()
    assert (np.asarray(scale) > 0).all()  # clamped, never divides by 0


def test_int8_page_scatter_gather_round_trip_bound():
    """The int8 page pool's write->gather->dequant path preserves every
    written cell within the quantize_kv_int8 bound (<= scale/2): the
    page scatter and the block-table gather never corrupt values, so
    the paged int8 cache inherits the dense cache's error bound."""
    rng = np.random.default_rng(1)
    B, C, KV, hd, n_ps = 2, 6, 2, 16, 3
    N = B * n_ps
    x = jnp.asarray(rng.normal(0, 2.0, (B, C, KV, hd)).astype(np.float32),
                    jnp.bfloat16)
    kq, ks = A.quantize_kv_int8(x)
    pool = jnp.zeros((N, PAGE, KV, hd), jnp.int8)
    spool = jnp.zeros((N, PAGE, KV, 1), jnp.float32)
    tbl = jnp.asarray(np.arange(N).reshape(B, n_ps)[:, ::-1].copy())
    pos0 = PAGE - 2  # chunk straddles a page boundary
    positions = pos0 + jnp.arange(C)[None]
    page_ids = jnp.take_along_axis(
        tbl, jnp.clip(positions // PAGE, 0, n_ps - 1).repeat(B, 0), axis=1)
    page_off = (positions % PAGE).repeat(B, 0)
    pool = pool.at[page_ids, page_off].set(kq, mode="drop")
    spool = spool.at[page_ids, page_off].set(ks, mode="drop")
    view = pool[tbl].reshape(B, n_ps * PAGE, KV, hd).astype(np.float32)
    sview = spool[tbl].reshape(B, n_ps * PAGE, KV, 1)
    dq = np.asarray(view) * np.asarray(sview)
    xf = np.asarray(x, np.float32)
    bound = np.asarray(ks) / 2 + 1e-6
    for j in range(C):
        cell = dq[:, pos0 + j]
        err = np.abs(cell - xf[:, j])
        assert (err <= bound[:, j]).all(), (j, float(err.max()))


def test_paged_attention_int8_close_to_fp():
    """One paged attention call, fp pool vs int8 pool from the same
    empty state: outputs agree within the int8 cache tolerance (the
    only divergence is the <= scale/2 dequant error on just-written
    K/V)."""
    rng = np.random.default_rng(11)
    B, H, hd, n_ps = 2, 2, 16, 2
    D = H * hd
    N = B * n_ps
    p = A.init_attention(jax.random.PRNGKey(2), D, H, H, hd)
    tbl = jnp.asarray(np.arange(N).reshape(B, n_ps))
    x = jnp.asarray(rng.normal(0, 1, (B, PAGE, D)), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(PAGE)[None], (B, PAGE))
    page_ids = jnp.take_along_axis(tbl, positions // PAGE, axis=1)
    page_off = positions % PAGE

    def run(kv):
        return A.paged_decode_attention_block(
            p, x, kv.with_view(tbl, positions, page_ids, page_off),
            n_heads=H, n_kv_heads=H, head_dim=hd, rope_theta=0.0,
            window=jnp.int32(0), qk_norm=False, norm_eps=1e-6)

    out_fp, _ = run(AB.PagedKV(
        k=jnp.zeros((N, PAGE, H * hd), jnp.float32),
        v=jnp.zeros((N, PAGE, H * hd), jnp.float32)))
    out_i8, kv8 = run(AB.PagedKV(
        k=jnp.zeros((N, PAGE, H * hd), jnp.int8),
        v=jnp.zeros((N, PAGE, H * hd), jnp.int8),
        k_scale=jnp.zeros((N, PAGE, H), jnp.float32),
        v_scale=jnp.zeros((N, PAGE, H), jnp.float32)))
    assert kv8.k.dtype == jnp.int8 and kv8.quantized
    scale = float(jnp.max(jnp.abs(out_fp)))
    assert float(jnp.max(jnp.abs(out_fp - out_i8))) < 0.05 * scale


def _naive_attention(q, k, v, q_pos, k_pos, window, causal):
    """Reference softmax attention with an explicit position mask."""
    hd = q.shape[-1]
    s = np.einsum("bqhd,bshd->bhqs", np.asarray(q, np.float32),
                  np.asarray(k, np.float32)) / np.sqrt(hd)
    qp, kp = np.asarray(q_pos), np.asarray(k_pos)
    diff = qp[:, :, None] - kp[:, None, :]
    ok = np.ones_like(diff, bool)
    if causal:
        ok &= diff >= 0
    if window > 0:
        ok &= diff < window
    s = np.where(ok[:, None], s, -1e30)
    p = jax.nn.softmax(jnp.asarray(s), axis=-1)
    return np.einsum("bhqs,bshd->bqhd", np.asarray(p, np.float32),
                     np.asarray(v, np.float32))


@pytest.mark.parametrize("q0", [PAGE - 2, PAGE - 1, PAGE, PAGE + 1,
                                3 * PAGE - 1, 3 * PAGE])
@pytest.mark.parametrize("window", [0, PAGE, PAGE + 3])
def test_attend_blocked_masks_at_page_boundaries(q0, window):
    """Causal + sliding-window masks are exact when query positions
    straddle page-boundary multiples — the positions the paged gather
    path hands to ``_mask_block``.  A window equal to the page size is
    the adversarial case: the valid span exactly covers one page."""
    rng = np.random.default_rng(q0 * 31 + window)
    B, Sq, Sk, H, hd = 1, 3, 4 * PAGE, 2, 8
    q = jnp.asarray(rng.normal(0, 1, (B, Sq, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (B, Sk, H, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (B, Sk, H, hd)), jnp.float32)
    q_pos = jnp.asarray(np.arange(q0, q0 + Sq)[None])
    k_pos = jnp.asarray(np.arange(Sk)[None])
    got = A.attend_blocked(q, k, v, q_pos, k_pos, jnp.int32(window),
                           causal=True, q_block=2)
    want = _naive_attention(q, k, v, q_pos, k_pos, window,
                            causal=True).reshape(B, Sq, H * hd)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


@pytest.mark.parametrize("window", [0, PAGE])
def test_paged_attention_masks_at_page_boundaries(window):
    """The paged variant agrees with the naive reference when a chunk
    straddles a page boundary, and never reads cells beyond the chunk's
    own positions (stale page contents are masked out)."""
    rng = np.random.default_rng(7)
    B, H, hd, n_ps = 2, 2, 8, 3
    D = H * hd
    N_pages = B * n_ps
    p = A.init_attention(jax.random.PRNGKey(0), D, H, H, hd)
    # stale garbage everywhere, lane-dense [N_pages, PAGE, H * hd]
    k_pages = jnp.asarray(rng.normal(0, 1, (N_pages, PAGE, H, hd)),
                          jnp.float32).reshape(N_pages, PAGE, H * hd)
    v_pages = jnp.asarray(rng.normal(0, 1, (N_pages, PAGE, H, hd)),
                          jnp.float32).reshape(N_pages, PAGE, H * hd)
    tbl = jnp.asarray(np.arange(N_pages).reshape(B, n_ps)[:, ::-1]
                      .copy())  # non-contiguous logical->physical map
    x_all = jnp.asarray(rng.normal(0, 1, (B, 2 * PAGE, D)), jnp.float32)

    def step(kv, x, pos, width):
        positions = pos[:, None] + jnp.arange(width)[None]
        lp = positions // PAGE
        page_ids = jnp.take_along_axis(tbl, jnp.clip(lp, 0, n_ps - 1),
                                       axis=1)
        return A.paged_decode_attention_block(
            p, x, kv.with_view(tbl, positions, page_ids,
                               positions % PAGE),
            n_heads=H, n_kv_heads=H, head_dim=hd,
            rope_theta=0.0, window=jnp.int32(window), qk_norm=False,
            norm_eps=1e-6)

    # token-by-token over 2 pages
    kv1 = AB.PagedKV(k=k_pages, v=v_pages)
    outs = []
    for i in range(2 * PAGE):
        o, kv1 = step(kv1, x_all[:, i: i + 1],
                      jnp.full((B,), i, jnp.int32), 1)
        outs.append(np.asarray(o))
    # chunks of 6 (straddles the boundary at PAGE=8: chunk [6..11])
    kv2 = AB.PagedKV(k=k_pages, v=v_pages)
    outs2 = []
    for i in range(0, 2 * PAGE, 6):
        w = min(6, 2 * PAGE - i)
        o, kv2 = step(kv2, x_all[:, i: i + w],
                      jnp.full((B,), i, jnp.int32), w)
        outs2.append(np.asarray(o))
    got1 = np.concatenate(outs, axis=1)
    got2 = np.concatenate(outs2, axis=1)
    np.testing.assert_allclose(got1, got2, atol=2e-5)
    # written cells land in the mapped physical pages, bitwise
    np.testing.assert_array_equal(
        np.asarray(kv1.k), np.asarray(kv2.k))


def test_paged_decode_attention_legacy_call_shape_removed():
    """The pre-PagedKV positional call shape was shimmed for exactly one
    release (PR 8); it is now a hard TypeError, for loose page pools and
    for stray positionals after a PagedKV alike."""
    rng = np.random.default_rng(23)
    B, H, hd, n_ps = 2, 2, 8, 2
    D = H * hd
    N = B * n_ps
    p = A.init_attention(jax.random.PRNGKey(5), D, H, H, hd)
    tbl = jnp.asarray(np.arange(N).reshape(B, n_ps))
    x = jnp.asarray(rng.normal(0, 1, (B, 3, D)), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(3)[None], (B, 3))
    page_ids = jnp.take_along_axis(tbl, positions // PAGE, axis=1)
    page_off = positions % PAGE
    kp = jnp.zeros((N, PAGE, H * hd), jnp.float32)
    kv0 = AB.PagedKV(k=kp, v=kp)
    kwargs = dict(n_heads=H, n_kv_heads=H, head_dim=hd, rope_theta=0.0,
                  window=jnp.int32(0), qk_norm=False, norm_eps=1e-6)
    with pytest.raises(TypeError):
        A.paged_decode_attention_block(
            p, x, kp, kp, tbl, positions, page_ids, page_off, **kwargs)
    # a bare page pool in the kv slot gets the explanatory error
    with pytest.raises(TypeError, match="PagedKV"):
        A.paged_decode_attention_block(p, x, kp, **kwargs)
    # stray positionals after a PagedKV are also rejected (keyword-only)
    with pytest.raises(TypeError):
        A.paged_decode_attention_block(p, x, kv0, tbl, **kwargs)
    # the legacy tuple pool to paged_decode_step is equally gone
    from repro.arch import model as M
    from repro.arch.config import ArchConfig
    cfg = ArchConfig(name="t", family="dense", n_layers=1, d_model=D,
                     n_heads=H, n_kv_heads=H, d_ff=2 * D, vocab_size=32)
    with pytest.raises(TypeError, match="PagedKV"):
        M.paged_decode_step({}, (kp, kp), tbl,
                            jnp.zeros((B,), jnp.int32),
                            jnp.zeros((B, 1), jnp.int32),
                            jnp.ones((B,), jnp.int32), cfg)


@pytest.mark.parametrize("window", [0, PAGE])
def test_dense_and_paged_share_mask_at_page_boundaries(window):
    """Regression for the shared ``position_mask`` helper: the dense
    ring-cache decode and the paged pool decode must stay bitwise
    identical at every position up to the cache size — including the
    exact page boundaries PAGE-1 / PAGE / 2*PAGE-1, where an
    off-by-one in either path's mask (e.g. attending a stale zeroed
    cell whose absolute position is negative) changes the softmax."""
    rng = np.random.default_rng(31)
    B, H, hd, n_ps = 2, 2, 8, 2
    D = H * hd
    S_max = n_ps * PAGE  # dense cache length == paged gathered length
    N = B * n_ps
    p = A.init_attention(jax.random.PRNGKey(9), D, H, H, hd)
    tbl = jnp.asarray(np.arange(N).reshape(B, n_ps))
    x_all = jnp.asarray(rng.normal(0, 1, (B, S_max, D)), jnp.float32)
    ck = jnp.zeros((B, S_max, H, hd), jnp.float32)
    cv = jnp.zeros((B, S_max, H, hd), jnp.float32)
    kv = AB.PagedKV(k=jnp.zeros((N, PAGE, H * hd), jnp.float32),
                    v=jnp.zeros((N, PAGE, H * hd), jnp.float32))
    kwargs = dict(n_heads=H, n_kv_heads=H, head_dim=hd, rope_theta=1e4,
                  window=jnp.int32(window), qk_norm=False, norm_eps=1e-6)
    dense = jax.jit(lambda *a: A.decode_attention_block(*a, **kwargs))
    paged = jax.jit(lambda *a: A.paged_decode_attention_block(*a, **kwargs))
    for pos in range(S_max):
        x = x_all[:, pos: pos + 1]
        out_d, ck, cv, _ = dense(p, x, ck, cv, jnp.int32(pos))
        positions = jnp.full((B, 1), pos, jnp.int32)
        page_ids = jnp.take_along_axis(tbl, positions // PAGE, axis=1)
        out_p, kv = paged(
            p, x, kv.with_view(tbl, positions, page_ids, positions % PAGE))
        np.testing.assert_array_equal(np.asarray(out_d), np.asarray(out_p),
                                      err_msg=f"pos={pos}")