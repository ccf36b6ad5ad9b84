"""Per-kernel validation: Pallas (interpret) vs ref.py oracle, shape sweeps."""
import numpy as np
import pytest

from repro.core.tables import pack_bits_uint32
from repro.kernels import ops


RNG = np.random.default_rng(42)


@pytest.mark.parametrize("B", [1, 7, 256, 1000])
@pytest.mark.parametrize("F,T", [(1, 1), (5, 9), (8, 32)])
def test_bucketize_sweep(B, F, T):
    vals = RNG.integers(0, 2**16, (B, F)).astype(np.int32)
    thr = np.sort(RNG.integers(0, 2**16, (F, T)), axis=1).astype(np.int32)
    a = np.asarray(ops.bucketize(vals, thr, backend="jnp"))
    b = np.asarray(ops.bucketize(vals, thr, backend="pallas"))
    np.testing.assert_array_equal(a, b)
    # oracle: searchsorted per feature
    for f in range(F):
        expect = np.searchsorted(thr[f], vals[:, f], side="right")
        np.testing.assert_array_equal(a[:, f], expect)


@pytest.mark.parametrize("B,N,W", [(1, 1, 1), (64, 100, 1), (200, 700, 2),
                                   (33, 513, 3)])
def test_ternary_match_sweep(B, N, W):
    values = RNG.integers(0, 2**32, (N, W), dtype=np.uint32)
    masks = RNG.integers(0, 2**32, (N, W), dtype=np.uint32)
    values &= masks
    actions = RNG.integers(0, 256, N).astype(np.int32)
    pa = (np.arange(N, dtype=np.int32) * 256 + actions)
    keys = RNG.integers(0, 2**32, (B, W), dtype=np.uint32)
    keys[: B // 2] = values[RNG.integers(0, N, B // 2)]  # force hits
    a = np.asarray(ops.ternary_match(keys, values, masks, pa, 254, "jnp"))
    b = np.asarray(ops.ternary_match(keys, values, masks, pa, 254, "pallas"))
    np.testing.assert_array_equal(a, b)


def test_ternary_priority_wins():
    # two overlapping rows; higher priority must win in both backends
    values = np.array([[0b1000], [0b1000]], np.uint32)
    masks = np.array([[0b1000], [0b1000]], np.uint32)
    pa = np.array([0 * 256 + 7, 1 * 256 + 9], np.int32)
    keys = np.array([[0b1010]], np.uint32)
    for backend in ("jnp", "pallas"):
        out = np.asarray(ops.ternary_match(keys, values, masks, pa, 0,
                                           backend))
        assert out[0] == 9


def test_ternary_default_action():
    values = np.array([[0xFFFFFFFF]], np.uint32)
    masks = np.array([[0xFFFFFFFF]], np.uint32)
    pa = np.array([5], np.int32)
    keys = np.array([[3]], np.uint32)
    for backend in ("jnp", "pallas"):
        out = np.asarray(ops.ternary_match(keys, values, masks, pa, 123,
                                           backend))
        assert out[0] == 123


@pytest.mark.parametrize("B,F,V,K", [(1, 1, 2, 1), (100, 5, 64, 6),
                                     (257, 3, 256, 16)])
def test_lb_lookup_sweep(B, F, V, K):
    codes = RNG.integers(0, V, (B, F)).astype(np.int32)
    luts = RNG.integers(-(2**15), 2**15, (F, V, K)).astype(np.int32)
    a = np.asarray(ops.lb_lookup(codes, luts, "jnp"))
    b = np.asarray(ops.lb_lookup(codes, luts, "pallas"))
    np.testing.assert_array_equal(a, b)
    expect = sum(luts[f][codes[:, f]] for f in range(F))
    np.testing.assert_array_equal(a, expect)


@pytest.mark.parametrize("B,n_in,n_out", [(1, 1, 1), (64, 40, 16),
                                          (100, 100, 3), (17, 64, 33)])
def test_bnn_matmul_sweep(B, n_in, n_out):
    xb = RNG.integers(0, 2, (B, n_in)) * 2 - 1
    w = RNG.integers(0, 2, (n_out, n_in)) * 2 - 1
    xp, wp = pack_bits_uint32(xb), pack_bits_uint32(w)
    expect = xb @ w.T
    for backend in ("jnp", "pallas"):
        got = np.asarray(ops.bnn_forward(xp, [(wp, n_in)], backend))
        np.testing.assert_array_equal(got, expect)


def test_bnn_two_layer():
    B, n_in, h, k = 32, 24, 16, 3
    xb = RNG.integers(0, 2, (B, n_in)) * 2 - 1
    w1 = RNG.integers(0, 2, (h, n_in)) * 2 - 1
    w2 = RNG.integers(0, 2, (k, h)) * 2 - 1
    hh = np.where(xb @ w1.T >= 0, 1, -1)
    expect = hh @ w2.T
    layers = [(pack_bits_uint32(w1), n_in), (pack_bits_uint32(w2), h)]
    for backend in ("jnp", "pallas"):
        got = np.asarray(ops.bnn_forward(pack_bits_uint32(xb), layers,
                                         backend))
        np.testing.assert_array_equal(got, expect)


def test_fused_eb_kernel_matches_staged():
    """encode+pack+match in one launch == the staged two-kernel path."""
    from repro.core import PlanterConfig, plant
    from repro.data import load_dataset
    import jax.numpy as jnp
    ds = load_dataset("unsw", n=1500)
    for model in ("rf", "kmeans"):
        y = None if model == "kmeans" else ds.y_train
        r = plant(PlanterConfig(model=model, strategy="eb", size="S"),
                  ds.X_train, y, None)
        xs = jnp.asarray(ds.X_test[:200])
        staged = np.asarray(r.mapped.jax_predict("pallas")(xs))
        fused = np.asarray(r.mapped.jax_predict("pallas_fused")(xs))
        np.testing.assert_array_equal(staged, fused)


def test_fused_eb_gate_tile_matches_throughput_tile():
    """Auto batch tiling (gate-sized launches) == 256-row tile == oracle."""
    from repro.core import PlanterConfig, plant
    from repro.data import load_dataset
    from repro.kernels.fused_eb import DEFAULT_BLOCK_B, gate_block_b
    import jax.numpy as jnp
    assert gate_block_b(4) == 128 and gate_block_b(130) == 256
    assert gate_block_b(1000) == DEFAULT_BLOCK_B
    ds = load_dataset("unsw", n=1500)
    r = plant(PlanterConfig(model="rf", strategy="eb", size="S"),
              ds.X_train, ds.y_train, None)
    xs = jnp.asarray(ds.X_test[:8])  # decode-batch-sized gate launch
    auto = np.asarray(r.mapped.jax_predict("pallas_fused")(xs))
    np.testing.assert_array_equal(auto, r.mapped.predict(ds.X_test[:8]))


def test_mapped_model_backend_selection():
    """In-step backend: fused EB kernel on TPU for gate-sized tables,
    jnp oracle everywhere else (CPU CI, large tables)."""
    from repro.core import PlanterConfig, plant
    from repro.data import load_dataset
    ds = load_dataset("unsw", n=1500)
    r = plant(PlanterConfig(model="rf", strategy="eb", size="S"),
              ds.X_train, ds.y_train, None)
    assert r.mapped.gate_sized()
    assert r.mapped.select_backend("tpu") == "pallas_fused"
    assert r.mapped.select_backend("cpu") == "jnp"
    lb = plant(PlanterConfig(model="svm", size="S"),  # lookup-based
               ds.X_train, ds.y_train, None)
    assert lb.mapped.select_backend("tpu") == "jnp"
    # 'auto' resolves against the actual local platform without error
    fn = r.mapped.jax_predict("auto")
    np.testing.assert_array_equal(
        np.asarray(fn(ds.X_test[:16])), r.mapped.predict(ds.X_test[:16]))


# ----------------------------------------------------- paged attention
def _paged_case(seed, B, C, H, KV, hd, page, n_ps, dtype, quantized):
    """Random q + fully-populated pools + a shuffled block table.

    Pools are filled with garbage everywhere; only the mask (absolute
    positions, causal + window) decides which cells each query sees,
    so stale-cell leakage shows up as a mismatch immediately.
    """
    import jax
    import jax.numpy as jnp
    from repro.nn import attn_backend as AB

    rng = np.random.default_rng(seed)
    N = B * n_ps
    q = jnp.asarray(rng.normal(0, 1, (B, C, H, hd)), dtype)
    tbl = jnp.asarray(rng.permutation(N).reshape(B, n_ps).astype(np.int32))
    pos0 = rng.integers(0, n_ps * page - C + 1, B)
    pos = jnp.asarray(pos0[:, None] + np.arange(C)[None], jnp.int32)

    def pool(draw, dt):  # lane-dense per-layer pool [N, page, KV * hd]
        return jnp.asarray(draw((N, page, KV, hd)).reshape(N, page, KV * hd),
                           dt)

    def planes():
        return jnp.asarray(rng.uniform(0.005, 0.02, (N, page, KV)),
                           jnp.float32)

    if quantized:
        kv = AB.PagedKV(
            k=pool(lambda s: rng.integers(-127, 128, s), jnp.int8),
            v=pool(lambda s: rng.integers(-127, 128, s), jnp.int8),
            k_scale=planes(), v_scale=planes())
    else:
        kv = AB.PagedKV(k=pool(lambda s: rng.normal(0, 1, s), dtype),
                        v=pool(lambda s: rng.normal(0, 1, s), dtype))
    page_ids = jnp.take_along_axis(tbl, jnp.clip(pos // page, 0, n_ps - 1),
                                   axis=1)
    return q, kv.with_view(tbl, pos, page_ids, pos % page)


def _run_both(q, kv, H, hd, window):
    """jit both backends (the serve path is always jitted; eager-vs-jit
    differs by ulps through XLA fusion, jit-vs-jit is bitwise)."""
    import functools
    import jax
    from repro.nn import attn_backend as AB

    outs = {}
    for name in ("jnp", "pallas"):
        fn = jax.jit(functools.partial(AB.get(name), n_heads=H,
                                       head_dim=hd, window=window))
        outs[name] = np.asarray(fn(q, kv))
    return outs


def _assert_kernel_close(oracle, kernel):
    """The kernel attends one slot and one KV group per matmul, the
    oracle the whole batch at once, so XLA may sum the f32 products in
    another order (it does for the ``C=1`` matrix-vector shapes).  Each
    output is a convex combination of V rows over at most 24 positions,
    so the reassociation costs a few ulps of the largest output: bound
    it at 8 eps of that magnitude."""
    tol = 8 * np.finfo(oracle.dtype).eps * np.abs(oracle).max()
    np.testing.assert_allclose(kernel, oracle, rtol=0, atol=tol)


@pytest.mark.parametrize("page,n_ps", [(4, 3), (8, 2)])
@pytest.mark.parametrize("C", [1, 5])
@pytest.mark.parametrize("H,KV", [(4, 4), (4, 2), (8, 1)])
def test_paged_attention_kernel_bitwise_fp(page, n_ps, C, H, KV):
    """The Pallas kernel (interpret mode on CPU) matches the jnp oracle
    for fp pools to a few f32 ulps (``_assert_kernel_close``) — decode
    (C=1) and prefill-chunk variants, across page sizes and GQA
    ratios."""
    import jax.numpy as jnp
    q, kv = _paged_case(page * 100 + C * 10 + H, 3, C, H, KV, 8,
                        page, n_ps, jnp.float32, quantized=False)
    outs = _run_both(q, kv, H, 8, jnp.int32(page))
    _assert_kernel_close(outs["jnp"], outs["pallas"])


@pytest.mark.parametrize("window", [0, 4, 13])
def test_paged_attention_kernel_bitwise_bf16_windows(window):
    import jax.numpy as jnp
    q, kv = _paged_case(window + 1, 2, 3, 4, 2, 16, 8, 2,
                        jnp.bfloat16, quantized=False)
    outs = _run_both(q, kv, 4, 16, jnp.int32(window))
    np.testing.assert_array_equal(outs["jnp"], outs["pallas"])


@pytest.mark.parametrize("C", [1, 6])
def test_paged_attention_kernel_int8(C):
    """int8 pools: kernel dequant (per-page scale planes, fused at the
    VMEM staging step) matches the jnp int8 oracle to a few f32 ulps
    (``_assert_kernel_close``), and the
    int8 result tracks an fp run of the dequantized pool exactly (the
    oracle dequantizes identically, so closeness to true fp is already
    pinned by the serve-level int8 tolerance tests)."""
    import jax.numpy as jnp
    from repro.nn import attn_backend as AB
    q, kv = _paged_case(C, 2, C, 4, 2, 8, 4, 3, jnp.float32,
                        quantized=True)
    outs = _run_both(q, kv, 4, 8, jnp.int32(0))
    _assert_kernel_close(outs["jnp"], outs["pallas"])
    # dequantizing the pool up front and running fp must agree closely
    def deq(pool, scale):
        N, page, _ = pool.shape
        return (pool.astype(jnp.float32).reshape(N, page, 2, 8)
                * scale[..., None]).reshape(N, page, 16)

    fp_kv = AB.PagedKV(
        k=deq(kv.k, kv.k_scale), v=deq(kv.v, kv.v_scale),
        block_tbl=kv.block_tbl, pos=kv.pos,
        page_ids=kv.page_ids, page_off=kv.page_off)
    fp = _run_both(q, fp_kv, 4, 8, jnp.int32(0))
    np.testing.assert_allclose(outs["pallas"], fp["pallas"], atol=1e-6)


@pytest.mark.parametrize("quantized", [False, True])
def test_paged_attention_kernel_reads_its_layer(quantized):
    """A stacked pool of three layers with distinct contents, read at
    layer 1: the kernel and the oracle agree with each other, and each
    is bitwise equal to its own call on layer 1's pool alone — an index
    map or gather that read a neighbouring layer would show here."""
    import jax
    import jax.numpy as jnp
    from repro.nn import attn_backend as AB
    H, KV, hd = 4, 2, 8
    # 4 pages a slot: the kernel fetches all 4 in one grid step
    cases = [_paged_case(70 + i, 2, 3, H, KV, hd, 4, 4, jnp.float32,
                         quantized=quantized) for i in range(3)]
    q, one = cases[1]
    stack = jax.tree.map(lambda *a: jnp.stack(a),
                         *[kv.pool() for _, kv in cases])
    view = (one.block_tbl, one.pos, one.page_ids, one.page_off)
    got = _run_both(q, stack.with_view(*view, jnp.int32(1)), H, hd,
                    jnp.int32(0))
    alone = _run_both(q, one, H, hd, jnp.int32(0))
    _assert_kernel_close(got["jnp"], got["pallas"])
    for name in ("jnp", "pallas"):
        np.testing.assert_array_equal(got[name], alone[name])
    # the neighbours really differ: layer 0 reads another answer
    other = _run_both(q, stack.with_view(*view, jnp.int32(0)), H, hd,
                      jnp.int32(0))
    assert not np.allclose(other["pallas"], got["pallas"])


def test_paged_attention_hbm_bytes_accounting():
    """The kernel's DMA-byte model: int8 pools move ~4x fewer KV bytes
    than fp32, and bytes scale linearly with the per-request page
    count (n_ps), independent of the pool size."""
    from repro.kernels.paged_attention import paged_attention_hbm_bytes
    kw = dict(B=8, C=1, H=4, KV=4, hd=64, page=16)
    fp = paged_attention_hbm_bytes(n_ps=8, pool_bytes=4, quantized=False,
                                   act_bytes=2, **kw)
    i8 = paged_attention_hbm_bytes(n_ps=8, pool_bytes=1, quantized=True,
                                   act_bytes=2, **kw)
    assert i8 < fp / 2.5
    fp2 = paged_attention_hbm_bytes(n_ps=16, pool_bytes=4, quantized=False,
                                    act_bytes=2, **kw)
    assert fp2 > 1.9 * fp


def test_attn_backend_registry():
    """Registry semantics mirror ``MappedModel.select_backend``: auto
    resolves by platform, explicit names pass through, unknown names
    fail loudly at config time."""
    from repro.nn import attn_backend as AB
    assert set(AB.available()) >= {"jnp", "pallas"}
    assert AB.resolve("auto", "tpu") == "pallas"
    assert AB.resolve("auto", "cpu") == "jnp"
    assert AB.resolve("jnp", "tpu") == "jnp"
    assert AB.resolve("pallas", "cpu") == "pallas"
    assert AB.resolve("auto") in AB.available()
    assert AB.valid_impls()[0] == "auto"
    with pytest.raises(ValueError):
        AB.resolve("triton")
    with pytest.raises(KeyError):
        AB.get("triton")


def test_paged_block_pallas_matches_jnp_end_to_end():
    """Full ``paged_decode_attention_block`` (projection + scatter +
    attend + output proj) under jit: impl="pallas" is bitwise
    identical to impl="jnp" — the acceptance gate for threading the
    backend through the serve path."""
    import functools
    import jax
    import jax.numpy as jnp
    from repro.nn import attention as A
    from repro.nn import attn_backend as AB

    rng = np.random.default_rng(3)
    B, H, hd, page, n_ps = 2, 4, 16, 4, 2
    D = H * hd
    N = B * n_ps
    p = A.init_attention(jax.random.PRNGKey(1), D, H, 2, hd, qk_norm=True)
    tbl = jnp.asarray(np.arange(N).reshape(B, n_ps))
    x = jnp.asarray(rng.normal(0, 1, (B, 3, D)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(3)[None], (B, 3)).astype(jnp.int32)
    page_ids = jnp.take_along_axis(tbl, pos // page, axis=1)
    kv = AB.PagedKV(k=jnp.zeros((N, page, 2 * hd), jnp.float32),
                    v=jnp.zeros((N, page, 2 * hd), jnp.float32))

    def run(impl):
        fn = jax.jit(functools.partial(
            A.paged_decode_attention_block, n_heads=H, n_kv_heads=2,
            head_dim=hd, rope_theta=1e4, qk_norm=True, norm_eps=1e-6,
            impl=impl))
        return fn(p, x, kv.with_view(tbl, pos, page_ids, pos % page),
                  window=jnp.int32(0))

    out_j, kv_j = run("jnp")
    out_p, kv_p = run("pallas")
    np.testing.assert_array_equal(np.asarray(out_j), np.asarray(out_p))
    np.testing.assert_array_equal(np.asarray(kv_j.k), np.asarray(kv_p.k))
