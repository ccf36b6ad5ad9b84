"""Serving integration: gate admission, fused step, generation."""
import jax
import numpy as np
import pytest

from repro.arch import model as M
from repro.configs import get_smoke_config
from repro.core import PlanterConfig, plant
from repro.data import load_dataset
from repro.serve.engine import ServeConfig, ServeEngine

DS = load_dataset("unsw", n=2000)


@pytest.fixture(scope="module")
def engine():
    cfg = get_smoke_config("qwen2_1_5b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    res = plant(PlanterConfig(model="rf", size="S"), DS.X_train, DS.y_train,
                DS.X_test)
    return ServeEngine(cfg, params, ServeConfig(max_batch=4, cache_len=32),
                       gate=res.mapped), res


def test_gate_admission(engine):
    eng, res = engine
    keep = eng.admit(DS.X_test[:128])
    # gate decisions == the mapped model's decisions
    labels = np.asarray(res.mapped.predict(DS.X_test[:128]))
    np.testing.assert_array_equal(keep, labels != 1)
    assert 0 < keep.sum() < 128  # both classes present


def test_fused_step_labels_match_gate(engine):
    eng, res = engine
    toks = np.zeros((4, 1), np.int32)
    feats = DS.X_test[:4]
    logits, labels = eng.step(toks, feats)
    np.testing.assert_array_equal(
        np.asarray(labels), np.asarray(res.mapped.predict(feats)))
    assert logits.shape == (4, eng.cfg.vocab_padded)


def test_generate_shapes(engine):
    eng, _ = engine
    eng.state = M.init_decode_state(eng.cfg, 4, 32)  # reset cache
    prompts = np.ones((4, 3), np.int64)
    out = eng.generate(prompts, n_tokens=5, features=DS.X_test[:4])
    assert out.shape == (4, 5)
    assert (out >= 0).all() and (out < eng.cfg.vocab_padded).all()


def test_greedy_determinism(engine):
    eng, _ = engine
    eng.state = M.init_decode_state(eng.cfg, 4, 32)
    prompts = np.ones((4, 3), np.int64)
    a = eng.generate(prompts, 4, features=DS.X_test[:4])
    eng.state = M.init_decode_state(eng.cfg, 4, 32)
    b = eng.generate(prompts, 4, features=DS.X_test[:4])
    np.testing.assert_array_equal(a, b)


def test_step_and_generate_nonblocking(engine):
    """block=False keeps logits/tokens as device arrays (no host sync)."""
    eng, _ = engine
    eng.state = M.init_decode_state(eng.cfg, 4, 32)
    logits, labels = eng.step(np.ones((4, 1), np.int32), DS.X_test[:4],
                              block=False)
    assert isinstance(logits, jax.Array) and isinstance(labels, jax.Array)
    eng.state = M.init_decode_state(eng.cfg, 4, 32)
    prompts = np.ones((4, 3), np.int64)
    dev = eng.generate(prompts, 4, features=DS.X_test[:4], block=False)
    assert isinstance(dev, jax.Array)
    eng.state = M.init_decode_state(eng.cfg, 4, 32)
    host = eng.generate(prompts, 4, features=DS.X_test[:4])
    np.testing.assert_array_equal(np.asarray(dev), host)


def test_continuous_batching_drains_queue(engine):
    from repro.serve.engine import ContinuousBatcher
    eng, _ = engine
    eng.state = M.init_decode_state(eng.cfg, 4, 32)
    cb = ContinuousBatcher(eng, eos_token=-1, max_tokens=4)
    rng = np.random.default_rng(0)
    n_submitted = 0
    for rid in range(10):  # 10 requests through 4 slots
        feats = DS.X_test[rid]
        if cb.submit(rid, int(rng.integers(1, 100)), features=feats):
            n_submitted += 1
    done = cb.run(max_steps=200)
    assert len(done) == n_submitted
    assert len(cb.dropped) == 10 - n_submitted
    for rid, toks in done.items():
        assert 1 <= len(toks) <= 5


# ---------------------------------------------------------------------------
# Device-resident continuous batching (DeviceContinuousBatcher)
# ---------------------------------------------------------------------------
from repro.serve.engine import ContinuousBatcher, DeviceContinuousBatcher


def _fresh_engine(engine, batch=4, cache_len=32):
    eng, res = engine
    return ServeEngine(eng.cfg, eng.params,
                       ServeConfig(max_batch=batch, cache_len=cache_len),
                       gate=res.mapped)


def _run_workload(cb, n_req=10, max_steps=300, seed=0):
    rng = np.random.default_rng(seed)
    for rid in range(n_req):
        cb.submit(rid, int(rng.integers(1, 100)), features=DS.X_test[rid])
    return cb.run(max_steps=max_steps)


def test_device_batcher_parity_max_token_eviction(engine):
    """Token streams + done/dropped sets match the host batcher exactly
    when every sequence runs to the max-token limit (eos disabled)."""
    host = ContinuousBatcher(_fresh_engine(engine), eos_token=-1,
                             max_tokens=4)
    dev = DeviceContinuousBatcher(_fresh_engine(engine), eos_token=-1,
                                  max_tokens=4, sync_every=3)
    done_h = _run_workload(host)
    done_d = _run_workload(dev)
    assert done_h == done_d
    assert host.dropped == dev.dropped
    assert all(len(v) == 4 for v in done_d.values())


def test_device_batcher_parity_eos_eviction(engine):
    """Same, with an eos token that actually fires mid-stream."""
    probe = ContinuousBatcher(_fresh_engine(engine), eos_token=-1,
                              max_tokens=6)
    done_p = _run_workload(probe)
    # pick a token generated mid-stream so eos eviction really triggers
    eos = next(int(v[1]) for v in done_p.values() if len(v) > 1)
    host = ContinuousBatcher(_fresh_engine(engine), eos_token=eos,
                             max_tokens=6)
    dev = DeviceContinuousBatcher(_fresh_engine(engine), eos_token=eos,
                                  max_tokens=6, sync_every=4)
    done_h = _run_workload(host)
    done_d = _run_workload(dev)
    assert done_h == done_d
    assert any(len(v) < 6 for v in done_d.values())  # eos actually evicted


def test_device_batcher_sync_every_invariant(engine):
    """The drain interval is a perf knob only — outputs are identical."""
    a = DeviceContinuousBatcher(_fresh_engine(engine), eos_token=-1,
                                max_tokens=4, sync_every=1)
    b = DeviceContinuousBatcher(_fresh_engine(engine), eos_token=-1,
                                max_tokens=4, sync_every=7)
    assert _run_workload(a) == _run_workload(b)


def test_device_batcher_in_step_gate_eviction(engine):
    """pregate=False: the fused gate's in-step verdict evicts dropped
    requests at their first step, before any token is recorded."""
    eng = _fresh_engine(engine)
    dev = DeviceContinuousBatcher(eng, eos_token=-1, max_tokens=4,
                                  pregate=False, sync_every=4)
    _run_workload(dev, n_req=10)
    keep = eng.admit(DS.X_test[:10])
    assert sorted(dev.dropped) == sorted(np.where(~keep)[0])
    assert not any(rid in dev.done for rid in dev.dropped)
    assert sorted(dev.done) == sorted(np.where(keep)[0])


def test_device_batcher_max_steps_resumes(engine):
    """A max_steps-bounded run keeps in-flight slots + un-admitted queue
    entries; repeated small runs reproduce the host batcher's single run
    exactly (same token streams, nothing lost)."""
    host = ContinuousBatcher(_fresh_engine(engine), eos_token=-1,
                             max_tokens=4)
    done_h = _run_workload(host)
    dev = DeviceContinuousBatcher(_fresh_engine(engine), eos_token=-1,
                                  max_tokens=4, sync_every=2)
    rng = np.random.default_rng(0)
    for rid in range(10):
        dev.submit(rid, int(rng.integers(1, 100)), features=DS.X_test[rid])
    for _ in range(100):  # 3 steps per run: expires mid-stream repeatedly
        before = len(dev.done)
        dev.run(max_steps=3)
        if len(dev.done) == before and not dev.queue \
                and all(c is None for c in dev._carry):
            break
    assert dev.done == done_h
    assert dev.dropped == host.dropped


def test_device_batcher_multi_wave_reuses_cache(engine):
    """Back-to-back run() calls share the decode cache (pos carries over)
    and accumulate done/dropped bookkeeping without collisions."""
    dev = DeviceContinuousBatcher(_fresh_engine(engine), eos_token=-1,
                                  max_tokens=3, sync_every=2)
    for rid in range(5):
        dev.submit(("a", rid), rid + 1, features=DS.X_test[rid])
    first = dict(dev.run(max_steps=100))
    for rid in range(5):
        dev.submit(("b", rid), rid + 1, features=DS.X_test[rid])
    both = dev.run(max_steps=100)
    assert set(first).issubset(both)
    n_admitted = sum(1 for k in both) + len(dev.dropped)
    assert n_admitted == 10


# ---------------------------------------------------------------------------
# Paged KV cache + chunked multi-token prefill
# ---------------------------------------------------------------------------


def _paged_engine(engine, batch=4, cache_len=32, page_size=8, pages=0,
                  **kw):
    eng, res = engine
    return ServeEngine(
        eng.cfg, eng.params,
        ServeConfig(max_batch=batch, cache_len=cache_len,
                    page_size=page_size, pages=pages, **kw),
        gate=res.mapped)


def _prompts(n=10, seed=0, max_len=8):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, 97, rng.integers(1, max_len))]
            for _ in range(n)]


def _run_prompt_workload(cb, prompts, max_steps=600):
    for rid, prompt in enumerate(prompts):
        cb.submit(rid, prompt, features=DS.X_test[rid])
    return cb.run(max_steps=max_steps)


def test_paged_decode_bit_identical_to_dense(engine):
    """Where the two caches' semantics coincide (one wave, every slot
    admitted at step 0, single-token prompts), paged decode must be
    bit-identical to the dense ring cache — the acceptance property the
    serve bench asserts on meshes."""
    dense = DeviceContinuousBatcher(_fresh_engine(engine), eos_token=-1,
                                    max_tokens=5, sync_every=3)
    paged = DeviceContinuousBatcher(_paged_engine(engine), eos_token=-1,
                                    max_tokens=5, sync_every=3)
    for rid in range(4):  # <= max_batch: no slot reuse
        dense.submit(rid, rid + 7, features=DS.X_test[rid])
        paged.submit(rid, rid + 7, features=DS.X_test[rid])
    assert dense.run(max_steps=100) == paged.run(max_steps=100)


def test_chunked_prefill_matches_token_by_token(engine):
    """Multi-token prompts through the chunked fused step produce the
    exact streams of token-by-token seeding — both against the host
    paged loop (one launch + one sync per token) and across chunk
    widths, through multiple waves of slot reuse."""
    prompts = _prompts()
    host = ContinuousBatcher(_paged_engine(engine), eos_token=-1,
                             max_tokens=4)
    done_h = _run_prompt_workload(host, prompts)
    for chunk in (1, 3, 8):
        dev = DeviceContinuousBatcher(_paged_engine(engine), eos_token=-1,
                                      max_tokens=4, sync_every=3,
                                      prefill_chunk=chunk)
        done_d = _run_prompt_workload(dev, prompts)
        assert done_d == done_h, f"prefill_chunk={chunk} diverged"
        assert dev.dropped == host.dropped
    assert len(done_h) > 0 and any(len(p) > 4 for p in prompts)


def test_paged_eos_eviction_frees_pages(engine):
    """EOS mid-stream evicts the slot and returns its pages; the pool
    ends the run fully free."""
    prompts = _prompts(n=8, max_len=6)
    probe = DeviceContinuousBatcher(_paged_engine(engine), eos_token=-1,
                                    max_tokens=6, prefill_chunk=4)
    done_p = _run_prompt_workload(probe, prompts)
    eos = next(int(v[1]) for v in done_p.values() if len(v) > 1)
    host = ContinuousBatcher(_paged_engine(engine), eos_token=eos,
                             max_tokens=6)
    dev = DeviceContinuousBatcher(_paged_engine(engine), eos_token=eos,
                                  max_tokens=6, sync_every=4,
                                  prefill_chunk=4)
    done_h = _run_prompt_workload(host, prompts)
    done_d = _run_prompt_workload(dev, prompts)
    assert done_h == done_d
    assert any(len(v) < 6 for v in done_d.values())  # eos actually fired
    assert dev._pfree.all() and host.page_free.all()


def test_paged_max_steps_resumes(engine):
    """Bounded runs carry in-flight paged slots (pos, prompt, block
    table) and un-admitted queue entries; repeated 3-step runs
    reproduce the single-run streams exactly."""
    prompts = _prompts()
    ref = DeviceContinuousBatcher(_paged_engine(engine), eos_token=-1,
                                  max_tokens=4, sync_every=3,
                                  prefill_chunk=3)
    done_ref = _run_prompt_workload(ref, prompts)
    dev = DeviceContinuousBatcher(_paged_engine(engine), eos_token=-1,
                                  max_tokens=4, sync_every=2,
                                  prefill_chunk=3)
    for rid, prompt in enumerate(prompts):
        dev.submit(rid, prompt, features=DS.X_test[rid])
    for _ in range(200):
        before = len(dev.done)
        dev.run(max_steps=3)
        if len(dev.done) == before and not dev.queue \
                and all(c is None for c in dev._carry):
            break
    assert dev.done == done_ref
    assert dev.dropped == ref.dropped


def test_paged_pool_oversubscription_fifo(engine):
    """A pool smaller than slots x demand admits FIFO-in-order as pages
    free up: reservation admission means nobody stalls mid-stream, and
    streams still match the host loop run on the same tight pool."""
    # demand per request: ceil((plen + max_tokens)/page) <= 2 pages;
    # pool of 4 pages => at most 2 concurrent slots despite 4 slots
    prompts = _prompts(n=6, max_len=8)
    host = ContinuousBatcher(_paged_engine(engine, pages=4), eos_token=-1,
                             max_tokens=4)
    dev = DeviceContinuousBatcher(_paged_engine(engine, pages=4),
                                  eos_token=-1, max_tokens=4,
                                  sync_every=3, prefill_chunk=4)
    done_h = _run_prompt_workload(host, prompts)
    done_d = _run_prompt_workload(dev, prompts)
    assert done_h == done_d
    admitted = [r for r in range(6) if r not in dev.dropped]
    assert sorted(done_d) == sorted(admitted)  # tight pool loses nothing


def test_paged_more_live_slots_at_fixed_memory(engine):
    """The tentpole memory claim: at this workload's footprint the paged
    pool holds every slot live with strictly less cache memory than the
    dense [B, cache_len] layout (equivalently: strictly more slots fit
    at fixed cache memory)."""
    from repro.serve.engine import page_demand
    scfg = ServeConfig(max_batch=4, cache_len=32, page_size=8)
    demand = page_demand(scfg, 8, 4)  # 8-token prompts + 4 decode tokens
    pool = scfg.max_batch * demand
    paged_tokens = pool * scfg.page_size
    dense_tokens = scfg.max_batch * scfg.cache_len
    assert paged_tokens < dense_tokens
    dev = DeviceContinuousBatcher(
        _paged_engine(engine, pages=pool), eos_token=-1, max_tokens=4,
        prefill_chunk=4)
    prompts = [[int(t) for t in np.arange(8) + rid + 1] for rid in range(4)]
    done = _run_prompt_workload(dev, prompts)
    admitted = [r for r in range(4) if r not in dev.dropped]
    assert sorted(done) == sorted(admitted)
    assert all(len(done[r]) == 4 for r in admitted)


def test_paged_in_step_gate_eviction(engine):
    """pregate=False on the paged path: the fused gate's verdict evicts
    dropped requests before any token is recorded, and their pages
    return to the pool."""
    eng = _paged_engine(engine)
    dev = DeviceContinuousBatcher(eng, eos_token=-1, max_tokens=4,
                                  pregate=False, sync_every=4,
                                  prefill_chunk=4)
    _run_prompt_workload(dev, _prompts())
    keep = eng.admit(DS.X_test[:10])
    assert sorted(dev.dropped) == sorted(np.where(~keep)[0])
    assert not any(rid in dev.done for rid in dev.dropped)
    assert sorted(dev.done) == sorted(np.where(keep)[0])
    assert dev._pfree.all()


def test_drop_reasons_split(engine):
    """Per-request drop reasons: queue-full (bounded queue at submit)
    vs gate-reject (Planter verdict), asserted as an exact split."""
    eng = _paged_engine(engine)
    keep = eng.admit(DS.X_test[:6])
    dev = DeviceContinuousBatcher(eng, eos_token=-1, max_tokens=3,
                                  prefill_chunk=4, max_queue=6)
    prompts = _prompts(n=10, max_len=6)
    for rid in range(10):
        dev.submit(rid, prompts[rid], features=DS.X_test[rid])
    dev.run(max_steps=300)
    expect = {rid: "queue-full" for rid in range(6, 10)}
    expect.update({rid: "gate-reject"
                   for rid in range(6) if not keep[rid]})
    assert dev.drop_reasons == expect
    assert sorted(dev.dropped) == sorted(expect)
    # both reasons actually present in this workload
    assert set(expect.values()) == {"queue-full", "gate-reject"}


def test_dense_device_rejects_multi_token_prompts(engine):
    dev = DeviceContinuousBatcher(_fresh_engine(engine), eos_token=-1)
    with pytest.raises(ValueError, match="paged"):
        dev.submit(0, [1, 2, 3])


# ---------------------------------------------------------------------------
# Prefix sharing + int8 page pool
# ---------------------------------------------------------------------------


def _prefix_prompts(n=8, seed=3, prefix_len=12, tail_max=6):
    """Prompts sharing a common token prefix (the sharing workload)."""
    rng = np.random.default_rng(seed)
    prefix = [int(t) for t in rng.integers(1, 97, prefix_len)]
    return [prefix + [int(t) for t in
                      rng.integers(1, 97, rng.integers(1, tail_max))]
            for _ in range(n)]


def test_shared_prefix_host_bit_identical(engine):
    """Host batcher: prefix sharing is invisible in the streams — shared
    pages hold exactly what each sharer would have written itself, so
    the shared run is bit-identical to the unshared run, while the pool
    records real sharing (and at least one COW on a partial tail)."""
    prompts = _prefix_prompts()
    plain = ContinuousBatcher(_paged_engine(engine), eos_token=-1,
                              max_tokens=4)
    shared = ContinuousBatcher(_paged_engine(engine, share_prefix=True),
                               eos_token=-1, max_tokens=4)
    done_p = _run_prompt_workload(plain, prompts)
    done_s = _run_prompt_workload(shared, prompts)
    assert done_s == done_p
    assert shared.pool.stats["shared_tokens"] > 0
    assert shared.pool.stats["cow_events"] > 0
    assert shared.pool.prefix_tokens_per_page() > 1.0
    # held pages are exactly the cached ones, one hold each
    held = np.where(shared.pool.ref > 0)[0]
    assert set(held.tolist()) == shared.pool.cached_pages()
    assert (shared.pool.ref[held] == 1).all()


def test_shared_prefix_device_bit_identical_multiwave(engine):
    """Device batcher: wave 1 populates the prefix trie (registration at
    drain), wave 2 shares it — both waves' streams bit-identical to an
    unshared device batcher fed the same two waves."""
    prompts = _prefix_prompts()
    plain = DeviceContinuousBatcher(_paged_engine(engine), eos_token=-1,
                                    max_tokens=4, sync_every=3,
                                    prefill_chunk=4)
    shared = DeviceContinuousBatcher(
        _paged_engine(engine, share_prefix=True), eos_token=-1,
        max_tokens=4, sync_every=3, prefill_chunk=4)
    for wave in ("a", "b"):
        for rid, p in enumerate(prompts):
            plain.submit((wave, rid), p, features=DS.X_test[rid])
            shared.submit((wave, rid), p, features=DS.X_test[rid])
        done_p = dict(plain.run(max_steps=600))
        done_s = dict(shared.run(max_steps=600))
        assert done_s == done_p, f"wave {wave} diverged under sharing"
    assert shared.pool.stats["shared_tokens"] > 0  # wave 2 really shared
    held = np.where(shared.pool.ref > 0)[0]
    assert set(held.tolist()) == shared.pool.cached_pages()


def test_shared_prefix_bounded_runs_resume(engine):
    """Sharing survives the resume path: repeated 3-step bounded runs
    (holds, refcounts and carried block tables crossing run boundaries)
    reproduce the un-interrupted shared run exactly."""
    prompts = _prefix_prompts(seed=5)
    ref = DeviceContinuousBatcher(_paged_engine(engine, share_prefix=True),
                                  eos_token=-1, max_tokens=4,
                                  sync_every=3, prefill_chunk=3)
    done_ref = _run_prompt_workload(ref, prompts)
    dev = DeviceContinuousBatcher(_paged_engine(engine, share_prefix=True),
                                  eos_token=-1, max_tokens=4,
                                  sync_every=2, prefill_chunk=3)
    for rid, prompt in enumerate(prompts):
        dev.submit(rid, prompt, features=DS.X_test[rid])
    for _ in range(300):
        before = len(dev.done)
        dev.run(max_steps=3)
        assert (dev.pool.ref >= 0).all()
        if len(dev.done) == before and not dev.queue \
                and all(c is None for c in dev._carry):
            break
    assert dev.done == done_ref
    assert dev.dropped == ref.dropped


def test_int8_paged_streams_shared_eq_unshared(engine):
    """int8 pool: quantization is deterministic, so shared int8 pages
    hold bit-identical content to self-written ones — int8-shared
    streams equal int8-unshared streams (wave 2 = trie warm), host
    equals device."""
    prompts = _prefix_prompts(seed=7)
    plain = DeviceContinuousBatcher(_paged_engine(engine, kv_int8=True),
                                    eos_token=-1, max_tokens=4,
                                    sync_every=3, prefill_chunk=4)
    shared = DeviceContinuousBatcher(
        _paged_engine(engine, kv_int8=True, share_prefix=True),
        eos_token=-1, max_tokens=4, sync_every=3, prefill_chunk=4)
    host = ContinuousBatcher(_paged_engine(engine, kv_int8=True),
                             eos_token=-1, max_tokens=4)
    for wave in ("a", "b"):
        for rid, p in enumerate(prompts):
            plain.submit((wave, rid), p, features=DS.X_test[rid])
            shared.submit((wave, rid), p, features=DS.X_test[rid])
            host.submit((wave, rid), p, features=DS.X_test[rid])
        done_p = dict(plain.run(max_steps=600))
        done_s = dict(shared.run(max_steps=600))
        done_h = dict(host.run(max_steps=600))
        assert done_s == done_p, f"int8 sharing diverged in wave {wave}"
        assert done_h == done_p, f"int8 host/device diverged in wave {wave}"
    assert shared.pool.stats["shared_tokens"] > 0


def test_int8_paged_logits_within_tolerance(engine):
    """int8 paged decode tracks fp paged decode within the dense int8
    cache's tolerance (|logits_fp - logits_int8| < 0.05 * max|logits|,
    the test_perf_features bound) over a multi-page sequence."""
    import jax.numpy as jnp

    eng, _ = engine
    cfg = eng.cfg
    kv_fp = M.init_paged_kv(cfg, 8, 8)
    kv_i8 = M.init_paged_kv(cfg, 8, 8, kv_dtype="int8")
    assert kv_i8.k.dtype == jnp.int8 and kv_i8.quantized
    assert not kv_fp.quantized and kv_fp.block_tbl is None
    tbl = jnp.asarray(np.arange(8).reshape(2, 4))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(1, 97, (2, 20)), jnp.int32)
    scale, diff = 0.0, 0.0
    for t in range(20):
        pos = jnp.full((2,), t, jnp.int32)
        n = jnp.ones((2,), jnp.int32)
        lf, kv_fp = M.paged_decode_step(eng.params, kv_fp, tbl, pos,
                                        toks[:, t: t + 1], n, cfg)
        l8, kv_i8 = M.paged_decode_step(eng.params, kv_i8, tbl, pos,
                                        toks[:, t: t + 1], n, cfg)
        scale = max(scale, float(jnp.max(jnp.abs(lf))))
        diff = max(diff, float(jnp.max(jnp.abs(lf - l8))))
    assert diff < 0.05 * scale, (diff, scale)


def test_paged_decode_step_pallas_matches_jnp(engine):
    """``attn_impl="pallas"`` threads through the full scanned decode
    step (per-layer windows, pool donation) and its logits are bitwise
    identical to ``attn_impl="jnp"`` — the serve-path acceptance gate
    for backend selection (interpret mode on CPU)."""
    import jax.numpy as jnp

    eng, _ = engine
    cfg = eng.cfg
    tbl = jnp.asarray(np.arange(8).reshape(2, 4))
    rng = np.random.default_rng(5)
    toks = jnp.asarray(rng.integers(1, 97, (2, 10)), jnp.int32)
    kv_j = M.init_paged_kv(cfg, 8, 8)
    kv_p = M.init_paged_kv(cfg, 8, 8)
    for t in range(10):
        pos = jnp.full((2,), t, jnp.int32)
        n = jnp.ones((2,), jnp.int32)
        lj, kv_j = M.paged_decode_step(eng.params, kv_j, tbl, pos,
                                       toks[:, t: t + 1], n, cfg,
                                       attn_impl="jnp")
        lp, kv_p = M.paged_decode_step(eng.params, kv_p, tbl, pos,
                                       toks[:, t: t + 1], n, cfg,
                                       attn_impl="pallas")
        np.testing.assert_array_equal(np.asarray(lj), np.asarray(lp),
                                      err_msg=f"step {t}")
    np.testing.assert_array_equal(np.asarray(kv_j.k), np.asarray(kv_p.k))


def test_serve_config_validates_attn_impl():
    """Unknown backend names fail at config time, not mid-serve."""
    ServeConfig(max_batch=2, cache_len=16, attn_impl="pallas")
    with pytest.raises(ValueError, match="attn_impl"):
        ServeConfig(max_batch=2, cache_len=16, attn_impl="triton")


def test_int8_pool_undercuts_fp_bytes(engine):
    """The memory claim behind --kv-int8: at the same page count the
    int8 pool (values + scale planes) costs strictly less than the bf16
    pool, so a fixed byte budget admits more concurrent slots."""
    eng, _ = engine
    fp = M.init_paged_kv(eng.cfg, 8, 8)
    i8 = M.init_paged_kv(eng.cfg, 8, 8, kv_dtype="int8")
    assert i8.nbytes < fp.nbytes


def test_submit_empty_prompt_rejected(engine):
    """Satellite regression: an empty prompt raises a clear ValueError,
    records an ``empty-prompt`` drop reason, and reserves nothing — on
    the host batcher, the device batcher and the router."""
    host = ContinuousBatcher(_paged_engine(engine), eos_token=-1,
                             max_tokens=4)
    with pytest.raises(ValueError, match="empty prompt"):
        host.submit("e1", [])
    assert host.drop_reasons["e1"] == "empty-prompt"
    assert "e1" in host.dropped and not host.queue
    assert host.page_free.all()  # zero-demand reservation never happened
    dev = DeviceContinuousBatcher(_paged_engine(engine), eos_token=-1,
                                  max_tokens=4)
    with pytest.raises(ValueError, match="empty prompt"):
        dev.submit("e2", np.array([], np.int32))
    assert dev.drop_reasons["e2"] == "empty-prompt"
    assert dev._pfree.all() and not dev.queue


def test_dense_host_batcher_loops_prompt(engine):
    """Satellite: the dense host baseline accepts prompt sequences and
    loops them one token per step (global-position semantics), emitting
    exactly max_tokens generated tokens."""
    host = ContinuousBatcher(_fresh_engine(engine), eos_token=-1,
                             max_tokens=3)
    host.submit(0, [5, 9, 13], features=DS.X_test[0])
    host.submit(1, 7, features=DS.X_test[1])  # bare int still accepted
    done = host.run(max_steps=100)
    admitted = [r for r in (0, 1) if r not in host.dropped]
    assert sorted(done) == sorted(admitted)
    for r in admitted:
        assert len(done[r]) == 3


# ---------------------------------------------------------------------------
# On-device sampling: seeds, temperature, determinism
# ---------------------------------------------------------------------------
# temperature 2.0 on purpose: the smoke model's logits are peaked
# enough that lower temperatures collapse sampled streams onto the
# greedy argmax, making every assertion here vacuous.  top_p stays at
# the 1.0 default for the same reason (the top token usually holds
# > 95% of the mass, so any real nucleus keeps only it); the top_p
# code path is exercised by the parity test below.
SAMPLED = dict(temperature=2.0, top_k=40)


def test_sampled_streams_diverge_from_greedy(engine):
    """Non-vacuity guard for everything below: at temperature 2.0 the
    sampled streams must actually differ from greedy ones (if they
    don't, the sampling tests assert nothing)."""
    prompts = _prompts(8)
    greedy = DeviceContinuousBatcher(_paged_engine(engine), eos_token=-1,
                                     max_tokens=6, sync_every=3,
                                     prefill_chunk=4)
    sampled = DeviceContinuousBatcher(_paged_engine(engine, **SAMPLED),
                                      eos_token=-1, max_tokens=6,
                                      sync_every=3, prefill_chunk=4)
    g = _run_prompt_workload(greedy, prompts)
    s = _run_prompt_workload(sampled, prompts)
    assert sorted(g) == sorted(s)  # same admissions either way
    assert g != s, "temperature 2.0 reproduced the greedy streams"


def test_sampled_seed_reproducibility(engine):
    """Same per-request seeds => bitwise-identical sampled streams on a
    fresh batcher; different seeds => different streams.  Defaulted
    seeds (hash of the request id) reproduce the same way."""
    prompts = _prompts(8)

    def run(seed_of):
        cb = DeviceContinuousBatcher(_paged_engine(engine, **SAMPLED),
                                     eos_token=-1, max_tokens=6,
                                     sync_every=3, prefill_chunk=4)
        for rid, p in enumerate(prompts):
            cb.submit(rid, p, features=DS.X_test[rid],
                      seed=seed_of(rid))
        return dict(cb.run(max_steps=600))

    a = run(lambda r: 1000 + r)
    b = run(lambda r: 1000 + r)
    assert a == b, "same seeds did not reproduce the sampled streams"
    c = run(lambda r: 7000 + r)
    assert a != c, "different seeds produced identical sampled streams"
    d1 = run(lambda r: None)  # default: derived from the request id
    d2 = run(lambda r: None)
    assert d1 == d2, "defaulted seeds did not reproduce"


def test_temperature_zero_bitwise_greedy_all_paths(engine):
    """``temperature=0`` must be bitwise-identical to the greedy
    default on the host batcher, the device batcher (dense AND paged)
    and the mesh-less sharded router — sampling machinery must cost
    nothing when it is off."""
    from repro.serve.router import ShardedServe

    prompts = _prompts(8)
    eng, res = engine

    def pair(mk):
        return (_run_prompt_workload(mk(dict()), prompts),
                _run_prompt_workload(mk(dict(temperature=0.0)), prompts))

    g, z = pair(lambda kw: ContinuousBatcher(
        _paged_engine(engine, **kw), eos_token=-1, max_tokens=5))
    assert g == z
    g, z = pair(lambda kw: DeviceContinuousBatcher(
        _paged_engine(engine, **kw), eos_token=-1, max_tokens=5,
        sync_every=3, prefill_chunk=4))
    assert g == z
    # dense device path takes single-token prompts only
    g = _run_workload(DeviceContinuousBatcher(
        _fresh_engine(engine), eos_token=-1, max_tokens=5, sync_every=3))
    z = _run_workload(DeviceContinuousBatcher(
        ServeEngine(eng.cfg, eng.params,
                    ServeConfig(max_batch=4, cache_len=32,
                                temperature=0.0), gate=res.mapped),
        eos_token=-1, max_tokens=5, sync_every=3))
    assert g == z
    scfg = dict(max_batch=4, cache_len=32, page_size=8)

    def shard(kw):
        srv = ShardedServe(eng.cfg, eng.params,
                           ServeConfig(**scfg, **kw), None,
                           gate=res.mapped, eos_token=-1, max_tokens=5,
                           sync_every=3, prefill_chunk=4, n_shards=2)
        return srv

    g = _run_prompt_workload(shard(dict()), prompts)
    z = _run_prompt_workload(shard(dict(temperature=0.0)), prompts)
    assert g == z


def test_sampled_host_device_parity_and_sync_invariance(engine):
    """One sampling definition everywhere: the host batcher and device
    batchers at different ``sync_every``/``prefill_chunk`` settings
    must produce identical sampled streams — the noise is keyed by
    (seed, position), never by wave or drain boundaries."""
    prompts = _prompts(8)
    host = ContinuousBatcher(_paged_engine(engine, **SAMPLED),
                             eos_token=-1, max_tokens=6)
    d1 = DeviceContinuousBatcher(_paged_engine(engine, **SAMPLED),
                                 eos_token=-1, max_tokens=6,
                                 sync_every=3, prefill_chunk=4)
    d2 = DeviceContinuousBatcher(_paged_engine(engine, **SAMPLED),
                                 eos_token=-1, max_tokens=6,
                                 sync_every=7, prefill_chunk=2)
    oh = _run_prompt_workload(host, prompts)
    o1 = _run_prompt_workload(d1, prompts)
    o2 = _run_prompt_workload(d2, prompts)
    assert oh == o1 == o2
    # nucleus-filter path coverage (top_p < 1.0 mostly reproduces
    # greedy on this peaked smoke model, so only parity is asserted)
    nuc = dict(temperature=2.0, top_k=40, top_p=0.95)
    hn = ContinuousBatcher(_paged_engine(engine, **nuc), eos_token=-1,
                           max_tokens=6)
    dn = DeviceContinuousBatcher(_paged_engine(engine, **nuc),
                                 eos_token=-1, max_tokens=6,
                                 sync_every=3, prefill_chunk=4)
    assert (_run_prompt_workload(hn, prompts)
            == _run_prompt_workload(dn, prompts))


def test_sampled_sharded_matches_single_host(engine):
    """Sampling on the mesh-less router: each request's stream is keyed
    by its own seed, so a 2-shard fleet must reproduce the single-host
    batcher's sampled streams request-for-request."""
    from repro.serve.router import ShardedServe

    eng, res = engine
    prompts = _prompts(8)
    single = DeviceContinuousBatcher(_paged_engine(engine, **SAMPLED),
                                     eos_token=-1, max_tokens=6,
                                     sync_every=3, prefill_chunk=4)
    ref = _run_prompt_workload(single, prompts)
    srv = ShardedServe(eng.cfg, eng.params,
                       ServeConfig(max_batch=4, cache_len=32, page_size=8,
                                   **SAMPLED), None, gate=res.mapped,
                       eos_token=-1, max_tokens=6, sync_every=3,
                       prefill_chunk=4, n_shards=2)
    got = _run_prompt_workload(srv, prompts)
    assert got == ref


# ---------------------------------------------------------------------------
# Speculative decoding: gate-drafted bigram proposer + chunked verify
# ---------------------------------------------------------------------------


def _trained_draft(engine, prompts, max_tokens=6):
    """Greedy baseline streams -> bigram draft (the draft imitates the
    LM it speculates for), plus the baseline's done dict for parity."""
    from repro.serve.spec import train_draft

    eng, _ = engine
    base = DeviceContinuousBatcher(_paged_engine(engine), eos_token=-1,
                                   max_tokens=max_tokens, sync_every=3,
                                   prefill_chunk=4)
    done = dict(_run_prompt_workload(base, prompts))
    chains = [list(prompts[r]) + list(t) for r, t in done.items()]
    return train_draft(chains, vocab_size=eng.cfg.vocab_size), done


def test_spec_greedy_parity_and_acceptance(engine):
    """Speculative greedy decode must be bitwise-invisible: token
    streams identical to the non-speculative baseline, while the
    acceptance counters prove drafts actually landed."""
    prompts = _prompts(8)
    draft, done_ref = _trained_draft(engine, prompts)
    spec = DeviceContinuousBatcher(_paged_engine(engine), eos_token=-1,
                                   max_tokens=6, sync_every=3,
                                   prefill_chunk=4, spec_k=3, draft=draft)
    done = dict(_run_prompt_workload(spec, prompts))
    assert done == done_ref
    st = spec.spec_stats()
    assert st["spec_k"] == 3
    assert st["drafted"] > 0 and st["accepted"] > 0
    assert 0.0 < st["acceptance_rate"] <= 1.0


def test_spec_eos_parity(engine):
    """Mid-chain EOS: speculative emission must truncate exactly where
    the baseline stops (EOS inside an accepted draft chain cannot leak
    extra tokens)."""
    prompts = _prompts(8)
    draft, done_ref = _trained_draft(engine, prompts)
    # pick a token the LM actually emits so EOS fires mid-stream
    eos = next(int(t[1]) for t in done_ref.values() if len(t) > 1)

    def run(**kw):
        cb = DeviceContinuousBatcher(_paged_engine(engine), eos_token=eos,
                                     max_tokens=6, sync_every=3,
                                     prefill_chunk=4, **kw)
        return dict(_run_prompt_workload(cb, prompts))

    assert run(spec_k=3, draft=draft) == run()


def test_spec_sampled_smoke(engine):
    """Speculative + sampled (rejection sampling): the combination must
    serve every admitted request with valid streams and accumulate
    acceptance stats.  NOTE: sampled spec streams are NOT asserted
    equal to non-spec sampled streams — rejection sampling preserves
    the distribution, not the realized sample path."""
    prompts = _prompts(8)
    draft, _ = _trained_draft(engine, prompts)
    plain = DeviceContinuousBatcher(_paged_engine(engine, **SAMPLED),
                                    eos_token=-1, max_tokens=6,
                                    sync_every=3, prefill_chunk=4)
    ref = dict(_run_prompt_workload(plain, prompts))
    spec = DeviceContinuousBatcher(_paged_engine(engine, **SAMPLED),
                                   eos_token=-1, max_tokens=6,
                                   sync_every=3, prefill_chunk=4,
                                   spec_k=3, draft=draft)
    done = dict(_run_prompt_workload(spec, prompts))
    assert sorted(done) == sorted(ref)  # same admissions
    for toks in done.values():
        assert 1 <= len(toks) <= 6
    st = spec.spec_stats()
    assert st["drafted"] > 0
    # reproducibility still holds under speculation: same seeds, same
    # streams
    spec2 = DeviceContinuousBatcher(_paged_engine(engine, **SAMPLED),
                                    eos_token=-1, max_tokens=6,
                                    sync_every=3, prefill_chunk=4,
                                    spec_k=3, draft=draft)
    assert dict(_run_prompt_workload(spec2, prompts)) == done


def test_spec_ctor_validation(engine):
    """spec_k needs the paged cache and a compiled draft whose table
    covers the LM vocab — each misuse is a loud ctor error, not a
    silent fallback."""
    from repro.serve.spec import train_draft

    draft, _ = _trained_draft(engine, _prompts(4))
    with pytest.raises(ValueError):
        DeviceContinuousBatcher(_fresh_engine(engine), eos_token=-1,
                                max_tokens=4, spec_k=2, draft=draft)
    with pytest.raises(ValueError):
        DeviceContinuousBatcher(_paged_engine(engine), eos_token=-1,
                                max_tokens=4, spec_k=2, draft=None)
    small = train_draft([[1, 2, 3, 1, 2]], vocab_size=8)
    with pytest.raises(ValueError):
        DeviceContinuousBatcher(_paged_engine(engine), eos_token=-1,
                                max_tokens=4, spec_k=2, draft=small)


def test_spec_traced_run_rejected(engine):
    """A traced speculative run is no longer rejected: the tracer reads
    the fused step's own stamps, so speculation serves the same streams
    traced as untraced and every lifecycle is complete and ordered."""
    from repro.obs import Metrics, Tracer

    draft, _ = _trained_draft(engine, _prompts(4))

    def run(**kw):
        cb = DeviceContinuousBatcher(_paged_engine(engine), eos_token=-1,
                                     max_tokens=4, sync_every=3,
                                     prefill_chunk=4, spec_k=2,
                                     draft=draft, **kw)
        cb.submit(0, [3, 5], features=DS.X_test[0])
        return cb, cb.run(max_steps=10)

    mx = Metrics()
    tr = Tracer(metrics=mx)
    cb, got = run(tracer=tr, metrics=mx)
    assert got == run()[1]
    assert tr.validate() == []
    assert all(r.terminal is not None for r in tr.requests.values())
