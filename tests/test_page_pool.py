"""Property-test harness for the refcounted, prefix-sharing page pool.

Random submit/evict/resume interleavings over ``repro.serve.pages`` pin
the allocator invariants the whole paged serve path leans on:

* **no double allocation** — own pages of concurrent reservations are
  pairwise disjoint (and disjoint from cached prefix pages);
* **refcounts match live references** — ``ref[p]`` equals the number of
  live tables containing ``p`` plus one if ``p`` is trie-cached, and is
  never negative;
* **conservation** — after every wave drains,
  ``freed + cached == pool size`` (with live reservations in flight,
  the per-page count identity above is the stronger form);
* **COW never mutates a page with refcount > 1** — the copy target is
  a fresh own page with exactly one reference, invisible to the trie
  and to every other reservation.

Both allocation protocols are exercised: the host batcher's atomic
``reserve``/``release`` and the device batcher's split protocol
(``plan`` at wave build, in-step fill/evict mimicked here, then
``register_completed`` at drain).  A third, model-backed test drives
``DeviceContinuousBatcher`` itself through random bounded ``run()``
calls (the resume path) and checks the pool after every wave.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.serve.pages import PagePool, page_demand

PAGE = 4
MAX_TOKENS = 3
VOCAB = 5  # tiny vocab => prompts collide on prefixes constantly


def _check_invariants(pool: PagePool, live):
    """``live``: list of (Reservation, prompt) — the harness's model of
    truth, checked against the pool's refcounts after every op."""
    counts = np.zeros(pool.n, np.int64)
    for res, _ in live:
        assert len(set(res.tbl)) == len(res.tbl)  # table never repeats
        np.add.at(counts, np.asarray(res.tbl, np.int64), 1)
    cached = pool.cached_pages()
    for pid in cached:
        counts[pid] += 1
    np.testing.assert_array_equal(counts, pool.ref)
    assert (pool.ref >= 0).all()
    own = [p for res, _ in live for p in res.tbl[res.n_shared:]]
    assert len(own) == len(set(own)), "own page double-allocated"
    assert not (set(own) & cached), "own page aliases a cached page"
    assert pool.n_cached <= pool.hold_budget


def _random_prompt(rng) -> list:
    plen = int(rng.integers(1, 3 * PAGE + 2))
    return [int(t) for t in rng.integers(0, VOCAB, plen)]


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_pool_reserve_release_invariants(seed):
    """Host protocol: random reserve/release interleavings keep every
    refcount equal to its live-reference count, never double-allocate,
    and COW only ever targets a freshly owned page."""
    rng = np.random.default_rng(seed)
    pool = PagePool(12, PAGE, share_prefix=True)
    live = []
    pool.begin_wave()
    for _ in range(60):
        op = int(rng.integers(0, 3))
        if op <= 1 or not live:  # submit-biased interleaving
            prompt = _random_prompt(rng)
            res = pool.reserve(prompt, MAX_TOKENS)
            if res is not None:
                assert len(res.tbl) == page_demand(PAGE, len(prompt),
                                                   MAX_TOKENS)
                # the final prompt token is never shared away
                assert res.n_shared * PAGE <= res.start <= len(prompt) - 1
                if res.cow is not None:
                    src, dst = res.cow
                    assert src != dst
                    assert dst == res.tbl[res.n_shared]  # first own page
                    assert pool.ref[dst] == 1, \
                        "COW target visible to another reference"
                    assert dst not in pool.cached_pages()
                live.append((res, prompt))
        else:
            res, prompt = live.pop(int(rng.integers(0, len(live))))
            pool.release(res, prompt)
        _check_invariants(pool, live)
    while live:  # drain the wave
        res, prompt = live.pop()
        pool.release(res, prompt)
        _check_invariants(pool, live)
    # conservation once everything is released: freed + cached == pool
    assert int((pool.ref == 0).sum()) + pool.n_cached == pool.n


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_pool_device_protocol_invariants(seed):
    """Device protocol: plan at wave build, fill/evict refcounting as
    the fused step does it (own pages from ref==0, +1 per table page,
    -1 on evict except held full-prompt pages), then drain-time
    registration.  Same invariants, plus wave conservation."""
    rng = np.random.default_rng(seed)
    pool = PagePool(12, PAGE, share_prefix=True)
    for _ in range(8):  # waves
        pool.begin_wave()
        live = []
        for _ in range(int(rng.integers(1, 6))):
            prompt = _random_prompt(rng)
            plan = pool.plan(prompt, MAX_TOKENS)
            if pool.free_count() < plan.own:
                continue  # FIFO-blocked entry: never filled
            own = [int(p) for p in np.where(pool.ref == 0)[0][:plan.own]]
            tbl = list(plan.shared) + own
            for p in tbl:  # in-step fill: one reference per table page
                pool.ref[p] += 1
            if plan.cow_src is not None:
                dst = tbl[len(plan.shared)]
                assert dst != plan.cow_src
                assert pool.ref[dst] == 1, \
                    "COW would mutate a page with refcount > 1"
            live.append((tbl, prompt, plan))
        # resume boundary: half the slots survive into a "second run"
        # (their references must hold), the rest evict now
        rng.shuffle(live)
        for phase in (live[len(live) // 2:], live[: len(live) // 2]):
            for tbl, prompt, plan in phase:
                nfp = len(prompt) // PAGE
                for j, p in enumerate(tbl):  # in-step evict
                    if not (plan.reg and j < nfp):
                        pool.ref[p] -= 1
                if plan.reg:  # drain-time registration
                    pool.register_completed(prompt, tbl[:nfp])
                assert (pool.ref >= 0).all()
        # after every wave: freed + cached == pool size
        assert int((pool.ref == 0).sum()) + pool.n_cached == pool.n
        assert pool.n_cached <= pool.hold_budget


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_pool_pressure_release_keeps_pinned(seed):
    """Cached prefixes release under pool pressure (LRU leaf-first) but
    pinned pages — the ones a pending wave shares — survive, and a
    reservation that shares pages never loses them mid-flight."""
    rng = np.random.default_rng(seed)
    pool = PagePool(8, PAGE, share_prefix=True)
    pool.begin_wave()
    base = [int(t) for t in rng.integers(0, VOCAB, 2 * PAGE)]
    first = pool.reserve(base + [1], MAX_TOKENS)
    assert first is not None
    pool.release(first, base + [1])  # registers base's full pages
    cached_before = pool.cached_pages()
    assert cached_before
    sharer = pool.reserve(base + [2], MAX_TOKENS)
    assert sharer is not None and sharer.n_shared > 0
    # flood the pool: reservations that force pressure releases
    flood = []
    for _ in range(6):
        r = pool.reserve(_random_prompt(rng), MAX_TOKENS)
        if r is not None:
            flood.append((r, None))
    # the sharer's shared pages still carry its reference
    for p in sharer.tbl[: sharer.n_shared]:
        assert pool.ref[p] >= 1
    assert (pool.ref >= 0).all()


def test_hold_budget_enforced_across_waves():
    """The cap on cached pages holds even when requests are admitted on
    different waves (plan-time budgeting resets per wave, so the cap is
    enforced at registration — the point of truth)."""
    pool = PagePool(16, PAGE, share_prefix=True, hold_budget=2)
    a_prompt = [1, 1, 1, 1, 2, 2, 2, 2, 9]   # 2 full pages
    b_prompt = [3, 3, 3, 3, 4, 4, 4, 4, 9]   # 2 different full pages
    pool.begin_wave()
    a = pool.reserve(a_prompt, MAX_TOKENS)
    pool.begin_wave()  # the host batcher resets every fill pass
    b = pool.reserve(b_prompt, MAX_TOKENS)
    pool.release(a, a_prompt)
    pool.release(b, b_prompt)
    assert pool.n_cached <= 2
    # and refcounts stay exact: every cached page holds exactly one ref
    held = np.where(pool.ref > 0)[0]
    assert set(held.tolist()) == pool.cached_pages()
    assert (pool.ref[held] == 1).all()


def test_stats_count_admitted_requests_once():
    """A FIFO-blocked head re-plans on every retry; the sharing metric
    counts a request only when its reservation lands (record_plan),
    so retries and never-admitted requests don't inflate it."""
    pool = PagePool(4, PAGE, share_prefix=True)
    big = [1] * (3 * PAGE)  # demand 4 pages: fills the whole pool
    res = pool.reserve(big, MAX_TOKENS)
    assert res is not None
    tokens_after_admit = pool.stats["prompt_page_tokens"]
    for _ in range(5):  # blocked head, re-planned every retry
        assert pool.plan(big, MAX_TOKENS) is not None
    assert pool.stats["prompt_page_tokens"] == tokens_after_admit
    pool.release(res, big)


@pytest.mark.parametrize("seed", [0, 1])
def test_batcher_interleaved_submit_resume_invariants(seed, _pool_engine):
    """End to end: DeviceContinuousBatcher under random interleavings of
    submit and bounded run() (the resume path).  After every run the
    pool mirror must satisfy the refcount invariants, and the final
    streams must match an un-interrupted reference batcher."""
    from repro.serve.engine import DeviceContinuousBatcher

    make_engine = _pool_engine
    rng = np.random.default_rng(seed)
    prefix = [int(t) for t in rng.integers(1, 9, 6)]
    prompts = [prefix + [int(t) for t in rng.integers(1, 97,
                                                      rng.integers(1, 5))]
               for _ in range(8)]
    ref = DeviceContinuousBatcher(make_engine(), eos_token=-1,
                                  max_tokens=3, sync_every=3,
                                  prefill_chunk=3)
    for rid, p in enumerate(prompts):
        ref.submit(rid, p)
    done_ref = dict(ref.run(max_steps=600))

    cb = DeviceContinuousBatcher(make_engine(), eos_token=-1, max_tokens=3,
                                 sync_every=2, prefill_chunk=3)
    pending = list(enumerate(prompts))
    for _ in range(200):
        while pending and rng.random() < 0.6:  # interleave submissions
            rid, p = pending.pop(0)
            cb.submit(rid, p)
        cb.run(max_steps=int(rng.integers(1, 6)))
        pool = cb.pool
        assert (pool.ref >= 0).all()
        live_pages = [int(p) for c in cb._carry if c is not None
                      for p in c["tbl"] if p < pool.n]
        counts = np.zeros(pool.n, np.int64)
        np.add.at(counts, live_pages, 1)
        for pid in pool.cached_pages():
            counts[pid] += 1
        np.testing.assert_array_equal(counts, pool.ref)
        if not pending and not cb.queue \
                and all(c is None for c in cb._carry):
            break
    assert cb.done == done_ref
    # drained: every remaining reference is exactly one cache hold
    held = np.where(cb.pool.ref > 0)[0]
    assert set(held.tolist()) == cb.pool.cached_pages()
    assert (cb.pool.ref[held] == 1).all()


@pytest.mark.parametrize("seed", [0, 1])
def test_batcher_fault_evictions_no_page_leak(seed, _pool_engine):
    """Exhaustion-recovery coverage for the fault path: mid-flight
    deadline evictions and poison quarantines (seeded CorruptTokens at
    drain boundaries) interleaved with submits and bounded run() must
    never strand a page — after every run() ``page_accounting`` over
    the live carry tables shows leaked == 0, and once drained
    ``freed + cached == pages`` exactly."""
    from repro.serve.engine import DeviceContinuousBatcher
    from repro.serve.faults import CorruptTokens, FaultPlan

    make_engine = _pool_engine
    rng = np.random.default_rng(seed)
    prefix = [int(t) for t in rng.integers(1, 9, 6)]
    prompts = [prefix + [int(t) for t in rng.integers(1, 97,
                                                      rng.integers(1, 5))]
               for _ in range(8)]
    # poison a random slot at several drain boundaries; whatever request
    # occupies it then is quarantined mid-flight (empty slots no-op)
    plan = FaultPlan([CorruptTokens(slot=int(rng.integers(0, 4)),
                                    at_drain=int(d))
                      for d in rng.integers(1, 12, 4)])
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    cb = DeviceContinuousBatcher(make_engine(), eos_token=-1, max_tokens=4,
                                 sync_every=2, prefill_chunk=3,
                                 fault_injector=plan.injector(),
                                 clock=clock)
    pending = list(enumerate(prompts))
    for _ in range(200):
        while pending and rng.random() < 0.6:
            rid, p = pending.pop(0)
            # a sprinkling of tight budgets => mid-flight deadline
            # evictions racing the quarantines for the same pages
            ddl = 3.0 if rng.random() < 0.4 else None
            cb.submit(rid, p, deadline_s=ddl)
        cb.run(max_steps=int(rng.integers(1, 6)))
        live = [c["tbl"] for c in cb._carry if c is not None]
        acct = cb.pool.page_accounting(live)
        assert acct["leaked"] == 0, acct
        assert acct["free"] + acct["cached"] + acct["live"] == cb.pool.n
        if not pending and not cb.queue \
                and all(c is None for c in cb._carry):
            break
    # every request reached a terminal state, exactly once
    assert sorted(list(cb.done) + list(cb.dropped)) == list(range(8))
    for rid in cb.dropped:
        assert cb.drop_reasons[rid] in ("deadline", "quarantined")
    acct = cb.pool.page_accounting()
    assert acct["leaked"] == 0 and acct["live"] == 0
    assert acct["free"] + acct["cached"] == cb.pool.n


def test_in_wave_cold_prefix_sharing(_pool_engine):
    """Identical full-page prefixes submitted in ONE wave to a COLD
    pool must share from wave 0: the wave plan dedupes the prefix
    inside the wave (no warm trie required), streams stay bit-identical
    to an unshared pool, and every page drains clean.

    Exactly ``max_batch`` requests => a single wave, so any
    ``shared_tokens`` here can only come from in-wave dedup (the trie
    is empty until the wave completes)."""
    from repro.serve.engine import DeviceContinuousBatcher

    prompts = [[5] * 17 + [i] for i in range(4)]  # 2 full pages shared

    def run(**kw):
        cb = DeviceContinuousBatcher(_pool_engine(pages=24, **kw),
                                     eos_token=-1, max_tokens=4,
                                     sync_every=3, prefill_chunk=4)
        for rid, p in enumerate(prompts):
            cb.submit(rid, p)
        done = dict(cb.run(max_steps=400))
        return cb, done

    un, done_un = run(share_prefix=False)
    sh, done_sh = run()
    assert done_sh == done_un, "in-wave sharing changed token streams"
    assert sh.pool.stats["shared_tokens"] > 0, (
        "cold identical prefixes in a single wave did not share — "
        "in-wave dedup is not running at wave 0")
    assert (sh.pool.ref >= 0).all()
    acct = sh.pool.page_accounting()
    assert acct["leaked"] == 0 and acct["live"] == 0


def test_in_wave_sharing_writer_death_recovers(_pool_engine):
    """When the wave's prefix WRITER dies (deadline eviction) before
    completing its prompt, the blocked in-wave readers must re-plan
    cold and still finish with the right streams — no hang, no leak."""
    from repro.serve.engine import DeviceContinuousBatcher

    prompts = [[5] * 17 + [i] for i in range(4)]

    ref = DeviceContinuousBatcher(_pool_engine(pages=24,
                                               share_prefix=False),
                                  eos_token=-1, max_tokens=4,
                                  sync_every=3, prefill_chunk=4)
    for rid in (1, 2, 3):
        ref.submit(rid, prompts[rid])
    done_ref = dict(ref.run(max_steps=400))

    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    cb = DeviceContinuousBatcher(_pool_engine(pages=24), eos_token=-1,
                                 max_tokens=4, sync_every=3,
                                 prefill_chunk=4, clock=clock)
    # request 0 is FIFO-first => it becomes the wave's prefix writer,
    # and its zero deadline kills it before the prefix completes
    cb.submit(0, prompts[0], deadline_s=0.0)
    for rid in (1, 2, 3):
        cb.submit(rid, prompts[rid])
    done = dict(cb.run(max_steps=400))
    assert 0 in cb.dropped and cb.drop_reasons[0] == "deadline"
    assert {r: done[r] for r in (1, 2, 3)} == done_ref, (
        "readers blocked on a dead writer diverged after re-planning")
    assert (cb.pool.ref >= 0).all()
    acct = cb.pool.page_accounting()
    assert acct["leaked"] == 0 and acct["live"] == 0


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_in_wave_cold_sharing_random_prefixes(seed, _pool_engine):
    """Property harness for in-wave sharing: random groups of prompts
    over a tiny vocab (constant full-page prefix collisions), all
    submitted COLD and drained through bounded run() calls (the resume
    path).  After every run the refcounts stay non-negative; the final
    streams must match an unshared reference and the pool must account
    for every page."""
    from repro.serve.engine import DeviceContinuousBatcher

    rng = np.random.default_rng(seed)
    page = 8
    prompts = []
    for _ in range(3):  # groups sharing 1-2 full pages of prefix
        d = int(rng.integers(1, 3))
        prefix = [int(t) for t in rng.integers(1, 4, d * page)]
        for _ in range(int(rng.integers(2, 4))):
            tail = [int(t) for t in rng.integers(1, 97,
                                                 rng.integers(1, 4))]
            prompts.append(prefix + tail)
    rng.shuffle(prompts)

    def drain(cb, step_rng):
        for rid, p in enumerate(prompts):
            cb.submit(rid, p)
        for _ in range(200):
            cb.run(max_steps=int(step_rng.integers(2, 8)))
            assert (cb.pool.ref >= 0).all()
            if not cb.queue and all(c is None for c in cb._carry):
                break
        return dict(cb.done)

    ref = DeviceContinuousBatcher(_pool_engine(pages=40,
                                               share_prefix=False),
                                  eos_token=-1, max_tokens=3,
                                  sync_every=2, prefill_chunk=4)
    done_ref = drain(ref, np.random.default_rng(seed + 100))
    cb = DeviceContinuousBatcher(_pool_engine(pages=40), eos_token=-1,
                                 max_tokens=3, sync_every=2,
                                 prefill_chunk=4)
    done_sh = drain(cb, np.random.default_rng(seed + 100))
    assert done_sh == done_ref
    acct = cb.pool.page_accounting()
    assert acct["leaked"] == 0 and acct["live"] == 0
    assert acct["free"] + acct["cached"] == cb.pool.n


@pytest.fixture(scope="module")
def _pool_engine():
    import jax

    from repro.arch import model as M
    from repro.configs import get_smoke_config
    from repro.serve.engine import ServeConfig, ServeEngine

    cfg = get_smoke_config("qwen2_1_5b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))

    def make(**kw):
        kw.setdefault("share_prefix", True)
        return ServeEngine(cfg, params,
                           ServeConfig(max_batch=4, cache_len=32,
                                       page_size=8, **kw))

    return make
