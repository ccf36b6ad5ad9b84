"""Observability: tracer lifecycle, metrics registry, chrome export.

The integration half pins the ``repro.obs`` contract on real serve
runs: traced token streams bit-identical to untraced, every admitted
request reaching exactly one terminal event (including across bounded
run() resumes, speculation, in-wave prefix sharing, in-step gate drops
and pool-exhaust faults), host ``done_at`` and tracer drain stamps
agreeing on the same clock, and the fused step's stamp steps staying
absolute across runs.  It also pins the marks the profiler sees: the
named scopes in the compiled paged step and the batcher's ``serve.*``
host spans in a CPU profiler trace.  The unit half pins the histogram
bucket geometry, the in-place metrics reset (cached instrument handles
must survive), the deferred-emission flush, and the Chrome trace-event
JSON schema.
"""
import glob
import json
import re

import jax
import numpy as np
import pytest

from repro.arch import model as M
from repro.configs import get_smoke_config
from repro.core import PlanterConfig, plant
from repro.data import load_dataset
from repro.obs import Histogram, Metrics, Tracer
from repro.obs.trace import step_time_interp
from repro.serve.engine import (ContinuousBatcher, DeviceContinuousBatcher,
                                ServeConfig, ServeEngine)
from repro.serve.faults import FaultPlan, PoolExhaust
from repro.serve.spec import train_draft

DS = load_dataset("unsw", n=2000)


# ---------------------------------------------------------------- unit: metrics
def test_histogram_bucket_edges():
    h = Histogram(lo=1e-3, hi=1e5, per_decade=4)
    assert h.edges[0] == pytest.approx(1e-3)
    assert h.edges[-1] == pytest.approx(1e5)
    assert len(h.counts) == len(h.edges) + 1
    # an exact edge value belongs to the bucket it opens
    for i, e in enumerate(h.edges[:-1]):
        assert h._bucket(e) == i + 1, e
    assert h._bucket(5e-4) == 0                  # underflow
    assert h._bucket(2e5) == len(h.counts) - 1   # overflow
    # bucket inversion agrees with a linear scan everywhere
    rng = np.random.default_rng(0)
    for v in 10.0 ** rng.uniform(-4, 6, 200):
        b = h._bucket(float(v))
        if v < h.edges[0]:
            assert b == 0
        elif v >= h.edges[-1]:
            assert b == len(h.counts) - 1
        else:
            assert h.edges[b - 1] <= v < h.edges[b]


def test_histogram_merge_by_adding_counts():
    a, b = Histogram(), Histogram()
    rng = np.random.default_rng(1)
    va, vb = rng.exponential(5, 50), rng.exponential(50, 50)
    for v in va:
        a.observe(v)
    for v in vb:
        b.observe(v)
    merged = Histogram()
    for v in np.concatenate([va, vb]):
        merged.observe(v)
    assert a.edges == b.edges == merged.edges  # fixed geometry
    assert [x + y for x, y in zip(a.counts, b.counts)] == merged.counts


def test_histogram_percentiles():
    h = Histogram(lo=1.0, hi=1e3, per_decade=10)
    assert h.percentile(50) is None  # empty
    for v in range(1, 101):
        h.observe(float(v))
    assert h.percentile(50) == pytest.approx(50, rel=0.2)
    assert h.percentile(99) == pytest.approx(99, rel=0.2)


def test_metrics_reset_keeps_handles_live():
    m = Metrics()
    c, g, h = m.counter("c"), m.gauge("g"), m.histogram("h")
    c.inc(3)
    g.set(7)
    h.observe(1.0)
    m.reset()
    assert c.value == 0 and g.value is None and h.count == 0
    c.inc()  # cached handle still feeds the registry after reset
    h.observe(2.0)
    snap = m.snapshot()
    assert snap["counters"]["c"] == 1
    assert snap["histograms"]["h"]["count"] == 1


# ----------------------------------------------------------------- unit: tracer
def test_tracer_lifecycle_rules():
    tr = Tracer()
    tr.submitted("r", t=10.0)
    tr.submitted("r", t=12.0)  # late re-stamp must not erase queue wait
    assert tr.requests["r"].t_submit == 10.0
    tr.admitted("r", t=11.0)
    tr.first_token("r", t=11.5)
    tr.finished("r", n_tokens=4, t=12.5)
    with pytest.raises(ValueError):  # exactly one terminal
        tr.dropped("r", "gate-reject", t=13.0)
    assert tr.validate() == []
    tr.admitted("s", t=1.0)  # admitted but never terminal
    assert any("never terminal" in p for p in tr.validate())


def test_tracer_deferred_emission_flush():
    tr = Tracer()
    order = []
    tr.defer(lambda: (order.append(1), tr.admitted("a", t=1.0)))
    tr.defer(lambda: (order.append(2), tr.finished("a", t=2.0)))
    assert order == []  # nothing runs on the hot path
    assert tr.requests["a"].terminal == "done"  # first read flushes, FIFO
    assert order == [1, 2]
    tr.defer(lambda: order.append(3))
    tr.reset()  # reset drops unflushed emission with the data
    assert tr.requests == {} and order == [1, 2]


def test_step_time_interp_clamps_and_interpolates():
    f = step_time_interp([(0, 10.0), (4, 14.0), (8, 16.0)])
    assert f(-1) == 10.0 and f(12) == 16.0  # clamped to the run window
    assert f(2) == pytest.approx(12.0)
    assert f(6) == pytest.approx(15.0)
    ts = [f(s) for s in range(-1, 13)]
    assert ts == sorted(ts)  # monotone


def test_chrome_trace_schema():
    tr = Tracer()
    tr.submitted("q", t=tr.epoch)
    tr.admitted("q", t=tr.epoch + 0.1, step=1, shard=2)
    tr.first_token("q", t=tr.epoch + 0.2, step=3)
    tr.finished("q", n_tokens=5, t=tr.epoch + 0.3, step=7)
    tr.drained("q", t=tr.epoch + 0.4)
    tr.dropped("d", "gate-reject", t=tr.epoch + 0.2)
    tr.span("bench", tr.epoch, tr.epoch + 1.0, tid=1, wave=0)
    tr.instant("rebalance", t=tr.epoch + 0.5)
    ct = json.loads(json.dumps(tr.chrome_trace()))  # JSON-serialisable
    assert set(ct) == {"traceEvents", "displayTimeUnit"}
    for e in ct["traceEvents"]:
        assert {"name", "ph", "pid", "tid"} <= set(e)
        assert e["ph"] in {"X", "i", "M"}
        if e["ph"] == "X":
            assert e["ts"] >= 0 and e["dur"] >= 0
    names = {e["name"] for e in ct["traceEvents"]}
    # all four request phases, the drop instant, metadata thread names
    assert {"queued", "prefill", "decode", "drained",
            "drop:gate-reject", "thread_name"} <= names
    ev = [e for e in ct["traceEvents"] if e["ph"] != "M"]
    assert [e["ts"] for e in ev] == sorted(e["ts"] for e in ev)


# ------------------------------------------------------------------ integration
@pytest.fixture(scope="module")
def planted():
    cfg = get_smoke_config("qwen2_1_5b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    res = plant(PlanterConfig(model="rf", size="S"), DS.X_train,
                DS.y_train, DS.X_test)
    return cfg, params, res.mapped


def _submit_all(cb, n_req=10, prompt_fn=None):
    rng = np.random.default_rng(0)
    for rid in range(n_req):
        p = (int(rng.integers(1, 100)) if prompt_fn is None
             else prompt_fn(rid, rng))
        cb.submit(rid, p, features=DS.X_test[rid])


def _dense_batcher(planted, **kw):
    cfg, params, gate = planted
    eng = ServeEngine(cfg, params, ServeConfig(max_batch=4, cache_len=32),
                      gate=gate)
    return DeviceContinuousBatcher(eng, eos_token=-1, max_tokens=4,
                                   sync_every=3, **kw)


@pytest.fixture(scope="module")
def dense_runs(planted):
    """One untraced and one traced device run over the same workload."""
    ref_cb = _dense_batcher(planted)
    _submit_all(ref_cb)
    ref = ref_cb.run(max_steps=300)
    mx = Metrics()
    tr = Tracer(metrics=mx)
    cb = _dense_batcher(planted, tracer=tr, metrics=mx)
    _submit_all(cb)
    got = cb.run(max_steps=300)
    return ref, got, cb, tr, mx


def test_traced_streams_bit_identical(dense_runs):
    ref, got, *_ = dense_runs
    assert got == ref


def test_traced_lifecycle_complete(dense_runs):
    _, got, cb, tr, _ = dense_runs
    assert tr.validate() == []
    term = [r for r in tr.requests.values() if r.terminal is not None]
    assert len(term) == 10  # every submitted request reached a terminal
    fin = {r.rid: r for r in term if r.terminal == "done"}
    assert set(fin) == set(got)
    for rid, r in fin.items():
        assert r.n_tokens == len(got[rid])
        # tracer drain stamp IS the done_at stamp (same clock, same
        # sync trip) — they can never disagree about ordering
        assert cb.done_at[rid] == r.t_drain


def test_drain_order_timestamps_non_decreasing(dense_runs):
    *_, cb, _, _ = dense_runs
    stamps = list(cb.done_at.values())  # dict preserves drain order
    assert stamps == sorted(stamps)


def test_metrics_fed_by_traced_run(dense_runs):
    _, got, _, _, mx = dense_runs
    snap = mx.snapshot()
    assert snap["counters"]["serve.requests_done"] == len(got)
    assert snap["counters"]["serve.tokens_generated"] == sum(
        len(v) for v in got.values())
    assert snap["counters"]["serve.requests_dropped"] == 10 - len(got)
    assert snap["histograms"]["serve.ttft_ms"]["count"] == len(got)
    pct = dense_runs[3].phase_percentiles()
    assert pct["ttft_ms"]["n"] == len(got)
    assert pct["ttft_ms"]["p50"] > 0


def test_resume_keeps_lifecycle_and_absolute_steps(planted, dense_runs):
    ref = dense_runs[0]
    tr = Tracer()
    cb = _dense_batcher(planted, tracer=tr)
    _submit_all(cb)
    cb.run(max_steps=2)   # bounded: most requests still in flight
    cb.run(max_steps=300)  # resume drains the rest
    assert cb.done == ref  # resume replays the exact schedule
    assert tr.validate() == []
    steps = [r.step_done for r in tr.requests.values()
             if r.step_done is not None]
    # step numbers are absolute across run() calls, not per-run
    assert steps and max(steps) >= 3


def test_host_batcher_traced(planted, dense_runs):
    ref = dense_runs[0]
    cfg, params, gate = planted
    eng = ServeEngine(cfg, params, ServeConfig(max_batch=4, cache_len=32),
                      gate=gate)
    tr = Tracer(metrics=Metrics())
    cb = ContinuousBatcher(eng, eos_token=-1, max_tokens=4, tracer=tr)
    _submit_all(cb)
    got = cb.run(max_steps=300)
    assert got == ref  # host and device paths agree traced too
    assert tr.validate() == []
    for r in tr.requests.values():
        if r.terminal == "done":
            assert cb.done_at[r.rid] == r.t_done == r.t_drain


def test_paged_traced_parity_and_prefix_metrics(planted):
    cfg, params, gate = planted
    scfg = ServeConfig(max_batch=4, cache_len=32, page_size=8, pages=16,
                       share_prefix=True)
    shared = [5, 6, 7, 8, 9, 10, 11, 12]

    def pfn(rid, rng):
        return shared + [int(rng.integers(1, 100))]

    def build(**kw):
        eng = ServeEngine(cfg, params, scfg, gate=gate)
        return DeviceContinuousBatcher(eng, eos_token=-1, max_tokens=4,
                                       sync_every=3, prefill_chunk=4, **kw)

    ref_cb = build()
    _submit_all(ref_cb, prompt_fn=pfn)
    ref = ref_cb.run(max_steps=300)
    mx = Metrics()
    tr = Tracer(metrics=mx)
    cb = build(tracer=tr, metrics=mx)
    _submit_all(cb, prompt_fn=pfn)
    got = cb.run(max_steps=300)
    assert got == ref
    # second wave hits the prefix trie the first wave registered
    rng = np.random.default_rng(1)
    for rid in range(100, 104):
        cb.submit(rid, pfn(rid, rng), features=DS.X_test[rid])
    cb.run(max_steps=300)
    assert tr.validate() == []
    snap = mx.snapshot()
    assert snap["counters"].get("pool.prefix_hits", 0) > 0
    assert snap["gauges"]["pool.free_pages"] >= 0
    ct = cb.tracer.chrome_trace()
    json.dumps(ct)
    assert any(e["ph"] == "X" and e["name"] == "decode"
               for e in ct["traceEvents"])


# ------------------------------------------------ integration: step stamps
SCOPES = ("gate", "kv", "attention", "lm_head", "sample")
SPANS = ("serve.admit", "serve.build", "serve.upload", "serve.launch",
         "serve.sync", "serve.drain")


def _paged_batcher(planted, scfg_kw=None, **kw):
    cfg, params, gate = planted
    scfg = ServeConfig(max_batch=4, cache_len=32, page_size=8,
                       **(scfg_kw or {}))
    eng = ServeEngine(cfg, params, scfg, gate=gate)
    return DeviceContinuousBatcher(eng, eos_token=-1, max_tokens=4,
                                   sync_every=3, prefill_chunk=4, **kw)


def _prompts(n, seed=0):
    rng = np.random.default_rng(seed)
    return {rid: [int(t) for t in rng.integers(1, 97, rng.integers(2, 12))]
            for rid in range(n)}


def _serve(cb, prompts, features=True, max_steps=300):
    for rid, p in prompts.items():
        cb.submit(rid, p, features=DS.X_test[rid] if features else None)
    while cb.pending_work():
        cb.run(max_steps=max_steps)
    return dict(cb.done)


def _draft(planted, prompts):
    base = _paged_batcher(planted)
    done = _serve(base, prompts, features=False)
    chains = [list(prompts[r]) + list(t) for r, t in done.items()]
    return train_draft(chains, vocab_size=planted[0].vocab_size)


def _case(planted, case):
    """(prompts, submit features?, batcher kwargs maker, ServeConfig
    kwargs) of each schedule the host replay could not follow."""
    prompts = _prompts(8)
    if case == "spec_k":
        draft = _draft(planted, prompts)
        return prompts, False, lambda: dict(spec_k=3, draft=draft), {}
    if case == "in_wave_sharing":
        # identical cold full-page prefixes in one wave: the readers
        # wait in the step for the writer's prefill
        return ({i: [5] * 17 + [i] for i in range(4)}, False, dict,
                dict(pages=24, share_prefix=True))
    if case == "pool_exhaust":
        plan = FaultPlan([PoolExhaust(at_drain=1, hold_drains=3)])
        return (prompts, False,
                lambda: dict(fault_injector=plan.injector()), {})
    assert case == "in_step_gate"  # the gate's verdict lands in the step
    return prompts, True, lambda: dict(pregate=False), {}


def _check_lifecycles(cb, tr, rids):
    assert tr.validate() == []
    for rid in rids:
        r = tr.requests[rid]
        assert r.terminal is not None, rid
        if r.terminal == "done":
            assert (r.t_submit <= r.t_admit <= r.t_first <= r.t_done
                    <= r.t_drain), rid
            assert r.step_admit <= r.step_first <= r.step_done, rid
            assert r.n_tokens == len(cb.done[rid])
            # the batcher's stamps are the tracer's: admission at the
            # interpolated step, first token at the sync that handed it
            # over, no later than the drain
            assert cb.admitted_at[rid] == r.t_admit
            assert r.t_first <= cb.first_at[rid] <= cb.done_at[rid]
            assert cb.done_at[rid] == r.t_drain
        elif r.t_admit is not None:
            assert r.step_admit <= r.step_done, rid


@pytest.mark.parametrize(
    "case", ["spec_k", "in_wave_sharing", "pool_exhaust", "in_step_gate"])
def test_stamped_lifecycles_complete_and_monotone(planted, case):
    prompts, feats, make_kw, scfg_kw = _case(planted, case)
    ref = _serve(_paged_batcher(planted, scfg_kw, **make_kw()), prompts,
                 feats)
    mx = Metrics()
    tr = Tracer(metrics=mx)
    cb = _paged_batcher(planted, scfg_kw, tracer=tr, metrics=mx,
                        **make_kw())
    got = _serve(cb, prompts, feats, max_steps=5)  # carried across calls
    assert got == ref
    _check_lifecycles(cb, tr, prompts)
    if case == "spec_k":
        assert cb.spec_stats()["accepted"] > 0
    elif case == "in_wave_sharing":
        assert cb.pool.stats["shared_tokens"] > 0
    elif case == "pool_exhaust":
        assert cb.injector.fired
    else:
        assert any(r.drop_reason == "gate-reject"
                   and r.step_admit is not None
                   for r in tr.requests.values())


def test_traced_schedule_identical_with_share_prefix(planted):
    """A Tracer changes nothing the batcher does: with prefix sharing on
    (cached and in-wave), traced and untraced runs give the same
    streams, the same sharing and the same number of device steps."""
    shared = [5] * 17

    def prompts(seed):
        rng = np.random.default_rng(seed)
        return {rid: shared[: int(rng.integers(8, 18))]
                + [int(t) for t in rng.integers(1, 97, 2)]
                for rid in range(8)}

    def serve(**kw):
        cb = _paged_batcher(planted, dict(pages=32, share_prefix=True),
                            **kw)
        for seed in (0, 1):  # the second wave hits the trie
            _serve(cb, {10 * seed + r: p for r, p in prompts(seed).items()},
                   features=False, max_steps=4)
        return cb

    un = serve()
    tr = Tracer()
    cb = serve(tracer=tr)
    assert cb.done == un.done
    assert cb.pool.stats == un.pool.stats
    assert cb.pool.stats["shared_tokens"] > 0
    assert cb._steps_total == un._steps_total
    assert tr.validate() == []


@pytest.mark.parametrize("arch,ffn", [("qwen2_1_5b", "mlp"),
                                      ("qwen2_moe_a2_7b", "moe")])
def test_scopes_in_compiled_paged_step(planted, arch, ffn):
    """The profiler's name stack of each device op is the op_name of
    its HLO metadata: the compiled paged step carries every scope."""
    _, _, gate = planted
    cfg = get_smoke_config(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    cb = _paged_batcher((cfg, params, gate))
    lowered = {}
    make = cb._make_run_k_paged

    def spy(*a):
        fn = make(*a)

        def call(*args):
            lowered["step"] = fn.lower(*args)  # before donation
            return fn(*args)
        return call

    cb._make_run_k_paged = spy
    _serve(cb, _prompts(3), features=True)
    text = lowered["step"].compile().as_text()
    parts = {p for n in re.findall(r'op_name="([^"]*)"', text)
             for p in n.split("/")}
    assert set(SCOPES + (ffn,)) <= parts
    assert ({"mlp", "moe"} - {ffn}).isdisjoint(parts)


def test_serve_spans_in_cpu_profile(planted, tmp_path):
    """One run() under the profiler leaves the six ``serve.*`` host
    spans in the trace, nested in the caller's span."""
    from jax.profiler import ProfileData

    cb = _paged_batcher(planted)
    _serve(cb, _prompts(4), features=True)  # compile outside the trace
    for rid, p in _prompts(4, seed=1).items():
        cb.submit(100 + rid, p, features=DS.X_test[rid])
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("caller.run"):
        cb.run(max_steps=6)
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    ev = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
          for p in ProfileData.from_file(path).planes
          if p.name.startswith("/host:") for line in p.lines
          for e in line.events]
    outer, = [(a, b) for n, a, b in ev if n == "caller.run"]
    inner = {n for n, a, b in ev
             if n.startswith("serve.") and outer[0] <= a and b <= outer[1]}
    assert inner == set(SPANS)
