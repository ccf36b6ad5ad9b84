#!/usr/bin/env python3
"""Smoke run of the gated serve path on a TPU, at qwen2-1.5b's widths.

    python chip_smoke.py               # one chip: device, gate, serve,
                                       # kernel against oracle
    python chip_smoke.py --four-chip   # ShardedServe over four one-chip
                                       # replicas against one chip

Weights are random, drawn from ``--seed``; the gate is a random forest
planted on the synthetic ``unsw`` flows, exactly as
``python -m repro.launch.serve`` plants it.  Each phase prints what it
checked; any failed check raises, and the script exits non-zero.  The
last line of a passing run is one JSON object naming the device:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Where JAX finds no TPU the script exits 2 and prints no such line: it
never falls back to the CPU.  Times printed on the way are one cold run
each, compilation included, and are not measurements.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.arch import model as M  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import PlanterConfig, plant  # noqa: E402
from repro.data import load_dataset  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_serve_mesh  # noqa: E402
from repro.launch.serve import derived_cache_len  # noqa: E402
from repro.nn import attn_backend as AB  # noqa: E402
from repro.serve.engine import (DeviceContinuousBatcher,  # noqa: E402
                                ServeConfig, ServeEngine)
from repro.serve.router import ShardedServe  # noqa: E402

ARCH = "qwen2-1.5b"
PAGE_SIZE = 16
PREFILL_CHUNK = 8
MAX_BATCH = 8
MAX_TOKENS = 16
PROMPT_MIN, PROMPT_MAX = 32, 256
SYNC_EVERY = 16
N_REQUESTS = 16          # one chip
N_ROUTED_REQUESTS = 32   # four chips: eight per shard on average
N_SHARDS = 4


def logits_tolerance(n_layers: int) -> float:
    """Bound on ``max|logits_pallas - logits_jnp| / max|logits_jnp|``.

    The two attention backends differ only in how their f32 sums are
    ordered; each layer rounds its attention output to bf16, whose
    relative rounding step is 2**-9.  A reordered sum can move a
    rounding by one step, and each of the ``n_layers`` layers adds such
    a perturbation to the residual stream, so the bound is one rounding
    step per layer (28 layers: 0.0547)."""
    return n_layers * 2.0 ** -9


# Bound on one attention call's ``max|dout| / max|out|``: both backends
# accumulate in f32 and round scores and outputs to bf16, so where their
# sums round differently an output moves by about one bf16 step (2**-8
# relative) of the largest output.
ATTN_TOLERANCE = 2.0 ** -8


class CompileClock:
    """Seconds JAX spent in backend compiles, from its monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, seconds, **_):
        if event == self.EVENT:
            self.seconds += seconds


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def phase_device(n_chips: int):
    devs = jax.devices()
    dev = devs[0]
    say("device", f"platform={dev.platform} kind={dev.device_kind} "
                  f"count={len(devs)}")
    check(len(devs) >= n_chips,
          f"this run needs {n_chips} chip(s), JAX sees {len(devs)}")
    return dev


def plant_gate():
    """The serve driver's gate: rf, size S, on 4,000 synthetic flows."""
    ds = load_dataset("unsw", n=4000)
    res = plant(PlanterConfig(model="rf", size="S"),
                ds.X_train, ds.y_train, ds.X_test)
    return res.mapped, ds


def phase_gate(gate, ds, expect_backend: str) -> None:
    backend = gate.select_backend()
    say("gate", f"rf-S resources={gate.resources()} backend={backend}")
    check(backend == expect_backend,
          f"gate backend {backend!r}, expected {expect_backend!r}")
    got = np.asarray(gate.jax_predict(backend)(jnp.asarray(ds.X_test)))
    want = gate.predict(ds.X_test)
    bad = int((got != want).sum())
    say("gate", f"{backend} on {len(want)} test flows: {bad} mismatches "
                f"against the numpy reference")
    check(bad == 0, f"{bad} gate verdicts differ from the numpy reference")


def init_model(cfg, seed: int):
    """Random f32 params at the config's published widths, built on the
    device in one jitted program (no host copy, no per-layer stack)."""
    return jax.jit(functools.partial(M.init_params, cfg))(
        jax.random.PRNGKey(seed))


def serve_config() -> ServeConfig:
    return ServeConfig(
        max_batch=MAX_BATCH,
        cache_len=derived_cache_len(PROMPT_MAX, 0, MAX_TOKENS, PAGE_SIZE),
        page_size=PAGE_SIZE, attn_impl="auto")


def make_prompts(cfg, n: int, seed: int) -> list:
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_MIN, PROMPT_MAX + 1, n)
    return [rng.integers(1, cfg.vocab_size, int(k)).tolist() for k in lens]


def make_batcher(engine) -> DeviceContinuousBatcher:
    return DeviceContinuousBatcher(
        engine, eos_token=-1, max_tokens=MAX_TOKENS, sync_every=SYNC_EVERY,
        prefill_chunk=PREFILL_CHUNK)


def step_budget() -> int:
    return 100 * (PROMPT_MAX + MAX_TOKENS)


def check_terminal(rids, done, drop_reasons, vocab_size: int) -> None:
    """Every request ends done (with ``MAX_TOKENS`` in-vocab tokens) or
    dropped with a reason."""
    for rid in rids:
        if rid in done:
            toks = np.asarray(done[rid])
            check(len(toks) == MAX_TOKENS,
                  f"request {rid}: {len(toks)} tokens, want {MAX_TOKENS}")
            check(bool(((toks >= 0) & (toks < vocab_size)).all()),
                  f"request {rid} emitted a token outside the vocab")
        else:
            check(rid in drop_reasons,
                  f"request {rid} neither done nor dropped with a reason")


def phase_serve(cfg, params, gate, ds, kind: str, expect_attn: str,
                clock: CompileClock, seed: int) -> DeviceContinuousBatcher:
    scfg = serve_config()
    resolved = AB.resolve(scfg.attn_impl)
    say("serve", f"{cfg.name}: {cfg.n_layers} layers, d_model "
                 f"{cfg.d_model}, vocab {cfg.vocab_size}; cache_len "
                 f"{scfg.cache_len}, page {scfg.page_size}, prefill chunk "
                 f"{PREFILL_CHUNK}, attn_impl auto -> {resolved}")
    check(resolved == expect_attn,
          f"attn_impl auto resolved to {resolved!r}, expected "
          f"{expect_attn!r}")
    engine = ServeEngine(cfg, params, scfg, gate=gate, gate_backend="auto")
    cb = make_batcher(engine)
    prompts = make_prompts(cfg, N_REQUESTS, seed=seed + 1)
    for rid, p in enumerate(prompts):
        cb.submit(rid, p, features=ds.X_test[rid])
    c0, t0 = clock.seconds, time.perf_counter()
    done = cb.run(max_steps=step_budget())
    wall = time.perf_counter() - t0
    check_terminal(range(N_REQUESTS), done, cb.drop_reasons, cfg.vocab_size)
    n_tok = sum(len(v) for v in done.values())
    say("serve", f"{len(done)} done, {len(cb.dropped)} dropped "
                 f"{sorted(set(cb.drop_reasons.values())) or ''}; all "
                 f"{N_REQUESTS} requests terminal, every token < "
                 f"{cfg.vocab_size}")
    say("serve", f"not a measurement ({kind}, one cold run): compile "
                 f"{clock.seconds - c0:.1f} s, wall {wall:.1f} s, "
                 f"{n_tok / wall:.1f} tokens/s")
    return cb


def phase_kernel_vs_oracle(cfg, params, pool, seed: int) -> None:
    """``paged_decode_step`` on the pool the wave left, under the Pallas
    kernel and under the jnp oracle, on identical inputs: one decode
    step (C=1) and one prefill chunk (C=``PREFILL_CHUNK``)."""
    n_pages, page = pool.n_pages, pool.page_size
    n_ps = n_pages // MAX_BATCH
    rng = np.random.default_rng(seed)
    tbl = jnp.asarray(np.arange(MAX_BATCH * n_ps, dtype=np.int32).reshape(
        MAX_BATCH, n_ps))
    tol = logits_tolerance(cfg.n_layers)
    for C in (1, PREFILL_CHUNK):
        pos = jnp.asarray(rng.integers(page, n_ps * page - C + 1, MAX_BATCH),
                          jnp.int32)
        toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (MAX_BATCH, C)),
                           jnp.int32)
        n_new = jnp.full(MAX_BATCH, C, jnp.int32)
        logits = {}
        for impl in ("pallas", "jnp"):
            step = jax.jit(functools.partial(M.paged_decode_step, cfg=cfg,
                                             attn_impl=impl))
            out, _ = step(params, pool, tbl, pos, toks, n_new)
            logits[impl] = np.asarray(out)[:, :cfg.vocab_size]
        ref, ker = logits["jnp"], logits["pallas"]
        check(bool(np.isfinite(ref).all() and np.isfinite(ker).all()),
              "non-finite logits")
        rel = float(np.abs(ker - ref).max() / np.abs(ref).max())
        agree = float((ker.argmax(-1) == ref.argmax(-1)).mean())
        say("kernel", f"paged_decode_step C={C} over {n_ps} pages/slot: "
                      f"max|dlogits|/max|logits| = {rel:.3e} (tolerance "
                      f"{tol:.4f}), argmax agreement {agree:.3f} over "
                      f"{MAX_BATCH} slots")
        check(rel <= tol, f"kernel-vs-oracle logits error {rel} > {tol}")
    # the attention call alone on layer 0 of the stacked pool, where the
    # logits error starts
    kv = pool.pool().with_view(tbl, pos[:, None], None, None,
                               jnp.int32(0))
    q = jax.random.normal(jax.random.PRNGKey(seed), (
        MAX_BATCH, 1, cfg.n_heads, cfg.head_dim_), jnp.bfloat16)
    outs = {}
    for impl in ("pallas", "jnp"):
        fn = jax.jit(functools.partial(AB.get(impl), n_heads=cfg.n_heads,
                                       head_dim=cfg.head_dim_, window=0))
        outs[impl] = np.asarray(fn(q, kv), np.float32)
    d = np.abs(outs["pallas"] - outs["jnp"])
    rel = float(d.max() / np.abs(outs["jnp"]).max())
    say("kernel", f"attention alone (layer 0, C=1): max|dout|/max|out| = "
                  f"{rel:.3e} (tolerance {ATTN_TOLERANCE:.4f}), "
                  f"{(d > 0).mean():.4f} of outputs differ")
    check(rel <= ATTN_TOLERANCE,
          f"kernel-vs-oracle attention error {rel} > {ATTN_TOLERANCE}")


def devices_of(tree) -> set:
    return {d for leaf in jax.tree.leaves(tree) for d in leaf.devices()}


def phase_router(cfg, params, gate, ds, kind: str, clock: CompileClock,
                 seed: int) -> None:
    """Four one-chip replicas behind the router, each shard's streams
    against one batcher on one chip fed that shard's requests in order."""
    scfg = serve_config()
    mesh = make_serve_mesh(f"{N_SHARDS}x1")
    router = ShardedServe(cfg, params, scfg, mesh, gate=gate,
                          gate_backend="auto", eos_token=-1,
                          max_tokens=MAX_TOKENS, sync_every=SYNC_EVERY,
                          prefill_chunk=PREFILL_CHUNK)
    prompts = make_prompts(cfg, N_ROUTED_REQUESTS, seed=seed + 2)
    for rid, p in enumerate(prompts):
        router.submit(rid, p, features=ds.X_test[rid])
    c0, t0 = clock.seconds, time.perf_counter()
    done = router.run(max_steps=step_budget())
    wall = time.perf_counter() - t0
    check_terminal(range(N_ROUTED_REQUESTS), done, router.drop_reasons,
                   cfg.vocab_size)
    say("router", f"{router.n_shards} shards over mesh {dict(mesh.shape)}: "
                  f"{len(done)} done, {len(router.dropped)} dropped; per "
                  f"shard {[len(a) for a in router.assigned]}")
    for s, (eng, b) in enumerate(zip(router.engines, router.batchers)):
        want = set(mesh.devices[s].flat)
        got_p, got_kv = devices_of(eng.params), devices_of(b.kv_pages)
        check(got_p == want and got_kv == want,
              f"shard {s}: params on {got_p}, page pool on {got_kv}, "
              f"expected {want}")
    say("router", "each shard's params and page pool live on its own "
                  "device")
    say("router", f"not a measurement ({kind}, one cold run): compile "
                  f"{clock.seconds - c0:.1f} s, wall {wall:.1f} s")
    # the reference batchers share shard 0's params, already on chip 0
    ref_params = router.engines[0].params
    for s, rids in enumerate(router.assigned):
        ref = make_batcher(ServeEngine(cfg, ref_params, scfg, gate=gate,
                                       gate_backend="auto"))
        for rid in rids:
            ref.submit(rid, prompts[rid], features=ds.X_test[rid])
        ref_done = ref.run(max_steps=step_budget())
        same = sum(done[rid] == ref_done.get(rid) for rid in rids)
        say("router", f"shard {s}: {same}/{len(rids)} streams identical "
                      f"to one batcher on one chip")
        check(same == len(rids), f"shard {s} streams differ from the "
                                 f"one-chip batcher")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the prompts' "
                         "token draws")
    ap.add_argument("--four-chip", action="store_true",
                    help=f"run only ShardedServe over {N_SHARDS} one-chip "
                         f"replicas and its one-chip comparison")
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {jax.devices()[0].platform}); "
              "this script does not run on the CPU", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    clock = CompileClock()
    n_chips = N_SHARDS if args.four_chip else 1
    dev = phase_device(n_chips)
    kind = dev.device_kind
    say("device", f"compile cache: {cache}")
    gate, ds = plant_gate()
    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    params = init_model(cfg, args.seed)
    jax.block_until_ready(params)
    say("model", f"{cfg.name} params from seed {args.seed} in "
                 f"{time.perf_counter() - t0:.1f} s ({kind}, not a "
                 f"measurement)")
    if args.four_chip:
        phase_router(cfg, params, gate, ds, kind, clock, args.seed)
    else:
        phase_gate(gate, ds, expect_backend="pallas_fused")
        cb = phase_serve(cfg, params, gate, ds, kind, expect_attn="pallas",
                         clock=clock, seed=args.seed)
        phase_kernel_vs_oracle(cfg, params, cb.kv_pages, seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
