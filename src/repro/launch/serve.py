"""Serving driver: batched requests through the Planter gate + LM decode.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
        --requests 64 --tokens 8 --gate rf

    # device-resident continuous batching (the production hot path)
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
        --continuous --requests 64 --tokens 8 --gate rf --sync-every 16

    # multi-host: shard over a data×model mesh behind the request router
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
        --continuous --mesh 2x4 --router --requests 64 --tokens 8

    # paged KV cache + chunked multi-token prefill (variable-length
    # prompts enter the fused step prefill_chunk tokens per launch)
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
        --continuous --requests 64 --tokens 4 --prompt-len 24 \
        --page-size 16 --prefill-chunk 8
"""
from __future__ import annotations

import argparse
import collections
import time

import jax
import numpy as np

from ..arch import model as M
from ..configs import get_config, get_smoke_config
from ..core import PlanterConfig, plant
from ..data import load_dataset
from ..serve.engine import (ContinuousBatcher, DeviceContinuousBatcher,
                            ServeConfig, ServeEngine)
from ..serve.router import ShardedServe

# prompt length of the one-batch generate() path (no --continuous)
GENERATE_PROMPT_LEN = 4


def derived_cache_len(prompt_len: int, shared_prefix_len: int,
                      tokens: int, page_size: int) -> int:
    """Cache length that holds the longest request this run submits:
    shared prefix + prompt + generated tokens, rounded up to whole
    pages (the one-batch path seeds ``GENERATE_PROMPT_LEN`` tokens)."""
    need = (shared_prefix_len + max(prompt_len, GENERATE_PROMPT_LEN)
            + tokens)
    page = max(1, page_size)
    return -(-need // page) * page


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--tokens", type=int, default=8)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--gate", default="rf",
                    help="planter model for admission (or 'none')")
    ap.add_argument("--gate-backend", default="auto",
                    help="jnp | pallas | pallas_fused | auto "
                         "(auto = fused EB kernel on TPU, jnp oracle else)")
    ap.add_argument("--continuous", action="store_true",
                    help="slot-based continuous batching over the request "
                         "stream instead of one fixed generate() batch")
    ap.add_argument("--batcher", default="device",
                    choices=["device", "host"],
                    help="continuous-batching engine (device = fused "
                         "jitted step; host = per-token reference)")
    ap.add_argument("--sync-every", type=int, default=16,
                    help="device batcher: steps per host round trip")
    ap.add_argument("--page-size", type=int, default=0,
                    help="paged KV cache: tokens per page (0 = dense "
                         "ring cache; paging enables multi-token "
                         "prompts + chunked prefill)")
    ap.add_argument("--pages", type=int, default=0,
                    help="paged KV cache: physical page pool size "
                         "(0 = max_batch * cache_len/page_size, the "
                         "dense-equivalent footprint; smaller pools "
                         "oversubscribe slots)")
    ap.add_argument("--prefill-chunk", type=int, default=8,
                    help="prompt tokens consumed per fused step on the "
                         "paged device path (1 = token-by-token)")
    ap.add_argument("--share-prefix", action="store_true",
                    help="paged cache: requests with a common token "
                         "prefix share refcounted read-only prefix "
                         "pages (COW on the partial tail page); needs "
                         "--page-size")
    ap.add_argument("--kv-int8", action="store_true",
                    help="paged cache: int8 page pool with per-page "
                         "scale planes (~2x pool tokens per byte at "
                         "the quantize round-trip bound); needs "
                         "--page-size")
    ap.add_argument("--attn-impl", default="auto",
                    choices=["auto", "jnp", "pallas"],
                    help="paged-attention backend (repro.nn.attn_backend "
                         "registry): auto = Pallas page-walking kernel "
                         "on TPU / jnp gather oracle elsewhere; "
                         "'pallas' off-TPU runs the kernel in interpret "
                         "mode (slow, correctness checks only)")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="workload: prepend this many common prefix "
                         "tokens to every prompt (exercises "
                         "--share-prefix; counts toward --prompt-len "
                         "budget checks)")
    ap.add_argument("--prompt-len", type=int, default=1,
                    help="max prompt length; prompts are drawn with "
                         "variable length in [1, prompt-len] "
                         "(>1 needs --page-size)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: table-mapped draft "
                         "tokens proposed per decoding slot per fused "
                         "step (0 = off; needs --page-size and the "
                         "device batcher; the LM verifies the whole "
                         "chain in one chunked launch)")
    ap.add_argument("--draft", default="pilot",
                    choices=["pilot", "prompts"],
                    help="draft-model training corpus for --spec-k: "
                         "'pilot' serves a first greedy wave and trains "
                         "the bigram table on what the LM actually "
                         "emitted (router falls back to prompts); "
                         "'prompts' trains on the prompt tokens only")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="on-device sampling temperature (0 = greedy, "
                         "bit-identical to the pre-sampling serve path)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="sampling: keep only the k highest logits "
                         "(0 = no top-k filter; needs --temperature > 0)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="sampling: nucleus filter to the smallest "
                         "prefix with cumulative mass >= p (1.0 = off; "
                         "needs --temperature > 0)")
    ap.add_argument("--mesh", default=None,
                    help="DATAxMODEL serve mesh (e.g. 1x8, 2x4) or 'auto'; "
                         "implies --continuous --router")
    ap.add_argument("--router", action="store_true",
                    help="route requests across data-parallel shards "
                         "(ShardedServe; --mesh picks the mesh, default "
                         "auto)")
    ap.add_argument("--rebalance-margin", type=int, default=None,
                    help="router: queue-depth slack before a request "
                         "spills off its home shard (default: max_batch)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write request-lifecycle spans as Chrome "
                         "trace-event JSON (open in chrome://tracing or "
                         "Perfetto); continuous mode only")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="append a repro.obs metrics snapshot (JSONL): "
                         "phase-latency histograms, drop counters, pool "
                         "occupancy, router gauges")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request deadline budget in seconds "
                         "(queue wait + decode + failover hops); "
                         "expired requests drop with reason 'deadline' "
                         "at admission or the next drain boundary")
    ap.add_argument("--max-retries", type=int, default=1,
                    help="failure retry budget: queue-full submissions "
                         "back off and re-attempt this many times, and "
                         "a failed shard's requests take at most this "
                         "many failover hops before dropping "
                         "'shard-failed'")
    ap.add_argument("--fault-plan", default=None, metavar="SPEC",
                    help="deterministic fault injection, e.g. "
                         "'crash:1@2,nan:0@1' or 'seed:7:2' "
                         "(serve.faults.FaultPlan.parse grammar); "
                         "applied at host drain boundaries only — the "
                         "jitted step never sees it")
    ap.add_argument("--snapshot-dir", default=None, metavar="DIR",
                    help="graceful degradation: install a SIGTERM "
                         "handler that stops admitting, drains "
                         "in-flight work and snapshots the un-served "
                         "queue here (CheckpointManager); on launch, "
                         "an existing snapshot warm-restarts into the "
                         "fresh batcher")
    ap.add_argument("--jax-profile", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the serve run "
                         "into DIR (view with TensorBoard); the run fails "
                         "if the profiler cannot start; pair with "
                         "XLA_FLAGS=--xla_step_marker_location=1 to mark "
                         "fused-step boundaries")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.mesh and not args.router:
        args.router = True
    if args.router:
        args.continuous = True

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    rng = np.random.default_rng(args.seed)
    params = M.init_params(cfg, jax.random.PRNGKey(args.seed))

    gate = None
    ds = load_dataset("unsw", n=4000)
    if args.gate != "none":
        res = plant(PlanterConfig(model=args.gate, size="S"),
                    ds.X_train, ds.y_train, ds.X_test)
        gate = res.mapped
        backend = (gate.select_backend() if args.gate_backend == "auto"
                   else args.gate_backend)
        print(f"gate: {args.gate} parity={res.parity:.3f} "
              f"resources={gate.resources()} backend={backend}")

    if args.prompt_len > 1 and not args.page_size:
        ap.error("--prompt-len > 1 needs --page-size (paged KV cache)")
    if (args.share_prefix or args.kv_int8) and not args.page_size:
        ap.error("--share-prefix/--kv-int8 need --page-size (paged "
                 "KV cache)")
    if args.shared_prefix_len and not args.share_prefix:
        ap.error("--shared-prefix-len needs --share-prefix")
    if args.spec_k:
        if not args.page_size:
            ap.error("--spec-k needs --page-size (drafts verify through "
                     "the chunked paged step)")
        if not args.continuous:
            ap.error("--spec-k needs --continuous")
        if args.batcher == "host" and not args.router:
            ap.error("--spec-k needs the device batcher")
        if args.trace:
            ap.error("--spec-k is incompatible with --trace (the "
                     "schedule replay assumes one token per step)")
    if (args.top_k or args.top_p < 1.0) and args.temperature == 0.0:
        ap.error("--top-k/--top-p need --temperature > 0")
    cache_len = derived_cache_len(args.prompt_len, args.shared_prefix_len,
                                  args.tokens, args.page_size)
    scfg = ServeConfig(max_batch=args.batch, cache_len=cache_len,
                       page_size=args.page_size, pages=args.pages,
                       share_prefix=args.share_prefix,
                       kv_int8=args.kv_int8, attn_impl=args.attn_impl,
                       temperature=args.temperature, top_k=args.top_k,
                       top_p=args.top_p)
    if args.page_size:
        from ..nn import attn_backend as AB
        print(f"paged attention backend: {args.attn_impl} "
              f"-> {AB.resolve(args.attn_impl)}")

    # wrap around the test set so any --requests count is serveable
    feats = ds.X_test[np.arange(args.requests) % len(ds.X_test)]
    tracer = metrics = None
    if args.trace or args.metrics_out:
        from ..obs import Metrics, Tracer
        metrics = Metrics()
        tracer = Tracer(metrics=metrics)
    profiling = bool(args.jax_profile)
    if profiling:
        jax.profiler.start_trace(args.jax_profile)
    injector = None
    if args.fault_plan:
        from ..serve.faults import FaultPlan
        plan = FaultPlan.parse(args.fault_plan)
        injector = plan.injector()
        print(f"fault plan: {len(plan)} fault(s) armed "
              f"({args.fault_plan})")
    if args.continuous:
        ft = dict(max_retries=args.max_retries,
                  deadline_s=args.deadline_s, fault_injector=injector)
        prefix = rng.integers(1, cfg.vocab_size,
                              args.shared_prefix_len).tolist()
        prompts = [
            prefix + rng.integers(
                1, cfg.vocab_size,
                int(rng.integers(1, args.prompt_len + 1))).tolist()
            for _ in range(args.requests)]
        engine = None
        if not args.router:
            engine = ServeEngine(cfg, params, scfg, gate=gate,
                                 gate_backend=args.gate_backend)
        spec_draft = None
        if args.spec_k:
            from ..serve.spec import train_draft
            chains = [list(p) for p in prompts]
            if engine is not None and args.draft == "pilot":
                # serve a first wave non-speculatively and train the
                # draft on the streams the LM actually emitted — the
                # draft imitates the LM, so pilot output beats a
                # prompts-only corpus on acceptance rate
                pilot = DeviceContinuousBatcher(
                    engine, eos_token=-1, max_tokens=args.tokens,
                    sync_every=args.sync_every,
                    prefill_chunk=args.prefill_chunk)
                n_pilot = min(args.batch, args.requests)
                for rid in range(n_pilot):
                    pilot.submit(rid, prompts[rid], features=feats[rid])
                pilot_done = pilot.run(
                    max_steps=100 * (args.tokens + args.prompt_len
                                     + args.shared_prefix_len))
                chains += [list(prompts[rid]) + list(toks)
                           for rid, toks in pilot_done.items()]
            spec_draft = train_draft(chains, vocab_size=cfg.vocab_size)
            print(f"spec draft: bigram table over {cfg.vocab_size} "
                  f"tokens, coverage "
                  f"{spec_draft.meta.get('coverage', 0.0):.2f}, "
                  f"{spec_draft.accounting()}")
        if args.router:
            from .mesh import make_serve_mesh
            mesh = make_serve_mesh(args.mesh or "auto")
            cb = ShardedServe(cfg, params, scfg, mesh, gate=gate,
                              gate_backend=args.gate_backend, eos_token=-1,
                              max_tokens=args.tokens,
                              sync_every=args.sync_every,
                              rebalance_margin=args.rebalance_margin,
                              prefill_chunk=args.prefill_chunk,
                              tracer=tracer, metrics=metrics,
                              spec_k=args.spec_k, draft=spec_draft, **ft)
            print(f"router: {cb.n_shards} shard(s) over mesh "
                  f"{dict(mesh.shape)}")
        else:
            if args.batcher == "device":
                cb = DeviceContinuousBatcher(
                    engine, eos_token=-1, max_tokens=args.tokens,
                    sync_every=args.sync_every,
                    prefill_chunk=args.prefill_chunk,
                    tracer=tracer, metrics=metrics,
                    spec_k=args.spec_k, draft=spec_draft, **ft)
            else:
                cb = ContinuousBatcher(engine, eos_token=-1,
                                       max_tokens=args.tokens,
                                       tracer=tracer, metrics=metrics, **ft)
        handler = None
        if args.snapshot_dir:
            from ..ckpt import CheckpointManager
            from ..dist.stragglers import PreemptionHandler
            from ..serve.faults import preempt_snapshot, warm_restart

            manager = CheckpointManager(args.snapshot_dir)
            restored = warm_restart(cb, manager)
            if restored:
                print(f"warm restart: {restored} un-served request(s) "
                      f"restored from {args.snapshot_dir}")
            # SIGTERM -> flag only; the serve loop below checks it at
            # the next wave boundary (stop admitting, drain in-flight,
            # snapshot whatever never reached a slot)
            handler = PreemptionHandler(
                lambda: preempt_snapshot(cb, manager)).install()
        # budget covers prefill too: the host loop costs one step per
        # prompt token, so prompt-heavy waves need the longer horizon
        budget = 100 * (args.tokens + args.prompt_len
                        + args.shared_prefix_len)
        # with sharing, run a small first wave to populate the prefix
        # cache (the device batcher consults the trie at wave build),
        # then serve the rest against the warm cache
        split = (min(args.batch, args.requests) if args.share_prefix
                 else args.requests)
        t0 = time.perf_counter()
        for rid in range(split):
            cb.submit(rid, prompts[rid], features=feats[rid])
        cb.run(max_steps=budget)
        if handler is None or not handler.preempted:
            # graceful degradation: a pending SIGTERM stops admission
            # at this wave boundary — in-flight work still drains below
            for rid in range(split, args.requests):
                cb.submit(rid, prompts[rid], features=feats[rid])
        done = cb.run(max_steps=budget)
        if handler is not None:
            if handler.drain():
                print(f"preempted: un-served queue snapshotted to "
                      f"{args.snapshot_dir} (warm restart restores it)")
            handler.uninstall()
        dt = time.perf_counter() - t0
        n_tok = sum(len(v) for v in done.values())
        tag = "router" if args.router else args.batcher
        reasons = collections.Counter(cb.drop_reasons.values())
        print(f"[{tag}] served {len(done)} requests "
              f"(dropped {len(cb.dropped)}: {dict(reasons) or 'none'}) — "
              f"{n_tok} tokens in {dt:.2f}s ({n_tok / dt:.1f} tok/s)")
        if args.router:
            print(f"  per-shard served: "
                  f"{[len(a) for a in cb.assigned]}")
        if args.spec_k:
            if args.router:
                drafted = sum(b._spec_prop for b in cb.batchers)
                accepted = sum(b._spec_acc for b in cb.batchers)
            else:
                st = cb.spec_stats()
                drafted, accepted = st["drafted"], st["accepted"]
            rate = accepted / drafted if drafted else 0.0
            print(f"  speculative: k={args.spec_k}, drafted {drafted}, "
                  f"accepted {accepted} (acceptance {rate:.2f})")
        if args.share_prefix:
            ratio = (cb.prefix_tokens_per_page() if args.router
                     else cb.pool.prefix_tokens_per_page())
            print(f"  prefix sharing: {ratio:.2f} live prefix tokens "
                  f"per pool page (1.0 = unshared)")
        if profiling:
            jax.profiler.stop_trace()
            print(f"  jax profile -> {args.jax_profile}")
        if tracer is not None:
            probs = tracer.validate()
            if probs:
                print(f"  TRACE LIFECYCLE VIOLATIONS: {probs}")
            pct = tracer.phase_percentiles()
            for phase, st in pct.items():
                if st["n"]:
                    print(f"  {phase}: p50={st['p50']:.2f} "
                          f"p99={st['p99']:.2f} (n={st['n']})")
            if args.trace:
                tracer.write_chrome_trace(args.trace)
                print(f"  chrome trace -> {args.trace} "
                      f"(open in chrome://tracing / Perfetto)")
            if args.metrics_out:
                metrics.write_jsonl(args.metrics_out, kind="serve",
                                    requests=args.requests,
                                    tokens_per_s=n_tok / dt)
                print(f"  metrics -> {args.metrics_out}")
        return done

    # request stream: (flow features, prompt) through one generate() batch
    engine = ServeEngine(cfg, params, scfg, gate=gate,
                         gate_backend=args.gate_backend)
    keep = engine.admit(feats)
    print(f"admitted {keep.sum()}/{len(keep)} requests "
          f"(dropped {100 * (1 - keep.mean()):.1f}% as attack traffic)")

    admitted = np.where(keep)[0][: args.batch]
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.batch, GENERATE_PROMPT_LEN))
    t0 = time.perf_counter()
    out = engine.generate(prompts, args.tokens,
                          features=feats[: args.batch])
    dt = time.perf_counter() - t0
    n_tok = out.size
    dev = jax.devices()[0]
    print(f"generated {n_tok} tokens in {dt:.2f}s "
          f"({n_tok / dt:.1f} tok/s on {dev.platform} {dev.device_kind}, "
          f"{cfg.name})")
    print("sample:", out[0][:8])
    if profiling:
        jax.profiler.stop_trace()
        print(f"jax profile -> {args.jax_profile}")
    if metrics is not None and args.metrics_out:
        metrics.write_jsonl(args.metrics_out, kind="serve-batch",
                            tokens_per_s=n_tok / dt)
        print(f"metrics -> {args.metrics_out}")
    return out


if __name__ == "__main__":
    from .compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
