"""End-to-end trainer (CPU-runnable at smoke scale, pod-ready by config).

Wires every substrate: token pipeline, sharded train step, checkpoint
manager (atomic, retained, async), preemption handler, straggler monitor.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --smoke \
        --steps 50 --ckpt-dir /tmp/ckpt --resume auto
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..arch import model as M
from ..arch.config import ArchConfig
from ..ckpt.manager import CheckpointManager, config_hash
from ..configs import get_config, get_smoke_config
from ..data.tokens import TokenPipeline, TokenPipelineConfig
from ..dist.stragglers import PreemptionHandler, StragglerMonitor
from ..train import optimizer as OPT
from ..train.step import TrainConfig, make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--moe-impl", default="dense")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--resume", default=None, help="'auto' or step number")
    ap.add_argument("--elastic", action="store_true",
                    help="run under the ElasticTrainer supervision loop: "
                         "straggler eviction -> remesh -> verified "
                         "checkpoint restore, SIGTERM warm restart")
    ap.add_argument("--fault-plan", default=None, metavar="SPEC",
                    help="TrainFaultPlan spec for --elastic (e.g. "
                         "'slow:1:1.0@1,lost:2@8' or 'seed:0:4'); see "
                         "repro.dist.elastic.TrainFaultPlan.parse")
    ap.add_argument("--snapshot-dir", default=None, metavar="DIR",
                    help="checkpoint directory for the elastic "
                         "supervision loop (defaults to --ckpt-dir; one "
                         "of the two is required with --elastic)")
    ap.add_argument("--workers", type=int, default=None,
                    help="simulated host count for --elastic (default: "
                         "devices // chips-per-host)")
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="pinned model-parallel degree for --elastic")
    ap.add_argument("--chips-per-host", type=int, default=None,
                    help="devices per simulated host (default: "
                         "--model-parallel)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write per-step spans as Chrome trace-event "
                         "JSON (chrome://tracing / Perfetto)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="append a metrics snapshot per step (JSONL): "
                         "step-time histogram, loss gauge, straggler "
                         "medians, gradient compression ratio")
    ap.add_argument("--jax-profile", default=None, metavar="DIR",
                    help="capture a jax.profiler trace into DIR (view "
                         "with TensorBoard); pair with "
                         "XLA_FLAGS=--xla_step_marker_location=1 to mark "
                         "step boundaries")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    tcfg = TrainConfig(
        microbatches=args.microbatches, compress_grads=args.compress_grads,
        moe_impl=args.moe_impl, q_block=min(512, args.seq),
        adamw=OPT.AdamWConfig(lr=args.lr, warmup_steps=5,
                              total_steps=args.steps),
    )
    pipe = TokenPipeline(TokenPipelineConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch, seed=args.seed))

    if args.elastic:
        return _run_elastic(args, cfg, tcfg, pipe)

    key = jax.random.PRNGKey(args.seed)
    params = M.init_params(cfg, key)
    state = {"opt": OPT.init(params), "step": jnp.zeros((), jnp.int32)}
    if tcfg.compress_grads:
        from ..dist import compress as C
        state["err"] = C.init_error_state(params)

    mgr = None
    start_step = 0
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir, keep=3, async_writes=True)
        if args.resume:
            step = (mgr.latest_step() if args.resume == "auto"
                    else int(args.resume))
            if step is not None:
                tree = {"params": params, "state": state}
                restored = mgr.restore(step, tree)
                params, state = restored["params"], restored["state"]
                start_step = step
                print(f"resumed from step {step}")

    train_step = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0, 1))
    monitor = StragglerMonitor(n_workers=1)
    chash = config_hash((cfg, dataclasses.asdict(tcfg)[
        "microbatches"], args.seq, args.batch))

    tracer = metrics = None
    if args.trace or args.metrics_out:
        from ..obs import Metrics, Tracer
        metrics = Metrics()
        tracer = Tracer(metrics=metrics)
    if metrics is not None and tcfg.compress_grads:
        # shape-only arithmetic: the ratio is a property of the pytree
        from ..dist.compress import compression_ratio
        metrics.gauge("train.compression_ratio").set(
            compression_ratio(params))
    profiling = bool(args.jax_profile)
    if profiling:
        jax.profiler.start_trace(args.jax_profile)

    def do_ckpt():
        if mgr is not None:
            s = int(state["step"])
            mgr.save(s, {"params": params, "state": state}, chash)

    handler = PreemptionHandler(do_ckpt).install()
    losses = []
    for step in range(start_step, args.steps):
        t0 = time.perf_counter()
        batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(step).items()}
        params, state, loss = train_step(params, state, batch)
        loss = float(loss)
        dt = time.perf_counter() - t0
        monitor.record(0, dt)
        losses.append(loss)
        if tracer is not None:
            tracer.span(f"step {step}", t0, t0 + dt, step=step, loss=loss)
        if metrics is not None:
            metrics.histogram("train.step_ms").observe(dt * 1e3)
            metrics.gauge("train.loss").set(loss)
            metrics.counter("train.steps").inc()
            # straggler heartbeats: per-worker median step time + the
            # flagged-worker count (single-process runs report worker 0)
            for w, med in monitor.medians().items():
                metrics.gauge(f"train.worker{w}.median_step_s").set(med)
            metrics.gauge("train.stragglers").set(
                len(monitor.stragglers()))
            if args.metrics_out:
                metrics.write_jsonl(args.metrics_out, kind="train",
                                    step=step)
        print(f"step {step:5d} loss {loss:8.4f} {dt*1e3:8.1f} ms")
        if handler.preempted:
            # safe point: params/state are rebound, donated buffers gone
            handler.drain()
            saved = ("checkpoint saved" if mgr is not None
                     else "no --ckpt-dir, nothing saved")
            print(f"preempted at step {step}; {saved}, stopping")
            break
        if mgr is not None and (step + 1) % args.ckpt_every == 0:
            do_ckpt()
    if mgr is not None:
        if not handler.preempted:  # drain() already saved this step
            do_ckpt()
        mgr.wait()
    handler.uninstall()
    if profiling:
        jax.profiler.stop_trace()
        print(f"jax profile -> {args.jax_profile}")
    if tracer is not None and args.trace:
        tracer.write_chrome_trace(args.trace)
        print(f"chrome trace -> {args.trace}")
    if metrics is not None:
        h = metrics.histogram("train.step_ms")
        if h.count:
            print(f"step time p50={h.percentile(50):.1f}ms "
                  f"p99={h.percentile(99):.1f}ms over {h.count} steps")
    if len(losses) >= 10:
        first, last = np.mean(losses[:5]), np.mean(losses[-5:])
        print(f"loss {first:.4f} -> {last:.4f} "
              f"({'improved' if last < first else 'NOT improved'})")
    return losses


def _run_elastic(args, cfg, tcfg, pipe):
    """--elastic: hand the loop to the ElasticTrainer supervision loop."""
    from ..dist.elastic import TrainFaultPlan, describe
    from ..train.elastic import ElasticTrainer

    snap = args.snapshot_dir or args.ckpt_dir
    if not snap:
        raise SystemExit(
            "--elastic needs --snapshot-dir (or --ckpt-dir): recovery "
            "restores from verified checkpoints")
    plan = (TrainFaultPlan.parse(args.fault_plan)
            if args.fault_plan else None)
    if plan is not None:
        for line in describe(plan):
            print(f"fault plan: {line}")

    tracer = metrics = None
    if args.trace or args.metrics_out:
        from ..obs import Metrics, Tracer
        metrics = Metrics()
        tracer = Tracer(metrics=metrics)

    # keep enough retained steps that a fallback past a corrupted latest
    # checkpoint always has somewhere to land
    mgr = CheckpointManager(snap, keep=max(8, 2 * args.ckpt_every))
    trainer = ElasticTrainer(
        cfg, tcfg, pipe, mgr, steps=args.steps,
        n_workers=args.workers, model_parallel=args.model_parallel,
        chips_per_host=args.chips_per_host, plan=plan,
        ckpt_every=args.ckpt_every, seed=args.seed,
        metrics=metrics, tracer=tracer, metrics_out=args.metrics_out)
    result = trainer.run()

    for i, seg in enumerate(result.segments):
        print(f"segment {i} ({seg.cause}): steps {seg.start}.."
              f"{seg.start + seg.n_steps} on mesh "
              f"{seg.mesh_shape[0]}x{seg.mesh_shape[1]}")
    print(f"elastic run: {result.steps_completed}/"
          f"{result.configured_steps} steps, {result.executed_steps} "
          f"executed, workers {result.workers_start} -> "
          f"{len(result.workers_final)}"
          + (" (externally preempted)" if result.preempted_externally
             else ""))
    if tracer is not None and args.trace:
        tracer.write_chrome_trace(args.trace)
        print(f"chrome trace -> {args.trace}")
    losses = result.losses
    if len(losses) >= 10:
        first, last = np.mean(losses[:5]), np.mean(losses[-5:])
        print(f"loss {first:.4f} -> {last:.4f} "
              f"({'improved' if last < first else 'NOT improved'})")
    return losses


if __name__ == "__main__":
    from .compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
