#!/usr/bin/env python3
"""Two tools for the program's trace marks, run on one chip.

    python3 bench/trace_probe.py record --workload <cell> --seed <n> \\
        --out <file.xplane.pb> [--calls 2]
    python3 bench/trace_probe.py cost --workload <cell> --seed <n> \\
        [--calls 24]

Both build the cell's system as a benchmark run does, warm every bucket
the cell reaches and serve its traffic for ``pre_window_s`` to reach the
steady state first.

``record`` then profiles ``--calls`` run() calls, each in the
benchmark's ``bench.run`` span, and copies the trace to ``--out`` (the
traces under ``bench/tests/data`` come from it).

``cost`` serves on, ``--calls`` run() calls per block in eight blocks
(profiler off / on x Tracer detached / attached, in the order ABCD DCBA)
and prints, per setting, the host time per run() call: its wall time less
the time blocked in the done-mask read that waits for the device.  A
Tracer's deferred emission, which runs when the trace is read, is timed
apart.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
sys.path.insert(0, ROOT)

import jax  # noqa: E402


def log(msg):
    print(msg, file=sys.stderr, flush=True)


class Server:
    """The cell's traffic, sent as the benchmark's driver sends it: due
    requests (open loop) or up to ``outstanding`` in flight (closed
    loop) before each run() call."""

    def __init__(self, system, mix, reqs):
        self.bt, self.clock = system.batcher, system.clock
        self.open = mix["arrivals"] == "open"
        self.n_out = int(mix.get("outstanding", 0))
        self.reqs = reqs
        self.nxt = 0
        self.live = set()
        self.t0 = self.clock() + float(mix.get("pre_window_s", 0.0))
        self.serial = 0

    def submit(self):
        bt, now = self.bt, self.clock()
        self.live = {k for k in self.live
                     if k not in bt.done_at and k not in bt.dropped_at}
        while self.nxt < len(self.reqs):
            r = self.reqs[self.nxt]
            if self.open and self.t0 + r.due > now:
                break
            if not self.open and len(self.live) >= self.n_out:
                break
            key = f"p{self.nxt}"
            bt.submit(key, r.prompt, features=r.feat)
            self.live.add(key)
            self.nxt += 1

    def call(self, k, span="bench.run"):
        """Send what is due, then one run() call inside ``span`` (None:
        no span); its wall time (s), None where nothing was pending."""
        with jax.profiler.TraceAnnotation("bench.submit"):
            self.submit()
        if not self.bt.pending_work():
            return None
        t = time.perf_counter()
        if span is None:
            self.bt.run(max_steps=k)
        else:
            with jax.profiler.TraceAnnotation(span):
                self.bt.run(max_steps=k)
        return time.perf_counter() - t


def build(args, require_chip=True, overrides=None):
    from bench.core import driver, harness, traffic, weights
    S = harness.load_spec(args.workload)
    S.update(overrides or {})
    cfg, cell, mix = S["config"], S["cell"], S["mix"]
    harness.device_info(int(S["workload"]["chips"]), require_chip)
    prog = driver.import_program()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    clock = driver.Clock()
    params = weights.make(cfg, args.seed)
    system = driver.System(prog, cfg, cell, mix, params, clock)
    del params
    t = time.perf_counter()
    targets = driver.warm_targets(mix, cell)
    driver.warm_walk(system, targets, system.arch.vocab_size)
    driver.warm_gate(system, driver.gate_counts(cell))
    jax.effects_barrier()
    log(f"[probe] warm-up {time.perf_counter() - t:.1f} s")
    reqs = traffic.generate(mix, args.seed, 600.0, system.arch.vocab_size,
                            system.flows, system.flow_reject)
    srv = Server(system, mix, reqs)
    k = int(cell["batcher"]["sync_every"])
    while srv.clock() < srv.t0:  # to the steady state
        srv.call(k)
    return system, srv, k


def start_profiler(trace_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # host spans only, as --trace 1 runs
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def record(args, **kw):
    system, srv, k = build(args, **kw)
    trace_dir = os.path.join(ROOT, ".bench_out", "probe-trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    start_profiler(trace_dir)
    # the profiler's start-up stall falls in a call outside the window
    srv.call(k, span=None)
    jax.effects_barrier()
    n = 0
    while n < args.calls:
        if srv.call(k) is not None:
            n += 1
        with jax.profiler.TraceAnnotation("bench.collect"):
            pass
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    shutil.copyfile(path, args.out)
    log(f"[probe] {args.calls} traced calls -> {args.out} "
        f"({os.path.getsize(args.out)} bytes)")


def cost(args, **kw):
    system, srv, k = build(args, **kw)
    from repro.obs import Tracer  # the program's, on the path from here
    bt = system.batcher
    blocked = [0.0]
    real_get = jax.device_get

    def timed_get(x):
        # the done-mask read: (out_done, alive), the one read that waits
        # for the device
        t = time.perf_counter()
        try:
            return real_get(x)
        finally:
            if isinstance(x, tuple) and len(x) == 2:
                blocked[0] += time.perf_counter() - t

    jax.device_get = timed_get
    trace_dir = os.path.join(ROOT, ".bench_out", "probe-cost")
    rows = {}
    for prof, traced in [(0, 0), (0, 1), (1, 0), (1, 1),
                         (1, 1), (1, 0), (0, 1), (0, 0)]:
        tracer = Tracer() if traced else None
        bt.attach_obs(tracer)
        if prof:
            shutil.rmtree(trace_dir, ignore_errors=True)
            start_profiler(trace_dir)
            srv.call(k, span=None)  # the start-up stall, not measured
        walls, hosts = [], []
        while len(walls) < args.calls:
            blocked[0] = 0.0
            w = srv.call(k)
            if w is not None:
                walls.append(w)
                hosts.append(w - blocked[0])
        if prof:
            jax.profiler.stop_trace()
        flush = 0.0
        if tracer is not None:
            t = time.perf_counter()
            tracer.flush()
            flush = (time.perf_counter() - t) / len(walls)
        r = rows.setdefault((prof, traced), dict(wall=[], host=[], flush=[]))
        r["wall"] += walls
        r["host"] += hosts
        r["flush"].append(flush)
    bt.attach_obs(None)
    jax.device_get = real_get
    for (prof, traced), r in sorted(rows.items()):
        print(json.dumps(dict(
            profiler=bool(prof), tracer=bool(traced), calls=len(r["wall"]),
            host_ms_mean=1e3 * statistics.mean(r["host"]),
            host_ms_median=1e3 * statistics.median(r["host"]),
            wall_ms_mean=1e3 * statistics.mean(r["wall"]),
            tracer_flush_ms_per_call=1e3 * statistics.mean(r["flush"]))),
            flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("tool", choices=("record", "cost"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--calls", type=int, default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if args.tool == "record":
        if not args.out:
            ap.error("record needs --out")
        args.calls = args.calls or 2
        record(args)
    else:
        args.calls = args.calls or 24
        cost(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
