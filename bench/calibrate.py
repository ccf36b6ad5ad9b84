#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's and the control's.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 \\
        --seconds 10 [--control-seeds 1,2,3]

One process, one system: for each seed the weights are made anew and
handed to the same engine, the cell's traffic for that seed is served for
``--seconds`` at the cell's own load, and the numbers a benchmark run
compares (``bench/core/check.py``, the cell's sample and limits) are read.
For the control seeds they are read again with the reference computed in
float8 in the program's place: at each position of the same prompts and
served tokens, the gap of the token that the fp8 forward puts first.
Prints one JSON line per seed and reading, each with the verdict the
cell's limits give it, then for each limited number the lower reading
(largest of the program) and the upper reading (smallest of the control).
The benchmark's own runs never run the control.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    ctrl = {int(s) for s in args.control_seeds.split(",") if s}

    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from bench.core import check, driver, harness, traffic, weights

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    S = harness.load_spec(args.workload)
    cfg, cell, mix = S["config"], S["cell"], S["mix"]
    harness.device_info(int(S["workload"]["chips"]), True)
    prog = driver.import_program()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    compiles = driver.CompileCounter()
    clock = driver.Clock()
    system = None
    rows = []
    for seed in sorted(set(seeds) | ctrl):
        if system is not None:  # one set of weights on the chip at a time
            system.engine.params = system.params = None
        params = weights.make(cfg, seed)
        if system is None:
            system = driver.System(prog, cfg, cell, mix, params, clock)
        else:
            system.engine.params = system.params = params
        reqs = traffic.generate(mix, seed, args.seconds,
                                system.arch.vocab_size, system.flows,
                                system.flow_reject)
        rec = driver.serve(system, mix, cell, reqs, args.seconds, T_PROCESS,
                           compiles, log=log)
        while system.batcher.pending_work():  # the next seed starts empty
            system.batcher.run(max_steps=1000)
        win = [o for o in rec.outcomes.values() if o.phase == "win"]
        runs = ([False] if seed in seeds else []) + (
            [True] if seed in ctrl else [])
        for control in runs:
            c = check.checks(cfg, params, cell, mix, win, rec.requests,
                             seed, control=control, log=log)
            row = dict(seed=seed, control=control,
                       correct=check.verdict(c),
                       **{k: v["value"] for k, v in c.items()})
            print(json.dumps(row), flush=True)
            rows.append(row)
        del params
    summary = {}
    for name in cell["correct"]["limits"]:
        prog_r = [r[name] for r in rows if not r["control"]]
        ctrl_r = [r[name] for r in rows if r["control"]]
        summary[name] = dict(
            limit=cell["correct"]["limits"][name],
            lower_reading=max(prog_r) if prog_r else None,
            upper_reading=min(ctrl_r) if ctrl_r else None)
    summary["control_all_not_correct"] = all(
        not r["correct"] for r in rows if r["control"])
    summary["program_all_correct"] = all(
        r["correct"] for r in rows if not r["control"])
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
