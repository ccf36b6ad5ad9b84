#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``<name>`` is a workload of ``BENCHMARK.json``.  With ``--trace 0`` the
last line of standard output is the result with the cell's end-to-end
metrics; with ``--trace 1`` it carries the per-layer metrics read from a
profiler trace of part of the window.  The numbers compared to decide
``correct`` are the last lines of standard error and the last key of the
result.  Exits 3, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the persistent compilation cache lives at a fixed path in the checkout
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    import jax
    # cache every program, however fast it compiles, so a second run of
    # a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # no size cap: a capped cache keeps access-time files beside its
    # entries, and writing them failed on the chip's machine
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from bench.core import harness

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), T_PROCESS, log=log)
    except harness.NoChip as e:
        log(f"[bench] {e}")
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
