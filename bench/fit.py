#!/usr/bin/env python3
"""How much of one TPU v5e a cell's programs need, compiled here for a
described chip (no chip needed; keep ``JAX_PLATFORMS=cpu``).

    JAX_PLATFORMS=cpu python3 bench/fit.py --workload <cell> [--layers 2,3,4]

For each depth it compiles, for one chip of a described ``v5e:2x2`` host,
the benchmark's weight maker (``bench/core/weights.py``) and the program's
fused serve step (``DeviceContinuousBatcher``'s ``run_k``) at the cell's
largest bucket: every slot filled, the queue and output ring at the sizes
the cell's backlog reaches, the longest prompt bucket.  It prints XLA's
``memory_analysis()`` of each: arguments, outputs, aliased (donated) bytes
and temporaries, and the peak they imply.  The admission gate is left out
(its tables are a few kB).  A depth whose step XLA refuses prints the
refusal.
"""
import argparse
import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

GB = 1e9


class _Compiled(Exception):
    pass


def _abstract(tree, sharding):
    import jax
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _report(mem) -> dict:
    arg, out = mem.argument_size_in_bytes, mem.output_size_in_bytes
    alias, tmp = mem.alias_size_in_bytes, mem.temp_size_in_bytes
    return dict(arguments_gb=arg / GB, outputs_gb=out / GB,
                aliased_gb=alias / GB, temp_gb=tmp / GB,
                peak_gb=(arg + out - alias + tmp) / GB)


def fit(workload: str, layers: int, one_chip) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench.core import driver, harness, weights

    S = harness.load_spec(workload)
    cfg = dict(S["config"], num_hidden_layers=layers)
    cell, mix = S["cell"], S["mix"]
    row = dict(workload=workload, layers=layers)

    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    maker = jax.jit(functools.partial(weights._init, cfg))
    row["weights_make"] = _report(maker.lower(key).compile().memory_analysis())

    prog = driver.import_program()
    E, M = prog["E"], sys.modules["repro.arch.model"]
    arch = driver.program_arch(prog, cfg)
    params = _abstract(jax.eval_shape(maker, jax.random.PRNGKey(0)), one_chip)
    b = cell["batcher"]
    page, out = int(b["page_size"]), int(mix["output_tokens"])
    cache_len = -(-(int(mix["prompt"]["max"]) + out) // page) * page
    scfg = E.ServeConfig(max_batch=int(b["max_batch"]), cache_len=cache_len,
                         page_size=page, attn_impl="auto")
    real_pool = M.init_paged_kv
    E.M.init_paged_kv = lambda *a, **k: _abstract(
        jax.eval_shape(functools.partial(real_pool, *a, **k)), one_chip)
    made = E.DeviceContinuousBatcher._make_run_k_paged

    def make_and_compile(self, *a):
        fn = made(self, *a)

        def call(*args):
            spec = _abstract(args, one_chip)
            spec = (params,) + spec[1:]
            compiled = fn.lower(*spec).compile()
            raise _Compiled(_report(compiled.memory_analysis()))
        return call

    E.DeviceContinuousBatcher._make_run_k_paged = make_and_compile
    try:
        eng = E.ServeEngine(arch, params, scfg)
        bt = E.DeviceContinuousBatcher(
            eng, eos_token=-1, max_tokens=out,
            sync_every=int(b["sync_every"]),
            prefill_chunk=int(b["prefill_chunk"]))
        w = cell["warm"]
        n_new = int(w.get("in_system_max", scfg.max_batch + w["waiting_max"]))
        rng = np.random.default_rng(0)
        for i in range(n_new):
            plen = int(mix["prompt"]["max"]) if i == 0 else 64
            bt.submit(i, rng.integers(0, arch.vocab_size, plen).tolist())
        try:
            bt.run(max_steps=1)
            row["step"] = "no fused step was called"
        except _Compiled as c:
            row["step"] = c.args[0]
        except Exception as e:  # XLA refuses a program that does not fit
            row["step"] = f"refused: {type(e).__name__}: {str(e)[:300]}"
    finally:
        E.M.init_paged_kv = real_pool
        E.DeviceContinuousBatcher._make_run_k_paged = made
    row["pool_gb"] = sum(
        int(np.prod(a.shape)) * a.dtype.itemsize
        for a in jax.tree.leaves(jax.eval_shape(
            lambda: real_pool(arch, scfg.n_pages, page)))) / GB
    row["weights_gb"] = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                            for a in jax.tree.leaves(params)) / GB
    return row


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--layers", default="")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    # call-time backend checks take their TPU branch: the paged step
    # then compiles the Pallas attention kernel, as on the chip
    jax.default_backend = lambda: "tpu"
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    from bench.core import harness
    S = harness.load_spec(args.workload)
    depths = ([int(x) for x in args.layers.split(",")] if args.layers
              else [S["config"]["num_hidden_layers"]])
    for n in depths:
        print(json.dumps(fit(args.workload, n, one_chip)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
