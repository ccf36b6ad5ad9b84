"""Compiles of the serve path's kernels for a described TPU v5e.

Nothing here runs on a chip: ``jax.experimental.topologies`` describes a
``v5e:2x2`` host and the TPU compiler, which is installed with jaxlib,
compiles for it.  It refuses what the chip would refuse (a block that is
not tiled the way Mosaic wants, an unsupported op, a program that does
not fit), which interpret mode on the CPU cannot see.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers all
import this file.  Code that asks ``jax.default_backend()`` still sees the
CPU here, so the tests that need the compiled kernel steer it with
``monkeypatch``.
"""
import dataclasses
import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from repro.arch import model as M
from repro.configs import get_config
from repro.core import PlanterConfig, plant
from repro.data import load_dataset
from repro.kernels.paged_attention import paged_attention

V5E_HBM_BYTES = 16 * 2**30
B, PAGE, PAGES_PER_SLOT = 8, 16, 32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    """Make call-time backend checks take their TPU branch (compiled
    Mosaic kernels, ``attn_impl='auto'`` -> pallas)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.fixture(scope="module")
def gate():
    ds = load_dataset("unsw", n=4000)
    res = plant(PlanterConfig(model="rf", size="S"),
                ds.X_train, ds.y_train, ds.X_test)
    return res.mapped, ds


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _shapes(tree, sharding):
    return jax.tree.map(lambda a: _spec(a.shape, a.dtype, sharding), tree)


@pytest.mark.parametrize("pool_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("C", [1, 8])
def test_paged_attention_compiles_at_qwen2_widths(one_chip, C, pool_dtype):
    """The kernel on one traced layer of a stacked lane-dense pool."""
    cfg = get_config("qwen2-1.5b")
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    L, N = 2, B * PAGES_PER_SLOT
    pdt = jnp.int8 if pool_dtype == "int8" else jnp.bfloat16
    args = [_spec((B, C, H, hd), jnp.bfloat16, one_chip),
            _spec((L, N, PAGE, KV * hd), pdt, one_chip),
            _spec((L, N, PAGE, KV * hd), pdt, one_chip),
            _spec((B, PAGES_PER_SLOT), jnp.int32, one_chip),
            _spec((B, C), jnp.int32, one_chip),
            _spec((), jnp.int32, one_chip),
            _spec((), jnp.int32, one_chip)]
    kw = {}
    if pool_dtype == "int8":
        kw = {k: _spec((L, N, PAGE, KV), jnp.float32, one_chip)
              for k in ("k_scale", "v_scale")}
    fn = jax.jit(functools.partial(paged_attention, interpret=False))
    hlo = fn.lower(*args, **kw).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("batch", [B, 1200])
def test_fused_eb_gate_compiles(one_chip, gate, on_tpu, batch):
    """The planted rf-S gate's predictor (one fused_eb launch per tree)
    at the decode batch and at the whole test split."""
    mapped, ds = gate
    assert mapped.select_backend("tpu") == "pallas_fused"
    fn = mapped.jax_predict("pallas_fused")
    x = _spec((batch, ds.X_test.shape[1]), jnp.int32, one_chip)
    hlo = fn.lower(x).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_gate_admission_compiles_over_four_chips(topo, gate, on_tpu):
    """``ShardedServe.admit``: one gate launch over the whole mesh, its
    rows placed data-parallel on a 4x1 serve mesh."""
    from jax.sharding import Mesh
    from repro.dist import sharding as SH
    from repro.serve.router import sharded_gate
    mapped, ds = gate
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "model"))
    n = 32
    spec = SH.queue_pspec(mesh, n, 2)
    launch = sharded_gate(mapped.jax_predict("pallas_fused"), mesh, spec)
    x = _spec((n, ds.X_test.shape[1]), jnp.int32, NamedSharding(mesh, spec))
    assert "tpu_custom_call" in launch.lower(x).compile().as_text()


def test_full_width_paged_decode_step_compiles(one_chip, on_tpu):
    """qwen2-1.5b at published widths through the paged step with the
    Pallas kernel, and it fits one chip."""
    cfg = get_config("qwen2-1.5b")
    params = _shapes(jax.eval_shape(
        functools.partial(M.init_params, cfg), jax.random.PRNGKey(0)),
        one_chip)
    pool = _shapes(jax.eval_shape(
        functools.partial(M.init_paged_kv, cfg, B * PAGES_PER_SLOT, PAGE)),
        one_chip)
    i32 = functools.partial(_spec, dtype=jnp.int32, sharding=one_chip)
    step = jax.jit(functools.partial(M.paged_decode_step, cfg=cfg,
                                     sample_greedy=True, attn_impl="auto"))
    compiled = step.lower(params, pool, i32((B, PAGES_PER_SLOT)), i32((B,)),
                          i32((B, 1)), i32((B,))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < V5E_HBM_BYTES, used


# ops that would move a pool, or one layer of it, instead of indexing it
_MOVES = re.compile(r"= \(?\w+\[([\d,]+)\]\S* (copy|copy-start|dynamic-slice|"
                    r"dynamic-update-slice|reshape|transpose)\(")


def _pool_moves(hlo: str, n_pages: int, layer_cells: int) -> list:
    """Ops of the optimized HLO whose result holds the pages of the pool
    (a ``n_pages, PAGE`` run in its dims) and is as large as one layer's
    pool."""
    moves = []
    for m in _MOVES.finditer(hlo):
        dims = tuple(int(d) for d in m.group(1).split(","))
        paged = any(dims[i:i + 2] == (n_pages, PAGE)
                    for i in range(len(dims) - 1))
        if paged and math.prod(dims) >= layer_cells:
            moves.append(f"{m.group(2)} {list(dims)}")
    return moves


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_step_keeps_the_pool_in_place(one_chip, on_tpu, kv_dtype):
    """The paged step inside a donated ``while_loop`` carry, as the
    batcher's fused ``run_k`` runs it, at qwen2-1.5b's widths with depth
    cut to 2: the stacked pool is written by in-place scatters and read
    by the kernel's page DMAs, with no copy, slice, restack or relayout
    of the pool or of one layer's pool anywhere in the program."""
    cfg = dataclasses.replace(get_config("qwen2-1.5b"), n_layers=2)
    N = B * PAGES_PER_SLOT
    params = _shapes(jax.eval_shape(
        functools.partial(M.init_params, cfg), jax.random.PRNGKey(0)),
        one_chip)
    pool = _shapes(jax.eval_shape(functools.partial(
        M.init_paged_kv, cfg, N, PAGE, kv_dtype=kv_dtype)), one_chip)
    i32 = functools.partial(_spec, dtype=jnp.int32, sharding=one_chip)

    def run_k(params, pool, tbl, pos, toks, n_new, k):
        def body(c):
            i, pool, toks = c
            nxt, pool = M.paged_decode_step(
                params, pool, tbl, pos + i, toks, n_new, cfg,
                sample_greedy=True, attn_impl="auto")
            return i + 1, pool, nxt[:, None]

        _, pool, toks = jax.lax.while_loop(
            lambda c: c[0] < k, body, (jnp.int32(0), pool, toks))
        return pool, toks

    hlo = jax.jit(run_k, donate_argnums=(1,)).lower(
        params, pool, i32((B, PAGES_PER_SLOT)), i32((B,)), i32((B, 1)),
        i32((B,)), i32(())).compile().as_text()
    assert "tpu_custom_call" in hlo
    layer_cells = N * PAGE * cfg.n_kv_heads * cfg.head_dim_
    assert _pool_moves(hlo, N, layer_cells) == []
