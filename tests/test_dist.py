"""Distribution substrate: sharding specs, stragglers, elasticity."""
import os
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.arch import model as M
from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.dist import compress as C
from repro.dist import pipeline as PP
from repro.dist import sharding as SH
from repro.dist.stragglers import (PreemptionHandler, StragglerMonitor,
                                   replan_data_axis)


def _fake_mesh(data=16, model=16, pod=None):
    """Spec-validation mesh: abstract, never used for execution."""
    # Use a real 1-device mesh but with the target *logical* sizes via
    # a shape-struct trick: we only need mesh.shape and axis_names.
    class FakeMesh:
        def __init__(self):
            self.axis_names = (("pod", "data", "model") if pod
                               else ("data", "model"))
            self.shape = ({"pod": pod, "data": data, "model": model}
                          if pod else {"data": data, "model": model})
    return FakeMesh()


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_specs_divisible(arch):
    """Every sharded dim divides the production mesh axis (16×16)."""
    cfg = get_config(arch)
    params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    mesh = _fake_mesh()
    specs = jax.tree_util.tree_map_with_path(
        lambda path, leaf: (SH.param_spec(path, leaf, mesh), leaf), params)

    def check(pair):
        spec, leaf = pair
        for dim, ax in zip(leaf.shape, spec):
            if ax is None:
                continue
            size = mesh.shape[ax] if isinstance(ax, str) else int(
                np.prod([mesh.shape[a] for a in ax]))
            assert dim % size == 0, (spec, leaf.shape, ax)

    jax.tree.map(check, specs, is_leaf=lambda x: isinstance(x, tuple))


def test_straggler_detection():
    mon = StragglerMonitor(n_workers=8, threshold=1.5)
    for step in range(20):
        for w in range(8):
            t = 1.0 if w != 3 else 2.5  # worker 3 is slow
            mon.record(w, t + np.random.default_rng(step * 8 + w).normal(0, .02))
    assert mon.stragglers() == [3]


def test_replan_after_pod_loss():
    data, model = replan_data_axis(n_healthy_hosts=48, model_parallel=16)
    assert model == 16 and data == 8  # 192 chips -> 8×16 mesh
    data2, _ = replan_data_axis(n_healthy_hosts=64, model_parallel=16)
    assert data2 == 16  # full pod


def test_batch_pspec():
    mesh = _fake_mesh()
    assert SH.batch_pspec(mesh, 256, 2) == P("data", None)
    assert SH.batch_pspec(mesh, 1, 2) == P(None, None)  # long_500k B=1
    mesh_mp = _fake_mesh(pod=2)
    assert SH.batch_pspec(mesh_mp, 256, 2) == P(("pod", "data"), None)


def test_cache_pspec_seq_sharded():
    mesh = _fake_mesh()
    leaf = jax.ShapeDtypeStruct((4, 128, 2048, 2, 64), jnp.bfloat16)
    spec = SH.cache_pspec((), leaf, mesh, 128)
    assert spec == P(None, "data", "model", None, None)


def test_cache_pspec_batch_not_dividing():
    """A batch that does not divide the data axis replicates instead of
    erroring — the sharded serve path admits ragged waves."""
    mesh = _fake_mesh(data=16, model=16)
    leaf = jax.ShapeDtypeStruct((4, 3, 2048, 2, 64), jnp.bfloat16)
    assert SH.cache_pspec((), leaf, mesh, 3) == P(
        None, None, "model", None, None)
    # sequence not dividing model either -> fully replicated
    leaf = jax.ShapeDtypeStruct((4, 3, 100, 2, 64), jnp.bfloat16)
    assert SH.cache_pspec((), leaf, mesh, 3) == P(
        None, None, None, None, None)


def test_cache_pspec_missing_axes_degrade():
    """Meshes narrower than (data, model) — e.g. a per-host serve slice —
    must degrade the absent axis to replication, not KeyError."""

    class _AxisMesh:
        def __init__(self, **shape):
            self.axis_names = tuple(shape)
            self.shape = shape

    leaf = jax.ShapeDtypeStruct((4, 8, 64, 2, 64), jnp.bfloat16)
    assert SH.cache_pspec((), leaf, _AxisMesh(model=8), 8) == P(
        None, None, "model", None, None)
    assert SH.cache_pspec((), leaf, _AxisMesh(data=8), 8) == P(
        None, "data", None, None, None)
    assert SH.batch_pspec(_AxisMesh(model=8), 64, 2) == P(None, None)


def test_cache_shardings_place_on_small_mesh():
    """End to end on real devices: a decode state whose batch does NOT
    divide the data axis still places (replicated batch dim)."""
    if jax.device_count() < 2:
        pytest.skip("needs >=2 devices (run under test.sh)")
    from repro.launch.mesh import make_smoke_mesh

    mesh = make_smoke_mesh(2, 1)
    cfg = get_smoke_config("qwen2_1_5b")
    for batch in (3, 4):  # 3 % 2 != 0 (replicates), 4 % 2 == 0 (shards)
        state = M.init_decode_state(cfg, batch, 64)
        placed = jax.device_put(
            state, SH.cache_shardings(state, mesh, batch))
        kv_spec = placed["kv"][0].sharding.spec
        assert kv_spec[1] == ("data" if batch == 4 else None)


def test_serve_pspec_rules():
    """The device batcher's donated pytree: slot arrays shard over data,
    rings and scalars replicate, the decode subtree follows cache rules."""
    mesh = _fake_mesh(data=8, model=16)
    B, R, T = 16, 32, 8
    st = {
        "decode": {"kv": jax.ShapeDtypeStruct((4, B, 2048, 2, 64),
                                              jnp.bfloat16)},
        "free": jax.ShapeDtypeStruct((B,), jnp.bool_),
        "gen": jax.ShapeDtypeStruct((B,), jnp.int32),
        "feat": jax.ShapeDtypeStruct((B, 7), jnp.int32),
        "head": jax.ShapeDtypeStruct((), jnp.int32),
        "out_tok": jax.ShapeDtypeStruct((R, T), jnp.int32),
        "out_done": jax.ShapeDtypeStruct((R,), jnp.bool_),
    }
    specs = jax.tree_util.tree_map_with_path(
        lambda path, leaf: SH.serve_pspec(path, leaf, mesh, B), st)
    assert specs["decode"]["kv"] == P(None, "data", "model", None, None)
    assert specs["free"] == P("data")
    assert specs["gen"] == P("data")
    assert specs["feat"] == P("data", None)
    assert specs["head"] == P()
    assert specs["out_tok"] == P(None, None)  # rings drain to host
    assert specs["out_done"] == P(None)
    # queue rows are data-parallel like any batch; ragged queues replicate
    assert SH.queue_pspec(mesh, 64, 2) == P("data", None)
    assert SH.queue_pspec(mesh, 9, 2) == P(None, None)


def test_paged_cache_pspec_rules():
    """Paged page pools [stack, n_pages, page, KV * hd] and their int8
    scale planes [stack, n_pages, page, KV]: pages shard over data, the
    within-page sequence over model where it divides, and non-dividing
    dims degrade to replication (small-mesh safe)."""
    mesh = _fake_mesh(data=8, model=16)
    leaf = jax.ShapeDtypeStruct((4, 64, 32, 128), jnp.bfloat16)
    assert SH.paged_cache_pspec(leaf, mesh) == P(
        None, "data", "model", None)
    leaf = jax.ShapeDtypeStruct((4, 64, 32, 2), jnp.float32)
    assert SH.paged_cache_pspec(leaf, mesh) == P(
        None, "data", "model", None)
    # page size not dividing model -> replicated page dim; pool not
    # dividing data -> replicated pages
    leaf = jax.ShapeDtypeStruct((4, 63, 20, 128), jnp.bfloat16)
    assert SH.paged_cache_pspec(leaf, mesh) == P(None, None, None, None)
    # non-pool leaves (defensive): replicate
    leaf = jax.ShapeDtypeStruct((64, 32), jnp.bfloat16)
    assert SH.paged_cache_pspec(leaf, mesh) == P(None, None)


def test_serve_pspec_paged_leaves():
    """The paged batcher's extra donated leaves: per-slot offsets,
    prompt buffers and block tables shard their slot dim over data
    (page-list dim replicated); the free-page mask replicates; the
    page pool follows paged_cache_pspec."""
    mesh = _fake_mesh(data=8, model=16)
    B = 16
    st = {
        "pages": (jax.ShapeDtypeStruct((4, 64, 32, 128), jnp.bfloat16),
                  jax.ShapeDtypeStruct((4, 64, 32, 128), jnp.bfloat16)),
        "pos": jax.ShapeDtypeStruct((B,), jnp.int32),
        "plen": jax.ShapeDtypeStruct((B,), jnp.int32),
        "pbuf": jax.ShapeDtypeStruct((B, 32), jnp.int32),
        "tbl": jax.ShapeDtypeStruct((B, 4), jnp.int32),
        "pfree": jax.ShapeDtypeStruct((64,), jnp.bool_),
    }
    specs = jax.tree_util.tree_map_with_path(
        lambda path, leaf: SH.serve_pspec(path, leaf, mesh, B), st)
    assert specs["pages"][0] == P(None, "data", "model", None)
    assert specs["pos"] == P("data")
    assert specs["plen"] == P("data")
    assert specs["pbuf"] == P("data", None)
    assert specs["tbl"] == P("data", None)
    assert specs["pfree"] == P(None)


def test_compression_lossless_in_the_limit():
    """Property: with *varying* per-step gradients, the accumulated
    dequantized gradient tracks the true gradient sum up to a single
    step's quantization error (the error-feedback telescoping sum) —
    stronger than the constant-gradient check in test_train.py."""
    rng = np.random.default_rng(42)
    shapes = {"w": (37, 11), "b": (64,), "k": (3, 5, 7)}

    def draw():
        return {k: jnp.asarray(rng.normal(1.0, 0.5, s), jnp.float32)
                for k, s in shapes.items()}

    err = C.init_error_state(draw())
    compress = jax.jit(C.compress_grads)  # must be jit-safe (train step)
    total_true = {k: np.zeros(s) for k, s in shapes.items()}
    total_deq = {k: np.zeros(s) for k, s in shapes.items()}
    K = 100
    for _ in range(K):
        g = draw()
        deq, err = compress(g, err)
        for k in shapes:
            total_true[k] += np.asarray(g[k])
            total_deq[k] += np.asarray(deq[k])
    for k in shapes:
        rel = (np.abs(total_deq[k] - total_true[k]).max()
               / np.abs(total_true[k]).max())
        assert rel < 5e-3, (k, rel)
    # residual error itself is bounded by ~one quantization step
    for e in jax.tree.leaves(err):
        assert float(jnp.abs(e).max()) < 0.1


def test_compression_ratio_near_4x():
    g = {"w": jnp.zeros((1024, 256)), "b": jnp.zeros((256,))}
    assert 3.9 < C.compression_ratio(g) <= 4.0


def test_preemption_handler_flags_then_drains_once():
    calls = []
    before = signal.getsignal(signal.SIGTERM)
    h = PreemptionHandler(lambda: calls.append(1)).install()
    try:
        os.kill(os.getpid(), signal.SIGTERM)
        for _ in range(200):  # handler runs at the next bytecode boundary
            if h.preempted:
                break
            time.sleep(0.005)
        # the handler only flags (checkpointing mid-step would touch
        # donated buffers); the loop drains at its next safe point
        assert h.preempted and calls == []
        assert h.drain() and calls == [1]
        assert not h.drain() and calls == [1]  # idempotent
    finally:
        h.uninstall()
    assert signal.getsignal(signal.SIGTERM) is before


def test_straggler_monitor_single_worker_never_flags():
    mon = StragglerMonitor(n_workers=1)
    for s in range(10):
        mon.record(0, 1.0 + s)  # drifting but alone: no fleet baseline
    assert mon.stragglers() == []


def test_split_layers_for_stages_structure():
    """Stage split re-cuts the stacked layer dim; specs stay per-leaf."""
    cfg = get_smoke_config("gemma3_27b")  # 6 layers
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    mesh = _fake_mesh()
    staged = PP.split_layers_for_stages(params, 3)
    assert "layers" not in staged and len(staged["stages"]) == 3
    for stage in staged["stages"]:
        assert jax.tree.leaves(stage)[0].shape[0] == 2
    specs = PP.staged_pspecs(SH.param_pspecs(params, mesh), 3)
    # staged tree and staged specs must be structurally congruent
    jax.tree.map(lambda leaf, spec: None, staged, specs)
    with pytest.raises(ValueError):
        PP.split_layers_for_stages(params, 4)  # 6 % 4 != 0


def test_pipeline_refuses_frontend_families():
    """vlm/encdec would silently train a token-only objective, and a
    stack with leading dense layers would stage without them — refuse."""
    mesh = _fake_mesh()
    for arch in ("internvl2_2b", "seamless_m4t_large_v2",
                 "moonlight_16b_a3b"):
        cfg = get_smoke_config(arch)
        with pytest.raises(NotImplementedError):
            PP.make_pipeline_step(cfg, mesh, {}, n_stages=1)
