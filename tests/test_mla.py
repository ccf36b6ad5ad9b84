"""Latent attention (MLA), sigmoid routing and the expert share, at the
moonlight-16b-a3b smoke size on the CPU.

The reference here is a plain float32 forward of the published
computation, kept for tests: MLA in its published form (per-head keys and
values up-projected from each token's latent), the leading dense layer,
sigmoid scores with the correction bias in the choice only, the chosen
scores renormalised and scaled, the layer's share of the experts and the
shared experts once.  It runs at ``jax.default_matmul_precision
("highest")``.  The program is compared with it computing in float32
(``M.COMPUTE_DTYPE``), where the two differ only in summation order: in
bfloat16 a near-tie of the sigmoid router's top-k flips on rounding and
moves a logit by as much as a wrong computation would.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.arch import model as M
from repro.configs import get_smoke_config
from repro.nn import attention as A
from repro.nn import attn_backend as AB
from repro.nn.attn_backend import PagedKV
from repro.nn.moe import moe_block, route

PAGE = 4


def _cfg(**kw):
    return dataclasses.replace(get_smoke_config("moonlight_16b_a3b"), **kw)


def _params(cfg, seed=0):
    """The program's tree with its zero-initialised gains and biases
    drawn, so that every term is exercised."""
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 64))

    def draw(path, a):
        name = jax.tree_util.keystr(path)
        if any(s in name for s in ("ln1", "ln2", "ln_f", "kv_norm",
                                   "router_bias")):
            return 0.1 * jax.random.normal(next(keys), a.shape, a.dtype)
        return a
    return jax.tree_util.tree_map_with_path(draw, params)


# ------------------------------------------------------------- reference
def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + g)


def _rope(x, pos, theta):
    """DeepSeek's pairing: interleaved dims to [evens | odds], rotated
    in halves.  x [S, H, d]."""
    S, H, d = x.shape
    x = x.reshape(S, H, d // 2, 2).swapaxes(-1, -2).reshape(S, H, d)
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None, None] * inv
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _swiglu(h, m):
    return (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]


def ref_mla(p, h, cfg):
    S = h.shape[0]
    H, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    pos = jnp.arange(S, dtype=jnp.float32)
    q = (h @ p["wq"]).reshape(S, H, dn + dr)
    kv_a = h @ p["w_kva"]
    c = _rms(kv_a[:, :r], p["kv_norm"], cfg.norm_eps)
    k_pe = _rope(kv_a[:, None, r:], pos, cfg.rope_theta)
    kv = (c @ p["w_kvb"]).reshape(S, H, dn + dv)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_pe, (S, H, dr))], -1)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos,
                                            cfg.rope_theta)], -1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(dn + dr)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), kv[..., dn:])
    return o.reshape(S, H * dv) @ p["wo"]


def ref_moe(p, h, cfg, rank=None):
    """The routed experts of ``rank`` (all of them where None) and,
    where ``rank`` is None or 0, the shared experts."""
    E, k, El = cfg.n_experts, cfg.n_experts_active, p["w_gate"].shape[0]
    scores = jax.nn.sigmoid(h @ p["router"][:, :E])
    _, idx = jax.lax.top_k(scores + p["router_bias"][:E], k)
    w = jnp.take_along_axis(scores, idx, -1)
    w = w / w.sum(-1, keepdims=True) * cfg.routed_scale
    comb = jnp.sum(jax.nn.one_hot(idx, E) * w[..., None], 1)
    first = 0 if rank is None else rank * El
    out = 0.0
    for e in range(El):
        m = {n: p[n][e] for n in ("w_gate", "w_up", "w_down")}
        out = out + comb[:, first + e:first + e + 1] * _swiglu(h, m)
    if rank in (None, 0):
        out = out + _swiglu(h, p["shared"])
    return out


def ref_logits(params, tokens, cfg):
    """Logits [S, vocab] of one sequence, float32 at highest."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        stacks = [(params["dense_layers"], lambda lp, h: _swiglu(h, lp["mlp"])),
                  (params["layers"], lambda lp, h: ref_moe(lp["moe"], h, cfg))]
        for stack, ffn in stacks:
            n = jax.tree.leaves(stack)[0].shape[0]
            for i in range(n):
                lp = jax.tree.map(lambda a: a[i], stack)
                x = x + ref_mla(lp["mixer"], _rms(x, lp["ln1"], cfg.norm_eps),
                                cfg)
                x = x + ffn(lp, _rms(x, lp["ln2"], cfg.norm_eps))
        x = _rms(x, params["ln_f"], cfg.norm_eps)
        return (x @ params["head"])[:, :cfg.vocab_size]


# ----------------------------------------------------------------- tests
@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_paged_prefill_then_decode_matches_reference(impl, monkeypatch):
    """Prefill in chunks, then decode token by token through
    ``paged_decode_step`` over a shuffled page table, against the
    reference's full-sequence logits at every position (the kernel runs
    interpreted).  Float32 compute: agreement to summation order."""
    monkeypatch.setattr(M, "COMPUTE_DTYPE", jnp.float32)
    cfg = _cfg(expert_ranks=4, expert_rank=0)
    params = _params(cfg)
    B, S, C, n_ps = 2, 21, 8, 8
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)
    want = np.stack([ref_logits(params, jnp.asarray(t), cfg) for t in tokens])
    tbl = jnp.asarray(rng.permutation(B * n_ps).reshape(B, n_ps), jnp.int32)
    kv = M.init_paged_kv(cfg, B * n_ps, PAGE)
    assert kv.latent and kv.k.shape[-1] == cfg.kv_lanes
    step = jax.jit(functools.partial(M.paged_decode_step, cfg=cfg,
                                     attn_impl=impl, all_positions=True))
    got = np.zeros_like(want)
    pos = 0
    for n in (C, C, 1, 1, 1, 1, 1):  # a 16-token prompt, then decode
        chunk = np.zeros((B, C), np.int32)
        chunk[:, :n] = tokens[:, pos:pos + n]
        logits, kv = step(params, kv, tbl, jnp.full((B,), pos, jnp.int32),
                          jnp.asarray(chunk), jnp.full((B,), n, jnp.int32))
        got[:, pos:pos + n] = np.asarray(logits)[:, :n, :cfg.vocab_size]
        pos += n
    scale = np.abs(want).max()
    # float32 throughout: only summation order differs
    assert np.abs(got[:, :pos] - want[:, :pos]).max() <= 1e-4 * scale


def test_absorbed_form_equals_published_form():
    """``paged_mla_block`` (absorbed, through a float32 latent pool)
    against ``mla_block`` (per-head keys and values) over one sequence
    written a chunk at a time."""
    cfg = _cfg()
    m = M.mla_dims(cfg)
    p = _params(cfg)["layers"]["mixer"]
    p = jax.tree.map(lambda a: a[0], p)
    B, S = 2, 12
    x = jax.random.normal(jax.random.PRNGKey(3), (B, S, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        want = A.mla_block(p, x, m)
        n_ps = S // PAGE
        tbl = jnp.arange(B * n_ps, dtype=jnp.int32).reshape(B, n_ps)
        kv = PagedKV(k=jnp.zeros((B * n_ps, PAGE, m.lanes), jnp.float32))
        outs = []
        for c0 in range(0, S, 4):
            pos = jnp.broadcast_to(jnp.arange(c0, c0 + 4), (B, 4))
            ids = jnp.take_along_axis(tbl, pos // PAGE, 1)
            o, kv = A.paged_mla_block(
                p, x[:, c0:c0 + 4], kv.with_view(tbl, pos, ids, pos % PAGE),
                m, window=0)
            kv = kv.pool()
            outs.append(o)
    got = jnp.concatenate(outs, 1)
    assert float(jnp.abs(got - want).max()) <= 1e-5 * float(
        jnp.abs(want).max())


@pytest.mark.parametrize("C,n_ps", [(1, 24), (4, 8)])
def test_latent_kernel_matches_oracle(C, n_ps):
    """The kernel on a latent pool at Moonlight's 576 / 512 lanes and
    its softmax scale 192**-0.5, 16 heads, against the jnp oracle, in
    float32 (interpret mode): several grid steps a slot, the online
    softmax across them, pages past a slot's position skipped."""
    H, r, dr, L = 16, 512, 64, 2
    rng = np.random.default_rng(C)
    B = 3
    N = B * n_ps
    pool = jnp.asarray(rng.normal(size=(L, N, PAGE, r + dr)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, C, H, r + dr)), jnp.float32)
    tbl = jnp.asarray(rng.permutation(N).reshape(B, n_ps), jnp.int32)
    start = np.array([0, 9, n_ps * PAGE - C])
    pos = jnp.asarray(start[:, None] + np.arange(C), jnp.int32)
    kv = PagedKV(k=pool).with_view(tbl, pos, None, None, jnp.int32(1))
    kw = dict(n_heads=H, head_dim=r + dr, window=0, scale=192 ** -0.5,
              v_dim=r)
    want = AB.get("jnp")(q, kv, **kw)
    got = AB.get("pallas")(q, kv, **kw)
    assert got.shape == (B, C, H, r)
    # float32: the online softmax and the full one differ in rounding
    assert float(jnp.abs(got - want).max()) <= 1e-5 * float(
        jnp.abs(want).max())


def test_expert_shares_add_up_to_the_whole_layer():
    """Four ranks' layers, each holding a quarter of the experts and
    routing over all of them: their outputs, with the shared experts
    (which every rank computes) counted once, add up to the layer that
    holds every expert, and each rank's to the reference's share."""
    cfg = _cfg()
    p = jax.tree.map(lambda a: a[0], _params(cfg)["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 7, cfg.d_model))
    kw = dict(n_experts=cfg.n_experts, top_k=cfg.n_experts_active,
              router="sigmoid", routed_scale=cfg.routed_scale)
    with jax.default_matmul_precision("highest"):
        whole, _ = moe_block(p, x, **kw)
        shared = _swiglu(x, p["shared"])
        El = cfg.n_experts // 4
        total = 0.0
        for rank in range(4):
            sl = slice(rank * El, (rank + 1) * El)
            pr = dict(p, w_gate=p["w_gate"][sl], w_up=p["w_up"][sl],
                      w_down=p["w_down"][sl])
            out, _ = moe_block(pr, x, first=rank * El, **kw)
            want = ref_moe(pr, x.reshape(-1, cfg.d_model), cfg,
                           rank=rank) + (0.0 if rank == 0 else shared.reshape(
                               -1, cfg.d_model))
            np.testing.assert_allclose(out.reshape(-1, cfg.d_model), want,
                                       rtol=1e-5, atol=1e-5)
            total = total + out
        total = total - 3 * shared
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-5)


def test_correction_bias_moves_the_choice_not_the_weights():
    """Four experts, top-2, by hand.  Logits 0, 1, 2, -1 give scores
    s = sigmoid(logits); the bias (0, 0, -2, 3) makes the choice experts
    3 and 1 (s + b: 0.5, 0.731, -1.119, 3.269), not 2 and 1, and their
    weights are their scores alone: s_3 / (s_3 + s_1) and s_1 / (s_3 +
    s_1), times the routed scale 2."""
    D = 4
    p = {"router": jnp.eye(D, dtype=jnp.float32),
         "router_bias": jnp.asarray([0.0, 0.0, -2.0, 3.0])}
    x = jnp.asarray([[0.0, 1.0, 2.0, -1.0]])
    idx, w, _ = route(p, x, n_experts=4, top_k=2, router="sigmoid",
                      routed_scale=2.0)
    s = 1.0 / (1.0 + np.exp(-np.array([0.0, 1.0, 2.0, -1.0])))
    assert idx.tolist() == [[3, 1]]
    np.testing.assert_allclose(
        w[0], 2.0 * np.array([s[3], s[1]]) / (s[3] + s[1]), rtol=1e-6)
    # without the bias the choice is the two largest scores
    idx0, _, _ = route(dict(p, router_bias=jnp.zeros(4)), x, n_experts=4,
                       top_k=2, router="sigmoid")
    assert idx0.tolist() == [[2, 1]]


def test_latent_pool_refuses_int8_and_ring_decode_refuses_mla():
    cfg = _cfg()
    with pytest.raises(ValueError, match="latent"):
        M.init_paged_kv(cfg, 8, PAGE, kv_dtype="int8")
    with pytest.raises(NotImplementedError, match="paged"):
        M.decode_step(M.init_params(cfg, jax.random.PRNGKey(0)),
                      {"pos": jnp.int32(0)}, jnp.zeros((1, 1), jnp.int32),
                      cfg)
