"""DeepSeek-V3 (``model_type`` ``deepseek_v3``; Moonlight-16B-A3B): multi-head
latent attention, ``first_k_dense_replace`` dense layers, then layers of
sigmoid-routed experts with shared experts.

Weights (``init``), in the tree the program's serve step reads:
``embed [Vp, D]``, ``head [D, Vp]``, ``ln_f [D]``; ``dense_layers`` and
``layers``, each stacked on a leading layer axis, with ``mixer`` (``wq``
``[D, H*(nope+rope)]``, per head ``[nope | rope]``; ``w_kva [D,
rank+rope]``; the latent's norm gain ``kv_norm [rank]``; ``w_kvb [rank,
H*(nope+v)]``, per head ``[k_nope | v]``; ``wo [H*v, D]``), ``ln1``,
``ln2``; the dense layers' ``mlp`` (SwiGLU of ``intermediate_size``); the
MoE layers' ``moe``: ``router [D, Ep]`` over every expert, its correction
bias ``router_bias [Ep]``, the experts this chip holds (``w_gate``,
``w_up`` ``[El, D, F]``, ``w_down [El, F, D]``: ``Ep / ep_size`` of them,
rank 0's slice) and ``shared``, one SwiGLU of ``n_shared_experts x F``.
Norm gains are stored as offsets from one.

Reference (``forward``), each layer behind an RMSNorm:

* MLA in its published (not absorbed) form: ``q = x wq`` split per head
  into ``q_nope`` and ``q_pe``; ``[c_raw | k_pe] = x w_kva``, the latent
  ``c = rms(c_raw)``; per head ``[k_nope | v] = c w_kvb``; rotary
  embedding on ``q_pe`` and the one ``k_pe`` (DeepSeek's pairing: the
  interleaved dims gathered into ``[evens | odds]``, then rotated in
  halves); keys ``[k_nope | k_pe]``, causal softmax over scores scaled by
  ``1/sqrt(nope + rope)``, ``o w_o``;
* dense layers: SwiGLU;
* MoE layers: scores ``sigmoid(x router)`` over every expert, the top
  ``num_experts_per_tok`` of ``scores + router_bias`` chosen (the bias
  moves the choice only), their scores renormalised to sum one
  (``norm_topk_prob``) and scaled by ``routed_scaling_factor``; of the
  chosen experts those this chip holds computed, each a SwiGLU of
  ``moe_intermediate_size``; the shared SwiGLU added.  The experts other
  ranks hold add nothing here, in the program and the reference alike;

then the final RMSNorm and the head over the ``vocab_size`` real columns.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.core.counts import BF16
from bench.core.weights import BIAS_SD, _mat, _round_up
from bench.models import qwen2
from bench.reference import _mm, _q8, _rms, _rope, _swiglu

Q_BLOCK = 1024   # query rows per attention block (bounds the score tile)
MOE_ROWS = 512   # token rows per expert block
RANK = 0         # the expert-parallel rank this chip stands for


# ---------------------------------------------------------------- sizes
def dims(cfg: dict) -> Dict[str, int]:
    E = cfg["n_routed_experts"]
    Ep = _round_up(E, 16)
    return dict(
        L=cfg["num_hidden_layers"], Ld=cfg["first_k_dense_replace"],
        D=cfg["hidden_size"], H=cfg["num_attention_heads"],
        V=cfg["vocab_size"], Vp=_round_up(cfg["vocab_size"], 256),
        r=cfg["kv_lora_rank"], dn=cfg["qk_nope_head_dim"],
        dr=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
        Fd=cfg["intermediate_size"],
        E=E, Ep=Ep, El=Ep // cfg["ep_size"],
        k=cfg["num_experts_per_tok"], F=cfg["moe_intermediate_size"],
        Fs=cfg["n_shared_experts"] * cfg["moe_intermediate_size"])


# -------------------------------------------------------------- weights
def _mla_tree(n: Dict[str, int], L: int, ks) -> Dict:
    D, H, r, dn, dr, dv = (n[k] for k in ("D", "H", "r", "dn", "dr", "dv"))
    return {
        "wq": _mat(next(ks), (L, D, H * (dn + dr)), D),
        "w_kva": _mat(next(ks), (L, D, r + dr), D),
        "kv_norm": qwen2._gain(ks, L, r),
        "w_kvb": _mat(next(ks), (L, r, H * (dn + dv)), r),
        "wo": _mat(next(ks), (L, H * dv, D), H * dv),
    }


def _swiglu_tree(ks, L: int, D: int, F: int) -> Dict:
    return {"w_gate": _mat(next(ks), (L, D, F), D),
            "w_up": _mat(next(ks), (L, D, F), D),
            "w_down": _mat(next(ks), (L, F, D), F)}


def init(cfg: dict, key) -> Dict:
    n = dims(cfg)
    Ld, D, Ep, El, F = n["Ld"], n["D"], n["Ep"], n["El"], n["F"]
    Lm = n["L"] - Ld
    ks = iter(jax.random.split(key, 48))
    dense = {"mixer": _mla_tree(n, Ld, ks), "ln1": qwen2._gain(ks, Ld, D),
             "ln2": qwen2._gain(ks, Ld, D),
             "mlp": _swiglu_tree(ks, Ld, D, n["Fd"])}
    layers = {"mixer": _mla_tree(n, Lm, ks), "ln1": qwen2._gain(ks, Lm, D),
              "ln2": qwen2._gain(ks, Lm, D)}
    layers["moe"] = {
        "router": _mat(next(ks), (Lm, D, Ep), D),
        "router_bias": BIAS_SD * jax.random.normal(next(ks), (Lm, Ep),
                                                   jnp.float32),
        "w_gate": _mat(next(ks), (Lm, El, D, F), D),
        "w_up": _mat(next(ks), (Lm, El, D, F), D),
        "w_down": _mat(next(ks), (Lm, El, F, D), F),
        "shared": _swiglu_tree(ks, Lm, D, n["Fs"]),
    }
    tree = qwen2.init_outer(cfg, n, ks, layers)
    tree["dense_layers"] = dense
    return tree


# ------------------------------------------------------------ reference
def rope(x, pos, theta):
    """Rotary embedding of ``x [S, H, d]`` in DeepSeek's pairing."""
    S, H, d = x.shape
    x = x.reshape(S, H, d // 2, 2).swapaxes(-1, -2).reshape(S, H, d)
    return _rope(x, pos, theta)


def mla(p, h, cfg, fp8):
    n = dims(cfg)
    S = h.shape[0]
    H, r, dn, dr, dv = n["H"], n["r"], n["dn"], n["dr"], n["dv"]
    theta = cfg["rope_theta"]
    pos = jnp.arange(S)
    q = _mm(h, p["wq"], fp8).reshape(S, H, dn + dr)
    kv_a = _mm(h, p["w_kva"], fp8)
    c = _rms(kv_a[:, :r], p["kv_norm"], cfg["rms_norm_eps"])
    k_pe = rope(kv_a[:, None, r:], pos, theta)  # [S, 1, dr]
    kv = _mm(c, p["w_kvb"], fp8).reshape(S, H, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (S, H, dr))],
                        -1)
    v = kv[..., dn:]
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos, theta)], -1)
    if fp8:
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, 0)
    outs = []
    for q0 in range(0, S, Q_BLOCK):
        qb = q[q0:q0 + Q_BLOCK]
        s = jnp.einsum("qhd,khd->hqk", qb, k) / np.sqrt(dn + dr)
        qi = jnp.arange(q0, q0 + qb.shape[0])
        s = jnp.where(qi[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        if fp8:
            w = _q8(w, -1)
        outs.append(jnp.einsum("hqk,khd->qhd", w, v))
    o = jnp.concatenate(outs, 0).reshape(S, H * dv)
    return _mm(o, p["wo"], fp8)


def route(p, h, cfg, fp8):
    """Combine weights ``[S, E]`` over every routed expert."""
    E, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    if cfg["scoring_func"] != "sigmoid" or cfg["topk_method"] != "noaux_tc":
        raise ValueError("only sigmoid scores with noaux_tc are computed")
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("group-limited routing is not computed here")
    scores = jax.nn.sigmoid(_mm(h, p["router"][:, :E], fp8))
    _, idx = jax.lax.top_k(scores + p["router_bias"][:E], k)
    w = jnp.take_along_axis(scores, idx, -1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * cfg["routed_scaling_factor"]
    return jnp.sum(jax.nn.one_hot(idx, E) * w[..., None], axis=1)


def moe(p, h, cfg, fp8):
    n = dims(cfg)
    El = n["El"]
    first = RANK * El
    # the held experts among the real ones (the last rank may hold pads)
    held = max(0, min(El, n["E"] - first))
    comb = route(p, h, cfg, fp8)[:, first:first + held]
    wg, wu, wd = (p["w_gate"][:held], p["w_up"][:held], p["w_down"][:held])
    hq = h
    if fp8:
        hq = _q8(h, -1)
        wg, wu, wd = _q8(wg, 1), _q8(wu, 1), _q8(wd, 1)
    outs = []
    for r0 in range(0, h.shape[0], MOE_ROWS):  # bounds the [rows, El, F] tile
        hb, cb = hq[r0:r0 + MOE_ROWS], comb[r0:r0 + MOE_ROWS]
        a = jax.nn.silu(jnp.einsum("sd,edf->sef", hb, wg)) * jnp.einsum(
            "sd,edf->sef", hb, wu)
        if fp8:
            a = _q8(a, -1)
        outs.append(jnp.einsum("sef,efd->sd", a * cb[..., None], wd))
    sp = p["shared"]
    return jnp.concatenate(outs, 0) + _swiglu(h, sp["w_gate"], sp["w_up"],
                                              sp["w_down"], fp8)


def forward(cfg, fp8, params, tokens, where):
    eps = cfg["rms_norm_eps"]
    x = params["embed"][tokens]

    def stack(ffn):
        def layer(x, lp):
            x = x + mla(lp["mixer"], _rms(x, lp["ln1"], eps), cfg, fp8)
            return x + ffn(lp, _rms(x, lp["ln2"], eps)), None
        return layer

    def dense(lp, h):
        m = lp["mlp"]
        return _swiglu(h, m["w_gate"], m["w_up"], m["w_down"], fp8)

    x, _ = jax.lax.scan(stack(dense), x, params["dense_layers"])
    x, _ = jax.lax.scan(stack(lambda lp, h: moe(lp["moe"], h, cfg, fp8)), x,
                        params["layers"])
    h = _rms(x[where], params["ln_f"], eps)
    return _mm(h, params["head"][:, :cfg["vocab_size"]], fp8)


# -------------------------------------------------------------- program
def program_settings(cfg: dict) -> Dict:
    """Depth, dense layers, rotary base, norm epsilon, the routed scale,
    and the expert share: ``ep_size`` ranks, this chip rank 0."""
    return dict(n_layers=cfg["num_hidden_layers"],
                n_dense_layers=cfg["first_k_dense_replace"],
                rope_theta=float(cfg["rope_theta"]),
                norm_eps=cfg["rms_norm_eps"],
                routed_scale=cfg["routed_scaling_factor"],
                expert_ranks=cfg["ep_size"], expert_rank=RANK)


def program_widths(cfg: dict) -> Dict:
    n = dims(cfg)
    return dict(d_model=n["D"], n_heads=n["H"], vocab_size=n["V"],
                n_layers=n["L"], kv_lora_rank=n["r"],
                qk_nope_head_dim=n["dn"], qk_rope_head_dim=n["dr"],
                v_head_dim=n["dv"], dense_d_ff=n["Fd"], d_ff=n["F"],
                n_experts=n["E"], n_experts_active=n["k"],
                shared_d_ff=n["Fs"], n_experts_local=n["El"],
                router=cfg["scoring_func"], norm_topk=cfg["norm_topk_prob"])


# --------------------------------------------------------------- counts
def mla_flops(d: Dict[str, int]) -> int:
    """Matmul FLOPs of one token through one layer's MLA projections
    (published form: the up-projection of its own latent included)."""
    D, H, r, dn, dr, dv = (d[k] for k in ("D", "H", "r", "dn", "dr", "dv"))
    return 2 * (D * H * (dn + dr) + D * (r + dr) + r * H * (dn + dv)
                + H * dv * D)


def linear_flops_per_token(d: Dict[str, int]) -> float:
    """Matmul FLOPs of one token through every layer, attention scores
    excluded, LM head excluded.  The routed experts count at this chip's
    expected share of a token's: ``k x El / E`` of them."""
    D = d["D"]
    dense = mla_flops(d) + 6 * D * d["Fd"]
    held = min(d["El"], d["E"])
    ffn = (2 * D * d["E"] + d["k"] * held / d["E"] * 6 * D * d["F"]
           + 6 * D * d["Fs"])
    return d["Ld"] * dense + (d["L"] - d["Ld"]) * (mla_flops(d) + ffn)


def attn_flops(d: Dict[str, int], pairs: int) -> int:
    """Published-form QK^T over ``nope + rope`` dims and PV over ``v``
    dims a head, over ``pairs`` query-key pairs, every layer."""
    return d["L"] * 2 * d["H"] * (d["dn"] + d["dr"] + d["dv"]) * pairs


def attn_bytes(d: Dict[str, int], ctx: int, n: int) -> int:
    """HBM bytes one slot's attention needs at one step, every layer:
    its ``ctx`` cached ``[latent | rotary key]`` rows read once, its
    ``n`` queries read and outputs written (published widths)."""
    kv = ctx * (d["r"] + d["dr"]) * BF16
    qo = n * d["H"] * (d["dn"] + d["dr"] + d["dv"]) * BF16
    return d["L"] * (kv + qo)
