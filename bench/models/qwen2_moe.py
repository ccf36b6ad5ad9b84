"""Qwen2-MoE (``model_type`` ``qwen2_moe``): Qwen2's attention, embedding,
head and norms (``qwen2.py``) with a sparse mixture of experts in place of
the MLP.

Weights (``init``): each layer's ``moe`` holds ``router [D, Ep]`` and the
experts' ``w_gate``, ``w_up`` ``[Ep, D, F]`` and ``w_down [Ep, F, D]``, the
``num_experts`` real ones padded to ``Ep``, a multiple of 16, as the
program stores them; and ``shared``, one SwiGLU of width
``shared_expert_intermediate_size``.

Reference (``forward``): a softmax router over the real experts, the
``num_experts_per_tok`` largest kept (renormalised to sum one where
``norm_topk_prob``), each a SwiGLU of width ``moe_intermediate_size``,
plus the shared SwiGLU added without a gate where ``shared_expert_gate``
is false.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from bench.core.weights import _mat, _round_up
from bench.models import qwen2
from bench.reference import _mm, _q8, _swiglu

MOE_ROWS = 512  # token rows per expert block


def dims(cfg: dict) -> Dict[str, int]:
    return dict(qwen2.base_dims(cfg),
                E=cfg["num_experts"],
                Ep=_round_up(cfg["num_experts"], 16),
                k=cfg["num_experts_per_tok"],
                F=cfg["moe_intermediate_size"],
                Fs=cfg["shared_expert_intermediate_size"])


def init(cfg: dict, key) -> Dict:
    n = dims(cfg)
    L, D, Ep, F, Fs = n["L"], n["D"], n["Ep"], n["F"], n["Fs"]
    ks = iter(jax.random.split(key, 32))
    layers = qwen2.init_attention(cfg, n, ks)
    layers["moe"] = {
        "router": _mat(next(ks), (L, D, Ep), D),
        "w_gate": _mat(next(ks), (L, Ep, D, F), D),
        "w_up": _mat(next(ks), (L, Ep, D, F), D),
        "w_down": _mat(next(ks), (L, Ep, F, D), F),
        "shared": {
            "w_gate": _mat(next(ks), (L, D, Fs), D),
            "w_up": _mat(next(ks), (L, D, Fs), D),
            "w_down": _mat(next(ks), (L, Fs, D), Fs),
        },
    }
    return qwen2.init_outer(cfg, n, ks, layers)


def moe(p, h, cfg, fp8):
    if cfg.get("shared_expert_gate"):
        raise ValueError("a gated shared expert is not computed here")
    E, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    logits = _mm(h, p["router"][:, :E], fp8)
    gates = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(gates, k)
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    comb = jnp.sum(jax.nn.one_hot(idx, E) * top[..., None], axis=1)  # [S,E]
    wg, wu, wd = (p["w_gate"][:E], p["w_up"][:E], p["w_down"][:E])
    hq = h
    if fp8:
        hq = _q8(h, -1)
        wg, wu, wd = _q8(wg, 1), _q8(wu, 1), _q8(wd, 1)
    outs = []
    for r0 in range(0, h.shape[0], MOE_ROWS):  # bounds the [rows, E, F] tile
        hb, cb = hq[r0:r0 + MOE_ROWS], comb[r0:r0 + MOE_ROWS]
        a = jax.nn.silu(jnp.einsum("sd,edf->sef", hb, wg)) * jnp.einsum(
            "sd,edf->sef", hb, wu)
        if fp8:
            a = _q8(a, -1)
        outs.append(jnp.einsum("sef,efd->sd", a * cb[..., None], wd))
    out = jnp.concatenate(outs, 0)
    sp = p["shared"]
    return out + _swiglu(h, sp["w_gate"], sp["w_up"], sp["w_down"], fp8)


def forward(cfg, fp8, params, tokens, where):
    return qwen2.decoder(cfg, fp8, params, tokens, where,
                         lambda lp, h: moe(lp["moe"], h, cfg, fp8))


program_settings = qwen2.program_settings


def program_widths(cfg: dict) -> Dict[str, int]:
    return dict(qwen2.base_widths(cfg),
                d_ff=cfg["moe_intermediate_size"],
                n_experts=cfg["num_experts"],
                n_experts_active=cfg["num_experts_per_tok"],
                shared_d_ff=cfg["shared_expert_intermediate_size"])


def linear_flops_per_token(d: Dict[str, int]) -> int:
    """Matmul FLOPs of one token through every layer, attention scores
    excluded, LM head excluded (active experts only)."""
    D = d["D"]
    ffn = 2 * D * d["E"] + d["k"] * 6 * D * d["F"] + 6 * D * d["Fs"]
    return d["L"] * (qwen2.proj_flops(d) + ffn)


attn_flops = qwen2.attn_flops
attn_bytes = qwen2.attn_bytes
