"""Mixture-of-Experts layer: top-k routing, stacked experts, shared experts.

Experts live as stacked tensors [E, d_model, d_ff] so the expert dimension
shards over the 'model' mesh axis (expert parallelism).  Dispatch/combine
uses dense one-hot einsums — the standard TPU-friendly formulation (no
dynamic scatter), with a capacity-free approximation: every token's top-k
weights are kept exactly, experts compute all tokens masked by routing
weight.  A ``router_noise``-free deterministic router keeps dry-runs and
tests reproducible.  Load-balancing aux loss follows Switch/GShard.

Expert padding: archs whose expert count doesn't divide the mesh axis
(qwen2-moe: 60) pad to ``n_experts_padded`` with dead experts; the router
logits for pads are masked to -inf, so they never receive tokens.

Routing (``route``) is a softmax over the experts, or sigmoid scores
with a learned correction bias that moves the top-k choice and not the
weights (DeepSeek-V3 ``noaux_tc``); the chosen weights are renormalised
where asked and scaled by a routed factor.

Expert share: a layer may hold one rank's slice of the experts
(``w_gate``/``w_up``/``w_down`` stacked ``[E_local, ...]``, from expert
``first``).  It routes over every expert (the router keeps its full
width) and computes its own experts' part of the result; what the other
ranks' experts add is theirs.  The shared experts are added once, here.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .common import ACTIVATIONS, dense_init, split_keys


def init_moe(key, d_model: int, moe_d_ff: int, n_experts_padded: int,
             n_shared: int, shared_d_ff: int, n_local: int = 0,
             router_bias: bool = False) -> Dict:
    """``n_local`` experts are held (0 = all ``n_experts_padded``);
    ``router_bias`` adds the sigmoid router's correction bias."""
    k = split_keys(key, 5)
    E = n_experts_padded
    El = n_local or E
    p = {
        "router": dense_init(k[0], (d_model, E)),
        "w_gate": dense_init(k[1], (El, d_model, moe_d_ff)),
        "w_up": dense_init(k[2], (El, d_model, moe_d_ff)),
        "w_down": dense_init(k[3], (El, moe_d_ff, d_model)),
    }
    if router_bias:
        p["router_bias"] = jnp.zeros((E,), jnp.float32)
    if n_shared > 0:
        ks = split_keys(k[4], 3)
        p["shared"] = {
            "w_gate": dense_init(ks[0], (d_model, shared_d_ff)),
            "w_up": dense_init(ks[1], (d_model, shared_d_ff)),
            "w_down": dense_init(ks[2], (shared_d_ff, d_model)),
        }
    return p


def route(p: Dict, x: jax.Array, *, n_experts: int, top_k: int,
          router: str = "softmax", norm_topk: bool = True,
          routed_scale: float = 1.0):
    """Top-k routing over every expert of the router.

    Returns ``(top_idx [..., k], top_vals [..., k], gates [..., E])``:
    the chosen experts, their weights, and the scores (softmax
    probabilities, or sigmoid scores) the load-balance loss reads.
    ``router="sigmoid"`` chooses by score plus ``p["router_bias"]`` and
    weighs by the score alone; padding experts are never chosen."""
    dt = x.dtype
    E = p["router"].shape[1]
    logits = (x @ p["router"].astype(dt)).astype(jnp.float32)  # [..., E]
    pad_mask = jnp.arange(E) >= n_experts
    if router == "sigmoid":
        gates = jax.nn.sigmoid(logits)
        choice = jnp.where(pad_mask, -jnp.inf, gates + p["router_bias"])
        _, top_idx = jax.lax.top_k(choice, top_k)
        top_vals = jnp.take_along_axis(gates, top_idx, axis=-1)
    elif router == "softmax":
        if n_experts < E:  # mask padding experts
            logits = jnp.where(pad_mask, -1e30, logits)
        gates = jax.nn.softmax(logits, axis=-1)
        top_vals, top_idx = jax.lax.top_k(gates, top_k)  # [..., k]
    else:
        raise ValueError(f"router must be 'softmax' or 'sigmoid', got "
                         f"{router!r}")
    if norm_topk:
        top_vals = top_vals / jnp.maximum(top_vals.sum(-1, keepdims=True),
                                          1e-9)
    if routed_scale != 1.0:
        top_vals = top_vals * routed_scale
    return top_idx, top_vals, gates


def moe_block(
    p: Dict,
    x: jax.Array,  # [B, S, D]
    *,
    n_experts: int,  # real experts (<= padded)
    top_k: int,
    act: str = "silu",
    router: str = "softmax",
    norm_topk: bool = True,
    routed_scale: float = 1.0,
    first: int = 0,  # index of the first expert this layer holds
) -> Tuple[jax.Array, jax.Array]:
    """Returns (output [B,S,D], aux_loss []).  The layer computes the
    experts it holds (``p["w_gate"].shape[0]`` of them from ``first``)
    for every token, weighted by the routing over all experts."""
    dt = x.dtype
    fn = ACTIVATIONS[act]
    E = p["router"].shape[1]
    El = p["w_gate"].shape[0]
    top_idx, top_vals, gates = route(
        p, x, n_experts=n_experts, top_k=top_k, router=router,
        norm_topk=norm_topk, routed_scale=routed_scale)
    # combine weights [B,S,E]: scatter top-k back densely via one-hot
    combine = (jax.nn.one_hot(top_idx, E, dtype=jnp.float32)
               * top_vals[..., None]).sum(axis=2)
    # aux load-balance loss (Switch): E * sum_e f_e * P_e
    density = (combine > 0).astype(jnp.float32).mean(axis=(0, 1))  # f_e
    router_prob = gates.mean(axis=(0, 1))  # P_e
    aux = E * jnp.sum(density * router_prob)
    if El != E:  # this layer's share of the experts
        combine = jax.lax.slice_in_dim(combine, first, first + El, axis=2)
    # expert compute over all tokens (dense dispatch, EP shards E)
    h_gate = jnp.einsum("bsd,edf->bsef", x, p["w_gate"].astype(dt))
    h_up = jnp.einsum("bsd,edf->bsef", x, p["w_up"].astype(dt))
    h = fn(h_gate) * h_up
    expert_out = jnp.einsum("bsef,efd->bsed", h, p["w_down"].astype(dt))
    out = jnp.einsum("bsed,bse->bsd", expert_out,
                     combine.astype(dt))
    if "shared" in p:
        sp = p["shared"]
        hs = fn(x @ sp["w_gate"].astype(dt)) * (x @ sp["w_up"].astype(dt))
        out = out + hs @ sp["w_down"].astype(dt)
    return out, aux


def moe_block_sparse(
    p: Dict,
    x: jax.Array,
    *,
    n_experts: int,
    top_k: int,
    act: str = "silu",
    capacity_factor: float = 1.25,
    router: str = "softmax",
    norm_topk: bool = True,
    routed_scale: float = 1.0,
    first: int = 0,
) -> Tuple[jax.Array, jax.Array]:
    """Capacity-based dispatch (GShard): tokens -> expert buffers.

    FLOP-proportional to k/E (vs dense ``moe_block`` computing all E per
    token).  Used by the perf-optimized path.  Holds every expert: a
    layer with a share of them runs ``moe_block``.
    """
    del first
    dt = x.dtype
    fn = ACTIVATIONS[act]
    B, S, D = x.shape
    E = p["router"].shape[1]
    if p["w_gate"].shape[0] != E:
        raise ValueError("moe_block_sparse holds every expert; a layer "
                         "with a share of them runs moe_block")
    N = B * S
    xf = x.reshape(N, D)
    top_idx, top_vals, gates = route(
        p, xf, n_experts=n_experts, top_k=top_k, router=router,
        norm_topk=norm_topk, routed_scale=routed_scale)  # [N,k]
    cap = int(capacity_factor * N * top_k / E)
    cap = max(cap, 1)
    # position of each (token, slot) within its expert buffer
    flat_idx = top_idx.reshape(-1)  # [N*k]
    onehot = jax.nn.one_hot(flat_idx, E, dtype=jnp.int32)  # [N*k, E]
    pos_in_expert = (jnp.cumsum(onehot, axis=0) - 1)  # [N*k, E]
    pos = jnp.take_along_axis(pos_in_expert, flat_idx[:, None], axis=1)[:, 0]
    keep = pos < cap
    # dispatch: build expert buffers [E, cap, D]
    buf = jnp.zeros((E, cap, D), dt)
    tok_ids = jnp.repeat(jnp.arange(N), top_k)
    buf = buf.at[flat_idx, jnp.where(keep, pos, 0)].add(
        jnp.where(keep[:, None], xf[tok_ids], 0).astype(dt))
    # expert FFN on buffers
    h = fn(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(dt)))
    h = h * jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(dt))
    eo = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(dt))
    # combine back
    gathered = eo[flat_idx, jnp.where(keep, pos, 0)]  # [N*k, D]
    w = (top_vals.reshape(-1) * keep).astype(dt)
    outf = jnp.zeros((N, D), dt).at[tok_ids].add(gathered * w[:, None])
    out = outf.reshape(B, S, D)
    density = jnp.zeros(E, jnp.float32).at[flat_idx].add(keep / N)
    aux = E * jnp.sum(density / top_k * gates.mean(axis=0))
    if "shared" in p:
        sp = p["shared"]
        hs = fn(x @ sp["w_gate"].astype(dt)) * (x @ sp["w_up"].astype(dt))
        out = out + hs @ sp["w_down"].astype(dt)
    return out, aux
