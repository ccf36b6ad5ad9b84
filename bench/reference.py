"""Plain float32 reference forward of the decoders the config files declare.

Straight ``jax.numpy`` under ``default_matmul_precision("highest")``: no
kernel, no cache, no batching, nothing imported from the program.  It reads
the weight tree the benchmark made (``bench/core/weights.py``) and the
configuration file: its published values, except where a ``departures``
entry names a key the program cannot compute as published, which is read
as run (``weights.as_run``):

* RMSNorm ``x / sqrt(mean(x^2) + eps) * g`` with the gain stored as
  ``g - 1`` in the tree;
* q/k/v projections (with bias where ``attention_bias``), rotary
  embedding on the two halves of each head (``rope_theta``), grouped
  query heads (head ``h`` reads key/value head ``h // (H / KV)``), causal
  softmax attention scaled by ``1/sqrt(head_dim)``;
* SwiGLU MLP ``down(silu(gate x) * up x)``; or the sparse MoE: softmax
  router over the ``num_experts`` real experts, the ``num_experts_per_tok``
  largest kept (renormalised to sum one where ``norm_topk_prob``), each a
  SwiGLU of width ``moe_intermediate_size``, plus one shared SwiGLU of
  width ``shared_expert_intermediate_size`` added without a gate where
  ``shared_expert_gate`` is false;
* final RMSNorm and the head over the ``vocab_size`` real columns: the
  embedding's transpose where ``tie_word_embeddings``, else its own.

``precision="fp8"`` is the control: the same forward with every matmul
operand rounded to float8 e4m3 (a scale per row of the activation and per
column of the weight, as fp8 serving does), the precision one step below
the bfloat16 the configurations compute in.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from .core.weights import as_run

F8_MAX = 448.0  # largest finite float8 e4m3 value
Q_BLOCK = 1024  # query rows per attention block (bounds the score tile)
MOE_ROWS = 512  # token rows per expert block


def _q8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, fp8: bool):
    """``a [.., K] @ b [K, N]`` in float32, or with fp8 operands."""
    if fp8:
        a, b = _q8(a, -1), _q8(b, 0)
    return a @ b


def _rms(x, g_minus_1, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + g_minus_1)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None, None].astype(jnp.float32) * inv  # [S, 1, hd/2]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(p, h, cfg, fp8):
    S = h.shape[0]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim", cfg["hidden_size"] // H)
    q = _mm(h, p["wq"], fp8)
    k = _mm(h, p["wk"], fp8)
    v = _mm(h, p["wv"], fp8)
    if cfg["attention_bias"]:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    pos = jnp.arange(S)
    q = _rope(q.reshape(S, H, hd), pos, cfg["rope_theta"])
    k = _rope(k.reshape(S, KV, hd), pos, cfg["rope_theta"])
    v = v.reshape(S, KV, hd)
    grp = H // KV
    k = jnp.repeat(k, grp, axis=1)  # head h reads kv head h // grp
    v = jnp.repeat(v, grp, axis=1)
    if fp8:
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, 0)
    outs = []
    for q0 in range(0, S, Q_BLOCK):
        qb = q[q0:q0 + Q_BLOCK]
        s = jnp.einsum("qhd,khd->hqk", qb, k) / np.sqrt(hd)
        qi = jnp.arange(q0, q0 + qb.shape[0])
        s = jnp.where(qi[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        if fp8:
            w = _q8(w, -1)
        outs.append(jnp.einsum("hqk,khd->qhd", w, v))
    o = jnp.concatenate(outs, 0).reshape(S, H * hd)
    return _mm(o, p["wo"], fp8)


def _swiglu(h, wg, wu, wd, fp8):
    return _mm(jax.nn.silu(_mm(h, wg, fp8)) * _mm(h, wu, fp8), wd, fp8)


def _moe(p, h, cfg, fp8):
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


def _forward(cfg, fp8, params, tokens, where):
    """Logits ``[len(where), vocab_size]`` at the positions ``where`` of
    the token sequence ``tokens`` (causal: padding after the last real
    token changes nothing before it)."""
    eps = cfg["rms_norm_eps"]
    x = params["embed"][tokens]

    def layer(x, lp):
        x = x + _attention(lp["mixer"], _rms(x, lp["ln1"], eps), cfg, fp8)
        h = _rms(x, lp["ln2"], eps)
        if cfg.get("num_experts"):
            x = x + _moe(lp["moe"], h, cfg, fp8)
        else:
            m = lp["mlp"]
            x = x + _swiglu(h, m["w_gate"], m["w_up"], m["w_down"], fp8)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    h = _rms(x[where], params["ln_f"], eps)
    V = cfg["vocab_size"]
    head = (params["embed"][:V].T if cfg["tie_word_embeddings"]
            else params["head"][:, :V])
    return _mm(h, head, fp8)


@functools.lru_cache(maxsize=None)
def _compiled(cfg_json: str, precision: str):
    cfg = as_run(json.loads(cfg_json))
    fwd = functools.partial(_forward, cfg, precision == "fp8")

    def run(params, tokens, where):
        with jax.default_matmul_precision("highest"):
            return fwd(params, tokens, where)

    return jax.jit(run)


def logits(cfg: dict, params, tokens: np.ndarray, where: np.ndarray,
           precision: str = "f32") -> np.ndarray:
    """Reference logits at positions ``where`` of one sequence.

    The sequence is padded to a power of two (at least 512) so that few
    shapes compile; ``where`` is padded by repeating its last entry."""
    if precision not in ("f32", "fp8"):
        raise ValueError(f"precision {precision!r}: 'f32' or 'fp8'")
    S = max(512, 1 << (len(tokens) - 1).bit_length())
    tok = np.zeros(S, np.int32)
    tok[: len(tokens)] = tokens
    W = max(8, 1 << (len(where) - 1).bit_length())
    wh = np.full(W, where[-1], np.int32)
    wh[: len(where)] = where
    fn = _compiled(json.dumps(cfg, sort_keys=True), precision)
    out = fn(params, jnp.asarray(tok), jnp.asarray(wh))
    return np.asarray(out)[: len(where)]
