"""Qwen2 (``model_type`` ``qwen2``): a dense decoder of grouped-query
attention and a SwiGLU MLP.  ``qwen2_moe.py`` takes everything here but
the MLP.

Weights (``init``), in the tree the program's serve step reads:
``embed [Vp, D]``, ``head [D, Vp]``, ``ln_f [D]``, and ``layers`` stacked on
a leading layer axis: ``mixer`` (``wq``, ``wk``, ``wv``, ``wo``, and ``bq``,
``bk``, ``bv`` where ``attention_bias``), the norm gains ``ln1`` and
``ln2``, and ``mlp`` (``w_gate``, ``w_up``, ``w_down``).  Norm gains are
stored as offsets from one (the step multiplies by ``1 + ln``).  Where the
configuration ties the output head to the embedding, ``head`` is a copy of
``embed`` transposed: the program keeps a separate head, and the copy makes
it compute the tied model.

Reference (``forward``), each layer behind an RMSNorm:

* q/k/v projections (with bias where ``attention_bias``), rotary
  embedding on the two halves of each head (``rope_theta``), grouped
  query heads (head ``h`` reads key/value head ``h // (H / KV)``), causal
  softmax attention scaled by ``1/sqrt(head_dim)``;
* SwiGLU MLP ``down(silu(gate x) * up x)``;

then the final RMSNorm and the head over the ``vocab_size`` real columns:
the embedding's transpose where ``tie_word_embeddings``, else its own.
"""
from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.core.counts import BF16
from bench.core.weights import BIAS_SD, GAIN_SD, _mat, _round_up
from bench.reference import _mm, _q8, _rms, _rope, _swiglu

Q_BLOCK = 1024  # query rows per attention block (bounds the score tile)


# ---------------------------------------------------------------- sizes
def base_dims(cfg: dict) -> Dict[str, int]:
    """Depth, width, attention heads and vocabulary."""
    D = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    return dict(
        L=cfg["num_hidden_layers"], D=D, H=H,
        KV=cfg["num_key_value_heads"],
        hd=cfg.get("head_dim", D // H),
        V=cfg["vocab_size"], Vp=_round_up(cfg["vocab_size"], 256),
    )


def dims(cfg: dict) -> Dict[str, int]:
    return dict(base_dims(cfg), F=cfg["intermediate_size"])


# -------------------------------------------------------------- weights
def _gain(ks, *shape):
    return GAIN_SD * jax.random.normal(next(ks), shape, jnp.float32)


def init_attention(cfg: dict, n: Dict[str, int], ks) -> Dict:
    """The layers' ``mixer``, ``ln1`` and ``ln2``, drawn from the key
    iterator ``ks`` in this order."""
    L, D, H, KV, hd = n["L"], n["D"], n["H"], n["KV"], n["hd"]
    mixer = {
        "wq": _mat(next(ks), (L, D, H * hd), D),
        "wk": _mat(next(ks), (L, D, KV * hd), D),
        "wv": _mat(next(ks), (L, D, KV * hd), D),
        "wo": _mat(next(ks), (L, H * hd, D), H * hd),
    }
    if cfg["attention_bias"]:
        for name, width in (("bq", H * hd), ("bk", KV * hd),
                            ("bv", KV * hd)):
            mixer[name] = BIAS_SD * jax.random.normal(
                next(ks), (L, width), jnp.float32)
    return {"mixer": mixer, "ln1": _gain(ks, L, D), "ln2": _gain(ks, L, D)}


def init_outer(cfg: dict, n: Dict[str, int], ks, layers: Dict) -> Dict:
    """The whole tree: embedding, head and final norm, drawn from ``ks``
    after the layers, around the stacked ``layers``."""
    D = n["D"]
    if cfg["tie_word_embeddings"]:
        # at 1/sqrt(D), so that as the head it gives logits of unit
        # spread, as an untied head N(0, 1/D) does
        embed = _mat(next(ks), (n["Vp"], D), D)
        head = embed.T
    else:
        embed = jax.random.normal(next(ks), (n["Vp"], D), jnp.float32)
        head = _mat(next(ks), (D, n["Vp"]), D)
    return {
        "embed": embed,
        "head": head,
        "ln_f": _gain(ks, D),
        "layers": layers,
    }


def init(cfg: dict, key) -> Dict:
    n = dims(cfg)
    L, D, F = n["L"], n["D"], n["F"]
    ks = iter(jax.random.split(key, 32))
    layers = init_attention(cfg, n, ks)
    layers["mlp"] = {
        "w_gate": _mat(next(ks), (L, D, F), D),
        "w_up": _mat(next(ks), (L, D, F), D),
        "w_down": _mat(next(ks), (L, F, D), F),
    }
    return init_outer(cfg, n, ks, layers)


# ------------------------------------------------------------ reference
def attention(p, h, cfg, fp8):
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


def decoder(cfg, fp8, params, tokens, where, ffn: Callable):
    """Logits ``[len(where), vocab_size]`` at the positions ``where`` of
    the token sequence ``tokens`` (causal: padding after the last real
    token changes nothing before it).  ``ffn(lp, h)`` is a layer's
    feed-forward on its normed residual ``h``, ``lp`` its weights."""
    eps = cfg["rms_norm_eps"]
    x = params["embed"][tokens]

    def layer(x, lp):
        x = x + attention(lp["mixer"], _rms(x, lp["ln1"], eps), cfg, fp8)
        h = _rms(x, lp["ln2"], eps)
        x = x + ffn(lp, h)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    h = _rms(x[where], params["ln_f"], eps)
    V = cfg["vocab_size"]
    head = (params["embed"][:V].T if cfg["tie_word_embeddings"]
            else params["head"][:, :V])
    return _mm(h, head, fp8)


def forward(cfg, fp8, params, tokens, where):
    def mlp(lp, h):
        m = lp["mlp"]
        return _swiglu(h, m["w_gate"], m["w_up"], m["w_down"], fp8)

    return decoder(cfg, fp8, params, tokens, where, mlp)


# -------------------------------------------------------------- program
def program_settings(cfg: dict) -> Dict:
    """Depth, rotary base, norm epsilon and q/k/v biases from the file."""
    return dict(n_layers=cfg["num_hidden_layers"],
                rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
                qkv_bias=cfg["attention_bias"])


def base_widths(cfg: dict) -> Dict[str, int]:
    return dict(d_model=cfg["hidden_size"],
                n_heads=cfg["num_attention_heads"],
                n_kv_heads=cfg["num_key_value_heads"],
                head_dim_=cfg.get("head_dim", cfg["hidden_size"]
                                  // cfg["num_attention_heads"]),
                vocab_size=cfg["vocab_size"],
                n_layers=cfg["num_hidden_layers"])


def program_widths(cfg: dict) -> Dict[str, int]:
    return dict(base_widths(cfg), d_ff=cfg["intermediate_size"])


# --------------------------------------------------------------- counts
def proj_flops(d: Dict[str, int]) -> int:
    """Matmul FLOPs of one token through one layer's q/k/v/o."""
    D, H, KV, hd = d["D"], d["H"], d["KV"], d["hd"]
    return 2 * D * (H + 2 * KV) * hd + 2 * H * hd * D


def linear_flops_per_token(d: Dict[str, int]) -> int:
    """Matmul FLOPs of one token through every layer, attention scores
    excluded, LM head excluded."""
    return d["L"] * (proj_flops(d) + 6 * d["D"] * d["F"])


def attn_flops(d: Dict[str, int], pairs: int) -> int:
    """QK^T and PV over ``pairs`` query-key pairs, every layer."""
    return d["L"] * 4 * d["H"] * d["hd"] * pairs


def attn_bytes(d: Dict[str, int], ctx: int, n: int) -> int:
    """HBM bytes one slot's attention needs at one step, every layer:
    its ``ctx`` cached keys and values read once, its ``n`` queries read
    and outputs written."""
    kv = 2 * ctx * d["KV"] * d["hd"] * BF16
    qo = 2 * n * d["H"] * d["hd"] * BF16
    return d["L"] * (kv + qo)
