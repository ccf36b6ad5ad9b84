"""GQA/MQA attention with qk-norm, biases, sliding windows, KV caches.

One implementation serves every assigned arch:

* full/causal/local masks are arithmetic — the window is a per-layer
  *scalar*, so mixed local:global stacks (Gemma-3's 5:1) stay scannable
  with stacked params;
* GQA K/V are broadcast to full heads before the score einsum, so the
  head dimension shards cleanly over the 'model' mesh axis even when
  kv_heads < tensor-parallel degree (Megatron-style GQA TP);
* training/prefill use *blocked* attention (lax.scan over query blocks)
  so the S×S score matrix never materializes — the memory-roofline
  requirement for the 4k/32k shapes;
* decode is a functional cache update + single-row attention.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import attn_backend as AB
from .attn_backend import NEG_INF, PagedKV
from .common import apply_rope, dense_init, rms_norm, split_keys

DEFAULT_Q_BLOCK = 512


def init_attention(key, d_model: int, n_heads: int, n_kv_heads: int,
                   head_dim: int, qkv_bias: bool = False,
                   qk_norm: bool = False) -> Dict:
    k = split_keys(key, 4)
    p = {
        "wq": dense_init(k[0], (d_model, n_heads * head_dim)),
        "wk": dense_init(k[1], (d_model, n_kv_heads * head_dim)),
        "wv": dense_init(k[2], (d_model, n_kv_heads * head_dim)),
        "wo": dense_init(k[3], (n_heads * head_dim, d_model)),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), jnp.float32)
        p["bk"] = jnp.zeros((n_kv_heads * head_dim,), jnp.float32)
        p["bv"] = jnp.zeros((n_kv_heads * head_dim,), jnp.float32)
    if qk_norm:
        p["q_norm"] = jnp.zeros((head_dim,), jnp.float32)
        p["k_norm"] = jnp.zeros((head_dim,), jnp.float32)
    return p


def _project_qkv(p: Dict, x: jax.Array, n_heads: int, n_kv_heads: int,
                 head_dim: int, positions: jax.Array, rope_theta: float,
                 qk_norm: bool, norm_eps: float):
    dt = x.dtype
    B, S, _ = x.shape
    q = x @ p["wq"].astype(dt)
    k = x @ p["wk"].astype(dt)
    v = x @ p["wv"].astype(dt)
    if "bq" in p:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = q.reshape(B, S, n_heads, head_dim)
    k = k.reshape(B, S, n_kv_heads, head_dim)
    v = v.reshape(B, S, n_kv_heads, head_dim)
    if qk_norm:
        q = rms_norm(q, p["q_norm"], norm_eps)
        k = rms_norm(k, p["k_norm"], norm_eps)
    if rope_theta > 0:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    return q, k, v


# shared position primitives live in attn_backend (the kernel and the
# dense/paged/blocked paths must mask identically); aliased here for
# the long-standing call sites and tests
_repeat_kv = AB.repeat_kv
_mask_block = AB.position_mask


def attend_blocked(q: jax.Array, k: jax.Array, v: jax.Array,
                   q_pos: jax.Array, k_pos: jax.Array, window,
                   *, causal: bool = True,
                   q_block: int = DEFAULT_Q_BLOCK) -> jax.Array:
    """Blocked softmax attention.  q [B,Sq,H,hd], k/v [B,Sk,H,hd].

    Scans over query blocks; the [B,H,qb,Sk] score tile is the peak
    intermediate (never Sq×Sk).
    """
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    qb = min(q_block, Sq)
    pad = (-Sq) % qb
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad)))
    nblk = (Sq + pad) // qb
    qt = q.reshape(B, nblk, qb, H, hd).transpose(1, 0, 2, 3, 4)
    pt = q_pos.reshape(B, nblk, qb).transpose(1, 0, 2)
    kT = k.transpose(0, 2, 3, 1)  # [B,H,hd,Sk]
    vT = v.transpose(0, 2, 1, 3)  # [B,H,Sk,hd]
    scale = 1.0 / np.sqrt(hd)

    def body(_, blk):
        qi, pi = blk  # [B,qb,H,hd], [B,qb]
        s = jnp.einsum("bqhd,bhds->bhqs", qi, kT) * scale
        m = _mask_block(pi, k_pos, window, causal)  # [B,qb,Sk]
        s = s.astype(jnp.float32) + m[:, None, :, :]
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("bhqs,bhsd->bqhd", p, vT)
        return None, o

    _, out = jax.lax.scan(body, None, (qt, pt))
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, nblk * qb, H, hd)
    return out[:, :Sq].reshape(B, Sq, H * hd)


def attention_block(
    p: Dict,
    x: jax.Array,
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    rope_theta: float,
    window,  # scalar per layer; 0 => global
    qk_norm: bool,
    norm_eps: float,
    positions: Optional[jax.Array] = None,
    kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,
    causal: bool = True,
    q_block: int = DEFAULT_Q_BLOCK,
    return_kv: bool = False,
):
    """Self (or cross, via kv_override [B,Sk,KV,hd]) attention, full seq.

    ``return_kv=True`` additionally returns the projected (k, v) so
    prefill can seed the decode cache without re-projection.
    """
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    q, k, v = _project_qkv(p, x, n_heads, n_kv_heads, head_dim, positions,
                           rope_theta, qk_norm, norm_eps)
    if kv_override is not None:
        ko, vo = kv_override
        k_pos = jnp.broadcast_to(jnp.arange(ko.shape[1])[None],
                                 (B, ko.shape[1]))
        out = attend_blocked(q, _repeat_kv(ko, n_heads),
                             _repeat_kv(vo, n_heads), positions, k_pos,
                             jnp.int32(0), causal=False, q_block=q_block)
    else:
        out = attend_blocked(q, _repeat_kv(k, n_heads),
                             _repeat_kv(v, n_heads), positions, positions,
                             window, causal=causal, q_block=q_block)
    out = out @ p["wo"].astype(x.dtype)
    if return_kv:
        return out, (k, v)
    return out


def cross_kv(p: Dict, enc_out: jax.Array, n_kv_heads: int, head_dim: int):
    """Precompute encoder K/V for decoder cross-attention."""
    dt = enc_out.dtype
    B, S, _ = enc_out.shape
    k = (enc_out @ p["wk"].astype(dt)).reshape(B, S, n_kv_heads, head_dim)
    v = (enc_out @ p["wv"].astype(dt)).reshape(B, S, n_kv_heads, head_dim)
    return k, v


def quantize_kv_int8(t: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """[..., hd] bf16 -> (int8 values, per-vector scale [..., 1] f32).

    The serving-side analogue of the paper's action-bits quantization:
    stored intermediate results shrink to 8 bits, halving the dominant
    memory-roofline term of decode (EXPERIMENTS.md §Perf).
    """
    scale = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1,
                    keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def _paged_write(kv: PagedKV, k: jax.Array, v: jax.Array) -> PagedKV:
    """Scatter a chunk's projected K/V into their physical pages.

    Shared verbatim by every attention backend — the write half is not
    part of the backend contract, so the returned pools are bitwise
    identical no matter which implementation attends afterwards.
    ``k``/``v`` ``[B, C, KV, hd]`` land as ``KV * hd``-lane rows at
    ``[layer, page_ids, page_off]`` of a stacked pool (in place: only
    the ``B * C`` rows move), or ``[page_ids, page_off]`` of a
    per-layer one.  Out-of-range ``page_ids`` drop the write (padded
    chunk slots).  The int8 pool quantizes per token vector and
    scatters the f32 scale planes alongside the values.
    """
    B, C = k.shape[:2]
    at = kv.index(kv.page_ids, kv.page_off)

    def put(pool, rows):
        return pool.at[at].set(rows.reshape(B, C, -1).astype(pool.dtype),
                               mode="drop")

    with jax.named_scope("kv"):
        if kv.quantized:
            kq, ks = quantize_kv_int8(k)
            vq, vs = quantize_kv_int8(v)
            return dataclasses.replace(
                kv, k=put(kv.k, kq), v=put(kv.v, vq),
                k_scale=put(kv.k_scale, ks), v_scale=put(kv.v_scale, vs))
        return dataclasses.replace(kv, k=put(kv.k, k), v=put(kv.v, v))


def paged_decode_attention_block(
    p: Dict,
    x: jax.Array,  # [B, C, D] chunk of current tokens' activations
    kv: PagedKV,  # PagedKV with view fields set
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    rope_theta: float,
    window,
    qk_norm: bool,
    norm_eps: float,
    impl: str = "jnp",
) -> Tuple[jax.Array, PagedKV]:
    """Chunked decode attention through a paged (block-table) KV cache.

    The serve-path analogue of ``decode_attention_block`` for the paged
    cache.  ``kv`` is a :class:`~repro.nn.attn_backend.PagedKV` with
    its per-call view attached (``kv.with_view(block_tbl, positions,
    page_ids, page_off, layer)`` — the scatter coordinates are
    precomputed once per step by the caller and shared across layers;
    ``layer`` names the layer of a stacked pool, which is written and
    read in place, and is left out for a per-layer pool).  The chunk's
    K/V are scattered into their physical pages (out-of-range ids drop
    the write, which is how padded chunk slots are masked), then every
    query attends over the *logical* view ``k_pages[block_tbl]`` —
    pages gathered in logical order, so cell ``i`` of the gathered axis
    holds absolute position ``i`` exactly like the dense cache holds
    position ``i`` before its ring wraps.  Masking uses the shared
    ``attn_backend.position_mask`` on the per-slot absolute positions,
    which makes it correct at page boundaries by construction: a chunk
    straddling two pages masks on positions, not on page geometry.
    Unwritten/stale cells (recycled pages) are killed by the causal
    term — a key cell is attended only when ``k_pos <= q_pos``, and
    every position ``<= q_pos`` of the owning slot has been written
    through its own table entry.

    ``impl`` selects the attention backend (``attn_backend.resolve``:
    ``'jnp'`` gather oracle, ``'pallas'`` page-walking kernel,
    ``'auto'`` = platform default).  The projection and the page write
    run *outside* the backend, so the returned pools are bitwise
    identical across impls; the attention outputs agree to the
    tolerance stated in ``attn_backend``.

    Bit-exactness contract: for a chunk of width 1 starting at the same
    position, the gathered axis has the same length, values and mask as
    the (unwrapped) dense cache axis, so logits match the dense path
    bit for bit (asserted by tests/test_serve.py).

    A quantized ``kv`` (``k_scale``/``v_scale`` planes present) is the
    **int8 page pool**: K/V quantize per token vector
    (``quantize_kv_int8``) on write and the gather dequantizes before
    the score einsum, at the same ``<= scale/2`` round-trip bound as
    the dense int8 cache.  Shared (prefix) pages need nothing special:
    quantization is deterministic, so a shared page holds bit-identical
    content to what each sharer would have written itself.

    Returns ``(out, new_kv)`` — ``new_kv`` keeps the caller's view
    fields, so layer loops can thread it without rebuilding the view.
    """
    if not isinstance(kv, PagedKV):
        raise TypeError(
            "paged_decode_attention_block expects (p, x, PagedKV); the "
            "pre-PagedKV loose-args call shape was removed after its "
            "one-release deprecation window — wrap the pool in "
            "repro.nn.attn_backend.PagedKV and attach the view with "
            f".with_view(block_tbl, positions, page_ids, page_off) "
            f"(got kv={type(kv)})")
    B, C, _ = x.shape
    q, k, v = _project_qkv(p, x, n_heads, n_kv_heads, head_dim, kv.pos,
                           rope_theta, qk_norm, norm_eps)
    kv = _paged_write(kv, k, v)
    attend = AB.get(AB.resolve(impl))
    with jax.named_scope("attention"):
        out = attend(q, kv, n_heads=n_heads, head_dim=head_dim,
                     window=window)
    out = out.reshape(B, C, n_heads * head_dim) @ p["wo"].astype(x.dtype)
    return out, kv


def decode_attention_block(
    p: Dict,
    x: jax.Array,  # [B, 1, D] current token
    cache_k: jax.Array,  # [B, S_max, KV, hd]
    cache_v: jax.Array,
    pos: jax.Array,  # [] current position
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    rope_theta: float,
    window,
    qk_norm: bool,
    norm_eps: float,
    gqa_impl: str = "repeat",  # 'repeat' (baseline) | 'grouped' (§Perf)
    kv_scales: Optional[Tuple[jax.Array, jax.Array]] = None,  # int8 cache
) -> Tuple[jax.Array, jax.Array, jax.Array, Optional[Tuple]]:
    """One decode step: insert K/V at ``pos % S_max`` (ring buffer for
    windowed layers sized to the window), attend over valid cells.

    ``gqa_impl='grouped'`` keeps the KV-head dimension grouped in the
    score einsums instead of broadcasting K/V to all query heads — the
    cache is read once, not ``H/KV`` times (the dominant decode memory
    term; see EXPERIMENTS.md §Perf iteration 1).
    ``kv_scales`` enables the int8 cache (iteration 2).
    """
    B = x.shape[0]
    S_max = cache_k.shape[1]
    int8_cache = cache_k.dtype == jnp.int8
    positions = jnp.broadcast_to(jnp.asarray(pos, jnp.int32)[None, None],
                                 (B, 1))
    q, k, v = _project_qkv(p, x, n_heads, n_kv_heads, head_dim, positions,
                           rope_theta, qk_norm, norm_eps)
    slot = jnp.mod(pos, S_max)
    with jax.named_scope("kv"):
        if int8_cache:
            sk, sv = kv_scales
            kq, ks = quantize_kv_int8(k)
            vq, vs = quantize_kv_int8(v)
            cache_k = jax.lax.dynamic_update_slice(cache_k, kq,
                                                   (0, slot, 0, 0))
            cache_v = jax.lax.dynamic_update_slice(cache_v, vq,
                                                   (0, slot, 0, 0))
            sk = jax.lax.dynamic_update_slice(
                sk, ks.astype(sk.dtype), (0, slot, 0, 0))
            sv = jax.lax.dynamic_update_slice(
                sv, vs.astype(sv.dtype), (0, slot, 0, 0))
            new_scales = (sk, sv)
            kf32 = cache_k.astype(x.dtype) * sk.astype(x.dtype)
            vf32 = cache_v.astype(x.dtype) * sv.astype(x.dtype)
        else:
            cache_k = jax.lax.dynamic_update_slice(
                cache_k, k.astype(cache_k.dtype), (0, slot, 0, 0))
            cache_v = jax.lax.dynamic_update_slice(
                cache_v, v.astype(cache_v.dtype), (0, slot, 0, 0))
            new_scales = None
            kf32 = cache_k.astype(x.dtype)
            vf32 = cache_v.astype(x.dtype)
    # cell i holds absolute position: i if i <= slot else i + (filled wraps)
    idx = jnp.arange(S_max)
    wraps = (pos // S_max)
    abs_pos = jnp.where(idx <= slot, idx + wraps * S_max,
                        idx + (wraps - 1) * S_max)
    # once unwrapped to absolute positions, the ring shares the paged
    # path's mask helper (causal = abs_pos <= pos, window on the same
    # diff); the one ring-specific term is the abs_pos >= 0 guard —
    # pre-wrap cells sit at negative positions, which the causal diff
    # alone would wrongly admit
    mask = jnp.where(
        abs_pos[None] >= 0,
        AB.position_mask(jnp.asarray(pos, jnp.int32)[None, None],
                         abs_pos[None], window, causal=True)[:, 0],
        NEG_INF)  # [1,S]
    with jax.named_scope("attention"):
        if gqa_impl == "grouped":
            KV = n_kv_heads
            G = n_heads // KV
            qg = q.reshape(B, 1, KV, G, head_dim)
            s = jnp.einsum("bqkgh,bskh->bkgqs", qg, kf32) / np.sqrt(
                head_dim)
            s = s.astype(jnp.float32) + mask[:, None, None, None, :]
            probs = jax.nn.softmax(s, axis=-1).astype(x.dtype)
            out = jnp.einsum("bkgqs,bskh->bqkgh", probs, vf32).reshape(
                B, 1, n_heads * head_dim)
        else:
            kf = _repeat_kv(kf32, n_heads)
            vf = _repeat_kv(vf32, n_heads)
            s = jnp.einsum("bqhd,bshd->bhqs", q, kf) / np.sqrt(head_dim)
            s = s.astype(jnp.float32) + mask[:, None, None, :]
            probs = jax.nn.softmax(s, axis=-1).astype(x.dtype)
            out = jnp.einsum("bhqs,bshd->bqhd", probs, vf).reshape(
                B, 1, n_heads * head_dim)
    return out @ p["wo"].astype(x.dtype), cache_k, cache_v, new_scales
