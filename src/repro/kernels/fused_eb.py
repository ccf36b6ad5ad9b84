"""Pallas TPU kernel: fully-fused EB pipeline (encode + pack + match).

The paper's EB promise is a *constant two logical stages*; on TPU the
natural endpoint is ONE kernel launch per tree: feature thresholds, the
code-key layout, and the ternary rows all live in VMEM, and a batch tile
flows encode -> pack -> match without touching HBM in between.  This is
the deployment kernel for gate-sized tables (entries ≤ a few thousand
rows, thresholds ≤ VMEM tile); larger models fall back to the staged
kernels (`ops.bucketize` + `ops.ternary_match`).

Layout constants (shift/word per feature) are Python-static, baked into
the kernel body at trace time — exactly like P4 compiles the key layout
into the parser.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_B = 256
LANE = 128  # TPU lane width: batch tiles must stay 128-aligned


def gate_block_b(batch: int) -> int:
    """Batch tile for gate-sized launches.

    The serve path calls this kernel with the decode batch (or the waiting
    queue) — typically 4–64 rows, not the 256-row throughput tile.  Tiling
    to the next lane multiple instead of DEFAULT_BLOCK_B cuts the padded
    work 2–32× while keeping the last dimension 128-aligned for Mosaic.
    """
    return min(DEFAULT_BLOCK_B, max(LANE, -(-batch // LANE) * LANE))


def match_rows(keys, rows_v, rows_m, pa):
    """Best ``prio*256+action`` per batch lane, -1 where no row hits.

    ``keys`` is a list of ``[1, Bb]`` int32 key words (batch on lanes);
    ``rows_v``/``rows_m`` are ``[N, W]`` and ``pa`` is ``[N, 1]``, so every
    compare is an ``[N, Bb]`` tile and the TCAM priority becomes one
    sublane max.  Shared by this kernel and ``ternary_match``."""
    hit = None
    for w, kw in enumerate(keys):
        h = (kw & rows_m[:, w:w + 1]) == rows_v[:, w:w + 1]
        hit = h if hit is None else hit & h
    score = jnp.where(hit, pa, -1)
    return score.max(axis=0, keepdims=True)


def _fused_kernel(values_ref, thresholds_ref, rows_v_ref, rows_m_ref,
                  pa_ref, out_ref, *, layout: Tuple[Tuple[int, int, int], ...],
                  n_words: int, identity: bool):
    # batch on lanes: every intermediate is a [rows, Bb] tile.  Codes and
    # key words are int32 bit patterns (Mosaic has no unsigned reduction);
    # the bits are the same as the uint32 oracle's.
    v = values_ref[...]  # [F, Bb] int32
    if identity:  # KM/KNN quadtree: raw quantized values ARE the codes
        codes = v
    else:
        t = thresholds_ref[...]  # [F, T] int32 (INT32_MAX padded)
        codes = jnp.zeros_like(v)
        for j in range(t.shape[1]):
            codes = codes + (v >= t[:, j:j + 1]).astype(jnp.int32)
    # pack codes into key words with static layout
    words = [jnp.zeros((1, v.shape[1]), jnp.int32) for _ in range(n_words)]
    for f, (word, off, width) in enumerate(layout):
        field = codes[f:f + 1] & jnp.int32((1 << width) - 1)
        words[word] = words[word] | (field << off)
    out_ref[...] = match_rows(words, rows_v_ref[...], rows_m_ref[...],
                               pa_ref[...])


def as_int32_bits(x: jax.Array) -> jax.Array:
    """uint32 key/row words -> int32 with the same bits."""
    return jax.lax.bitcast_convert_type(x.astype(jnp.uint32), jnp.int32)


@functools.partial(jax.jit, static_argnames=("layout", "n_words",
                                             "default_action", "block_b",
                                             "interpret", "identity"))
def fused_eb_pallas(
    values: jax.Array,
    thresholds: jax.Array,
    rows_v: jax.Array,
    rows_m: jax.Array,
    prio_action: jax.Array,
    *,
    layout: Tuple[Tuple[int, int, int], ...],
    n_words: int,
    default_action: int,
    block_b: int = 0,
    interpret: bool = True,
    identity: bool = False,
) -> jax.Array:
    """values [B,F] -> actions [B] in one kernel launch.

    ``block_b=0`` (default) auto-tiles: gate-sized batches get one
    lane-aligned tile (``gate_block_b``) instead of padding to the
    256-row throughput tile.  The batch rides the lane axis inside the
    kernel (values enter transposed, ``[F, B]``), so every block is 2-D
    and its last dimension is a multiple of 128.
    """
    B, F = values.shape
    N, W = rows_v.shape
    if block_b <= 0:
        block_b = gate_block_b(B)
    pad_b = (-B) % block_b
    Bp = B + pad_b
    values_t = jnp.pad(values.astype(jnp.int32), ((0, pad_b), (0, 0))).T
    kern = functools.partial(_fused_kernel, layout=layout, n_words=n_words,
                             identity=identity)
    best = pl.pallas_call(
        kern,
        grid=(Bp // block_b,),
        in_specs=[
            pl.BlockSpec((F, block_b), lambda i: (0, i)),
            pl.BlockSpec(thresholds.shape, lambda i: (0, 0)),
            pl.BlockSpec((N, W), lambda i: (0, 0)),
            pl.BlockSpec((N, W), lambda i: (0, 0)),
            pl.BlockSpec((N, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_b), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Bp), jnp.int32),
        interpret=interpret,
    )(values_t, thresholds.astype(jnp.int32), as_int32_bits(rows_v),
      as_int32_bits(rows_m), prio_action.astype(jnp.int32).reshape(N, 1))
    best = best[0, :B]
    return jnp.where(best >= 0, best % 256, default_action).astype(jnp.int32)
