"""Jitted public ops over the Pallas kernels, with backend dispatch.

``backend='jnp'``   — pure-jnp oracle (ref.py), runs anywhere.
``backend='pallas'`` — Pallas TPU kernels; on CPU they execute in
                       interpret mode (kernel-body semantics validated),
                       on TPU they compile to Mosaic.

These are the compute primitives the compiled Planter pipelines call.
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .bnn_mlp import bnn_popcount_matmul_pallas
from .bucketize import bucketize_pallas
from .fused_eb import fused_eb_pallas
from .lb_lookup import lb_lookup_pallas
from .ternary_match import ternary_match_pallas


def _interpret() -> bool:
    """Pallas interpret mode off-TPU, decided at call time (importing
    this module touches no device)."""
    return jax.default_backend() != "tpu"


__all__ = [
    "bucketize",
    "fused_eb_match",
    "ternary_match",
    "lb_lookup",
    "bnn_popcount_matmul",
    "bnn_forward",
    "pack_bits_jnp",
]


def bucketize(values, thresholds, backend: str = "jnp"):
    values = jnp.asarray(values, jnp.int32)
    thresholds = jnp.asarray(thresholds, jnp.int32)
    if backend == "pallas":
        return bucketize_pallas(values, thresholds, interpret=_interpret())
    return ref.bucketize_ref(values, thresholds)


def ternary_match(keys, values, masks, prio_action, default_action: int,
                  backend: str = "jnp"):
    keys = jnp.asarray(keys, jnp.uint32)
    values = jnp.asarray(values, jnp.uint32)
    masks = jnp.asarray(masks, jnp.uint32)
    prio_action = jnp.asarray(prio_action, jnp.int32)
    if values.shape[0] == 0:  # all rows folded into the default action
        return jnp.full(keys.shape[0], default_action, jnp.int32)
    if backend == "pallas":
        return ternary_match_pallas(
            keys, values, masks, prio_action,
            default_action=int(default_action), interpret=_interpret(),
        )
    return ref.ternary_match_ref(keys, values, masks, prio_action,
                                 int(default_action))


def lb_lookup(codes, luts, backend: str = "jnp", action_bits: int = 16):
    codes = jnp.asarray(codes, jnp.int32)
    luts = jnp.asarray(luts, jnp.int32)
    if backend == "pallas" and action_bits <= 16:
        return lb_lookup_pallas(codes, luts, interpret=_interpret())
    return ref.lb_lookup_ref(codes, luts)


def bnn_popcount_matmul(x_packed, w_packed, backend: str = "jnp"):
    x_packed = jnp.asarray(x_packed, jnp.uint32)
    w_packed = jnp.asarray(w_packed, jnp.uint32)
    if backend == "pallas":
        return bnn_popcount_matmul_pallas(x_packed, w_packed,
                                          interpret=_interpret())
    return ref.bnn_popcount_matmul_ref(x_packed, w_packed)


def fused_eb_match(values, thresholds, rows_v, rows_m, prio_action,
                   layout, n_words: int, default_action: int,
                   backend: str = "pallas", identity: bool = False,
                   block_b: int = 0):
    """Single-launch EB pipeline (encode+pack+match); gate-sized tables.

    ``block_b=0`` auto-tiles the batch (lane-aligned single tile for
    gate-sized batches, 256-row tiles for throughput batches).
    """
    if backend == "pallas":
        return fused_eb_pallas(
            jnp.asarray(values, jnp.int32), jnp.asarray(thresholds, jnp.int32),
            jnp.asarray(rows_v, jnp.uint32), jnp.asarray(rows_m, jnp.uint32),
            jnp.asarray(prio_action, jnp.int32), layout=tuple(layout),
            n_words=int(n_words), default_action=int(default_action),
            block_b=int(block_b), interpret=_interpret(), identity=identity)
    # jnp composition fallback (same semantics, two ops)
    codes = (jnp.asarray(values, jnp.int32) if identity else
             ref.bucketize_ref(jnp.asarray(values, jnp.int32),
                               jnp.asarray(thresholds, jnp.int32)))
    words = [jnp.zeros(codes.shape[0], jnp.uint32) for _ in range(n_words)]
    for f, (word, off, width) in enumerate(layout):
        field = codes[:, f].astype(jnp.uint32) & jnp.uint32((1 << width) - 1)
        words[word] = words[word] | (field << jnp.uint32(off))
    keys = jnp.stack(words, axis=1)
    return ref.ternary_match_ref(keys, jnp.asarray(rows_v, jnp.uint32),
                                 jnp.asarray(rows_m, jnp.uint32),
                                 jnp.asarray(prio_action, jnp.int32),
                                 int(default_action))


def pack_bits_jnp(bits01: jax.Array) -> jax.Array:
    """Pack 0/1 int array [..., N] -> uint32 words [..., ceil(N/32)].

    LSB-first, matching ``core.tables.pack_bits_uint32``.
    """
    n = bits01.shape[-1]
    pad = (-n) % 32
    if pad:
        bits01 = jnp.pad(bits01, [(0, 0)] * (bits01.ndim - 1) + [(0, pad)])
    b = bits01.reshape(*bits01.shape[:-1], -1, 32).astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return (b << shifts).sum(axis=-1).astype(jnp.uint32)


def bnn_forward(
    x_packed: jax.Array,
    layers: Sequence[Tuple[np.ndarray, int]],
    backend: str = "jnp",
) -> jax.Array:
    """Full DM-BNN forward per paper Eq. 8.

    ``layers[i] = (w_packed [N, W] uint32, n_in)`` — ``n_in`` is the true
    (unpadded) fan-in; pad bits contribute ``popcount(~(0^0)) = 1`` per pad
    bit on both x and w (both zero-padded), so the dot product is
    ``2*counts - n_in - pad_correction`` with pad bits counted as matches:
    counts include ``32*W - n_in`` always-matching pad bits, subtracted here.
    Hidden layers apply SIGN; the final layer returns raw scores.
    """
    h = jnp.asarray(x_packed, jnp.uint32)
    for i, (w_packed, n_in) in enumerate(layers):
        w = jnp.asarray(w_packed, jnp.uint32)
        counts = bnn_popcount_matmul(h, w, backend=backend)
        pad_bits = 32 * w.shape[1] - n_in
        dot = 2 * (counts - pad_bits) - n_in  # = x·w over ±1 vectors
        if i < len(layers) - 1:
            bits = (dot >= 0).astype(jnp.uint32)
            h = pack_bits_jnp(bits)
        else:
            return dot
    return dot
