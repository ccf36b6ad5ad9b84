"""Pallas TPU kernel: TCAM-style ternary match (the EB decision table).

A TCAM returns the *first* matching row in physical order.  We give every
row a unique priority (its build order) and pack ``prio*256 + action`` into
one int32, so "first match" becomes an associative ``max`` — which tiles
over VMEM row-blocks with a running-best scratch accumulator.  This is the
central hardware adaptation: TCAM priority encoding -> arithmetic
priority-max on the VPU (DESIGN.md §2, row 3).

Grid: ``(batch_blocks, row_blocks)``; rows iterate fastest (TPU minor grid
axis), the scratch carries the per-batch running best across row blocks,
and the output is emitted on the last row block.  Keys enter transposed
(``[W, B]``) so the batch rides the lanes and every block is 2-D.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .fused_eb import match_rows, as_int32_bits

DEFAULT_BLOCK_B = 256
DEFAULT_BLOCK_N = 512


def _ternary_kernel(keys_ref, values_ref, masks_ref, pa_ref, out_ref, best_ref):
    n_idx = pl.program_id(1)
    n_blocks = pl.num_programs(1)

    @pl.when(n_idx == 0)
    def _init():
        best_ref[...] = jnp.full_like(best_ref, -1)

    k = keys_ref[...]  # [W, Bb] int32 key bits (batch on lanes)
    blk_best = match_rows([k[w:w + 1] for w in range(k.shape[0])],
                           values_ref[...], masks_ref[...],
                           pa_ref[...])  # [1, Bb]
    best_ref[...] = jnp.maximum(best_ref[...], blk_best)

    @pl.when(n_idx == n_blocks - 1)
    def _emit():
        out_ref[...] = best_ref[...]


@functools.partial(
    jax.jit, static_argnames=("default_action", "block_b", "block_n", "interpret")
)
def ternary_match_pallas(
    keys: jax.Array,
    values: jax.Array,
    masks: jax.Array,
    prio_action: jax.Array,
    *,
    default_action: int,
    block_b: int = DEFAULT_BLOCK_B,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = True,
) -> jax.Array:
    """keys [B, W] uint32; rows [N, W]; prio_action [N] int32 -> [B] int32."""
    B, W = keys.shape
    N = values.shape[0]
    pad_b = (-B) % block_b
    pad_n = (-N) % block_n
    keys_t = as_int32_bits(jnp.pad(keys, ((0, pad_b), (0, 0)))).T
    if pad_n:
        # padding rows: mask=all-ones, value=all-ones -> never match a real
        # key unless key is all-ones AND... make them unmatchable by giving
        # pa=-1 so even a hit loses to any real row and maps to default.
        ones = jnp.uint32(0xFFFFFFFF)
        values = jnp.pad(values, ((0, pad_n), (0, 0)), constant_values=ones)
        masks = jnp.pad(masks, ((0, pad_n), (0, 0)), constant_values=ones)
        prio_action = jnp.pad(prio_action, (0, pad_n), constant_values=-1)
    Bp, Np = B + pad_b, N + pad_n
    best = pl.pallas_call(
        _ternary_kernel,
        grid=(Bp // block_b, Np // block_n),
        in_specs=[
            pl.BlockSpec((W, block_b), lambda i, j: (0, i)),
            pl.BlockSpec((block_n, W), lambda i, j: (j, 0)),
            pl.BlockSpec((block_n, W), lambda i, j: (j, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_b), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Bp), jnp.int32),
        scratch_shapes=[pltpu.VMEM((1, block_b), jnp.int32)],
        interpret=interpret,
    )(keys_t, as_int32_bits(values), as_int32_bits(masks),
      prio_action.reshape(Np, 1))
    best = best[0, :B]
    return jnp.where(best >= 0, best % 256, default_action).astype(jnp.int32)
