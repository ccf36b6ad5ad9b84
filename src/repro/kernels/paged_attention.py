"""Pallas paged-attention: fused block-table walk + dequant + attend.

The jnp serve path materializes the paged cache's *logical* view in
HBM every layer of every decode step: ``k_pages[block_tbl]`` writes a
``[B, n_ps*page, KV, hd]`` gather (then reads it back), the int8 path
adds a dequant round trip, and ``repeat_kv`` multiplies the read
traffic by ``H/KV`` for GQA stacks.  For decode (1 query token) that
gather traffic *is* the roofline — see ``benchmarks/roofline.py
--paged-attn`` for the computed bytes.

This kernel fuses the whole read side into one launch.  It reads the
stacked pool ``[n_layers, N_pages, page, KV*hd]`` where it lies, in
the lane-dense layout it is stored in, with no hand-off relayout: the
layer index is a scalar-prefetch operand, so the layer scan passes the
whole pool and one traced index.  A scalar-prefetch grid
``(B, n_ps / per_step)`` walks each slot's block table, ``per_step``
pages a step (8 where the table's width allows), each page through its
own ``BlockSpec``, so that many page DMAs are in flight at once: the
prefetched (clipped) table and the layer drive the K/V index maps, so
each physical page of that layer is DMA'd HBM->VMEM exactly once, at
pool dtype, dequantized (int8 pools: per-page f32 scale planes ride
along and the multiply happens in registers) and staged into a
per-slot VMEM view ``[S, KV*hd]``.  The last step of a slot runs
masking + softmax + the value matmul for that slot out of VMEM, one
KV head at a time against its ``H/KV`` query heads (no repeated K/V).
VMEM holds one slot's view, ``2*S*KV*hd`` elements, whatever the batch.

The softmax runs full-axis over the staged view, with the same
operations as the jnp oracle; only the matmul shapes differ (one slot
and one KV group per call, f32 accumulation as the TPU requires), so
the f32 sums may be ordered differently.  The kernel agrees with the
oracle to a stated tolerance: a few f32 ulps in interpret mode
(``tests/test_kernels.py``), about one bf16 step per call compiled for
the TPU (``chip_smoke.py``).  Scores are divided by ``sqrt(hd)``, or
by ``1 / scale`` where a softmax scale is given.

Masking goes through ``attn_backend.mask_from_diff`` — the helper the
jnp oracle and the dense decode path reach through ``position_mask`` —
on per-slot absolute positions, so page-boundary behaviour cannot drift
between implementations.

Decode is the ``C=1`` case of the prefill-chunk ``[B, C]`` variant;
one kernel serves both (the chunk width only changes block shapes).

A **latent pool** (multi-head latent attention: ``v_pages`` None) holds
one ``[c | k_pe]`` row per token that is the key of every head, and
whose first ``v_lanes`` lanes are the value; its queries are the
absorbed ``[B, C, H, lanes]``.  The pool is read once per page, with no
second copy for V, as one KV group of all ``H`` heads.  A slot's view
of such rows does not fit VMEM at long contexts (8,192 positions of 576
lanes are 9.4 MB, and 256 query rows' scores over them 8 MB of f32), so
this case attends block by block with an online softmax: each grid step
stages its ``per_step`` pages, folds their scores into a running max,
sum and ``[rows, v_lanes]`` f32 accumulator, and the slot's last step
writes ``acc / sum``.  Pages past the slot's last position are neither
fetched (their index map repeats the last page, and Pallas skips the
copy of an unchanged block) nor attended (their step is skipped).

Exposed through the ``repro.nn.attn_backend`` registry as
``"pallas"``; ``interpret=None`` auto-selects interpret mode off-TPU
so CPU CI executes the same kernel the TPU path compiles.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..nn.attn_backend import mask_from_diff

__all__ = ["paged_attention", "paged_attention_hbm_bytes"]


def _kernel(n_ps: int, page: int, n_kv: int, hd: int, group: int,
            n_chunk: int, quantized: bool, out_dtype, per_step: int,
            div: float, tbl_ref, pos_ref, win_ref, layer_ref, q_ref, *rest):
    """One grid step ``(b, s)``: stage slot b's logical pages
    ``s*per_step ..`` ``(s+1)*per_step - 1`` into the slot's VMEM view;
    on the slot's last step, attend for slot b.

    ``tbl_ref``/``pos_ref``/``win_ref``/``layer_ref`` are scalar-
    prefetch operands in SMEM, read one scalar at a time (the flattened
    clipped block table and the layer also drive the K/V BlockSpec
    index maps, which is what makes the gather a sequence of page DMAs
    instead of an HBM materialization).  ``q_ref`` is slot b's queries
    grouped by KV head, ``[1, KV, C*group, hd]`` with row
    ``c*group + g``.  ``rest`` holds ``per_step`` K page refs, as many
    V page refs (each one page of the layer, ``[1, page, KV*hd]``),
    the scale-plane refs of an int8 pool likewise, then the output and
    the two VMEM views."""
    del tbl_ref, layer_ref
    n_in = (4 if quantized else 2) * per_step
    pages, (out_ref, kg, vg) = rest[:n_in], rest[n_in:]
    b = pl.program_id(0)
    s = pl.program_id(1)
    f32 = jnp.float32
    for j in range(per_step):
        kp_ref, vp_ref = pages[j], pages[per_step + j]
        rows = pl.ds(pl.multiple_of((s * per_step + j) * page, page), page)
        if quantized:
            ks_ref, vs_ref = pages[2 * per_step + j], pages[3 * per_step + j]
            # dequant in-flight: int8 page * scale plane, one rounding to
            # the compute dtype (the product of two bf16 values is exact
            # in f32, so this equals the oracle's multiply in the compute
            # dtype)
            for h in range(n_kv):
                cols = pl.ds(h * hd, hd)
                for src, scl, dst in ((kp_ref, ks_ref, kg),
                                      (vp_ref, vs_ref, vg)):
                    sc = scl[0, :, pl.ds(h, 1)].astype(out_dtype).astype(f32)
                    dst[rows, cols] = (src[0, :, cols].astype(f32)
                                       * sc).astype(out_dtype)
        else:
            kg[rows, :] = kp_ref[0].astype(out_dtype)
            vg[rows, :] = vp_ref[0].astype(out_dtype)

    @pl.when(s == n_ps // per_step - 1)
    def _attend():
        n_rows, S = n_chunk * group, n_ps * page
        shape = (n_rows, S)
        r = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        q_pos = jnp.full(shape, pos_ref[b * n_chunk], jnp.int32)
        for c in range(1, n_chunk):
            q_pos = jnp.where(r >= c * group, pos_ref[b * n_chunk + c], q_pos)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        mask = mask_from_diff(q_pos - k_pos, win_ref[0], causal=True)
        for h in range(n_kv):
            cols = pl.ds(h * hd, hd)
            sc = jax.lax.dot_general(
                q_ref[0, h], kg[:, cols], (((1,), (1,)), ((), ())),
                preferred_element_type=f32)
            sc = sc.astype(out_dtype).astype(f32) / div + mask
            probs = jax.nn.softmax(sc, axis=-1).astype(out_dtype)
            out = jax.lax.dot_general(
                probs, vg[:, cols], (((1,), (0,)), ((), ())),
                preferred_element_type=f32)
            out_ref[0, h] = out.astype(out_dtype)


def _latent_kernel(n_ps: int, page: int, n_chunk: int, group: int,
                   v_lanes: int, div: float, out_dtype, per_step: int,
                   tbl_ref, pos_ref, win_ref, layer_ref, q_ref, *rest):
    """One grid step ``(b, s)`` over a latent pool: attend slot b's
    queries ``q_ref [1, 1, C*group, lanes]`` over its logical pages
    ``s*per_step ..`` ``(s+1)*per_step - 1`` (``rest`` starts with
    their ``per_step`` refs ``[1, page, lanes]``), folding them into the
    running max ``m``, sum ``l`` and accumulator ``acc``; the slot's
    last step writes the output."""
    del tbl_ref, layer_ref
    pages, (out_ref, kb, m_ref, l_ref, acc_ref) = (rest[:per_step],
                                                   rest[per_step:])
    b = pl.program_id(0)
    s = pl.program_id(1)
    f32 = jnp.float32
    n_rows, T = n_chunk * group, per_step * page
    first = s * T

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, f32)
        l_ref[...] = jnp.zeros(l_ref.shape, f32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

    # a block past the slot's last query position is wholly masked
    @pl.when(first <= pos_ref[b * n_chunk + n_chunk - 1])
    def _block():
        for j in range(per_step):
            kb[pl.ds(j * page, page), :] = pages[j][0].astype(out_dtype)
        shape = (n_rows, T)
        r = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        q_pos = jnp.full(shape, pos_ref[b * n_chunk], jnp.int32)
        for c in range(1, n_chunk):
            q_pos = jnp.where(r >= c * group, pos_ref[b * n_chunk + c], q_pos)
        k_pos = first + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        mask = mask_from_diff(q_pos - k_pos, win_ref[0], causal=True)
        sc = jax.lax.dot_general(
            q_ref[0, 0], kb[...], (((1,), (1,)), ((), ())),
            preferred_element_type=f32)
        sc = sc.astype(out_dtype).astype(f32) / div + mask
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(out_dtype), kb[:, pl.ds(0, v_lanes)],
            (((1,), (0,)), ((), ())), preferred_element_type=f32)
        m_ref[...] = m_new

    @pl.when(s == n_ps // per_step - 1)
    def _out():
        out_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(out_dtype)


def _paged_latent(q, pool, gtbl, pos, win, lyr, *, n_ps, scale, v_lanes,
                  interpret):
    """The latent-pool case of ``paged_attention`` (its docstring)."""
    B, C, H, lanes = q.shape
    _, _, page, _ = pool.shape
    dt = q.dtype
    per_step = max(m for m in (8, 4, 2, 1) if n_ps % m == 0)

    def page_map(j):
        def index(b, s, tbl, pos, _win, lyr):
            # pages past the slot's last position repeat its last page
            last = jnp.clip(pos[b * C + C - 1] // page, 0, n_ps - 1)
            return (lyr[0], tbl[b * n_ps + jnp.minimum(s * per_step + j,
                                                       last)], 0, 0)
        return index

    def slot_map(b, s, *_):
        return (b, 0, 0, 0)

    R = C * H
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # gtbl, pos, win, layer
        grid=(B, n_ps // per_step),
        in_specs=[pl.BlockSpec((1, 1, R, lanes), slot_map)] + [
            pl.BlockSpec((None, 1, page, lanes), page_map(j))
            for j in range(per_step)],
        out_specs=pl.BlockSpec((1, 1, R, v_lanes), slot_map),
        scratch_shapes=[pltpu.VMEM((per_step * page, lanes), dt),  # block
                        pltpu.VMEM((R, 1), jnp.float32),  # running max
                        pltpu.VMEM((R, 1), jnp.float32),  # running sum
                        pltpu.VMEM((R, v_lanes), jnp.float32)],  # acc
    )
    kern = functools.partial(_latent_kernel, n_ps, page, C, H, v_lanes,
                             np.float32(1.0 / scale), dt, per_step)
    # row c*H + h is chunk position c, head h: one KV group of H heads
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, R, v_lanes), dt),
        interpret=interpret,
    )(gtbl, pos, win, lyr, q.reshape(B, 1, R, lanes), *[pool] * per_step)
    return out.reshape(B, C, H, v_lanes)


@functools.partial(jax.jit, static_argnames=("scale", "v_lanes",
                                             "interpret"))
def paged_attention(q: jax.Array, k_pages: jax.Array,
                    v_pages: Optional[jax.Array],
                    block_tbl: jax.Array, positions: jax.Array, window,
                    layer, *, k_scale: Optional[jax.Array] = None,
                    v_scale: Optional[jax.Array] = None,
                    scale: Optional[float] = None,
                    v_lanes: Optional[int] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Attend ``q [B, C, H, hd]`` over one layer of a stacked paged pool
    through its block table.  Matches the registered ``"jnp"`` backend
    on the same operands to the tolerance stated in the module
    docstring.

    Args:
      q: projected queries, rope applied, ``[B, C, H, hd]`` (``C=1``
        for pure decode, ``C>1`` for a prefill chunk).
      k_pages/v_pages: stacked physical pool
        ``[n_layers, N_pages, page, KV*hd]`` (bf16/f32, or int8 with
        ``k_scale``/``v_scale`` planes ``[n_layers, N_pages, page,
        KV]``), read in place.  One layer's pool is the
        ``n_layers = 1, layer = 0`` case.
      block_tbl: ``[B, n_ps]`` logical->physical page map (entries may
        exceed the pool; they are clipped exactly like the oracle's
        gather — stale reads are masked by the causal term).
      positions: ``[B, C]`` int32 absolute position per chunk slot.
      window: per-layer scalar (0 = full) — may be traced (stacked
        layer scan), hence passed as a scalar-prefetch operand.
      layer: which layer of the stack to read — traced in the layer
        scan, hence a scalar-prefetch operand too.
      scale: the softmax scale (``None``: ``hd ** -0.5``).
      v_lanes: with ``v_pages`` None, the pool is a latent pool whose
        rows' first ``v_lanes`` lanes are the values, and the output is
        ``[B, C, H, v_lanes]``.
      interpret: force Pallas interpret mode; ``None`` auto-selects it
        off-TPU (CPU CI runs this exact kernel interpreted).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, C, H, hd = q.shape
    _, N_pages, page, lanes = k_pages.shape
    KV = lanes // hd
    n_ps = block_tbl.shape[1]
    group = H // KV
    dt = q.dtype
    quantized = k_scale is not None

    gtbl = jnp.clip(block_tbl, 0, N_pages - 1).astype(jnp.int32).reshape(-1)
    pos = positions.astype(jnp.int32).reshape(-1)
    win = jnp.asarray(window, jnp.int32).reshape(1)
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)
    if v_pages is None:
        return _paged_latent(q, k_pages, gtbl, pos, win, lyr, n_ps=n_ps,
                             scale=hd ** -0.5 if scale is None else scale,
                             v_lanes=v_lanes, interpret=interpret)
    # query head kv*group + g (repeat_kv's order) -> [B, KV, C*group, hd]
    qg = q.reshape(B, C, KV, group, hd).transpose(0, 2, 1, 3, 4).reshape(
        B, KV, C * group, hd)

    # each grid step fetches `per_step` of a slot's pages, one BlockSpec
    # (one double-buffered DMA) each, so that several page DMAs from HBM
    # are in flight at once: Mosaic buffers a BlockSpec at most twice
    per_step = max(m for m in (8, 4, 2, 1) if n_ps % m == 0)

    def page_map(j):
        def index(b, s, tbl, _pos, _win, lyr):
            return (lyr[0], tbl[b * n_ps + s * per_step + j], 0, 0)
        return index

    def slot_map(b, s, *_):
        return (b, 0, 0, 0)

    in_specs, operands = [pl.BlockSpec((1, KV, C * group, hd), slot_map)], [qg]
    for pool, width in ((k_pages, KV * hd), (v_pages, KV * hd),
                        (k_scale, KV), (v_scale, KV)):
        if pool is not None:
            in_specs += [pl.BlockSpec((None, 1, page, width), page_map(j))
                         for j in range(per_step)]
            operands += [pool] * per_step

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # gtbl, pos, win, layer
        grid=(B, n_ps // per_step),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KV, C * group, hd), slot_map),
        scratch_shapes=[pltpu.VMEM((n_ps * page, KV * hd), dt),  # slot K
                        pltpu.VMEM((n_ps * page, KV * hd), dt)],  # slot V
    )
    div = np.float32(np.sqrt(hd) if scale is None else 1.0 / scale)
    kern = functools.partial(_kernel, n_ps, page, KV, hd, group, C,
                             quantized, dt, per_step, div)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, C * group, hd), dt),
        interpret=interpret,
    )(gtbl, pos, win, lyr, *operands)
    return out.reshape(B, KV, C, group, hd).transpose(0, 2, 1, 3, 4).reshape(
        B, C, H, hd)


def paged_attention_hbm_bytes(B: int, C: int, H: int, KV: int, hd: int,
                              n_ps: int, page: int, *, pool_bytes: int,
                              quantized: bool, act_bytes: int) -> int:
    """Exact HBM bytes one kernel launch moves, from BlockSpec geometry.

    This is arithmetic, not a model: the grid DMAs each of the
    ``B*n_ps`` table-selected K and V pages (+ scale planes when
    quantized) exactly once at pool dtype, plus the q block in and the
    out block back.  ``benchmarks/roofline.py --paged-attn`` divides
    this by decoded tokens and compares against the measured jnp-path
    bytes (XLA cost analysis) for the same shapes.
    """
    page_cells = page * KV * hd
    kv_bytes = 2 * B * n_ps * page_cells * pool_bytes
    scale_bytes = 2 * B * n_ps * page * KV * 4 if quantized else 0
    q_out = 2 * B * C * H * hd * act_bytes
    prefetch = (B * n_ps + B * C + 1) * 4
    return kv_bytes + scale_bytes + q_out + prefetch
