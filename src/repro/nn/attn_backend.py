"""Attention-backend registry and the ``PagedKV`` cache pytree.

Two things live here, both shared by every paged-attention
implementation so they cannot drift apart:

* **position primitives** — ``position_mask`` (the single source of
  truth for causal + sliding-window masking, used by dense decode, the
  blocked prefill path, the jnp paged gather AND the Pallas kernel) and
  ``repeat_kv`` (GQA group broadcast);
* **the backend registry** — paged decode attention now has two
  implementations (the jnp gather oracle and the Pallas page-walking
  kernel), selected by name.  ``resolve("auto")`` mirrors
  ``MappedModel.select_backend``: Pallas on TPU, the jnp oracle
  everywhere else (where the kernel still runs, in interpret mode, but
  only as a correctness vehicle, not a fast path).

A backend is a callable ``fn(q, kv, *, n_heads, head_dim, window) ->
[B, C, H, hd]`` that attends the already-projected queries over an
already-written :class:`PagedKV` (pools updated, view fields set).  The
scatter/write half of the step is *not* part of the backend contract —
it runs once in ``nn.attention.paged_decode_attention_block`` so the
returned pools are bitwise identical no matter which backend attends.

Every registered backend must match the jnp oracle to a stated
tolerance: a few f32 ulps in interpret mode (asserted across page sizes
/ chunk widths / GQA ratios in ``tests/test_kernels.py``), and
``max|dlogits| / max|logits| <= n_layers * 2**-9`` for a whole decode
step compiled for the TPU (``chip_smoke.py``).  A different summation
order can flip a greedy near-tie, so token streams may differ across
``--attn-impl`` settings on the chip.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -2.0**30

# --------------------------------------------------------------------
# shared position primitives
# --------------------------------------------------------------------


def position_mask(q_pos: jax.Array, k_pos: jax.Array, window,
                  causal: bool) -> jax.Array:
    """Additive mask ``[..., qb, Sk]`` from absolute positions.

    ``window`` is a per-layer *scalar* (0 = full attention) so mixed
    local:global stacks stay scannable.  Masking on positions — never
    on page or ring geometry — is what makes every caller correct at
    page boundaries by construction: a chunk straddling two pages, or a
    ring cell that wrapped, is masked by where it *is* in the sequence,
    not where it lives in memory.
    """
    return mask_from_diff(q_pos[..., :, None] - k_pos[..., None, :], window,
                          causal)


def mask_from_diff(diff: jax.Array, window, causal: bool) -> jax.Array:
    """The additive mask of ``position_mask`` from ``q_pos - k_pos``
    (the Pallas kernel builds ``diff`` from 2-D iotas and calls this)."""
    ok = jnp.ones(diff.shape, bool)
    if causal:
        ok = ok & (diff >= 0)
    ok = ok & ((window <= 0) | (diff < window))
    return jnp.where(ok, 0.0, NEG_INF)


def repeat_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """[B,S,KV,hd] -> [B,S,H,hd] by group broadcast (TP-friendly heads)."""
    B, S, KV, hd = k.shape
    if KV == n_heads:
        return k
    reps = n_heads // KV
    return jnp.broadcast_to(k[:, :, :, None, :], (B, S, KV, reps, hd)).reshape(
        B, S, n_heads, hd)


# --------------------------------------------------------------------
# the PagedKV pytree
# --------------------------------------------------------------------


@dataclasses.dataclass
class PagedKV:
    """The paged KV cache as one typed pytree.

    Replaces the loose ``(k_pages, v_pages[, (k_scales, v_scales)])``
    tuples + four positional table/position arguments that previously
    threaded through every paged call site.  The pools are lane-dense,
    in the layout the Pallas kernel reads: one token's K (or V) for all
    KV heads is one row of ``KV * hd`` lanes.  Two granularities share
    the type:

    * **stacked** (what ``model.init_paged_kv`` returns and the donated
      serve state carries): ``k``/``v`` are
      ``[n_layers, N_pages, page, KV * hd]`` physical pools, int8 pools
      add f32 ``k_scale``/``v_scale`` planes
      ``[n_layers, N_pages, page, KV]``.  An attention call on it names
      its layer in ``layer`` (a traced int32 scalar): the page write and
      the read both index the stack in place, so the pool is never
      sliced out or restacked.
    * **per-layer** (``[N_pages, page, KV * hd]`` pools, scale planes
      ``[N_pages, page, KV]``, ``layer`` None): one layer's pool on its
      own, as tests build it; the kernel reads it as the one-layer
      stack.

    The per-call view is ``block_tbl [B, n_ps]`` (logical page ->
    physical page), ``pos [B, C]`` (absolute position per chunk slot),
    the precomputed scatter coordinates ``page_ids``/``page_off [B, C]``
    (out-of-range ids drop the write — how padded chunk slots are
    masked) and ``layer``; a pool as carried has all of them ``None``.

    ``None`` fields contribute no pytree leaves, so bare pools flow
    through ``jax.tree.map`` (page copy-on-write), a ``lax.scan``
    carry, buffer donation and ``NamedSharding`` trees exactly like the
    old tuples did.
    """

    k: jax.Array
    v: jax.Array
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None
    block_tbl: Optional[jax.Array] = None
    pos: Optional[jax.Array] = None
    page_ids: Optional[jax.Array] = None
    page_off: Optional[jax.Array] = None
    layer: Optional[jax.Array] = None

    @property
    def quantized(self) -> bool:
        """True for the int8 pool (scale planes present) — a *static*
        property: None-ness is pytree structure, not data, so it is
        knowable at trace time."""
        return self.k_scale is not None

    @property
    def n_pages(self) -> int:
        return self.k.shape[-3]

    @property
    def page_size(self) -> int:
        return self.k.shape[-2]

    @property
    def nbytes(self) -> int:
        """Total bytes across all array leaves (pool accounting)."""
        return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(self))

    def with_view(self, block_tbl, pos, page_ids, page_off,
                  layer=None) -> "PagedKV":
        """Attach the per-call view (table + positions + scatter
        coordinates, and the layer of a stacked pool) to a pool, for
        one attention call."""
        return dataclasses.replace(self, block_tbl=block_tbl, pos=pos,
                                   page_ids=page_ids, page_off=page_off,
                                   layer=layer)

    def pool(self) -> "PagedKV":
        """Strip the per-call view, keeping only the pools — the form
        carried in serve state and through the layer scan's carry."""
        return dataclasses.replace(self, block_tbl=None, pos=None,
                                   page_ids=None, page_off=None,
                                   layer=None)

    def index(self, *idx) -> tuple:
        """Index of this call's cells in a pool leaf: ``idx`` prefixed
        with the layer on a stacked pool, ``idx`` alone on a per-layer
        one."""
        return idx if self.layer is None else (self.layer, *idx)

    def scales(self) -> Optional[Tuple[jax.Array, jax.Array]]:
        """Legacy ``(k_scales, v_scales)`` tuple, or None (fp pool)."""
        if not self.quantized:
            return None
        return (self.k_scale, self.v_scale)


jax.tree_util.register_dataclass(
    PagedKV,
    data_fields=["k", "v", "k_scale", "v_scale", "block_tbl", "pos",
                 "page_ids", "page_off", "layer"],
    meta_fields=[],
)


# --------------------------------------------------------------------
# backend registry
# --------------------------------------------------------------------

_BACKENDS: Dict[str, Callable] = {}


def register(name: str, fn: Callable) -> None:
    """Register (or override) a paged-attention backend."""
    _BACKENDS[name] = fn


def get(name: str) -> Callable:
    if name not in _BACKENDS:
        raise KeyError(f"unknown attention backend {name!r}; "
                       f"registered: {available()}")
    return _BACKENDS[name]


def available() -> Tuple[str, ...]:
    return tuple(sorted(_BACKENDS))


def resolve(impl: str, platform: Optional[str] = None) -> str:
    """Resolve an ``attn_impl`` name to a registered backend.

    ``"auto"`` mirrors ``MappedModel.select_backend``: the Pallas
    kernel on TPU, the jnp oracle on every other platform.  Explicit
    names pass through (so ``--attn-impl pallas`` on CPU runs the
    kernel in interpret mode — slow, but the correctness leg CI uses).
    """
    if impl == "auto":
        platform = platform if platform is not None else jax.default_backend()
        return "pallas" if platform == "tpu" else "jnp"
    if impl not in _BACKENDS:
        raise ValueError(f"attn_impl must be 'auto' or one of "
                         f"{available()}; got {impl!r}")
    return impl


def valid_impls() -> Tuple[str, ...]:
    """Accepted ``attn_impl`` spellings (``"auto"`` + registered)."""
    return ("auto",) + available()


# --------------------------------------------------------------------
# the two in-tree backends
# --------------------------------------------------------------------


def _gathered_views(q: jax.Array, kv: PagedKV):
    """Logical [B, n_ps*page, KV, hd] K/V views through the block
    table, gathered from the call's layer of the pool and dequantized
    to ``q.dtype`` — the jnp oracle's gather, also the reference the
    kernel tests diff against."""
    dt = q.dtype
    B, hd = q.shape[0], q.shape[-1]
    S = kv.block_tbl.shape[1] * kv.page_size
    at = kv.index(jnp.clip(kv.block_tbl, 0, kv.n_pages - 1))

    def view(pool, scale):
        x = pool[at].astype(dt).reshape(B, S, -1, hd)
        if scale is not None:
            x = x * scale[at].astype(dt).reshape(B, S, -1, 1)
        return x

    return view(kv.k, kv.k_scale), view(kv.v, kv.v_scale)


def _attend_jnp(q: jax.Array, kv: PagedKV, *, n_heads: int, head_dim: int,
                window) -> jax.Array:
    """The jnp oracle: gather the full logical view, mask on absolute
    positions, full-axis softmax.  Bitwise-reference semantics; every
    other backend is gated against this path."""
    B, C = q.shape[0], q.shape[1]
    S = kv.block_tbl.shape[1] * kv.page_size
    # the gather moves the pool's walked pages
    with jax.named_scope("kv"):
        kf, vf = _gathered_views(q, kv)
    kf = repeat_kv(kf, n_heads)
    vf = repeat_kv(vf, n_heads)
    k_pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    mask = position_mask(kv.pos, k_pos, window, causal=True)  # [B, C, S]
    s = jnp.einsum("bqhd,bshd->bhqs", q, kf) / np.sqrt(head_dim)
    s = s.astype(jnp.float32) + mask[:, None, :, :]
    probs = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqs,bshd->bqhd", probs, vf)


def _attend_pallas(q: jax.Array, kv: PagedKV, *, n_heads: int,
                   head_dim: int, window) -> jax.Array:
    """The Pallas page-walking kernel (``kernels.paged_attention``),
    reading the call's layer of the stacked pool in place; a per-layer
    pool is the one-layer stack at layer 0.

    Imported lazily so this module stays importable without pulling the
    Pallas toolchain in (and so kernels can import the primitives above
    without a cycle).
    """
    from ..kernels.paged_attention import paged_attention
    pools = (kv.k, kv.v, kv.k_scale, kv.v_scale)
    layer = kv.layer
    if layer is None:
        pools = jax.tree.map(lambda a: a[None], pools)
        layer = 0
    k, v, k_scale, v_scale = pools
    return paged_attention(q, k, v, kv.block_tbl, kv.pos, window, layer,
                           k_scale=k_scale, v_scale=v_scale)


register("jnp", _attend_jnp)
register("pallas", _attend_pallas)
