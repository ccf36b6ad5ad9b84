"""Mesh-aware partition-spec derivation for params, batches and caches.

The rules are Megatron-flavoured and *name-driven* — they key off the
leaf names the model builders use (``wq``/``wo``/``w_down``/…), so one
rule table covers every assigned family (dense, GQA, MoE, recurrent,
enc-dec, VLM):

* column-parallel projections shard their output dim over ``model``;
* row-parallel projections (``wo``/``w_down``/``w_out``) shard their
  input dim over ``model``;
* the embedding shards the (256-padded) vocab, the LM head its vocab
  output dim;
* MoE expert stacks ``[E, D, F]`` shard the expert dim over ``model``
  (expert parallelism; ``E`` is padded to a multiple of 16);
* stacked-layer leading dims (``layers``/``macros``/``enc_layers``/
  ``cross_layers``) are scan axes and never shard;
* every proposal is validated against the mesh: an axis that does not
  divide the dim is dropped (replicated), so specs are safe for any mesh
  from the 1×2 CPU smoke mesh to the 16×16 production pod.

A ``pod`` super-axis, when present, folds into data parallelism:
``batch_pspec`` returns ``P(("pod", "data"), ...)``.

Works with abstract mesh stand-ins too: only ``mesh.axis_names`` and
``mesh.shape`` are consulted until a ``NamedSharding`` is built.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

MODEL_AXIS = "model"

# roots whose first array dim is a lax.scan layer stack (never sharded)
_STACKED_ROOTS = ("layers", "macros", "enc_layers", "cross_layers")

# output-dim ("column") parallel projections: shard the last dim
_COL_PARALLEL = {
    "wq", "wk", "wv", "w_gate", "w_up", "w_lin", "w_rec_gate", "w_in_gate",
    "w_i", "w_f", "w_gates", "r_gates", "router", "conv", "frontend_proj",
    "embed_proj",
}
# input-dim ("row") parallel projections: shard the first dim
_ROW_PARALLEL = {"wo", "w_down", "w_out"}


def _path_names(path) -> Tuple[str, ...]:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
        elif hasattr(p, "name"):
            out.append(str(p.name))
        else:
            out.append(str(p))
    return tuple(out)


def _axis_size(mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        return int(np.prod([int(mesh.shape[a]) for a in axis]))
    return int(mesh.shape[axis])


def _present(mesh, axis):
    """Restrict a proposed axis to the names the mesh actually has.

    Serve submeshes are narrower than the training pod (a per-host slice
    may carry only ``model``, a CPU smoke mesh only ``data``); a proposal
    naming an absent axis must degrade to replication on that axis, not
    KeyError inside ``mesh.shape``.
    """
    names = tuple(mesh.axis_names)
    if axis is None:
        return None
    if isinstance(axis, (tuple, list)):
        kept = tuple(a for a in axis if a in names)
        if not kept:
            return None
        return kept if len(kept) > 1 else kept[0]
    return axis if axis in names else None


def _divides(mesh, axis, dim: int) -> bool:
    return dim > 0 and dim % _axis_size(mesh, axis) == 0


def data_axis(mesh):
    """The (possibly compound) data-parallel axis: pod folds into data."""
    if "pod" in tuple(mesh.axis_names):
        return ("pod", "data")
    return "data"


def _validated(shape: Sequence[int], axes: Sequence[Any], mesh) -> P:
    """Drop any proposed axis absent from the mesh or not dividing its dim."""
    out = []
    for dim, ax in zip(shape, axes):
        ax = _present(mesh, ax)
        if ax is not None and _divides(mesh, ax, dim):
            out.append(ax)
        else:
            out.append(None)
    return P(*out)


# ------------------------------------------------------------------ params
def param_spec(path, leaf, mesh) -> P:
    """PartitionSpec for one parameter leaf (path from tree_map_with_path)."""
    names = _path_names(path)
    name = names[-1] if names else ""
    shape = tuple(leaf.shape)
    ndim = len(shape)
    if ndim == 0:
        return P()

    lead = 1 if (names and names[0] in _STACKED_ROOTS and ndim > 1) else 0
    core = shape[lead:]
    axes: Tuple[Any, ...] = tuple(None for _ in core)

    if name == "embed" and ndim == 2:
        axes = (MODEL_AXIS, None)  # vocab rows (256-padded -> always even)
    elif name == "head" and ndim == 2:
        axes = (None, MODEL_AXIS)  # vocab columns
    elif ("moe" in names and "shared" not in names
          and name in ("w_gate", "w_up", "w_down") and len(core) == 3):
        axes = (MODEL_AXIS, None, None)  # expert parallelism over [E, ., .]
    elif name in _ROW_PARALLEL and len(core) == 2:
        axes = (MODEL_AXIS, None)
    elif name in _COL_PARALLEL and len(core) >= 2:
        axes = tuple(None for _ in core[:-1]) + (MODEL_AXIS,)
    # 1-D leaves (norm scales, biases, gate biases, lam) replicate: they
    # are tiny and feed elementwise ops on model-sharded activations.

    full = tuple([None] * lead) + tuple(axes)
    return _validated(shape, full, mesh)


def param_pspecs(params, mesh):
    """Tree of PartitionSpecs mirroring ``params``."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: param_spec(path, leaf, mesh), params)


def param_shardings(params, mesh):
    """Tree of NamedShardings mirroring ``params`` (requires a real Mesh)."""
    from jax.sharding import NamedSharding

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, param_spec(path, leaf, mesh)),
        params)


# ------------------------------------------------------------------- batch
def batch_pspec(mesh, batch_size: int, ndim: int) -> P:
    """Batch-dim data parallelism; replicate when the batch can't split
    (e.g. the long_500k single-sequence shape) or the mesh has no data
    axis (a model-only serve submesh)."""
    dp = _present(mesh, data_axis(mesh))
    if dp is None or not _divides(mesh, dp, batch_size):
        return P(*([None] * ndim))
    return P(dp, *([None] * (ndim - 1)))


# ------------------------------------------------------------------ caches
def cache_pspec(path, leaf, mesh, batch: int) -> P:
    """PartitionSpec for one decode-state leaf.

    Decode state trees (see ``model.init_decode_state``) hold

    * KV caches ``[stack, B, S, KV, hd]`` — batch shards over data, and the
      *sequence* dim shards over ``model`` (KV heads are often < TP degree,
      the sequence never is: this is what fits 32k/500k caches per chip);
    * recurrent states ``[stack, B, ...]`` / tail states ``[B, ...]`` —
      batch shards over data, the rest replicates;
    * scalars (``pos``) — replicated.
    """
    shape = tuple(leaf.shape)
    if not shape:
        return P()
    axes: list = [None] * len(shape)
    dp = _present(mesh, data_axis(mesh))
    names = _path_names(path)

    # Stacked leaves ([stack, B, ...]) carry batch at dim 1: KV/cross
    # caches, macro-block recurrent states, and any >=4-D leaf.  Tail
    # states and other per-batch leaves carry it at dim 0.  Checking the
    # layout before sizes avoids misdetection when stack depth == batch.
    stacked_key = bool(names) and (
        names[0] in ("kv", "kv_scales", "cross")
        or (names[0].startswith("m") and "_" in names[0]))
    tail_key = bool(names) and names[0].startswith("tail")
    bdim: Optional[int] = None
    if tail_key:
        bdim = 0 if shape[0] == batch else None
    elif ((stacked_key or len(shape) >= 4)
          and len(shape) >= 2 and shape[1] == batch):
        bdim = 1
    else:
        for i, d in enumerate(shape):
            if d == batch:
                bdim = i
                break
    if bdim is not None and dp is not None and _divides(mesh, dp, batch):
        axes[bdim] = dp

    if len(shape) == 5 and bdim == 1:  # [stack, B, S, KV, hd] cache layout
        mp = _present(mesh, MODEL_AXIS)
        if mp is not None and shape[2] > 1 and _divides(mesh, mp, shape[2]):
            axes[2] = mp
    return P(*axes)


def cache_shardings(state, mesh, batch: int):
    """Tree of NamedShardings for a decode-state tree."""
    from jax.sharding import NamedSharding

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(
            mesh, cache_pspec(path, leaf, mesh, batch)), state)


def paged_cache_pspec(leaf, mesh) -> P:
    """PartitionSpec for a stacked paged KV page pool ``[stack,
    n_pages, page, KV * hd]`` (see ``model.init_paged_kv``) — the int8
    pool's f32 scale planes ``[stack, n_pages, page, KV]`` follow the
    same rule.

    Physical pages shard over ``data`` (the pool is the per-shard slot
    memory, like the dense cache's batch dim), and the *within-page*
    sequence dim shards over ``model`` where the page size divides it —
    preserving the dense cache's KV-seq-over-``model`` rule at page
    granularity.  The block table stays replicated (its page-list dim
    is tiny control state), so a page gather is index arithmetic plus
    whatever collective GSPMD derives for the sharded pool.
    """
    shape = tuple(leaf.shape)
    if len(shape) != 4:
        return P(*([None] * len(shape)))
    return _validated(shape, (None, data_axis(mesh), MODEL_AXIS, None),
                      mesh)


def paged_kv_shardings(kv, mesh):
    """NamedShardings for a page pool.

    ``kv`` is any pytree of pool leaves — canonically the
    :class:`repro.nn.attn_backend.PagedKV` dataclass from
    ``model.init_paged_kv`` (``k``/``v`` pools, optional int8
    ``k_scale``/``v_scale`` planes; ``None`` view fields contribute no
    leaves) — but legacy ``(k_pages, v_pages[, scales])`` tuples map
    the same way.  Every 4-D leaf, value pool or scale plane, follows
    ``paged_cache_pspec``.
    """
    from jax.sharding import NamedSharding

    return jax.tree_util.tree_map(
        lambda leaf: NamedSharding(mesh, paged_cache_pspec(leaf, mesh)), kv)


# ------------------------------------------------------------------- serve
# The device-resident batcher's donated pytree (serve.engine
# DeviceContinuousBatcher): a decode-state subtree under "decode" (or a
# page pool under "pages"), flat per-slot arrays, per-request output
# rings, and a scalar queue head.
_SLOT_LEAVES = ("free", "req", "gen", "last", "hasf", "pos", "plen",
                "reg", "seed", "qidx")
_RING_LEAVES = ("out_tok", "out_len", "out_done", "out_drop", "out_tbl")


def serve_pspec(path, leaf, mesh, batch: int) -> P:
    """PartitionSpec for one serve-state leaf.

    * the ``decode`` subtree follows ``cache_pspec`` (batch over data,
      KV sequence over model); the paged ``pages`` pool follows
      ``paged_cache_pspec`` (pages over data, within-page seq over
      model);
    * per-slot arrays (``free``/``req``/``gen``/``last``/``hasf``, the
      sampling ``seed`` and queue-index ``qidx``, the paged
      ``pos``/``plen``/``reg``, the ``[B, F]`` gate features, the
      ``[B, P]`` prompt buffer and the ``[B, n_ps]`` block table) shard
      their slot dim over data; the block table's page-list dim
      replicates;
    * output rings (including the ``out_tbl`` block-table ring the
      prefix cache registers from) and the page refcounts (``pref`` —
      read by every slot's fill and drained to host at the end of each
      run) replicate — a replicated ring keeps the ``sync_every`` drain
      one local read instead of an all-gather per round trip;
    * scalars (queue ``head``) replicate.
    """
    names = _path_names(path)
    if names and names[0] == "decode":
        return cache_pspec(path[1:], leaf, mesh, batch)
    if names and names[0] == "pages":
        return paged_cache_pspec(leaf, mesh)
    shape = tuple(leaf.shape)
    name = names[-1] if names else ""
    if not shape or name == "head" or name in ("pfree", "pref") \
            or name in _RING_LEAVES:
        return P(*([None] * len(shape)))
    if name in _SLOT_LEAVES or name in ("feat", "pbuf", "tbl"):
        return batch_pspec(mesh, shape[0], len(shape))
    return P(*([None] * len(shape)))


def serve_state_shardings(state, mesh, batch: int):
    """Tree of NamedShardings for the device batcher's donated pytree."""
    from jax.sharding import NamedSharding

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(
            mesh, serve_pspec(path, leaf, mesh, batch)), state)


def queue_pspec(mesh, n_queue: int, ndim: int) -> P:
    """Spec for the device FIFO queue / the batched admission-gate launch:
    queue rows are data-parallel like any request batch."""
    return batch_pspec(mesh, n_queue, ndim)
