"""Pallas TPU kernels for the Planter data-plane primitives.

Each kernel ships as ``<name>.py`` (pl.pallas_call + BlockSpec), with its
jit'd public wrapper in ``ops.py`` and its pure-jnp oracle in ``ref.py``.

Kernel index:

* ``fused_eb.py`` — fused encode/bucketize gate predict (the mapped
  Planter model's data-plane lookup chain in one launch); wrapper
  ``ops.bnn_forward``/friends, oracle ``ref.py``.
* ``paged_attention.py`` — serve-path paged decode attention: walks
  the block table page-by-page via scalar-prefetch BlockSpec index
  maps over one layer of the stacked pool, read in place, fusing
  gather + int8 dequant + masked softmax attention in one launch
  (decode ``C=1`` and prefill-chunk ``[B, C]`` variants).  Its
  oracle is the registered ``"jnp"`` backend in
  ``repro.nn.attn_backend`` (matched to a stated tolerance); selected via
  ``ServeConfig(attn_impl=...)`` / ``--attn-impl``.
"""
from .ops import (
    bucketize,
    ternary_match,
    lb_lookup,
    bnn_popcount_matmul,
    bnn_forward,
    pack_bits_jnp,
)
from .paged_attention import paged_attention

__all__ = [
    "bucketize",
    "ternary_match",
    "lb_lookup",
    "bnn_popcount_matmul",
    "bnn_forward",
    "pack_bits_jnp",
    "paged_attention",
]
