"""Plain float32 reference forward of the decoders the config files declare.

Straight ``jax.numpy`` under ``default_matmul_precision("highest")``: no
kernel, no cache, no batching, nothing imported from the program.  It reads
the weight tree the benchmark made (``bench/core/weights.py``) and the
configuration file: its published values, except where a ``departures``
entry names a key the program cannot compute as published, which is read
as run (``weights.as_run``).  The forward is the configuration's family
module's (``bench/core/models.py``); the pieces families share are here:
RMSNorm ``x / sqrt(mean(x^2) + eps) * g`` with the gain stored as ``g - 1``
in the tree, rotary embedding on the two halves of each head, the SwiGLU
``down(silu(gate x) * up x)``, and every matmul in float32 or fp8.

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

from .core import models
from .core.weights import as_run

F8_MAX = 448.0  # largest finite float8 e4m3 value


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


def _swiglu(h, wg, wu, wd, fp8):
    return _mm(jax.nn.silu(_mm(h, wg, fp8)) * _mm(h, wu, fp8), wd, fp8)


@functools.lru_cache(maxsize=None)
def _compiled(cfg_json: str, precision: str):
    cfg = as_run(json.loads(cfg_json))
    fwd = functools.partial(models.load(cfg).forward, cfg,
                            precision == "fp8")

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
