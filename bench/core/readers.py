"""Shared arithmetic of the per-layer metric readers (``bench/metrics``).

Each reader gets ``ctx``: the reduced trace (``trace``), the traced window
(``window``, seconds on the trace clock), device busy seconds in it
(``busy``), the chip's ``peaks``, the config's sizes (``dims``), the
``chips`` used, the per-step work model (``work``) and the global fused
steps the trace covers (``steps``).  A reader that finds nothing to read
returns None, and the metric is left out of the result line.
"""
from __future__ import annotations

from typing import Optional

# names of the kernels' operations in the device trace
GATE_OP = "fused_eb_pallas"  # kernels/fused_eb.py, the Planter gate
ATTN_OP = "paged_attention"  # kernels/paged_attention.py
STEP_MODULE = "run_k"        # the fused serve step's jitted program


def share(num: float, den: float, what: str) -> float:
    """``num / den`` in percent, refused above 100: a share of a
    roofline, a peak or the busy time cannot pass it unless the work is
    counted too high or the time leaves out part of the work."""
    v = 100.0 * num / den
    if v > 100.0:
        raise ValueError(f"{what} reads {v:.2f}% (over 100%): the work is "
                         f"counted too high or the time misses part of it")
    return v


def _span(ctx) -> float:
    a, b = ctx["window"]
    return b - a


def idle_share(ctx) -> Optional[float]:
    if _span(ctx) <= 0 or ctx["busy"] <= 0:
        return None
    return 100.0 * (1.0 - ctx["busy"] / _span(ctx))


def gate_share(ctx) -> Optional[float]:
    t = ctx["trace"].op_time(ctx["dev"], ctx["window"], GATE_OP)
    if t <= 0 or ctx["busy"] <= 0:
        return None
    return share(t, ctx["busy"], "gate_share")


def host_gap_ms(ctx) -> Optional[float]:
    """Mean device-idle time between consecutive executions of the fused
    serve step, in ms."""
    lo, hi = ctx["window"]
    mods = sorted((a, b) for n, a, b in ctx["trace"].modules.get(
        ctx["dev"], []) if STEP_MODULE in n and a >= lo and b <= hi)
    gaps = [max(0.0, a2 - b1) for (_, b1), (a2, _) in zip(mods, mods[1:])]
    if not gaps:
        return None
    return 1e3 * sum(gaps) / len(gaps)


def attn_roofline(ctx) -> Optional[float]:
    """Attention kernel's least time at the chip's peaks, for the work
    of the traced steps, over its device time (percent)."""
    t_kernel = ctx["trace"].op_time(ctx["dev"], ctx["window"], ATTN_OP)
    if t_kernel <= 0 or ctx["peaks"] is None:
        return None
    p = ctx["peaks"]
    lo, hi = ctx["steps"]
    t_roof, n_mem = ctx["work"].attn_roofline_s(
        lo, hi, p["bf16_flops_per_s"], p["hbm_bytes_per_s"])
    if t_roof <= 0:
        return None
    ctx["log"](f"[bench] attn_roofline: {n_mem} of {hi - lo} traced steps "
               f"bound by HBM bandwidth, the rest by bf16 compute")
    return share(t_roof, t_kernel, "attn_roofline")


def mfu(ctx) -> Optional[float]:
    """Model FLOPs of the real positions of the traced steps over window
    x chips x bf16 peak (percent)."""
    if ctx["peaks"] is None:
        return None
    lo, hi = ctx["steps"]
    flops = ctx["work"].totals(lo, hi)["model_flops"]
    if flops <= 0:
        return None
    return share(flops, _span(ctx) * ctx["chips"]
                 * ctx["peaks"]["bf16_flops_per_s"], "mfu")
