"""Roofline analysis per (arch × shape) from compiled dry-run artifacts.

The cell path (``--arch``/``--all``) must run before anything else
initializes jax — it pulls in ``repro.launch.dryrun``, which pins 512
placeholder devices via XLA_FLAGS.  That import is lazy (``_dryrun()``)
so the ``--paged-attn`` mode, and callers like ``serve_bench`` that
already hold an initialized backend, can import this module without
the device-count side effect.

Accounting methodology (see EXPERIMENTS.md §Roofline):

XLA's ``cost_analysis()`` counts while-loop bodies ONCE, so a scanned
64-layer model under-reports by ~L×.  We therefore compile each cell
twice at reduced depth (L1, L2) with every scan structurally removed
(layer scans unrolled, q-block = full seq, mLSTM chunk = full seq,
microbatches = 1) and extrapolate affinely — exact, because HLO cost is
affine in layer count.  Corrections applied on top:

* microbatching re-reads weights: bytes += (m-1) × param_bytes_f32;
* sLSTM's time scan cannot be unrolled (S steps): analytic per-step
  flops/bytes are added for the missing (S-1) iterations.

Hardware model (TPU v5e): 197 bf16 TFLOP/s, 819 GB/s HBM, ~50 GB/s/link
ICI.  Collective shapes in the partitioned HLO are per-device, so
``collective term = local_collective_bytes / link_bw``.
"""
import argparse
import json
from typing import Any, Dict, Optional

import numpy as np

from repro.arch.config import SHAPES
from repro.configs import ARCH_IDS, get_config


def _dryrun():
    """Import the dry-run toolchain on first use.  Side effect: pins
    512 placeholder devices (XLA_FLAGS) — call before jax initializes,
    and never from the paged-attn path."""
    import repro.launch.dryrun as DR
    return DR


PEAK_FLOPS = 197e12  # bf16 / chip
HBM_BW = 819e9  # bytes/s / chip
LINK_BW = 50e9  # bytes/s / ICI link
CHIPS = {"16x16": 256, "2x16x16": 512}


def _variant_layers(cfg) -> Any:
    """Reduced depths for the affine fit.

    L=1·pat is avoided: XLA special-cases trip-1/length-1 programs (scan
    elimination, different fusion), breaking affinity — measured in
    EXPERIMENTS.md §Roofline.  L=2·pat / 3·pat sit on the clean affine
    segment.
    """
    pat = len(cfg.block_pattern) if cfg.block_pattern else 1
    return 2 * pat, 3 * pat


def _slstm_correction(cfg, shape, kind: str) -> Dict[str, float]:
    """Analytic flops/bytes for the (S-1) uncounted sLSTM scan steps."""
    if not cfg.block_pattern or "slstm" not in cfg.block_pattern:
        return {"flops": 0.0, "bytes": 0.0}
    if kind == "decode":
        return {"flops": 0.0, "bytes": 0.0}  # decode body runs once: exact
    n_slstm = sum(1 for i in range(cfg.n_layers)
                  if cfg.block_pattern[i % len(cfg.block_pattern)] == "slstm")
    B, S = shape.global_batch, shape.seq_len
    H, hd = cfg.n_heads, cfg.d_model // cfg.n_heads
    per_step = 2 * B * H * hd * 4 * hd + 20 * B * cfg.d_model  # rec + gates
    mult = 3.0 if kind == "train" else 1.0  # fwd+bwd ~ 3x fwd
    flops = (S - 1) * per_step * n_slstm * mult
    bytes_ = (S - 1) * (4 * B * H * hd * 4) * n_slstm * mult  # state traffic
    return {"flops": flops, "bytes": bytes_}


def measure_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
                 overrides: Optional[Dict[str, Any]] = None,
                 verbose: bool = True) -> Dict[str, Any]:
    """Roofline terms for one cell via unrolled-variant extrapolation."""
    DR = _dryrun()
    overrides = dict(overrides or {})
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    L_full = cfg.n_layers
    L1, L2 = _variant_layers(cfg)
    acct = dict(overrides)
    acct.update(unroll=True, microbatches=1,
                q_block=shape.seq_len, mlstm_chunk=shape.seq_len)

    def run(n_layers):
        o = dict(acct)
        o["n_layers"] = n_layers
        lowered, meta = DR.lower_cell(arch, shape_name, multi_pod=multi_pod,
                                      overrides=o)
        compiled = lowered.compile()
        return DR.analyze(lowered, compiled), meta

    a1, meta1 = run(L1)
    a2, _ = run(L2)
    per_layer = {
        "flops": (a2["flops"] - a1["flops"]) / (L2 - L1),
        "bytes": (a2["bytes"] - a1["bytes"]) / (L2 - L1),
        "coll": (a2["collective_bytes_total"]
                 - a1["collective_bytes_total"]) / (L2 - L1),
    }
    flops = a1["flops"] + per_layer["flops"] * (L_full - L1)
    bytes_ = a1["bytes"] + per_layer["bytes"] * (L_full - L1)
    coll = (a1["collective_bytes_total"]
            + per_layer["coll"] * (L_full - L1))
    # corrections
    corr = _slstm_correction(cfg, shape, meta1["kind"])
    flops += corr["flops"]
    bytes_ += corr["bytes"]
    mesh = "2x16x16" if multi_pod else "16x16"
    chips = CHIPS[mesh]
    if meta1["kind"] == "train":
        m_full = overrides.get("microbatches",
                               8 if shape.global_batch >= 8 else 1)
        # each microbatch re-reads this chip's weight shard (f32 master)
        param_bytes_per_chip = 4.0 * cfg.param_count() / chips
        bytes_ += (m_full - 1) * param_bytes_per_chip
    # cost_analysis of the partitioned module reports PER-DEVICE work
    compute_s = flops / PEAK_FLOPS
    memory_s = bytes_ / HBM_BW
    coll_s = coll / LINK_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": coll_s}
    dominant = max(terms, key=terms.get)

    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6.0 * cfg.param_count() * tokens
        if cfg.n_experts:
            model_flops = 6.0 * cfg.active_param_count() * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2.0 * (cfg.active_param_count()
                             if cfg.n_experts else cfg.param_count()) * tokens
    else:
        tokens = shape.global_batch
        model_flops = 2.0 * (cfg.active_param_count()
                             if cfg.n_experts else cfg.param_count()) * tokens
    hlo_flops_global = flops * chips
    useful = model_flops / hlo_flops_global if hlo_flops_global else 0.0

    out = {
        "arch": arch, "shape": shape_name, "mesh": mesh, "kind": meta1["kind"],
        "hlo_flops_per_chip": flops, "hlo_bytes_per_chip": bytes_,
        "collective_bytes_per_chip": coll,
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": coll_s, "dominant": dominant,
        "model_flops": model_flops,
        "useful_flops_ratio": useful,
        "step_s_bound": max(terms.values()),
        "roofline_fraction": (compute_s / max(terms.values())
                              if max(terms.values()) else 0.0),
        "per_layer": per_layer,
    }
    if verbose:
        print(f"{arch:24s} {shape_name:12s} {mesh:8s} "
              f"C={compute_s*1e3:9.2f}ms M={memory_s*1e3:9.2f}ms "
              f"N={coll_s*1e3:9.2f}ms dom={dominant[:4]} "
              f"useful={useful:5.2f} roofline={out['roofline_fraction']:.2f}")
    return out


def measure_paged_attention(*, verbose: bool = True) -> Dict[str, Any]:
    """HBM bytes per decoded token, jnp gather path vs the Pallas
    paged-attention kernel, at a serve-decode-shaped cell.

    The jnp side is *measured*: XLA ``cost_analysis()`` of the jitted
    ``"jnp"`` backend (which materializes the gathered logical view,
    its dequant, and the GQA head expansion in HBM).  The kernel side
    is the exact DMA model from its BlockSpec geometry
    (``paged_attention_hbm_bytes`` — every mapped page crosses HBM
    exactly once, dequant/expansion happen in VMEM).  Both are
    deterministic byte accountings, so ``reduction`` is a hard CI gate
    (``check_regression``), not a timing measurement.
    """
    import functools

    import jax
    import jax.numpy as jnp

    from repro.kernels.paged_attention import paged_attention_hbm_bytes
    from repro.nn import attn_backend as AB

    B, C, H, KV, hd = 8, 1, 8, 2, 64
    page, n_ps = 16, 16
    N = B * n_ps
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(0, 1, (B, C, H, hd)), jnp.float32)
    tbl = jnp.asarray(rng.permutation(N).reshape(B, n_ps).astype(np.int32))
    pos = jnp.full((B, C), n_ps * page - 1, jnp.int32)
    out: Dict[str, Any] = {
        "shape": {"B": B, "C": C, "H": H, "KV": KV, "hd": hd,
                  "page": page, "pages_per_req": n_ps},
    }
    for name, quantized in (("fp32", False), ("int8", True)):
        if quantized:
            kv = AB.PagedKV(
                k=jnp.zeros((N, page, KV * hd), jnp.int8),
                v=jnp.zeros((N, page, KV * hd), jnp.int8),
                k_scale=jnp.ones((N, page, KV), jnp.float32),
                v_scale=jnp.ones((N, page, KV), jnp.float32))
            pool_bytes = 1
        else:
            kv = AB.PagedKV(k=jnp.zeros((N, page, KV * hd), jnp.float32),
                            v=jnp.zeros((N, page, KV * hd), jnp.float32))
            pool_bytes = 4
        kv = kv.with_view(tbl, pos, None, None)
        fn = jax.jit(functools.partial(AB.get("jnp"), n_heads=H,
                                       head_dim=hd, window=jnp.int32(0)))
        ca = fn.lower(q, kv).compile().cost_analysis()
        if isinstance(ca, (list, tuple)):  # older jax returns [dict]
            ca = ca[0]
        jnp_bytes = float(ca.get("bytes accessed", 0.0))
        kernel_bytes = float(paged_attention_hbm_bytes(
            B=B, C=C, H=H, KV=KV, hd=hd, n_ps=n_ps, page=page,
            pool_bytes=pool_bytes, quantized=quantized, act_bytes=4))
        tokens = B * C
        entry = {
            "jnp_bytes_per_token": jnp_bytes / tokens,
            "kernel_bytes_per_token": kernel_bytes / tokens,
            "reduction": (jnp_bytes / kernel_bytes if kernel_bytes
                          else 0.0),
        }
        out[name] = entry
        if verbose:
            print(f"paged-attn {name:5s}: jnp "
                  f"{entry['jnp_bytes_per_token']:12.0f} B/token  kernel "
                  f"{entry['kernel_bytes_per_token']:12.0f} B/token  "
                  f"reduction {entry['reduction']:6.2f}x")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--moe-impl", default="dense")
    ap.add_argument("--paged-attn", action="store_true",
                    help="measure paged-attention HBM bytes/token "
                         "(jnp gather vs Pallas kernel DMA model) "
                         "instead of arch×shape cells")
    args = ap.parse_args()
    if args.paged_attn:
        res = measure_paged_attention()
        if args.out:
            with open(args.out, "w") as f:
                json.dump(res, f, indent=1)
        bad = [k for k in ("fp32", "int8")
               if res[k]["reduction"] <= 1.0]
        if bad:
            print(f"FAIL: kernel does not undercut the jnp gather "
                  f"path's HBM bytes/token for {bad}")
            raise SystemExit(1)
        return
    DR = _dryrun()
    cells = []
    if args.all:
        for arch in ARCH_IDS:
            for shape in SHAPES:
                cfg = get_config(arch)
                ok, why = DR.cell_supported(cfg, SHAPES[shape])
                if ok:
                    cells.append((arch, shape))
    else:
        cells = [(args.arch.replace("-", "_"), args.shape)]
    results = []
    for arch, shape in cells:
        try:
            results.append(measure_cell(
                arch, shape, multi_pod=args.multi_pod,
                overrides={"moe_impl": args.moe_impl}))
        except Exception as e:
            print(f"FAIL {arch} {shape}: {e}")
            results.append({"arch": arch, "shape": shape,
                            "error": str(e)[:300]})
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
