"""GPipe-style pipeline parallelism over the stacked transformer layers.

The model keeps its layers as one stacked pytree scanned by ``lax.scan``;
pipelining re-cuts that stack into ``n_stages`` contiguous stages and runs
the classic GPipe schedule: microbatch *m* enters stage *s* at tick
``t = m + s``, so at any tick every stage works on a different microbatch
and stage *s*'s input is stage *s−1*'s output from the previous tick.
The whole schedule is one jitted SPMD program — each stage's parameters
carry their own shardings, and XLA overlaps the per-tick stage programs
(the skew exists so that it *can*).  Gradients come from differentiating
the full schedule (synchronous GPipe: all microbatch gradients accumulate
into one update), and the per-microbatch loss is the same objective the
unpipelined train step optimizes — next-token CE + z-loss + MoE aux.

``n_stages`` defaults to the mesh's ``pod`` axis, the natural pipeline
dimension on a multi-pod fleet (inter-pod links are the slow ones; the
pipeline crosses them once per stage boundary instead of every layer).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..arch import model as M
from ..arch.config import ArchConfig


def n_pipeline_stages(mesh, n_stages: Optional[int] = None) -> int:
    """Explicit stage count, else the mesh's pod axis (1 without pods)."""
    if n_stages is not None:
        if n_stages < 1:
            raise ValueError(f"n_stages must be >= 1, got {n_stages}")
        return int(n_stages)
    try:
        return int(dict(mesh.shape).get("pod", 1))
    except (AttributeError, TypeError):
        return 1


def _stack_len(layers) -> int:
    return int(jax.tree.leaves(layers)[0].shape[0])


def split_layers_for_stages(params: Dict[str, Any], n_stages: int):
    """Re-cut the stacked ``layers`` pytree into per-stage stacks.

    Returns the staged tree: every non-layer entry unchanged, plus
    ``stages`` — a list of ``n_stages`` layer-stack pytrees of depth
    ``n_layers // n_stages`` each.
    """
    if "layers" not in params:
        raise NotImplementedError(
            "pipeline parallelism currently supports the homogeneous "
            "stacked-'layers' families (dense/moe); heterogeneous "
            "macro stacks pipeline at macro granularity in a follow-up")
    n_layers = _stack_len(params["layers"])
    if n_stages < 1 or n_layers % n_stages:
        raise ValueError(
            f"{n_layers} layers do not split into {n_stages} equal stages")
    per = n_layers // n_stages
    staged = {k: v for k, v in params.items() if k != "layers"}
    staged["stages"] = [
        jax.tree.map(lambda x: x[i * per:(i + 1) * per], params["layers"])
        for i in range(n_stages)]
    return staged


def staged_pspecs(pspecs: Dict[str, Any], n_stages: int):
    """Partition-spec tree matching ``split_layers_for_stages`` output.

    Slicing the layer stack along its (unsharded) leading scan dim leaves
    every leaf's spec unchanged, so each stage reuses the stack's specs.
    """
    staged = {k: v for k, v in pspecs.items() if k != "layers"}
    staged["stages"] = [pspecs["layers"] for _ in range(n_stages)]
    return staged


def make_pipeline_step(cfg: ArchConfig, mesh, pspecs, *,
                       n_stages: Optional[int] = None, n_micro: int = 1,
                       q_block: int = 512, moe_impl: str = "dense",
                       remat: bool = False) -> Tuple[Callable, Any]:
    """Build the microbatched pipeline step.

    Returns ``(step_fn, staged_specs)`` where
    ``step_fn(staged_params, batch) -> (loss, grads)`` runs the GPipe
    schedule over ``n_micro`` microbatches and ``staged_specs`` mirrors
    the staged parameter tree (feed to ``NamedSharding``/``jax.jit``).
    """
    if cfg.n_dense_layers:
        raise NotImplementedError(
            "pipeline step does not support leading dense layers yet: "
            "they are a stack of their own beside the staged layers")
    if cfg.family in ("vlm", "encdec") or cfg.block_pattern:
        # vlm needs the patch frontend prepended / sliced, encdec needs
        # the encoder + cross-attention path, and block_pattern stacks
        # keep their layers under 'macros'/'tail' — all diverge from the
        # token-only homogeneous schedule below and would train a
        # *different* objective silently.  Refuse rather than drift.
        kind = cfg.family if not cfg.block_pattern else "hybrid/ssm"
        raise NotImplementedError(
            f"pipeline step does not support '{kind}' configs yet: their "
            "compute path is outside the staged homogeneous layer stack")
    n_stages = n_pipeline_stages(mesh, n_stages)
    if cfg.n_layers % n_stages:
        raise ValueError(
            f"n_layers={cfg.n_layers} not divisible by n_stages={n_stages}")
    per = cfg.n_layers // n_stages
    windows = M.layer_windows(cfg)
    stage_windows = [np.asarray(windows[s * per:(s + 1) * per])
                     for s in range(n_stages)]
    specs = staged_pspecs(pspecs, n_stages)

    def run_stage(s: int, stage_params, h_aux, pos):
        h, aux = h_aux
        h, a = M._dense_stack(stage_params, cfg, h, stage_windows[s], pos,
                              moe_impl, q_block, remat=remat)
        return h, aux + a

    def lm_loss(staged, h_aux, tokens):
        h, aux = h_aux
        logits = M.lm_head(staged, h, cfg)
        return M.token_ce_loss(logits, tokens, aux)

    def pipeline_loss(staged, batch):
        tokens = batch["tokens"]
        B, S = tokens.shape
        if B % n_micro:
            raise ValueError(f"batch {B} not divisible by n_micro={n_micro}")
        mb = B // n_micro
        micros = [tokens[m * mb:(m + 1) * mb] for m in range(n_micro)]
        pos = jnp.broadcast_to(jnp.arange(S)[None], (mb, S))

        # GPipe schedule: tick t runs stage s on microbatch m = t - s.
        # outs[s] is stage s's (activation, aux) from the previous tick;
        # stage s's input this tick is outs[s-1] (microbatch t-s).
        outs: list = [None] * n_stages
        losses = []
        for t in range(n_micro + n_stages - 1):
            new_outs: list = [None] * n_stages
            for s in range(n_stages):
                m = t - s
                if not 0 <= m < n_micro:
                    continue
                h_in = ((staged["embed"][micros[m]].astype(M.COMPUTE_DTYPE),
                         jnp.float32(0.0)) if s == 0 else outs[s - 1])
                new_outs[s] = run_stage(s, staged["stages"][s], h_in, pos)
                if s == n_stages - 1:
                    losses.append(lm_loss(staged, new_outs[s], micros[m]))
            outs = new_outs
        return sum(losses) / n_micro

    def step_fn(staged, batch):
        return jax.value_and_grad(pipeline_loss)(staged, batch)

    return step_fn, specs
