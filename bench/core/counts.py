"""Operations and bytes the algorithm needs, counted from shapes.

Counted from the work itself (valid positions, the context each query
attends, the experts each token is routed to), never from what a kernel
happens to move, so any implementation reads against the same work.

A request of prompt ``L`` and ``T`` output tokens passes through the fused
step as ``ceil(L / chunk)`` prefill steps (the last one also yields the
first token) and then ``T - 1`` decode steps.  Query position ``q``
attends ``q + 1`` keys (causal, itself included).  What a token costs in
the layers is the family's to count (``bench/core/models.py``): its
``linear_flops_per_token``, ``attn_flops`` and ``attn_bytes``.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

from . import models

BF16 = 2  # bytes of the compute and KV-cache type


def life_steps(prompt_len: int, out_tokens: int, chunk: int) -> List[Tuple]:
    """Per step of a request's life: (first query position, queries,
    logits taken)."""
    steps = []
    n_pf = -(-prompt_len // chunk)
    for j in range(n_pf):
        q0 = j * chunk
        n = min(chunk, prompt_len - q0)
        steps.append((q0, n, j == n_pf - 1))
    for t in range(out_tokens - 1):
        steps.append((prompt_len + t, 1, True))
    return steps


def attn_pairs(q0: int, n: int) -> int:
    """Query-key pairs of ``n`` causal queries from position ``q0``."""
    return n * q0 + n * (n + 1) // 2


def head_flops(d: Dict[str, int]) -> int:
    return 2 * d["D"] * d["V"]


class StepWork:
    """Work per global fused step, accumulated from request lives."""

    def __init__(self, d: Dict[str, int]):
        self.d = d
        self.family = models.load(d)
        self.model_flops: Dict[int, float] = {}
        self.attn_flops: Dict[int, float] = {}
        self.attn_bytes: Dict[int, float] = {}

    def add_request(self, admit_step: int, prompt_len: int, out_tokens: int,
                    chunk: int) -> None:
        d, fam = self.d, self.family
        lin = fam.linear_flops_per_token(d)
        for j, (q0, n, logits) in enumerate(
                life_steps(prompt_len, out_tokens, chunk)):
            s = admit_step + j
            pairs = attn_pairs(q0, n)
            af = fam.attn_flops(d, pairs)
            mf = n * lin + af + (head_flops(d) if logits else 0)
            self.model_flops[s] = self.model_flops.get(s, 0.0) + mf
            self.attn_flops[s] = self.attn_flops.get(s, 0.0) + af
            self.attn_bytes[s] = self.attn_bytes.get(s, 0.0) + fam.attn_bytes(
                d, q0 + n, n)

    def totals(self, lo: int, hi: int) -> Dict[str, float]:
        """Sums over global steps ``lo <= s < hi``, and the attention
        roofline time summed step by step at the given peaks."""
        steps = range(lo, hi)
        return dict(
            model_flops=sum(self.model_flops.get(s, 0.0) for s in steps),
            attn_flops=sum(self.attn_flops.get(s, 0.0) for s in steps),
            attn_bytes=sum(self.attn_bytes.get(s, 0.0) for s in steps),
        )

    def attn_roofline_s(self, lo: int, hi: int, peak_flops: float,
                        peak_bw: float) -> Tuple[float, int]:
        """Least time the chip needs for the attention of steps
        ``lo..hi-1`` (each step bound by the larger of its compute and
        memory times), and how many steps were memory-bound."""
        t, mem = 0.0, 0
        for s in range(lo, hi):
            tf = self.attn_flops.get(s, 0.0) / peak_flops
            tb = self.attn_bytes.get(s, 0.0) / peak_bw
            t += max(tf, tb)
            mem += tb >= tf and tb > 0
        return t, mem
