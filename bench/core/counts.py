"""Operations and bytes the algorithm needs, counted from shapes.

Counted from the work itself (valid positions, the context each query
attends, the experts each token is routed to), never from what a kernel
happens to move, so any implementation reads against the same work.

A request of prompt ``L`` and ``T`` output tokens passes through the fused
step as ``ceil(L / chunk)`` prefill steps (the last one also yields the
first token) and then ``T - 1`` decode steps.  Query position ``q``
attends ``q + 1`` keys (causal, itself included).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

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


def linear_flops_per_token(d: Dict[str, int]) -> int:
    """Matmul FLOPs of one token through every layer, attention scores
    excluded, LM head excluded (active experts only)."""
    D, H, KV, hd = d["D"], d["H"], d["KV"], d["hd"]
    proj = 2 * D * (H + 2 * KV) * hd + 2 * H * hd * D
    if "E" in d:
        ffn = 2 * D * d["E"] + d["k"] * 6 * D * d["F"] + 6 * D * d["Fs"]
    else:
        ffn = 6 * D * d["F"]
    return d["L"] * (proj + ffn)


def attn_flops(d: Dict[str, int], pairs: int) -> int:
    """QK^T and PV over ``pairs`` query-key pairs, every layer."""
    return d["L"] * 4 * d["H"] * d["hd"] * pairs


def attn_bytes(d: Dict[str, int], ctx: int, n: int) -> int:
    """HBM bytes one slot's attention needs at one step, every layer:
    its ``ctx`` cached keys and values read once, its ``n`` queries read
    and outputs written."""
    kv = 2 * ctx * d["KV"] * d["hd"] * BF16
    qo = 2 * n * d["H"] * d["hd"] * BF16
    return d["L"] * (kv + qo)


def head_flops(d: Dict[str, int]) -> int:
    return 2 * d["D"] * d["V"]


class StepWork:
    """Work per global fused step, accumulated from request lives."""

    def __init__(self, d: Dict[str, int]):
        self.d = d
        self.model_flops: Dict[int, float] = {}
        self.attn_flops: Dict[int, float] = {}
        self.attn_bytes: Dict[int, float] = {}

    def add_request(self, admit_step: int, prompt_len: int, out_tokens: int,
                    chunk: int) -> None:
        d = self.d
        lin = linear_flops_per_token(d)
        for j, (q0, n, logits) in enumerate(
                life_steps(prompt_len, out_tokens, chunk)):
            s = admit_step + j
            pairs = attn_pairs(q0, n)
            af = attn_flops(d, pairs)
            mf = n * lin + af + (head_flops(d) if logits else 0)
            self.model_flops[s] = self.model_flops.get(s, 0.0) + mf
            self.attn_flops[s] = self.attn_flops.get(s, 0.0) + af
            self.attn_bytes[s] = self.attn_bytes.get(s, 0.0) + attn_bytes(
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
