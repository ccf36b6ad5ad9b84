"""The comparison that decides ``correct``.

* Language model: once the window has closed and the program's state is
  freed, a sample of the finished window requests, drawn from the seed and
  holding the one with the longest prompt, is run through the plain
  float32 reference (``bench/reference.py``) over its prompt and its served
  tokens.  Two numbers are read: the widest gap by which a served token's
  logit lies below the reference's best logit at that position
  (``logit_gap``), and the share of served tokens that are not the
  reference's first choice (``token_mismatch``).  Greedy serving of a
  faithful model keeps both near rounding; a wrong token, a stale cache
  or a skipped layer opens them wide.  A cell compares the numbers its
  file gives a limit: where a bfloat16 router flip of the MoE can move one
  token as far as a lower precision does, the widest gap cannot tell the
  two apart and the share can.
* Gate: every window request's outcome (rejected or served) against a
  plain majority vote of the planted forest's trees.
* Every served window request has exactly ``output_tokens`` tokens, all in
  the vocabulary, and every window request ended (served or dropped).
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from .. import reference as R


def sample(outcomes: List, seed: int, n: int) -> List:
    """``n`` served window requests drawn from the seed, the one with the
    longest prompt among them."""
    done = sorted((o for o in outcomes if o.reason is None
                   and o.tokens is not None), key=lambda o: o.rid)
    if not done:
        return []
    longest = max(done, key=lambda o: (o.prompt_len, -o.rid))
    rest = [o for o in done if o is not longest]
    rng = np.random.default_rng([int(seed) % 2**64, 7])
    pick = rng.permutation(len(rest))[: max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


def logit_gaps(cfg: dict, params, prompt: np.ndarray, tokens: np.ndarray,
               control: bool = False) -> np.ndarray:
    """Per served token: reference best logit minus the reference logit
    of the token chosen.  The served tokens are chosen by the program;
    with ``control`` they are the fp8 reference's own greedy choice at
    each position of the same prompt and tokens."""
    L, T = len(prompt), len(tokens)
    seq = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
    where = np.arange(L - 1, L + T - 1, dtype=np.int32)
    ref = R.logits(cfg, params, seq, where, "f32")
    if control:
        chosen = R.logits(cfg, params, seq, where, "fp8").argmax(-1)
    else:
        chosen = np.asarray(tokens)
    return ref.max(-1) - ref[np.arange(T), chosen]


def readings(cfg: dict, params, sampled: List, requests: Dict,
             control: bool = False) -> Dict[str, float]:
    """``logit_gap`` and ``token_mismatch`` over the sampled requests
    (``requests`` maps a request id to its request, for the prompt).
    With ``control`` they read the tokens the fp8 reference puts first
    at the same positions.  With no request to compare, each reads as no
    limit admits."""
    gaps = [logit_gaps(cfg, params, requests[o.rid].prompt,
                       np.asarray(o.tokens), control) for o in sampled]
    g = np.concatenate(gaps) if gaps else np.array([1e30])
    return {"logit_gap": float(g.max()),
            "token_mismatch": float((g > 0).mean())}


def checks(cfg: dict, params, cell: dict, mix: dict, outcomes: List,
           requests: Dict, seed: int, control: bool = False,
           log=print) -> Dict[str, Dict]:
    """Every number compared for ``correct``, each with its limit: the
    readings the cell's file gives a limit, then the gate, the outputs
    and the missing requests of the window."""
    sampled = sample(outcomes, seed, int(cell["correct"]["sample"]))
    got = readings(cfg, params, sampled, requests, control)
    log(f"[bench] reference compared {len(sampled)} requests, "
        f"{sum(len(o.tokens) for o in sampled)} served tokens")
    out = {name: {"value": got[name], "limit": limit}
           for name, limit in cell["correct"]["limits"].items()}
    for name in got.keys() - out.keys():
        log(f"[bench] {name} {got[name]} (not compared in this cell)")
    out.update({
        "gate_mismatches": {"value": gate_mismatches(outcomes), "limit": 0},
        "bad_outputs": {"value": bad_outputs(
            outcomes, int(mix["output_tokens"]), cfg["vocab_size"]),
            "limit": 0},
        "missing": {"value": missing(outcomes), "limit": 0},
    })
    return out


def gate_mismatches(outcomes: List) -> int:
    return sum(1 for o in outcomes
               if (o.reason == "gate-reject") != o.reject_expected
               and o.end is not None)


def bad_outputs(outcomes: List, out_tokens: int, vocab: int) -> int:
    n = 0
    for o in outcomes:
        if o.reason is None and o.tokens is not None:
            t = np.asarray(o.tokens)
            if len(t) != out_tokens or (t < 0).any() or (t >= vocab).any():
                n += 1
    return n


def missing(outcomes: List) -> int:
    return sum(1 for o in outcomes if o.end is None)


def verdict(checks: Dict[str, Dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
