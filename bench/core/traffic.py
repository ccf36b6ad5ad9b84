"""The one traffic generator: a mix file's parameters -> a request list.

Every seed gets the same work.  Lengths and inter-arrival gaps are the
quantiles of their distributions at evenly spaced probabilities; the share
of requests the gate rejects is fixed the same way.  The arrival times are
the same for every seed (one fixed order of the gaps); the seed deals the
lengths and verdicts onto them in its own order, and draws the token ids
and the flow rows that carry the features.  So two seeds differ in
arrangement and data, not in the amount of prefill, decode or gate work
or in when the bursts come, and runs of different seeds spread no wider
than runs of one seed need to.

A mix file (``bench/traffic/<name>.json``) holds:

``arrivals``      ``"open"`` (Poisson schedule at ``rate_rps``, due times
                  fixed in advance) or ``"closed"`` (``outstanding``
                  requests in flight; each completion sends the next).
``prompt``        ``{"dist": "lognormal", "median", "sigma", "min", "max"}``
                  or ``{"dist": "uniform", "min", "max"}``, in tokens.
``output_tokens`` tokens every admitted request decodes.
``pre_window_s``  seconds of the same traffic served before the window
                  opens (set-up), so the window starts in steady state.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List, Optional

import numpy as np

# traffic after the window keeps the load on while late requests finish
POST_WINDOW_S = 60.0
# closed loops: requests generated per window second (more than any
# cell completes; the tail is never sent)
CLOSED_PER_S = 64
# requests per stratum of lengths and gate verdicts
STRATUM = 32
# the stream that orders the inter-arrival gaps, the same for every seed
ARRIVALS = 0x4A7


@dataclasses.dataclass
class Request:
    rid: int
    phase: str            # "pre" | "win" | "post"; "seq" = closed loop
    due: Optional[float]  # seconds from window open; None = closed loop
    prompt: np.ndarray    # int32 token ids
    feat: np.ndarray      # gate features (one flow row)
    reject: bool          # the reference gate's verdict: dropped


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def prompt_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` prompt lengths at evenly spaced quantiles, ascending."""
    u = _quantiles(n)
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        nd = NormalDist()
        z = np.array([nd.inv_cdf(float(p)) for p in u])
        x = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    elif spec["dist"] == "uniform":
        x = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown prompt distribution {spec['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def poisson_gaps(n: int, rate: float) -> np.ndarray:
    """``n`` exponential gaps at evenly spaced quantiles, scaled so that
    they sum to exactly ``n / rate`` seconds."""
    g = -np.log1p(-_quantiles(n))
    return g / g.mean() / rate


def _stratum(prompt: dict, n: int, share: float):
    """``n`` lengths and gate verdicts: lengths stratified within the
    kept and within the rejected requests, so the admitted work is the
    same whatever order a seed deals them in."""
    n_rej = int(round(n * share))
    lens = np.concatenate([prompt_lengths(prompt, n - n_rej),
                           prompt_lengths(prompt, n_rej)])
    return lens, np.arange(n) >= n - n_rej


def _phase_count(mix: dict, seconds: float) -> int:
    if mix["arrivals"] == "open":
        return int(round(float(mix["rate_rps"]) * seconds))
    return int(math.ceil(CLOSED_PER_S * seconds))


def generate(mix: dict, seed: int, window_s: float, vocab: int,
             flows: np.ndarray, flow_reject: np.ndarray) -> List[Request]:
    """The requests of one run, in the order they are sent.

    ``flows`` are candidate gate feature rows and ``flow_reject`` the
    reference gate's verdict on each; a fixed share of the requests
    (that of the rows) carries a rejected row.  Pre-window, window and
    post-window requests are dealt from separate streams of ``seed``, so
    the window's requests do not depend on the pre-window length.
    """
    share = float(np.mean(flow_reject))
    rej_rows = np.flatnonzero(flow_reject)
    keep_rows = np.flatnonzero(~flow_reject)
    out: List[Request] = []
    pre = float(mix.get("pre_window_s", 0.0))
    if mix["arrivals"] == "open":
        phases = [("pre", pre), ("win", float(window_s)),
                  ("post", POST_WINDOW_S)]
    else:
        # one sequence: the driver sends it in order and dates each
        # request by when it went out
        phases = [("seq", pre + float(window_s))]
    t0 = -phases[0][1]
    for k, (phase, secs) in enumerate(phases):
        n = _phase_count(mix, secs)
        if n == 0:
            continue
        rng = np.random.default_rng([int(seed) % 2**64, k])
        # an open phase is one stratum of all its requests; a closed
        # loop consumes a prefix of its sequence, so every STRATUM
        # consecutive requests are one
        size = n if mix["arrivals"] == "open" else STRATUM
        lens, rej = _stratum(mix["prompt"], size, share)
        order = np.concatenate([rng.permutation(size)
                                for _ in range(-(-n // size))])[:n]
        lens, rej = lens[order], rej[order]
        rows = np.where(rej, rng.choice(rej_rows, n),
                        rng.choice(keep_rows, n))
        if mix["arrivals"] == "open":
            # one fixed order of the gaps for every seed: where the bursts
            # fall sets the tail, and a tail that moved with the seed
            # would spread the runs of a cell
            gaps = np.random.default_rng([ARRIVALS, k]).permutation(
                poisson_gaps(n, float(mix["rate_rps"])))
            due = t0 + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        else:
            due = [None] * n
        for i in range(n):
            out.append(Request(
                rid=len(out), phase=phase,
                due=None if due[i] is None else float(due[i]),
                prompt=rng.integers(0, vocab, int(lens[i]), dtype=np.int32),
                feat=np.asarray(flows[rows[i]]), reject=bool(rej[i])))
        t0 += secs
    return out
