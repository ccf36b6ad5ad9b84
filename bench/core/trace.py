"""Reduce a profiler trace (``.xplane.pb``) to intervals and sums.

Read with ``jax.profiler.ProfileData`` and nothing else.  Device planes
are named ``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds one
event per operation that ran (kernels included) and ``XLA Modules`` one per
compiled program execution.  The driver's host spans (``bench.*``
``TraceAnnotation`` names) lie on the host plane, on the same clock.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]  # seconds, trace clock


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


class Trace:
    """Device operations, program executions and host spans of a trace."""

    def __init__(self, ops: Dict[int, List[tuple]],
                 modules: Dict[int, List[tuple]],
                 spans: List[tuple]):
        self.ops = ops          # device -> [(name, start_s, end_s)]
        self.modules = modules  # device -> [(name, start_s, end_s)]
        self.spans = spans      # [(name, start_s, end_s)]

    @classmethod
    def load(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        ops: Dict[int, List[tuple]] = {}
        modules: Dict[int, List[tuple]] = {}
        spans: List[tuple] = []
        for plane in pd.planes:
            name = plane.name
            if name.startswith("/device:TPU:"):
                dev = int(name.rsplit(":", 1)[1])
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        ops[dev] = _events(line)
                    elif line.name == "XLA Modules":
                        modules[dev] = _events(line)
            elif name.startswith("/host:"):
                for line in plane.lines:
                    spans += [e for e in _events(line)
                              if e[0].startswith("bench.")]
        return cls(ops, modules, sorted(spans, key=lambda e: e[1]))

    def window(self, first: int = 0,
               calls: Optional[int] = None) -> Optional[Interval]:
        """From the start of the ``first``-th ``bench.run`` span (0-based)
        to the end of the ``calls``-th after it (default: the last)."""
        runs = [s for s in self.spans if s[0] == "bench.run"][first:]
        if calls is not None:
            runs = runs[:calls]
        if not runs:
            return None
        return runs[0][1], max(s[2] for s in runs)

    def busy(self, dev: int, win: Interval) -> float:
        return union_length([(a, b) for _, a, b in self.ops.get(dev, [])],
                            win)

    def op_time(self, dev: int, win: Interval, base: str) -> float:
        """Device seconds of the operations named ``base`` (``base.N``),
        clipped to the window (overlaps merged)."""
        return union_length([(a, b) for n, a, b in self.ops.get(dev, [])
                             if op_base(n) == base], win)

    def idle_gaps(self, dev: int, win: Interval) -> List[Interval]:
        return gaps([(a, b) for _, a, b in self.ops.get(dev, [])], win)


def _events(line) -> List[tuple]:
    return [(short_name(e.name), e.start_ns * 1e-9,
             (e.start_ns + e.duration_ns) * 1e-9) for e in line.events]


def short_name(name: str) -> str:
    """``%copy.106 = bf16[28,4352]{...} copy(...)`` -> ``copy.106 =
    bf16[28,4352]``: the operation and the shape of its result (TPU op
    events are named by their whole HLO text)."""
    name = name.lstrip("%")
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    return f"{head} = {rest.split('{', 1)[0].split(' ', 1)[0]}"


def op_base(name: str) -> str:
    """``copy.106 = bf16[...]`` -> ``copy``."""
    return name.split(" = ", 1)[0].rsplit(".", 1)[0]


def self_times(events: List[tuple], win: Interval) -> Dict[str, float]:
    """Seconds per operation name inside the window, less the time of the
    operations nested in it (a ``while`` holds its whole body)."""
    lo, hi = win
    out: Dict[str, float] = {}
    stack: List[list] = []  # [name, start, end, child seconds]

    def close(ent):
        a, b = max(ent[1], lo), min(ent[2], hi)
        own = max(0.0, (b - a) - ent[3])
        if b > a:
            out[ent[0]] = out.get(ent[0], 0.0) + own
        if stack:
            stack[-1][3] += max(0.0, b - a)

    for n, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= a:
            close(stack.pop())
        stack.append([n, a, b, 0.0])
    while stack:
        close(stack.pop())
    return out


def merge(iv: List[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def union_length(iv: List[Interval], win: Interval) -> float:
    lo, hi = win
    return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in merge(iv))


def gaps(iv: List[Interval], win: Interval) -> List[Interval]:
    """Stretches of the window that no interval covers."""
    lo, hi = win
    out, t = [], lo
    for a, b in merge(iv):
        if b <= lo:
            continue
        if a >= hi:
            break
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def attribute(gs: List[Interval], spans: List[tuple]) -> Dict[str, float]:
    """Seconds of the gaps ``gs`` covered by each host span name (the
    innermost wins where spans nest); the rest is ``unattributed``."""
    out: Dict[str, float] = {}
    for a, b in gs:
        covered = 0.0
        for name, s, e in spans:
            ov = min(b, e) - max(a, s)
            if ov > 0:
                out[name] = out.get(name, 0.0) + ov
                covered += ov
        if b - a > covered:
            out["unattributed"] = out.get("unattributed", 0.0) + (
                b - a - covered)
    return out
