"""The program's own marks in a profiler trace: named scopes and spans.

The program names the layers of its fused serve step with
``jax.named_scope`` (``gate``, ``kv``, ``attention``, ``mlp`` or ``moe``,
``lm_head``, ``sample``) and the phases of each ``run()`` call with
host spans (``serve.admit``, ``.build``, ``.upload``, ``.launch``,
``.sync``, ``.drain``).  The profiler keeps each device operation's JAX
name stack as the ``tf_op`` stat of its event metadata, which
``jax.profiler.ProfileData`` does not expose; this module reads it from
the ``.xplane.pb`` with the protobuf wire format alone (no tensorflow),
and the host spans with ``ProfileData``.

An operation's scope is the innermost of those names on its stack; an
operation with none (the copies XLA adds for a loop's carry, the layer
scan's slicing, projections and norms, the step's scheduler) is
``unscoped``.  A trace of a program without the marks reads as nothing,
and the readers built on this module then return None.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

from .readers import share
from .trace import Interval, find_xplane, merge, short_name

SCOPES = ("gate", "kv", "attention", "mlp", "moe", "lm_head", "sample")
UNSCOPED = "unscoped"
SPAN_PREFIX = "serve."
OUT_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), ".bench_out")


# ------------------------------------------------------------ wire format
def _varint(b, i: int) -> Tuple[int, int]:
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        if c < 0x80:
            return r, i
        s += 7


def _fields(b, i: int = 0, end: Optional[int] = None):
    """(field number, value) of a message: ints for varints, memoryview
    slices for length-delimited fields (fixed-width ones are skipped)."""
    end = len(b) if end is None else end
    while i < end:
        key, i = _varint(b, i)
        f, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(b, i)
            yield f, v
        elif wt == 2:
            n, i = _varint(b, i)
            yield f, b[i:i + n]
            i += n
        elif wt == 1:
            i += 8
        elif wt == 5:
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wt}")


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _events(line, ts_ns: int, names: Dict[int, str],
            stacks: Dict[int, str],
            win: Optional[Interval] = None) -> List[tuple]:
    """(short name, start s, end s, name stack) of each event of an
    ``XLine`` (only those overlapping ``win``, if given); times as
    ``ProfileData`` gives them (whole ns)."""
    lo, hi = win if win is not None else (float("-inf"), float("inf"))
    out = []
    for f, ev in _fields(line):
        if f != 4:
            continue
        mid = off = dur = 0
        for g, v in _fields(ev):
            if g == 1:
                mid = v
            elif g == 2:
                off = v
            elif g == 3:
                dur = v
        a = ts_ns + off // 1000
        t0, t1 = a * 1e-9, (a + dur // 1000) * 1e-9
        if t1 > lo and t0 < hi:
            out.append((names.get(mid, ""), t0, t1, stacks.get(mid, "")))
    return out


def read_device_ops(path: str, win: Optional[Interval] = None
                    ) -> Dict[int, List[tuple]]:
    """device -> [(short name, start s, end s, name stack)] of the ``XLA
    Ops`` line of each ``/device:TPU:<n>`` plane (only the operations
    overlapping ``win``, if given).  The name stack is the ``tf_op``
    stat of the event's metadata less its ``:type`` suffix (empty where
    the profiler stored none)."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out: Dict[int, List[tuple]] = {}
    for f, plane in _fields(buf):
        if f != 1:  # XSpace.planes
            continue
        name, lines, emeta, smeta = "", [], [], {}
        for g, v in _fields(plane):
            if g == 2:
                name = _text(v)
                if not name.startswith("/device:TPU:"):
                    break
            elif g == 3:
                lines.append(v)
            elif g == 4:
                emeta.append(v)
            elif g == 5:  # map<int64, XStatMetadata>
                for h, ent in _fields(v):
                    if h == 2:
                        d = dict(_fields(ent))
                        smeta[d.get(1, 0)] = _text(d.get(2, b""))
        if not name.startswith("/device:TPU:"):
            continue
        tf_op = {k for k, v in smeta.items() if v == "tf_op"}
        names: Dict[int, str] = {}
        stacks: Dict[int, str] = {}
        for ent in emeta:  # map<int64, XEventMetadata>
            md = None
            for h, v in _fields(ent):
                if h == 2:
                    md = v
            if md is None:
                continue
            mid, text, stack = 0, "", ""
            for h, v in _fields(md):
                if h == 1:
                    mid = v
                elif h == 2:
                    text = short_name(_text(v))
                elif h == 5:  # XStat
                    st = dict(_fields(v))
                    if st.get(1) in tf_op:
                        if 5 in st:
                            stack = _text(st[5])
                        elif 7 in st:
                            stack = smeta.get(st[7], "")
            names[mid] = text
            stacks[mid] = stack.rsplit(":", 1)[0] if ":" in stack else stack
        dev = int(name.rsplit(":", 1)[1])
        for line in lines:
            lname, ts = "", 0
            for h, v in _fields(line):
                if h == 2:
                    lname = _text(v)
                elif h == 3:
                    ts = v
            if lname == "XLA Ops":
                out[dev] = _events(line, ts, names, stacks, win)
    return out


def read_spans(path: str, prefix: str = SPAN_PREFIX) -> List[tuple]:
    """[(name, start s, end s)] of the host spans named ``prefix...``,
    sorted by start."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns * 1e-9,
                           (e.start_ns + e.duration_ns) * 1e-9)
                          for e in line.events if e.name.startswith(prefix)]
    return sorted(spans, key=lambda e: e[1])


# ------------------------------------------------------------ attribution
def scope_of(stack: str) -> str:
    """The innermost of ``SCOPES`` on a name stack, else ``unscoped``."""
    for part in reversed(stack.split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def _innermost(items: List[tuple], win: Interval) -> Dict[str, float]:
    """Seconds of the window covered by each label, where
    ``items = [(label, start, end)]``: a stretch covered by several goes
    to the one that began last (the innermost where they nest).  The
    sum is the length of the items' union in the window."""
    lo, hi = win
    pts = []
    for k, (_, a, b) in enumerate(items):
        a, b = max(a, lo), min(b, hi)
        if b > a:
            pts.append((a, 1, k))
            pts.append((b, 0, k))
    pts.sort()
    out: Dict[str, float] = {}
    live: Dict[int, float] = {}
    t = None
    for x, opening, k in pts:
        if live and t is not None and x > t:
            top = max(live, key=lambda j: (live[j], -items[j][2]))
            name = items[top][0]
            out[name] = out.get(name, 0.0) + (x - t)
        t = x
        if opening:
            live[k] = items[k][1]
        else:
            live.pop(k, None)
    return out


def scope_times(ops: List[tuple], win: Interval) -> Dict[str, float]:
    """Device seconds by scope in the window: each moment the device is
    busy goes to the innermost operation running (a ``while`` holds its
    body), and that operation's scope.  The values sum to the busy
    time."""
    return _innermost([(scope_of(o[3]), o[1], o[2]) for o in ops], win)


def attribute_innermost(gs: List[Interval],
                        spans: List[tuple]) -> Dict[str, float]:
    """Seconds of the gaps ``gs`` by the innermost host span covering
    them (spans nest: ``serve.*`` inside ``bench.run``); the rest is
    ``unattributed``."""
    out: Dict[str, float] = {}
    for a, b in merge(gs):
        inside = [s for s in spans if s[1] < b and s[2] > a]
        got = _innermost(inside, (a, b))
        for k, v in got.items():
            out[k] = out.get(k, 0.0) + v
        rest = (b - a) - sum(got.values())
        if rest > 0:
            out["unattributed"] = out.get("unattributed", 0.0) + rest
    return out


# ------------------------------------------------------------ per run
class Marks:
    """The scoped device operations and the program spans of a trace."""

    def __init__(self, ops: Dict[int, List[tuple]], spans: List[tuple]):
        self.ops = ops      # device -> [(name, start, end, name stack)]
        self.spans = spans  # [(serve.* name, start, end)]
        self.logged = set()  # the splits already logged for this run

    @classmethod
    def load(cls, path: str, win: Optional[Interval] = None) -> "Marks":
        return cls(read_device_ops(path, win), read_spans(path))

    def scoped(self, dev: int, win: Interval) -> bool:
        """Whether any operation in the window carries a scope."""
        lo, hi = win
        return any(b > lo and a < hi and scope_of(s) != UNSCOPED
                   for _, a, b, s in self.ops.get(dev, []))


def latest_xplane(root: str = OUT_DIR) -> Optional[str]:
    """The newest ``.xplane.pb`` under the benchmark's trace directory:
    the run's own, as a run traces into a fresh directory of it and the
    readers run before it is removed."""
    dirs = glob.glob(os.path.join(root, "trace", "*"))
    paths = [p for p in (find_xplane(d) for d in dirs) if p]
    return max(paths, key=os.path.getmtime) if paths else None


def for_run(ctx) -> Optional[Marks]:
    """The marks of the trace the run's ``ctx["trace"]`` was read from
    (``ctx["xplane"]``, else the newest under the benchmark's trace
    directory), read once per run and kept in ``ctx``; None if there is
    none, or if its device operations do not agree with ``ctx["trace"]``
    in the window."""
    if "marks" in ctx:
        return ctx["marks"]
    ctx["marks"] = None
    path = ctx.get("xplane") or latest_xplane()
    if path is None:
        return None
    lo, hi = ctx["window"]
    dev = ctx["dev"]
    m = Marks.load(path, (lo, hi))
    mine = [(n, a, b) for n, a, b, _ in m.ops.get(dev, [])
            if b > lo and a < hi]
    theirs = [o for o in ctx["trace"].ops.get(dev, []) if o[2] > lo
              and o[1] < hi]
    if sorted(mine) != sorted(theirs):
        ctx["log"](f"[bench] scopes: {path} does not hold the run's "
                   f"device operations ({len(mine)} against "
                   f"{len(theirs)}); not read")
        return None
    ctx["marks"] = m
    return m


# ------------------------------------------------------------ readers
def _logged(m: Marks, what: str) -> bool:
    if what in m.logged:
        return True
    m.logged.add(what)
    return False


def scope_share(ctx, names: Tuple[str, ...], what: str) -> Optional[float]:
    """Device time under the scopes ``names`` over busy time, in
    percent; None where the trace holds no scoped operation."""
    m = for_run(ctx)
    dev, win = ctx["dev"], ctx["window"]
    if m is None or ctx["busy"] <= 0 or not m.scoped(dev, win):
        return None
    t = scope_times(m.ops.get(dev, []), win)
    if not _logged(m, "scopes"):
        split = ", ".join(f"{k} {100 * v / ctx['busy']:.2f}%"
                          for k, v in sorted(t.items(), key=lambda kv: -kv[1]))
        ctx["log"](f"[bench] device time by scope (of busy): {split}")
    v = sum(t.get(n, 0.0) for n in names)
    if v <= 0:
        return None
    return share(v, ctx["busy"], what)


# the phases of a run() call that rebuild the wave before the launch
WAVE_SPANS = ("serve.admit", "serve.build", "serve.upload")


def wave_gap_ms(ctx) -> Optional[float]:
    """Device-idle time inside ``serve.admit``, ``.build`` and
    ``.upload`` (each gap to the innermost host span covering it), per
    traced run() call, in ms; None where the trace holds no ``serve.*``
    span."""
    m = for_run(ctx)
    dev, win = ctx["dev"], ctx["window"]
    lo, hi = win
    spans = [s for s in ctx["trace"].spans + m.spans
             if s[2] > lo and s[1] < hi] if m is not None else []
    calls = sum(1 for s in spans if s[0] == "bench.run" and s[1] >= lo)
    if (not calls or ctx["busy"] <= 0
            or not any(s[0].startswith(SPAN_PREFIX) for s in spans)):
        return None
    att = attribute_innermost(ctx["trace"].idle_gaps(dev, win), spans)
    if not _logged(m, "spans"):
        split = ", ".join(f"{k} {1e3 * v / calls:.3f}"
                          for k, v in sorted(att.items(),
                                             key=lambda kv: -kv[1]))
        ctx["log"](f"[bench] device idle by innermost host span, ms per "
                   f"run() call over {calls}: {split}")
    return 1e3 * sum(att.get(n, 0.0) for n in WAVE_SPANS) / calls
