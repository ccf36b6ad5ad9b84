"""Drive the system under test: build it, warm it up, serve one window.

The system is the program's gated serve path: ``ServeEngine`` with the
Planter gate (a random forest of size S planted on the ``unsw`` flows, as
``python -m repro.launch.serve`` plants it) feeding a
``DeviceContinuousBatcher`` over the paged KV pool, attention backend
``auto`` and greedy sampling.  The program takes submissions only between
``run()`` calls, so the driver alternates: submit what is due, then
``run(max_steps=sync_every)``.  Each request is timed from when it was due
to the drain stamp the batcher records when its last token reaches the
host (``done_at``) or it is dropped (``dropped_at``).

The fused step is compiled once per bucket of (queue length, output rows,
longest prompt), and the admission gate once per count of waiting
requests.  Set-up reaches every bucket the cell's traffic can reach with a
walk of one-step ``run()`` calls (each leaves the pool empty again: its
requests carry a deadline that a jump of the driver's clock expires), then
makes the gate launches at every count the backlog can reach, then serves
``pre_window_s`` of the cell's own traffic.  Compilations inside the
window are counted all the same.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import sys
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from . import models
from . import traffic as T

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SRC = os.path.join(REPO, "src")

# warm-up requests expire after this long on the driver's clock; the
# clock then jumps past it
WARM_DEADLINE_S = 1.0e5
# trace: how long after the window opens the calls that the per-layer
# metrics read begin, and how many run() calls they read
TRACE_DELAY_S = 2.0
TRACE_CALLS = 24


def import_program():
    """The program's modules (``src/`` of the checkout)."""
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    from repro.configs import get_config, get_smoke_config
    from repro.core import PlanterConfig, plant
    from repro.data import load_dataset
    from repro.serve import engine as E
    return dict(get_config=get_config, get_smoke_config=get_smoke_config,
                PlanterConfig=PlanterConfig, plant=plant,
                load_dataset=load_dataset, E=E)


class Clock:
    """``perf_counter`` plus an offset that only the warm-up walk moves."""

    def __init__(self):
        self.offset = 0.0

    def __call__(self) -> float:
        return time.perf_counter() + self.offset


class CompileCounter:
    """Counts JAX's compilations (each backend compile or persistent-cache
    load of a program) from its monitoring events, with their names."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0
        self.seconds = 0.0
        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, seconds, **kw):
        if event == self.EVENT:
            self.n += 1
            self.seconds += seconds
            self.names.append(str(kw.get("fun_name", "?")))


def gate_forest_predict(trees, X: np.ndarray) -> np.ndarray:
    """Plain majority vote of the forest's trees (the user's model that
    the program plants into tables), ties to the lower class."""
    X = np.asarray(X, np.int64)
    votes = []
    for t in trees:
        node = np.zeros(len(X), np.int64)
        for _ in range(int(t["depth"]) + 1):
            f = t["feature"][node]
            inner = f >= 0
            left = X[np.arange(len(X)), np.maximum(f, 0)] <= t["threshold"][node]
            node = np.where(inner, np.where(left, t["left"][node],
                                            t["right"][node]), node)
        votes.append(t["value"][node].argmax(axis=1))
    votes = np.stack(votes, 1)
    n_cls = max(2, int(votes.max()) + 1)
    counts = np.stack([(votes == c).sum(1) for c in range(n_cls)], 1)
    return counts.argmax(1)


@dataclasses.dataclass
class Outcome:
    rid: int
    phase: str
    due: float           # absolute, driver clock
    prompt_len: int
    reject_expected: bool
    end: Optional[float] = None     # done_at / dropped_at
    reason: Optional[str] = None    # None = done; else drop reason
    tokens: Optional[np.ndarray] = None
    call_done: Optional[int] = None  # index of the run() call it drained in


@dataclasses.dataclass
class RunRecord:
    outcomes: Dict[int, Outcome]
    requests: Dict[int, "T.Request"]
    window: tuple          # (open, close), driver clock
    calls: List[tuple]     # (start, end) of each run() call served
    compiles_in_window: int
    compile_s_in_window: float
    lag: List[float]       # sent - due, open loop, window requests
    setup_s: float
    memory_peak_bytes: int
    trace_calls: tuple     # (first, last) call index traced
    steps_per_call: int
    backlog: List[tuple]   # (time, waiting in the batcher's queue) per call


class System:
    """The program's serve path for one cell, with bench-made weights."""

    def __init__(self, prog, cfg_file: dict, cell: dict, mix: dict,
                 params, clock: Clock):
        E = prog["E"]
        b = cell["batcher"]
        arch = program_arch(prog, cfg_file)
        ds = prog["load_dataset"]("unsw", n=4000)
        res = prog["plant"](prog["PlanterConfig"](model="rf", size="S"),
                            ds.X_train, ds.y_train, ds.X_test)
        self.gate = res.mapped
        self.trees = [dict(feature=t.tree_.feature, threshold=t.tree_.threshold,
                           left=t.tree_.left, right=t.tree_.right,
                           value=t.tree_.value, depth=t.tree_.max_depth)
                      for t in res.trained.estimators_]
        self.flows = np.asarray(ds.X_test)
        self.flow_reject = gate_forest_predict(self.trees, self.flows) == 1
        page = int(b["page_size"])
        out = int(mix["output_tokens"])
        cache_len = -(-(int(mix["prompt"]["max"]) + out) // page) * page
        if cache_len > int(cfg_file.get("max_position_embeddings",
                                        cache_len)):
            raise SystemExit(f"the mix needs {cache_len} positions a slot; "
                             f"the configuration runs at most "
                             f"{cfg_file['max_position_embeddings']}")
        self.scfg = E.ServeConfig(max_batch=int(b["max_batch"]),
                                  cache_len=cache_len, page_size=page,
                                  attn_impl="auto")
        self.arch = arch
        self.params = params
        self.engine = E.ServeEngine(arch, params, self.scfg, gate=self.gate,
                                    gate_backend="auto")
        self.batcher = E.DeviceContinuousBatcher(
            self.engine, eos_token=-1, max_tokens=out,
            sync_every=int(b["sync_every"]),
            prefill_chunk=int(b["prefill_chunk"]), clock=clock)
        self.clock = clock

    def free(self):
        """Drop every device buffer the program holds."""
        self.batcher = None
        self.engine = None
        self.params = None
        gc.collect()


def program_arch(prog, cfg: dict):
    """The program's config for a config file.  The program's own preset
    gives the architecture; the depth, and the published values of the
    keys the program states as settings, come from the file (the family's
    ``program_settings``), so that the program computes the published
    function wherever it can."""
    get = prog["get_smoke_config" if cfg.get("program_smoke")
               else "get_config"]
    arch = dataclasses.replace(get(cfg["program_arch"]),
                               **models.load(cfg).program_settings(cfg))
    check_arch(arch, cfg)
    return arch


def check_arch(arch, cfg: dict) -> None:
    """The program's config must run the widths and depth the config
    file states (the family's ``program_widths``)."""
    want = models.load(cfg).program_widths(cfg)
    got = {k: getattr(arch, k) for k in want}
    bad = {k: (got[k], v) for k, v in want.items() if got[k] != v}
    if bad:
        raise SystemExit(f"program config differs from the config file "
                         f"(program, file): {bad}")


# ------------------------------------------------------------- warm-up
def _bucket8(n: int) -> int:
    return max(8, 1 << (max(1, n) - 1).bit_length())


def _bucket_p(n: int) -> int:
    return max(4, 1 << (n - 1).bit_length())


def warm_targets(mix: dict, cell: dict) -> List[tuple]:
    """(carried slots, new requests, prompt length) for one run() call
    per fused-step bucket that the cell's traffic reaches.

    The cell file's ``warm`` bounds what it reaches: at most
    ``waiting_max`` requests waiting, between ``in_system_min`` and
    ``in_system_max`` (default: slots + waiting_max) in flight and
    waiting together, and a longest prompt of at least
    ``longest_prompt_min`` tokens among them."""
    w = cell["warm"]
    B = int(cell["batcher"]["max_batch"])
    lo, hi = int(mix["prompt"]["min"]), int(mix["prompt"]["max"])
    lo = max(lo, int(w.get("longest_prompt_min", lo)))
    # one prompt length per bucket of the longest prompt in a wave
    plens = sorted({min(hi, _bucket_p(x)) for x in range(lo, hi + 1)})
    n_max = int(w["waiting_max"])
    s_min = int(w.get("in_system_min", 1))
    s_max = int(w.get("in_system_max", B + n_max))
    seen, pairs = set(), []
    for c in range(B + 1):
        for n in range(n_max + 1):
            if not s_min <= c + n <= s_max or c + n == 0:
                continue
            key = (_bucket8(n), _bucket8(c + n))
            if key not in seen:
                seen.add(key)
                pairs.append((c, n))
    return [(c, n, p) for p in plens for c, n in pairs]


def gate_counts(cell: dict) -> range:
    """Counts of waiting requests the admission gate is launched at."""
    w = cell["warm"]
    B = int(cell["batcher"]["max_batch"])
    lo = max(1, int(w.get("in_system_min", 1)) - B)
    return range(lo, int(w["waiting_max"]) + 1)


def warm_walk(sys_: System, targets: List[tuple], vocab: int) -> int:
    """One-step run() calls that visit every target bucket; returns the
    number of calls.  Each target leaves the pool empty again."""
    bt, clock = sys_.batcher, sys_.clock
    keep = sys_.flows[~sys_.flow_reject]
    rng = np.random.default_rng(0)
    calls = 0
    rid = 0

    def send(k, plen):
        nonlocal rid
        for _ in range(k):
            bt.submit(f"w{rid}", rng.integers(0, vocab, plen).tolist(),
                      features=keep[rid % len(keep)],
                      deadline_s=WARM_DEADLINE_S)
            rid += 1

    for c, n, plen in targets:
        if c:
            send(c, plen)
            bt.run(max_steps=1)
            calls += 1
        send(n, plen)
        bt.run(max_steps=1)
        clock.offset += 2 * WARM_DEADLINE_S  # expire every warm request
        bt.run(max_steps=1)
        calls += 2
    if bt.pending_work():
        raise RuntimeError("warm-up walk left work in the batcher")
    return calls


def warm_gate(sys_: System, counts: range) -> None:
    """The admission gate at every count of waiting requests the cell
    reaches (it compiles once per count)."""
    X = sys_.flows
    for k in counts:
        sys_.engine.admit(X[np.arange(k) % len(X)])


# ---------------------------------------------------------------- serve
def serve(sys_: System, mix: dict, cell: dict, reqs: List["T.Request"],
          window_s: float, t_process: float, compiles: CompileCounter,
          trace_dir: Optional[str] = None,
          log: Callable = print) -> RunRecord:
    """Warm up, serve the pre-window, the window and the late requests."""
    bt, clock = sys_.batcher, sys_.clock
    b = cell["batcher"]
    k_steps = int(b["sync_every"])
    vocab = sys_.arch.vocab_size
    open_loop = mix["arrivals"] == "open"

    t_w = time.perf_counter()
    targets = warm_targets(mix, cell)
    n_calls = warm_walk(sys_, targets, vocab)
    t_g = time.perf_counter()
    warm_gate(sys_, gate_counts(cell))
    jax.effects_barrier()
    log(f"[bench] warm-up: {len(targets)} step buckets in {n_calls} run() "
        f"calls, {t_g - t_w:.1f} s; gate counts {time.perf_counter() - t_g:.1f}"
        f" s; {compiles.n} compilations so far ({compiles.seconds:.1f} s)")

    if trace_dir:
        # the profiler starts before any traffic: its start-up can stall
        # the device for seconds, which set-up absorbs (a one-bucket walk
        # runs under it); the metrics read calls from inside the window
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host spans only: less overhead
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        warm_walk(sys_, targets[-1:], vocab)
    pre = float(mix.get("pre_window_s", 0.0))
    t_open = clock() + pre
    t_close = t_open + window_s
    byid = {r.rid: r for r in reqs}
    outcomes: Dict[int, Outcome] = {}
    live: Dict[int, Outcome] = {}
    calls: List[tuple] = []
    lag: List[float] = []
    backlog: List[tuple] = []
    nxt = 0
    n_out = int(mix.get("outstanding", 0))
    traced = (None, None)
    c0 = None

    serial = getattr(sys_, "serial", 0)
    sys_.serial = serial + 1

    def key(rid):  # ids stay unique across serve() calls on one system
        return f"{serial}:{rid}"

    def submit(r, due, now):
        o = Outcome(r.rid, r.phase, due, len(r.prompt), r.reject)
        if r.phase == "seq":
            o.phase = ("pre" if now < t_open else
                       "win" if now < t_close else "post")
        outcomes[r.rid] = o
        live[r.rid] = o
        bt.submit(key(r.rid), r.prompt, features=r.feat)
        if open_loop and o.phase == "win":
            lag.append(now - due)

    def collect(call_idx):
        for rid in list(live):
            o, k = live[rid], key(rid)
            if k in bt.done_at:
                o.end, o.tokens = bt.done_at[k], bt.done[k]
            elif k in bt.dropped_at:
                o.end, o.reason = bt.dropped_at[k], bt.drop_reasons[k]
            else:
                continue
            o.call_done = call_idx
            del live[rid]

    def window_done(now):
        # every request sent in the window is waited for (a closed loop
        # sends no more after the close)
        if now < t_close:
            return False
        return not any(o.phase == "win" for o in live.values())

    c1 = None
    while True:
        now = clock()
        if c0 is None and now >= t_open:
            c0 = (compiles.n, compiles.seconds)
            n_names = len(compiles.names)
        if c1 is None and now >= t_close:
            # after the close a closed loop sends no more, and its
            # shrinking queue reaches buckets no window call reaches
            c1 = (compiles.n, compiles.seconds)
            n_names1 = len(compiles.names)
        with jax.profiler.TraceAnnotation("bench.submit"):
            if open_loop:
                while nxt < len(reqs) and t_open + reqs[nxt].due <= now:
                    r = reqs[nxt]
                    if now < t_close + T.POST_WINDOW_S or r.phase == "win":
                        submit(r, t_open + r.due, now)
                    nxt += 1
            else:
                while (now < t_close and len(live) < n_out
                       and nxt < len(reqs)):
                    submit(reqs[nxt], now, now)
                    nxt += 1
        if (trace_dir and traced[0] is None
                and now >= t_open + TRACE_DELAY_S):
            traced = (len(calls), None)
        if bt.pending_work():
            t_a = clock()
            with jax.profiler.TraceAnnotation("bench.run"):
                bt.run(max_steps=k_steps)
            t_b = clock()
            calls.append((t_a, t_b))
            backlog.append((t_b, len(bt.queue)))
        else:
            wake = (t_open + reqs[nxt].due if open_loop and nxt < len(reqs)
                    else now + 0.001)
            with jax.profiler.TraceAnnotation("bench.idle"):
                time.sleep(max(0.0, min(wake - clock(), 0.05)))
        with jax.profiler.TraceAnnotation("bench.collect"):
            collect(len(calls) - 1)
        now = clock()
        if window_done(now) or now > t_close + T.POST_WINDOW_S:
            break
    if c1 is None:
        c1 = (compiles.n, compiles.seconds)
        n_names1 = len(compiles.names)
    if trace_dir:
        # stopping exports the whole trace and blocks for seconds: done
        # once every window request has ended, never inside the window
        jax.profiler.stop_trace()
        first = traced[0] if traced[0] is not None else 0
        traced = (first, min(len(calls), first + TRACE_CALLS) - 1)
    if c0 is not None and c1[0] > c0[0]:
        import collections
        log(f"[bench] compiled inside the window: "
            f"{dict(collections.Counter(compiles.names[n_names:n_names1]))}")
    if c0 is None:
        c0 = c1
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    return RunRecord(
        outcomes=outcomes, requests=byid, window=(t_open, t_close),
        calls=calls, compiles_in_window=c1[0] - c0[0],
        compile_s_in_window=c1[1] - c0[1], lag=lag,
        setup_s=t_open - t_process - clock.offset,
        memory_peak_bytes=int(stats.get("peak_bytes_in_use", 0)),
        trace_calls=traced, steps_per_call=k_steps,
        backlog=backlog)
